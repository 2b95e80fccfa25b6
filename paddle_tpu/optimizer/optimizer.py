"""Optimizer base (reference: python/paddle/optimizer/optimizer.py:128).

Design: each optimizer implements a *functional* per-parameter update
``_update(p, g, state, lr) -> (new_p, new_state)`` over raw jax arrays. The
eager ``step()`` applies it in place (dygraph parity); jitted train steps call
``apply_gradients_functional`` on whole pytrees so the update fuses into the
compiled step (the fused adamw kernel analog — XLA fuses the elementwise
chain into one pass over HBM).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core.dispatch import no_grad
from ..profiler import device_span
from .lr import LRScheduler

__all__ = ["Optimizer"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._learning_rate = learning_rate
        self._parameter_list = self._flatten_params(parameters)
        self._param_groups = self._build_groups(parameters)
        self._grad_clip = grad_clip
        self._weight_decay = weight_decay
        self._accumulators: Dict[int, Dict[str, jnp.ndarray]] = {}
        self._global_step = 0

    @staticmethod
    def _flatten_params(parameters):
        if parameters is None:
            return []
        out = []
        for p in parameters:
            if isinstance(p, dict):
                out.extend(p["params"])
            else:
                out.append(p)
        return out

    def _build_groups(self, parameters):
        if parameters is None:
            return []
        groups = []
        plain = []
        for p in parameters:
            if isinstance(p, dict):
                groups.append(dict(p))
            else:
                plain.append(p)
        if plain:
            groups.insert(0, {"params": plain})
        return groups

    # -- lr ----------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- state -------------------------------------------------------------
    def _get_state(self, p: Parameter) -> Dict[str, jnp.ndarray]:
        key = id(p)
        if key not in self._accumulators:
            self._accumulators[key] = self._init_state(p._value)
        return self._accumulators[key]

    def _init_state(self, value) -> Dict[str, jnp.ndarray]:
        return {}

    def _update(self, p, g, state, lr, **group_kw):
        raise NotImplementedError

    # -- the eager step ----------------------------------------------------
    @no_grad()
    def step(self):
        lr = self.get_lr()
        params_grads = [(p, p._grad) for p in self._parameter_list
                        if p._grad is not None and p.trainable]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        wd = self._weight_decay
        for p, g in params_grads:
            if g is None:
                continue
            gv = g._value if isinstance(g, Tensor) else g
            pv = p._value
            # per-parameter regularizer objects (reference regularizer.py via
            # ParamAttr) override the optimizer-global weight_decay
            preg = getattr(p, "regularizer", None)
            if preg is not None and callable(preg):
                gv = gv + preg(pv)
            elif wd is not None and self._decoupled_wd is False:
                if callable(wd):          # L1Decay/L2Decay instance
                    gv = gv + wd(pv)
                else:
                    gv = gv + float(wd) * pv
            state = self._get_state(p)
            plr = lr * p.optimize_attr.get("learning_rate", 1.0) \
                if hasattr(p, "optimize_attr") else lr
            new_p, new_state = self._update(pv, gv, state, plr)
            p._set_value(new_p)
            # keyed per parameter: bounded by the model, not steps
            # graftlint: disable=LEAK001
            self._accumulators[id(p)] = new_state
        self._global_step += 1

    _decoupled_wd = False  # True for AdamW-style optimizers

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        return None, [(p, p._grad) for p in self._parameter_list]

    @no_grad()
    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def finish_step(self):
        """Post-step bookkeeping shared by compiled train steps: advance the
        LR scheduler (if any) and the global step counter."""
        if isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.step()
        self._global_step += 1

    # -- functional application (jit path) ---------------------------------
    def apply_gradients_functional(self, params: dict, grads: dict, opt_state: dict,
                                   lr=None, lr_scales: Optional[dict] = None):
        """Pure update over {name: value} pytrees; used by compiled train
        steps. Returns (new_params, new_opt_state).

        lr_scales: optional {name: float} per-param LR multipliers (the
        optimize_attr['learning_rate'] values the eager step() honors)."""
        lr = self.get_lr() if lr is None else lr
        wd = self._weight_decay
        new_params, new_state = {}, {}
        with device_span("optimizer"):
            for name, pv in params.items():
                gv = grads.get(name)
                if gv is None:
                    new_params[name] = pv
                    new_state[name] = opt_state.get(name, {})
                    continue
                if wd is not None and self._decoupled_wd is False:
                    # same L1Decay/L2Decay-object handling as the eager step()
                    gv = gv + (wd(pv) if callable(wd) else float(wd) * pv)
                st = opt_state.get(name)
                if st is None or not st:
                    st = self._init_state(pv)
                plr = lr * lr_scales[name] if lr_scales and name in lr_scales else lr
                np_, ns = self._update(pv, gv, st, plr)
                new_params[name] = np_
                new_state[name] = ns
        return new_params, new_state

    def init_opt_state(self, params: dict) -> dict:
        return {name: self._init_state(v) for name, v in params.items()}

    # -- checkpointing -----------------------------------------------------
    def state_dict(self):
        sd = {}
        names = self._param_names()
        for p in self._parameter_list:
            state = self._accumulators.get(id(p))
            if state is None:
                continue
            pname = names[id(p)]
            for k, v in state.items():
                sd[f"{pname}.{k}"] = Tensor(v)
        sd["global_step"] = self._global_step
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        return sd

    def set_state_dict(self, state_dict):
        names = self._param_names()
        inv = {v: k for k, v in names.items()}
        for p in self._parameter_list:
            pname = names[id(p)]
            state = {}
            template = self._init_state(p._value)
            for k in template:
                key = f"{pname}.{k}"
                if key in state_dict:
                    v = state_dict[key]
                    state[k] = v._value if isinstance(v, Tensor) else jnp.asarray(v)
                else:
                    state[k] = template[k]
            self._accumulators[id(p)] = state
        self._global_step = int(state_dict.get("global_step", 0))
        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])

    def _param_names(self):
        names = {}
        for i, p in enumerate(self._parameter_list):
            names[id(p)] = p.name or f"param_{i}"
        return names
