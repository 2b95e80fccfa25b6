"""What `inference.paged.ServingEngine` needs of a model family: the ONE seam
between the engine and the model fns it serves.

A configuration object says which family it belongs to by its
``paged_family(**build_kw)`` method (`LlamaConfig`, `NemotronHConfig`,
`MlaMoeConfig`, `SambaYConfig`); the engine calls it and never looks at a model's name again.
Every fn takes and returns the WHOLE cache as one pytree, which the engine
donates, carries through the decode horizon and rebinds:

  cache = init_cache()
      a dict of device arrays.  The family NAMES its page stores
      (``page_leaves``): ``cache[name]`` for each is an array, or a dict of
      arrays (a quantized store's data and scales), whose AXIS 2 is the page
      axis (the contract of `models/llama.gather_kv_pages`) — K and V pages
      ``("k", "v")`` for the Llama-shaped and Nemotron-H families, ONE store
      of compressed rows ``("latent",)`` for latent attention.  Whatever
      copies, forks, exports or counts pages walks those leaves and no
      other.  Every further leaf belongs to SLOTS (recurrent state, one row
      a slot; a selection log) or is the family's counters.
  logits, cache = prefill(params, ids, true_len, page_row, slot, cache)
  logits, tok, cache = prefill_chunk(params, ids, start, chunk_len,
                                     page_row, slot, cache)
      one sequence, riding engine slot ``slot``; a recurrent family takes
      the slot's state as it stands (a run from position 0 starts from
      zero) and leaves it after the run's last real token.
  logits, cache = decode_step(params, toks, lengths, page_tables, cache,
                              active)
      one token for every slot (row s IS slot s); an inactive slot's pages
      and state stay as they were.
  logits0, greedy, cache = verify_step(params, toks, lengths, page_tables,
                                       cache, n_q)        (or None)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

__all__ = ["PagedFamily", "log_selections_run"]


@dataclasses.dataclass(frozen=True)
class PagedFamily:
    name: str
    init_cache: Callable
    prefill: Callable
    prefill_chunk: Callable
    decode_step: Callable
    # the cache's page stores, each with the page axis AXIS 2 of its leaves
    page_leaves: Tuple[str, ...] = ("k", "v")
    # `serving/quant.quantize_params` knows the family's weight tree
    int8_weights: bool = False
    # speculative verify; None: the family cannot score drafted positions
    verify_step: Optional[Callable] = None
    # the slots hold state that a token changes irreversibly (no rewind, no
    # prefix to attach without the state that belongs to it)
    recurrent: bool = False
    # (mp_axis) -> (param PartitionSpecs, page PartitionSpec) for mesh=
    mesh_specs: Optional[Callable] = None
    # (cache) -> {name: number}: the family's device-side counters, fetched
    counters: Callable = lambda cache: {}
    # pages a prefill chunk's page-table slice is rounded up to (a table
    # width is a shape: every width is an executable of its own); 0: the
    # whole table, for fns whose cost does not follow the table's width
    chunk_table_granule: int = 4
    # ``prefill_chunk`` takes a further STATIC keyword ``last``: whether the
    # chunk is its prompt's last.  For a family whose layers past some depth
    # run for a prompt's last token alone (`models/sambay.py`): a chunk that
    # is not the last is an executable without them and returns no logits
    chunk_takes_last: bool = False
    # which form of attention the fns take, for the engine's spans
    attention_path: str = "paged_kv"
    # (cache, slot) -> {name: host array}: what the cache holds of the slot
    # beside its pages (recurrent state, the selection log)
    slot_state: Optional[Callable] = None


def log_selections_run(log, j, slot, sel, start):
    """A run's selections ``sel [C, k]`` -> ``log[j, slot, :, start:start +
    C]`` (``log [layers, slots, k, positions]``: a family's per-slot selection
    log) in ONE update: where the padded run would pass the end of the row,
    the block starts earlier and keeps what stands there."""
    import jax
    import jax.numpy as jnp
    k, ctx = log.shape[2:]
    block = sel.T[:, :ctx]
    width = block.shape[1]
    at = jnp.minimum(start, ctx - width)
    old = jax.lax.dynamic_slice(log, (j, slot, 0, at), (1, 1, k, width))[0, 0]
    block = jnp.where(jnp.arange(width) < start - at, old,
                      jnp.roll(block, start - at, axis=1))
    return jax.lax.dynamic_update_slice(log, block[None, None],
                                        (j, slot, 0, at))
