"""Expert-parallel MoE (reference: python/paddle/incubate/distributed/models/moe/)."""
from .gate import BaseGate, NaiveGate, GShardGate, SwitchGate, top_k_gating, compute_capacity
from .moe_layer import (MoELayer, moe_dispatch, moe_combine, moe_ffn,
                        ep_all_to_all, ep_all_to_all_back)
from .dropless import (sigmoid_topk_route, sort_pairs_by_held_expert,
                       grouped_swiglu, dropless_expert_ffn)
from .grad_clip import ClipGradForMOEByGlobalNorm
from . import utils

__all__ = ["BaseGate", "NaiveGate", "GShardGate", "SwitchGate", "top_k_gating",
           "compute_capacity", "MoELayer", "moe_dispatch", "moe_combine",
           "moe_ffn", "ep_all_to_all", "ep_all_to_all_back",
           "sigmoid_topk_route", "sort_pairs_by_held_expert",
           "grouped_swiglu", "dropless_expert_ffn",
           "ClipGradForMOEByGlobalNorm", "utils"]
