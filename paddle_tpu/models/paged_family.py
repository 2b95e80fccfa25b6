"""What `inference.paged.ServingEngine` needs of a model family: the ONE seam
between the engine and the model fns it serves.

A configuration object says which family it belongs to by its
``paged_family(**build_kw)`` method (`LlamaConfig`, `NemotronHConfig`); the
engine calls it and never looks at a model's name again.  Every fn takes and
returns the WHOLE cache as one pytree, which the engine donates, carries
through the decode horizon and rebinds:

  cache = init_cache()
      a dict of device arrays.  ``cache["k"]`` / ``cache["v"]`` are the KV
      page stores, the page axis AXIS 2 of every leaf (the contract of
      `models/llama.gather_kv_pages`); a family with recurrent state keeps
      it in further leaves, one row a SLOT, and its counters beside them.
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
from typing import Callable, Optional

__all__ = ["PagedFamily"]


@dataclasses.dataclass(frozen=True)
class PagedFamily:
    name: str
    init_cache: Callable
    prefill: Callable
    prefill_chunk: Callable
    decode_step: Callable
    # speculative verify; None: the family cannot score drafted positions
    verify_step: Optional[Callable] = None
    # the slots hold state that a token changes irreversibly (no rewind, no
    # prefix to attach without the state that belongs to it)
    recurrent: bool = False
    # (mp_axis) -> (param PartitionSpecs, page PartitionSpec) for mesh=
    mesh_specs: Optional[Callable] = None
    # (cache) -> {name: number}: the family's device-side counters, fetched
    counters: Callable = lambda cache: {}
    # (cache, slot) -> {name: host array}: what the cache holds of the slot
    # beside its pages (recurrent state, the selection log)
    slot_state: Optional[Callable] = None
