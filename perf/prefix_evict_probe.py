#!/usr/bin/env python3
"""What the prefix cache's eviction costs the host under
``serve_longdoc_c64``'s page traffic: a replay through ``PagePool`` and
``PrefixCache`` alone, with no model and no device.

    python3 perf/prefix_evict_probe.py [--root DIR] [--tag NAME] [--seed 1]
        [--steps 400]

The cache and pool are imported from the checkout ``--root`` (this one, or
a parent unpacked by ``git archive``).  The replay follows the engine's
order of page operations for the cell: 64 clients in a closed loop over 64
slots, page 64, 146 pages a sequence, a pool of 64 x 146 = 9,344 pages;
prompts log-uniform over 2,048-8,192 ids drawn uniform over 40,960 (no
shared prefix), outputs uniform over 256-1,024; an admission looks its
prompt up, evicts what it lacks and allocates every prompt page, then runs
its first chunk of 1,024; each mid-prefill slot runs one chunk a step; a
finished prefill registers its blocks; the horizon of 16 grows each
decoding slot's pages, evicting first; a finished request registers what
it wrote with its partial tail and frees its pages.

Over the last half of the steps it times ``evict`` and, apart, every other
call into the cache and the pool, and prints one JSON line:

  ``evict_ms_per_step`` / ``other_ms_per_step``   host ms an engine step
  ``us_per_evicted_page``, ``pages_evicted_per_step``
  ``entries``                 mean entries in the cache at a step's end
  ``visits_per_evicted_page`` the cache's ``evict_visits`` over its
                              evictions (None where the cache counts none:
                              a full scan examines every entry a page)
"""
import argparse
import json
import math
import os
import sys
import time
from collections import deque

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SLOTS, PAGE, PAGES_PER_SEQ, CHUNK, HORIZON, VOCAB = 64, 64, 146, 1024, 16, \
    40960
PROMPT, OUTPUT = (2048, 8192), (256, 1024)


class Clocked:
    """Wraps an object's methods: the seconds spent in ``evict`` and in
    everything else, kept apart."""

    def __init__(self, obj, tally):
        self._obj, self._tally = obj, tally

    def __getattr__(self, name):
        fn = getattr(self._obj, name)
        if not callable(fn):
            return fn
        key = "evict" if name == "evict" else "other"

        def timed(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                self._tally[key] += time.perf_counter() - t0
        return timed


class Replay:
    def __init__(self, paged, seed):
        self.rng = np.random.default_rng(seed)
        self.tally = {"evict": 0.0, "other": 0.0}
        raw_pool = paged.PagePool(SLOTS * PAGES_PER_SEQ, PAGE)
        self.cache_raw = paged.PrefixCache(raw_pool, PAGE)
        self.pool = Clocked(raw_pool, self.tally)
        self.cache = Clocked(self.cache_raw, self.tally)
        self.slots = [None] * SLOTS
        self.queue = deque(self.request() for _ in range(SLOTS))
        self.evicted = 0

    def request(self):
        lo, hi = PROMPT
        n = int(round(math.exp(self.rng.uniform(math.log(lo),
                                                math.log(hi)))))
        return {"prompt": self.rng.integers(0, VOCAB, n, dtype=np.int32),
                "out": int(self.rng.integers(OUTPUT[0], OUTPUT[1] + 1))}

    def evict(self, n):
        self.evicted += self.cache.evict(n)

    def admit(self):
        while self.queue and None in self.slots:
            req = self.queue[0]
            prompt = req["prompt"]
            shared, partial = self.cache.lookup(prompt)
            pin = list(shared) + ([partial[0]] if partial else [])
            if pin:
                self.pool.share(pin)
            need = math.ceil(len(prompt) / PAGE) - len(shared)
            if need > self.pool.num_free:
                self.evict(need - self.pool.num_free)
            if need > self.pool.num_free:
                if pin:
                    self.pool.free(pin)
                return
            own = self.pool.alloc(need)
            if partial:                   # copied at once, as the engine
                self.pool.free([partial[0]])
            self.queue.popleft()
            s = self.slots.index(None)
            self.slots[s] = {"req": req, "pages": list(shared) + own,
                             "pos": 0, "length": 0, "gen": []}
            self.chunk(s)

    def chunk(self, s):
        slot = self.slots[s]
        prompt = slot["req"]["prompt"]
        slot["pos"] = min(len(prompt), slot["pos"] + CHUNK)
        slot["length"] = slot["pos"]
        if slot["pos"] == len(prompt):
            self.cache.register(prompt, slot["pages"])
            slot["pos"] = None
            slot["gen"].append(int(self.rng.integers(0, VOCAB)))

    def step(self):
        prefilling = [s for s, sl in enumerate(self.slots)
                      if sl is not None and sl["pos"] is not None]
        self.admit()
        for s in prefilling:
            self.chunk(s)
        for s, slot in enumerate(self.slots):
            if slot is None or slot["pos"] is not None:
                continue
            m = min(HORIZON, slot["req"]["out"] - len(slot["gen"]))
            grow = math.ceil((slot["length"] + m) / PAGE) - len(slot["pages"])
            if grow > 0:
                if grow > self.pool.num_free:
                    self.evict(grow - self.pool.num_free)
                if grow > self.pool.num_free:
                    continue              # stalls this horizon
                slot["pages"] += self.pool.alloc(grow)
            slot["length"] += m
            slot["gen"] += self.rng.integers(0, VOCAB, m).tolist()
            if len(slot["gen"]) >= slot["req"]["out"]:
                seq = np.concatenate([slot["req"]["prompt"],
                                      np.asarray(slot["gen"], np.int32)])
                self.cache.register(seq[:slot["length"]], slot["pages"],
                                    with_partial=True)
                self.pool.free(slot["pages"])
                self.slots[s] = None
                self.queue.append(self.request())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=400)
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from paddle_tpu.inference import paged

    rp = Replay(paged, args.seed)
    read = args.steps // 2
    entries = []
    for i in range(args.steps):
        if i == args.steps - read:
            t = dict(rp.tally)
            evicted = rp.evicted
            visits = getattr(rp.cache_raw, "evict_visits", None)
        rp.step()
        if i >= args.steps - read:
            entries.append(len(rp.cache_raw))
    evict_s = rp.tally["evict"] - t["evict"]
    pages = rp.evicted - evicted
    if visits is not None:
        visits = (rp.cache_raw.evict_visits - visits) / pages if pages \
            else None
    print(json.dumps({"prefix_evict_probe": {
        "root": root, "tag": args.tag, "seed": args.seed,
        "steps_read": read,
        "evict_ms_per_step": 1e3 * evict_s / read,
        "other_ms_per_step": 1e3 * (rp.tally["other"] - t["other"]) / read,
        "us_per_evicted_page": 1e6 * evict_s / pages if pages else None,
        "pages_evicted_per_step": pages / read,
        "entries": sum(entries) / len(entries),
        "visits_per_evicted_page": visits}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
