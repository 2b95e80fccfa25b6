"""The one traffic generator: a pure function of a traffic file and --seed.

Every seed gets the SAME multiset of (prompt length, output length) pairs and
of arrival gaps, in another order, so that a seed changes the order of the
work and never its amount: lengths are the ``cycle`` mid-quantiles of the
file's distributions, paired by a fixed permutation, and each cycle is
shuffled by the seed.  Token ids are uniform in [1, vocab) from the seed.

Traffic file keys (serve):
  clients      closed loop: this many callers, each waiting for its reply
  rate_rps     open loop: mean arrivals per second (``arrival``: "poisson")
  prompt_len   {"dist": "uniform" | "loguniform", "min": a, "max": b}
  output_len   the same
  cycle        pairs per cycle (default 64)
"""
import math

import numpy as np

PAIRING_SEED = 20250925          # fixed: the pairing is part of the mix


def _quantile(dist, u):
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif dist["dist"] == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return int(min(hi, max(lo, round(x))))


def length_pairs(traffic):
    """The cycle's (prompt_len, output_len) pairs, the same for every seed."""
    n = int(traffic.get("cycle", 64))
    us = [(i + 0.5) / n for i in range(n)]
    prompts = [_quantile(traffic["prompt_len"], u) for u in us]
    outputs = [_quantile(traffic["output_len"], u) for u in us]
    perm = np.random.default_rng(PAIRING_SEED).permutation(n)
    return [(prompts[i], outputs[int(perm[i])]) for i in range(n)]


def requests(traffic, vocab_size, seed):
    """Endless iterator of (prompt ids int32[T], max_new_tokens)."""
    pairs = length_pairs(traffic)
    rng = np.random.default_rng(seed)
    while True:
        for i in rng.permutation(len(pairs)):
            t, n = pairs[int(i)]
            yield rng.integers(1, vocab_size, (t,)).astype(np.int32), n


def arrival_gaps(traffic, seed):
    """Endless iterator of seconds between arrivals of an open loop: the
    cycle's mid-quantiles of the exponential distribution, shuffled."""
    if traffic.get("arrival", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    n = int(traffic.get("cycle", 64))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / float(traffic["rate_rps"])
            for i in range(n)]
    rng = np.random.default_rng(seed + 1)
    while True:
        for i in rng.permutation(n):
            yield gaps[int(i)]


def warmup_lengths(traffic, bucket):
    """One prompt length in every ``bucket``-wide band the mix can produce:
    the engine pads prompts and chunks to multiples of ``bucket``, so these
    lengths reach every executable shape the mix will use."""
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    out = []
    for top in range(-(-lo // bucket) * bucket, hi + bucket, bucket):
        out.append(min(top, hi))
    return sorted(set(out))
