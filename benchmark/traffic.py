"""The one generator of request traffic. It reads a traffic mix's
parameters (``workloads/<cell>.json``) and the run's seed, and yields
requests; a new mix is a new data file, never new code.

Every seed sends the same multiset of (prompt length, answer length)
pairs: ``pool_requests`` pairs, each length list the stratified
quantiles of its distribution, paired by a permutation fixed in the
file (``pairing_seed``). ``order`` says what the run's seed decides
besides the token ids, which are uniform over the vocabulary:
``"fixed"`` sends the pairs in the file's own order (``order_seed``)
whatever the seed, so every run does the same work on the same
schedule; ``"seeded"`` (the default) shuffles the order by the seed.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def quantile_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` stratified draws of a length distribution: the value at
    each of the quantiles (i + 0.5) / n."""
    low, high = float(spec["low"]), float(spec["high"])
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "log_uniform":
        x = low * (high / low) ** u
    elif spec["dist"] == "uniform":
        x = low + (high - low) * u
    elif spec["dist"] == "fixed":
        x = np.full(n, low)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(v) for v in np.clip(np.rint(x), low, high)]


def length_pairs(traffic: Dict[str, Any]) -> List[Tuple[int, int]]:
    n = int(traffic["pool_requests"])
    prompts = quantile_lengths(traffic["prompt_len"], n)
    answers = quantile_lengths(traffic["output_len"], n)
    order = np.random.default_rng(int(traffic["pairing_seed"])).permutation(n)
    return [(prompts[i], answers[int(j)]) for i, j in enumerate(order)]


def requests(traffic: Dict[str, Any], vocab_size: int, seed: int
             ) -> Iterator[Dict[str, Any]]:
    """An endless stream of ``{"prompt": int32[plen], "max_new_tokens"}``:
    the pool in seeded order, again and again, fresh token ids each time."""
    rng = np.random.default_rng(int(seed))
    pairs = length_pairs(traffic)
    fixed = traffic.get("order", "seeded") == "fixed"
    order_rng = np.random.default_rng(int(traffic["order_seed"])) \
        if fixed else rng
    while True:
        for i in order_rng.permutation(len(pairs)):
            plen, new = pairs[int(i)]
            yield {"prompt": rng.integers(0, vocab_size, plen,
                                          dtype=np.int32),
                   "max_new_tokens": int(new)}
