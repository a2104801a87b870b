"""Closed loop: ``streams`` clients, each sending its next request when its
last one finishes.

Traffic keys: ``streams``, ``requests_per_stream``, ``shape_seed``,
``drain_s``, ``prompt_tokens`` and ``output_tokens`` (clipped lognormal:
{median, sigma, min, max}).  The lengths are one fixed multiset of
``streams * requests_per_stream`` pairs, cut into rounds of ``streams``:
round k holds every stream's k-th request, and the seed only deals a
round's pairs to the streams in another order and fills in the token
ids.  So every seed opens the window with the same burst of first
requests.  No prompt shares a prefix with another.
"""
from __future__ import annotations

import numpy as np

from bench.schedule import (
    ClosedSchedule, RequestSpec, clipped_lognormal, token_ids,
)


def make(traffic: dict, seed: int, seconds: float, vocab: int):
    n_streams = int(traffic["streams"])
    n = n_streams * int(traffic["requests_per_stream"])
    shape = np.random.default_rng(traffic["shape_seed"])
    plen = clipped_lognormal(shape, traffic["prompt_tokens"], n)
    out = clipped_lognormal(shape, traffic["output_tokens"], n)
    rng = np.random.default_rng(seed)
    order = np.concatenate([k + rng.permutation(n_streams)
                            for k in range(0, n, n_streams)])
    streams = [[] for _ in range(n_streams)]
    for i, j in enumerate(order):
        streams[i % n_streams].append(RequestSpec(
            index=i, prompt=token_ids(rng, vocab, int(plen[j])),
            max_new=int(out[j]), stream=i % n_streams))
    return ClosedSchedule(streams)
