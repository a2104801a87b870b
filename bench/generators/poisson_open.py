"""Open loop with Poisson arrivals at ``rate_per_s``, prompts behind shared
system prompts of Zipf popularity.

Traffic keys: ``rate_per_s``, ``shape_seed``, ``drain_s``,
``system_prompts`` ({count, tokens, zipf_s}; count 0 for no shared
prefix), ``prompt_tokens`` and ``output_tokens`` (clipped lognormal:
{median, sigma, min, max}).  The window holds exactly ``rate * seconds``
requests whose gaps, lengths and prompt choices are one fixed multiset
(exponential gaps rescaled to fill the window); arrivals go on at the
same rate after the window so that the load stays steady while the
window's requests finish.
"""
from __future__ import annotations

import numpy as np

from bench.schedule import (
    OpenSchedule, RequestSpec, clipped_lognormal, token_ids,
)


def _shapes(rng, traffic: dict, n: int, span: float):
    gaps = rng.exponential(1.0, n)
    gaps *= span / gaps.sum()
    user = clipped_lognormal(rng, traffic["prompt_tokens"], n)
    out = clipped_lognormal(rng, traffic["output_tokens"], n)
    sp = traffic.get("system_prompts") or {"count": 0}
    if sp["count"]:
        w = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
        which = rng.choice(sp["count"], n, p=w / w.sum())
    else:
        which = np.full(n, -1)
    return list(zip(gaps, user, out, which))


def make(traffic: dict, seed: int, seconds: float, vocab: int):
    rate = float(traffic["rate_per_s"])
    n_win = max(1, int(round(rate * seconds)))
    n_drain = int(round(rate * traffic["drain_s"]))
    shape = np.random.default_rng(traffic["shape_seed"])
    window = _shapes(shape, traffic, n_win, seconds)
    drain = _shapes(shape, traffic, n_drain, traffic["drain_s"])
    rng = np.random.default_rng(seed)
    window = [window[i] for i in rng.permutation(n_win)]
    drain = [drain[i] for i in rng.permutation(n_drain)]
    sp = traffic.get("system_prompts") or {"count": 0}
    systems = [token_ids(rng, vocab, sp["tokens"])
               for _ in range(sp["count"])]
    specs = []
    for part, t in ((window, 0.0), (drain, float(seconds))):
        for gap, user, out, which in part:
            prompt = token_ids(rng, vocab, int(user))
            if which >= 0:
                prompt = np.concatenate([systems[which], prompt])
            specs.append(RequestSpec(index=len(specs), prompt=prompt,
                                     max_new=int(out), due=t))
            t += gap
    return OpenSchedule(specs)
