"""The comparison that decides ``correct``.

After the window, a sample of the window's finished requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the plain float32 reference (``bench.reference.dense``) with its
prompt and served tokens.  The number compared is the widest gap by which
a served (greedy) token's logit lies below the reference's best at its
position.  The limit is the configuration's ``check.max_logit_gap``; how
it was set is in PERF.md.
"""
from __future__ import annotations

import numpy as np


def sample(requests: list, seed: int, target_tokens: int) -> list:
    """(prompt, served tokens) of finished requests: the longest, then
    others in an order drawn from the seed, until ``target_tokens`` served
    tokens are in."""
    done = [r for r in requests if r.done_at is not None and r.n_out]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (done[i].n_out, -i))
    rng = np.random.default_rng([seed, 11])
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    out, n = [], 0
    for i in order:
        r = done[i]
        out.append((np.asarray(r.spec.prompt, np.int32),
                    np.asarray(r.req.output, np.int32)))
        n += len(r.req.output)
        if n >= target_tokens:
            break
    return out


def max_logit_gap(weights: dict, m, pairs: list, control: bool = False):
    """Widest gap over every served token of ``pairs`` (or, with
    ``control``, over the float8 control's first choices), and the number
    of tokens compared."""
    from bench.reference.dense import served_gaps
    gaps = [served_gaps(weights, m, p, s, control=control) for p, s in pairs]
    n = sum(len(g) for g in gaps)
    return (float(max(g.max() for g in gaps)) if n else float("nan")), n
