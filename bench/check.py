"""The comparison that decides ``correct``.

After the window, a sample of the window's finished requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the plain float32 reference of the configuration's family (its
module's ``reference``, such as ``bench/reference/dense.py``) with its
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


def served_gaps(reference, w: dict, m, prompt: np.ndarray, served,
                control: bool = False) -> np.ndarray:
    """For each served token, how far its float32 logit lies below the
    reference's best at that position, given the prompt and the tokens
    served before it.  With ``control`` the token is instead the one the
    reference's float8 control puts first at the same position."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served])
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    ref = reference.logits(w, m, seq[:-1], rows)
    pick = served
    if control:
        pick = reference.logits(w, m, seq[:-1], rows, fp8=True).argmax(-1)
    return ref.max(-1) - ref[np.arange(len(rows)), pick]


def max_logit_gap(reference, weights: dict, m, pairs: list,
                  control: bool = False):
    """Widest gap over every served token of ``pairs`` (or, with
    ``control``, over the float8 control's first choices), and the number
    of tokens compared."""
    gaps = [served_gaps(reference, weights, m, p, s, control=control)
            for p, s in pairs]
    n = sum(len(g) for g in gaps)
    return (float(max(g.max() for g in gaps)) if n else float("nan")), n
