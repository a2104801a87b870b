"""95th percentile over the window's requests of (last token - first
token) / (tokens - 1).  A request still unfinished when the run stopped
waiting counts its time up to that moment."""
from bench.stats import p95


def read(rec):
    vals = []
    for r in rec.requests:
        if r.first is None:
            continue
        if r.done_at is None:
            vals.append((rec.end_s - r.first) / max(r.n_out - 1, 1) * 1e3)
        elif r.n_out >= 2:
            vals.append((r.last - r.first) / (r.n_out - 1) * 1e3)
    return p95(vals) if vals else None
