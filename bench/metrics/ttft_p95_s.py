"""95th percentile over every request due in the window of the time from
its due time (open loop: the schedule; closed loop: its submission) to its
first token.  A request with no token when the run stopped waiting counts
up to that moment."""
from bench.stats import p95


def read(rec):
    vals = [(r.first if r.first is not None else rec.end_s) - r.spec.due
            for r in rec.requests]
    return p95(vals) if vals else None
