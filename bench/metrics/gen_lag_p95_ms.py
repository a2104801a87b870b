"""Open loop: 95th percentile of how late the generator sent each of the
window's requests (send time - due time)."""
from bench.stats import p95


def read(rec):
    if not rec.open_loop or not rec.requests:
        return None
    return p95([(r.sent - r.spec.due) * 1e3 for r in rec.requests])
