"""95th percentile over the window's requests of the seconds each waited
in the admission queue before its first token (``bench.waits``)."""
from bench.stats import p95
from bench.waits import splits


def read(rec):
    vals = [q for q, _ in splits(rec)]
    return p95(vals) if vals else None
