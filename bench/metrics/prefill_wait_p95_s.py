"""95th percentile over the window's requests of the seconds from the
admission that produced each one's first token to that token: chunked
prefill, with its chunks waiting for token budget behind the decode lanes
(``bench.waits``)."""
from bench.stats import p95
from bench.waits import splits


def read(rec):
    vals = [p for _, p in splits(rec)]
    return p95(vals) if vals else None
