"""The wait for each window request's first token, split where the engine
saw it.  ``repro.serving`` keeps on each request the seconds it queued
before its first token (``queue_s``: submission and any requeue until
admission) and the seconds from the admission that produced the first
token to that token (``prefill_s``), from stamps on one clock: the
``perf_counter`` of an untraced engine, the clock of an attached tracer,
whose QUEUED and PREFILL spans then read the same stamps.  A request
still without a token when the run stopped waiting counts its open wait
up to that moment, as ``ttft_p95_s`` does.  A program that keeps no such
record gives nothing."""
from __future__ import annotations

from typing import Optional


def split(rec, tracked) -> Optional[tuple]:
    """(queue seconds, prefill seconds) of one ``harness.Tracked``."""
    req = tracked.req
    if getattr(req, "submitted_at", None) is None:
        return None
    if req.prefill_s is not None:
        return req.queue_s, req.prefill_s
    # when the run stopped waiting, on the program's clock: the request
    # was submitted at ``sent`` after the window opened
    end = rec.end_s + req.submitted_at - tracked.sent
    if req.queued_at is not None:
        return req.queue_s + end - req.queued_at, 0.0
    return req.queue_s, end - req.admitted_at


def splits(rec) -> list:
    """(queue seconds, prefill seconds) of each of the window's requests
    that has them."""
    return [s for s in (split(rec, r) for r in rec.requests)
            if s is not None]
