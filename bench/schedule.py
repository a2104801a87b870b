"""What every traffic generator hands the harness: requests and when each
is due.

A generator (``bench/generators/<name>.py``) turns a traffic file and a
seed into a schedule.  The multiset of sizes and gaps comes from the
traffic file's own ``shape_seed``; the run's seed only orders them and
fills in the token ids, so every seed offers the same work.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass

import numpy as np


@dataclass
class RequestSpec:
    index: int                 # order of creation, unique in a run
    prompt: np.ndarray         # (P,) int32 token ids
    max_new: int               # tokens to generate (greedy, no EOS)
    due: float = 0.0           # seconds after the window opens
    stream: int = -1           # closed loop: the stream that sends it


def clipped_lognormal(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths, lognormal around ``spec['median']`` with
    ``spec['sigma']``, clipped to ``[spec['min'], spec['max']]``."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def token_ids(rng, vocab: int, n: int) -> np.ndarray:
    """``n`` token ids, avoiding ids 0 and 1 (pad and the usual BOS/EOS)."""
    return rng.integers(2, vocab, n).astype(np.int32)


class OpenSchedule:
    """Open loop: requests at fixed due times, sent whether or not earlier
    ones have finished."""

    open_loop = True

    def __init__(self, specs: list):
        self._specs = sorted(specs, key=lambda s: (s.due, s.index))
        self._next = 0

    def pop_due(self, now: float) -> list:
        out = []
        while self._next < len(self._specs) and \
                self._specs[self._next].due <= now:
            out.append(self._specs[self._next])
            self._next += 1
        return out

    def next_due(self):
        if self._next < len(self._specs):
            return self._specs[self._next].due
        return None

    def finished(self, spec: RequestSpec, now: float) -> None:
        pass


class ClosedSchedule:
    """Closed loop: each stream sends its next request when its last one
    finishes; its due time is that moment."""

    open_loop = False

    def __init__(self, streams: list):
        self._streams = [list(reversed(s)) for s in streams]
        self._ready: list = []               # heap of (due, index, spec)
        for s in self._streams:
            self._release(s, 0.0)

    def _release(self, stream: list, now: float) -> None:
        if stream:
            spec = stream.pop()
            spec.due = now
            heapq.heappush(self._ready, (now, spec.index, spec))

    def pop_due(self, now: float) -> list:
        out = []
        while self._ready and self._ready[0][0] <= now:
            out.append(heapq.heappop(self._ready)[2])
        return out

    def next_due(self):
        return self._ready[0][0] if self._ready else None

    def finished(self, spec: RequestSpec, now: float) -> None:
        self._release(self._streams[spec.stream], now)
