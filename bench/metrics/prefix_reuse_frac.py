"""Share of the prompt tokens admitted in the window that the prefix cache
served: the engine's reused-token counter over reused plus prefilled."""


def read(rec):
    reused = rec.counters.get("prefix_reused_tokens", 0)
    total = reused + rec.counters.get("prefill_tokens", 0)
    return reused / total if reused and total else None
