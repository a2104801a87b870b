"""Share of the serve step's token budget used: decode plus prefill tokens
over iterations times ``max_batch_tokens``, from ``serve_stats``."""


def read(rec):
    it = rec.counters.get("iterations", 0)
    if not it or not rec.budget:
        return None
    spent = rec.counters["decode_tokens"] + rec.counters["prefill_tokens"]
    return spent / (it * rec.budget)
