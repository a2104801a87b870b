"""Device time per decode-carrying engine step in the traced stretch: the
busy device time inside each ``engine.step`` host span that decoded a
token, averaged over those steps."""


def read(rec):
    if rec.trace is None:
        return None
    steps = rec.trace["step_busy_s"]
    if len(steps) != len(rec.traced_steps):
        return None
    busy = [b for b, runs in zip(steps, rec.traced_steps) if runs]
    return 1e3 * sum(busy) / len(busy) if busy else None
