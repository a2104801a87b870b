"""1 - the union of device operation intervals over the traced stretch."""


def read(rec):
    if rec.trace is None or rec.trace["window_s"] <= 0:
        return None
    return 1.0 - rec.trace["busy_s"] / rec.trace["window_s"]
