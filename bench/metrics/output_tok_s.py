"""Tokens generated by the steps begun in the window, over the window."""


def read(rec):
    return rec.window_tokens / rec.window_s if rec.window_s > 0 else None
