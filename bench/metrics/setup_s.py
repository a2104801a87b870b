"""Seconds from the start of the process to the opening of the window:
imports, weights, engine, compilation or cache loads, warm-up."""


def read(rec):
    return rec.setup_s
