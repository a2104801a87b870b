"""The least time the chip needs for counted work.

The counts themselves are the configuration's family's
(``bench/families/<family>.py``: ``model_flops``, ``paged_decode_work``),
taken from shapes: the yardstick's counts, not the program's, so a later
change to how the program computes cannot change them.
"""
from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip needs for the work: the larger of its
    compute and its memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
