"""The held experts' grouped matmuls' share of their roofline in the traced
stretch's decode passes: the least time the chip needs for the expert work
of the traced steps' decoded tokens, over the device time of the decode
passes' ``moe_experts_up``/``moe_experts_down`` kernels in the trace (a
prefill chunk's expert kernels are named ``moe_experts_prefill_*`` and
left out).  The work is the cell's family's ``moe_work`` of the window's
``moe_*`` counters, which count the decode passes' live lanes alone,
scaled to the traced stretch by its share of the window's decoded
tokens."""
import re

from bench.flops import roofline_seconds

#: the decode passes' expert kernels (their ``pallas_call`` names)
KERNEL = re.compile(r"^moe_experts_(up|down)\b")


def read(rec):
    work = getattr(rec.cell.family, "moe_work", None)
    c = rec.counters
    if (rec.trace is None or rec.peak is None or work is None
            or not c.get("moe_expert_passes")):
        return None
    t = sum(s for name, s in rec.trace["ops_s"].items() if KERNEL.search(name))
    traced = sum(n for step in rec.traced_steps for _, n in step)
    window = sum(n for _, n in rec.decode_runs)
    if t <= 0 or not traced or not window:
        return None
    flops, nbytes = work(rec.cell.dims, c)
    share = traced / window
    return 100.0 * roofline_seconds(flops * share, nbytes * share,
                                    rec.peak) / t
