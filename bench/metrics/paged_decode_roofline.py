"""The paged decode kernel's share of its roofline in the traced stretch:
the least time the chip needs for the work the traced steps required
(each decoded token's live KV lines, the cell's family's
``paged_decode_work``), over the kernel's device time in the trace."""
import re

from bench.flops import roofline_seconds

#: the kernel's device operations in the trace: its pallas_call has no
#: name, so the trace shows the jitted wrapper's
#: (``%flash_decode_paged_bkgd.11 = ... custom-call(...)``)
KERNEL = re.compile(r"^flash_decode_paged")


def read(rec):
    if rec.trace is None or rec.peak is None:
        return None
    t = sum(s for name, s in rec.trace["ops_s"].items() if KERNEL.search(name))
    runs = [r for step in rec.traced_steps for r in step]
    if t <= 0 or not runs:
        return None
    work = rec.cell.family.paged_decode_work(rec.cell.dims, runs)
    return 100.0 * roofline_seconds(*work, rec.peak) / t
