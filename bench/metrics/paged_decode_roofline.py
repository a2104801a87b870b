"""The paged decode kernel's share of its roofline in the traced stretch:
the least time the chip needs for the work the traced steps required
(each decoded token's live KV lines, ``bench.flops.paged_decode_work``),
over the kernel's device time in the trace."""
import re

from bench.flops import paged_decode_work, roofline_seconds

#: the kernel's device operations in the trace: its pallas_call has no
#: name, so the trace shows the jitted wrapper's
#: (``%flash_decode_paged_bkgd.11 = ... custom-call(...)``)
KERNEL = re.compile(r"^flash_decode_paged")


def read(rec):
    if rec.trace is None or rec.peak is None:
        return None
    t = sum(s for name, s in rec.trace["ops_s"].items() if KERNEL.search(name))
    tokens = sum(d for d, _ in rec.traced_steps)
    ctx = sum(c for _, c in rec.traced_steps)
    if t <= 0 or not tokens:
        return None
    need = roofline_seconds(*paged_decode_work(rec.cell.dims, tokens, ctx),
                            rec.peak)
    return 100.0 * need / t
