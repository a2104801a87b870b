"""Model FLOPs of every token generated and every prompt token prefilled
in the window, each at its own context, over the window times the chip's
peak.  Prompt tokens served from the prefix cache count as work."""
from bench.flops import model_flops


def read(rec):
    if rec.peak is None or rec.window_s <= 0:
        return None
    m = rec.cell.dims
    flops = (model_flops(m, rec.decode_tokens, rec.decode_ctx)
             + model_flops(m, rec.prompt_tokens, rec.prompt_ctx))
    return 100.0 * flops / (rec.window_s * rec.peak["bf16_flops_per_s"])
