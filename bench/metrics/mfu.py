"""Model FLOPs of every token generated and every prompt token prefilled
in the window, each at its own context (the cell's family's
``model_flops``), over the window times the chip's peak.  Prompt tokens
served from the prefix cache count as work."""


def read(rec):
    if rec.peak is None or rec.window_s <= 0:
        return None
    fam, m = rec.cell.family, rec.cell.dims
    flops = (fam.model_flops(m, rec.decode_runs)
             + fam.model_flops(m, rec.prompt_runs))
    return 100.0 * flops / (rec.window_s * rec.peak["bf16_flops_per_s"])
