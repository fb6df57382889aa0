"""step_mfu (%, model step): model operations of the decode steps in the
traced window, counted from shapes (``counts.decode_step``), over the
window times the chips times the bf16 peak."""
from benchmarks.tpu import counts


def read(ctx):
    if not ctx.positions:
        return None
    B = ctx.traffic["batch"]
    flops = sum(counts.decode_step(ctx.config, B, p).flops
                for p in ctx.positions)
    lo, hi = ctx.window
    return 100.0 * flops / ((hi - lo) * ctx.chips * ctx.peaks.bf16_flops)
