"""step_roofline (%, model step): least time of the decode steps in the
traced window (``counts.decode_step``: the larger of operations over the
bf16 peak and needed bytes over the HBM bandwidth, spread over the
chips) over the device time of the step program (``XLA Modules`` events
named ``jit_step``), both in chip-seconds."""
from benchmarks.tpu import counts


def read(ctx):
    module_s = ctx.module_seconds()
    if not ctx.positions or module_s <= 0:
        return None
    B = ctx.traffic["batch"]
    least = sum(counts.decode_step(ctx.config, B, p).least_s(ctx.peaks)
                for p in ctx.positions)
    return 100.0 * least / module_s
