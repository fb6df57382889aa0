"""flash_decode.roofline (%, kernels): least time of the decode attention
of the traced steps (``counts.attention_kernel``: K and V rows up to
``kv_len`` read once, q read and the output written, each layer) over
the device time of the ``flash_decode`` Pallas kernel, both in
chip-seconds.  The kernel has no stable name yet: it is the custom call
whose HLO name starts ``flash_decode`` (the jitted wrapper in
``kernels/flash_decode.py``) or names its body, ``_decode_kernel``.
Operations that only consume the kernel's output name it among their
operands and are not counted."""
from benchmarks.tpu import counts
from benchmarks.tpu.xplane import op_name


def is_kernel(text: str) -> bool:
    name = op_name(text)
    return name.startswith("flash_decode") or "_decode_kernel" in name


def read(ctx):
    kernel_s = sum(o.dur for d in ctx.devices() for o in d.ops
                   if ctx.in_window(o) and is_kernel(o.name))
    if kernel_s <= 0 or not ctx.positions:
        return None
    B, L = ctx.traffic["batch"], ctx.config["n_layers"]
    least = sum(counts.attention_kernel(ctx.config, B, p + 1)
                .least_s(ctx.peaks) for p in ctx.positions) * L
    return 100.0 * least / kernel_s
