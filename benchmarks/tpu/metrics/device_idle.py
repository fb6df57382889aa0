"""device_idle (%, device layer): share of the traced window in which no
operation ran on the chip, averaged over the cell's chips.  The window
runs from the first ``bench.*`` host span to the last."""
from benchmarks.tpu.xplane import union


def read(ctx):
    devs = ctx.devices()
    if not devs:
        return None
    lo, hi = ctx.window
    busy = sum(union([(o.start, o.end) for o in d.ops], lo, hi)
               for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
