"""collective_share (%, sharding): device time of collective operations
(all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
their async halves included) over the device time of the step program,
both summed over the cell's chips.  An operation is a collective by its
HLO name or opcode.  Nothing to read on one chip."""
from benchmarks.tpu.xplane import op_name, opcode

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def is_collective(text: str) -> bool:
    return op_name(text).startswith(KINDS) or opcode(text).startswith(KINDS)


def read(ctx):
    module_s = ctx.module_seconds()
    if ctx.chips < 2 or module_s <= 0:
        return None
    coll = sum(o.dur for d in ctx.devices() for o in d.ops
               if ctx.in_window(o) and is_collective(o.name))
    return 100.0 * coll / module_s
