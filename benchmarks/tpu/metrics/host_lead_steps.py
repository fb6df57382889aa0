"""host_lead_steps (steps, host: step dispatch): how many steps the host
has handed the chip and the chip has not begun when the host stops to
wait.  For each of the program's ``serve.sync`` host spans in the traced
window (the ``block_until_ready`` that closes a phase), the executions
of the step program (``XLA Modules`` events named ``jit_step``) on the
first chip that start while the span is open; averaged over the syncs.
The host dispatches nothing while it waits, so each was queued before:
a host stall shorter than this lead times the step cannot idle the chip.
Nothing to read where the program names no syncs or the trace holds no
step executions."""
from benchmarks.tpu.harness import STEP_MODULE

SPAN = "serve.sync"


def read(ctx):
    syncs = [h for h in ctx.trace.host if h.name == SPAN and ctx.in_window(h)]
    devs = ctx.devices()
    if not syncs or not devs:
        return None
    starts = [m.start for m in devs[0].modules
              if m.name.startswith(STEP_MODULE)]
    if not starts:
        return None
    return sum(sum(1 for t in starts if s.start < t <= s.end)
               for s in syncs) / len(syncs)
