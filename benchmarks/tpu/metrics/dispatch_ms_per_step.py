"""dispatch_ms_per_step (ms, host: step dispatch): what one dispatch of
the step costs the host in the program's loop
(``launch.serve.prefill``/``decode``), where the runtime's queue does
not hide it.  A phase (``serve.prompt``, ``serve.decode``) opens after a
sync, with nothing queued; once the host is as many executions ahead as
the runtime queues (32 on a TPU v5e), each further dispatch waits for
one to finish, and its ``serve.step`` span lasts about a step.  So the
reading is the median ``serve.step`` host span among the first
``FIRST`` of each phase in the traced window, far inside that depth.
Nothing to read where the program names no steps."""
import statistics

PHASES = ("serve.prompt", "serve.decode")
SPAN = "serve.step"
#: dispatches read at the head of each phase: half the runtime's queue
FIRST = 16


def read(ctx):
    steps = sorted((h for h in ctx.trace.host if h.name == SPAN),
                   key=lambda h: h.start)
    durs = []
    for ph in ctx.trace.host:
        if ph.name in PHASES and ctx.in_window(ph):
            durs += [s.dur for s in steps
                     if ph.start <= s.start and s.end <= ph.end][:FIRST]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
