"""The host-dispatch readers on a small trace of the program's own loop.

``data/small_serve.xplane.pb`` was recorded on one TPU v5e through the
``serve`` driver: a jitted ``step`` (a 4096 x 4096 bf16 matmul, an
embedding row written into a donated cache, a head over the cache) at
2 streams, a prompt of 4 and 4 generated tokens, traced after one warm
batch: 7 ``serve.step`` spans, 2 ``serve.sync`` spans and 7 ``jit_step``
executions.  56 KB.  Times below are the trace's, in ns.  Each
execution starts 0.77-0.94 ms before the host span of the dispatch that
made it: the host and device planes share one clock to about 1 ms.
"""
import os

import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import harness, xplane
from benchmarks.tpu.peaks import peaks
from benchmarks.tpu.spec import load_module

DATA = os.path.join(os.path.dirname(__file__), "data")
METRICS = os.path.join(h.REPO, "benchmarks", "tpu", "metrics")

# serve.step spans: (start, end)
STEPS = [(46372109, 46746219), (46758169, 47192699), (47202009, 47582629),
         (47593659, 48083919), (48548739, 48809989), (48819179, 49166289),
         (49176669, 49748289)]
# serve.sync spans, the prompt's then the decode's
SYNCS = [(48094099, 48485089), (49755169, 50037609)]
# starts of the jit_step executions on the chip
MODULES = [45469661, 45968426, 46320183, 46793323, 47614858, 47882798,
           48406243]


def reader(name):
    return load_module(os.path.join(METRICS, f"{name}.py"),
                       "bench_metric_" + name).read


def _ctx(path, window=None):
    tr = xplane.read(os.path.join(DATA, path))
    return harness.TraceContext({}, {"batch": 2, "prompt_len": 4}, 1,
                                peaks("TPU v5 lite"), tr,
                                window or tr.window(), list(range(7)))


@pytest.fixture(scope="module")
def ctx():
    return _ctx("small_serve.xplane.pb")


def _ns(o):
    return (round(o.start * 1e9), round(o.end * 1e9))


def test_program_spans_share_the_host_plane_with_the_benchmark(ctx):
    tr = ctx.trace
    host = {n: [_ns(o) for o in tr.host if o.name == n]
            for n in ("serve.prompt", "serve.decode", "serve.step",
                      "serve.sync")}
    assert host["serve.step"] == STEPS
    assert host["serve.sync"] == SYNCS
    prompt, = tr.spans["bench.prompt"]
    decode, = tr.spans["bench.decode"]
    for outer, inner in ((prompt, host["serve.prompt"]),
                         (decode, host["serve.decode"])):
        s, e = _ns(outer)
        assert len(inner) == 1 and s <= inner[0][0] <= inner[0][1] <= e
    # 4 steps and a sync in the prompt phase, 3 and a sync in decode
    (ps, pe), = host["serve.prompt"]
    assert sum(ps <= a and b <= pe for a, b in STEPS + SYNCS) == 5
    starts = [round(m.start * 1e9) for m in tr.devices[0].modules
              if m.name.startswith(harness.STEP_MODULE)]
    assert starts == MODULES
    assert all(-1e6 < m - s < 0 for m, (s, _) in zip(MODULES, STEPS))


def test_dispatch_ms_per_step_by_hand(ctx):
    # both phases are shorter than the runtime's queue: every span counts
    durs = sorted(e - s for s, e in STEPS)
    assert durs[3] == 380620                 # the median of 7
    assert reader("dispatch_ms_per_step")(ctx) == pytest.approx(
        durs[3] * 1e-6, rel=1e-6)


def test_dispatch_ms_per_step_keeps_to_the_window(ctx):
    lo = STEPS[4][0] * 1e-9                  # from the first decode step
    inside = _ctx("small_serve.xplane.pb", (lo, ctx.window[1]))
    median_ns = sorted(e - s for s, e in STEPS[4:])[1]     # 347110
    assert reader("dispatch_ms_per_step")(inside) == pytest.approx(
        median_ns * 1e-6, rel=1e-6)


def _phases(*lengths, blocked_ms=14.4, free_ms=0.4):
    """Host spans of phases of the given lengths, as a chip far behind
    the host gives them: the first 32 dispatches of each phase return in
    ``free_ms``, the rest wait about a step for the runtime's queue."""
    host, t = [], 0.0
    for i, n in enumerate(lengths):
        t0 = t
        for k in range(n):
            d = 1e-3 * (free_ms if k < 32 else blocked_ms)
            host.append(xplane.Op("serve.step", t, t + d))
            t += d + 1e-5
        host.append(xplane.Op("serve.sync", t, t + 0.4))
        t += 0.4
        name = "serve.prompt" if i % 2 == 0 else "serve.decode"
        host.append(xplane.Op(name, t0, t))
        t += 1e-3
    return host


def test_dispatch_ms_per_step_reads_what_the_queue_does_not_hide():
    """A chip far behind makes the later dispatches of a phase wait
    about a step each; the reading is the host's own cost regardless of
    how many steps wait, and so does not follow the step's length."""
    for lengths, blocked in (((64, 255), 14.4), ((64, 255), 50.0),
                             ((20,), 14.4), ((64, 255, 64, 255), 14.4)):
        host = _phases(*lengths, blocked_ms=blocked)
        # a span of the step outside any phase is not the loop's
        host.append(xplane.Op("serve.step", -1.0, -0.9))
        tr = xplane.Trace({}, host, {"bench.prompt": [
            xplane.Op("bench.prompt", -2.0, max(h.end for h in host))]})
        c = harness.TraceContext({}, {}, 1, None, tr, tr.window(), [])
        assert reader("dispatch_ms_per_step")(c) == pytest.approx(0.4)


def test_host_lead_steps_by_hand(ctx):
    # the prompt's sync sees the execution starting at 48406243; none
    # starts while the decode's sync waits
    lead = [sum(s < t <= e for t in MODULES) for s, e in SYNCS]
    assert lead == [1, 0]
    assert reader("host_lead_steps")(ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["dispatch_ms_per_step",
                                  "host_lead_steps"])
def test_readers_find_nothing_where_the_program_names_no_steps(name):
    """A trace of a program without the spans (the benchmark's own loop,
    ``small_decode.xplane.pb``) reads nothing, and does not raise."""
    assert reader(name)(_ctx("small_decode.xplane.pb")) is None

