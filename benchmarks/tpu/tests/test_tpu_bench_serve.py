"""The program's serving loop (``launch.serve.prefill``/``decode``) as the
``serve`` driver runs it, on the CPU.

A profiler trace of the driver on a reduced minitron-4b holds the
program's ``serve.*`` spans inside the benchmark's ``bench.*`` spans:
one ``serve.step`` per dispatch and one ``serve.sync`` per phase.  The
``serve`` and ``lockstep`` drivers make the same step calls and serve
the same tokens, and keep the same window.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import harness, xplane
from benchmarks.tpu.drivers import lockstep
from benchmarks.tpu.drivers import serve as serve_driver
from repro.launch import serve

CELL = "minitron-4b.chat-b4"
P, G = 8, 8
DRIVERS = {"lockstep": lockstep, "serve": serve_driver}


@pytest.fixture(scope="module")
def served():
    cell, cfg = h.tiny(CELL, prompt_len=P, gen_len=G)
    with h.program(cfg):
        s = harness.set_up(cell, cfg, jax.devices()[:1], 3_000_000_007)
    return cell, s


def test_cell_runs_the_program_loop():
    from benchmarks.tpu import spec
    assert spec.cell(CELL).driver().run_window.__module__ == \
        "bench_driver_serve"


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    cell, s = served
    d = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(d)
    try:
        win = serve_driver.run_window(s, cell.traffic, 1e9, max_batches=2)
    finally:
        jax.profiler.stop_trace()
    path = xplane.find(d)
    return win, xplane.read(path), path


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def _host(trace, name):
    return [o for o in trace.host if o.name == name]


def test_program_spans_nest_inside_the_benchmark_spans(traced):
    win, tr, _ = traced
    names = {o.name for o in tr.host if o.name.startswith("serve.")}
    assert names == {serve.SPAN_PROMPT, serve.SPAN_DECODE, serve.SPAN_STEP,
                     serve.SPAN_SYNC}
    bench = {"bench.prompt": serve.SPAN_PROMPT,
             "bench.decode": serve.SPAN_DECODE}
    phases = []
    for outer, inner in bench.items():
        assert len(tr.spans[outer]) == len(win.batches) == 2
        mine = _host(tr, inner)
        assert len(mine) == 2
        assert all(any(_inside(m, b) for b in tr.spans[outer])
                   for m in mine)
        phases += mine
    for name in (serve.SPAN_STEP, serve.SPAN_SYNC):
        assert all(any(_inside(s, p) for p in phases)
                   for s in _host(tr, name))


def test_each_batch_has_its_steps_and_two_syncs(traced):
    win, tr, _ = traced
    for prompt, dec in zip(sorted(tr.spans["bench.prompt"],
                                  key=lambda o: o.start),
                           sorted(tr.spans["bench.decode"],
                                  key=lambda o: o.start)):
        batch = [o for o in tr.host
                 if prompt.start <= o.start and o.end <= dec.end]
        steps = [o for o in batch if o.name == serve.SPAN_STEP]
        assert len(steps) == P + G - 1
        assert sum(o.name == serve.SPAN_SYNC for o in batch) == 2
        assert [sum(_inside(s, p) for s in steps)
                for p in (prompt, dec)] == [P, G - 1]


def test_spans_carry_their_arguments(traced):
    from jax.profiler import ProfileData
    _, _, path = traced
    args, step_nums = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name in (serve.SPAN_PROMPT, serve.SPAN_DECODE):
                    args.setdefault(e.name, []).append(
                        (stats["batch"], stats["steps"]))
                elif e.name == serve.SPAN_STEP:
                    step_nums.append((e.start_ns, int(stats["step_num"])))
    assert args == {serve.SPAN_PROMPT: [(4, P)] * 2,
                    serve.SPAN_DECODE: [(4, G - 1)] * 2}
    nums = [n for _, n in sorted(step_nums)]
    assert nums == list(range(nums[0], nums[0] + 2 * (P + G - 1)))


def test_serve_and_lockstep_make_the_same_calls(served):
    cell, s = served
    step = s.step
    calls = {}

    def run(name):
        seen = calls[name] = []

        def recording(params, cache, tok, pos):
            seen.append((np.asarray(tok).copy(), int(pos)))
            return step(params, cache, tok, pos)

        s.step = recording
        try:
            win = DRIVERS[name].run_window(s, cell.traffic, 1e9,
                                           first_batch=1, max_batches=1)
        finally:
            s.step = step
        return np.asarray(jnp.stack(win.batches[0].tokens, axis=1))

    tokens = {name: run(name) for name in DRIVERS}
    assert tokens["serve"].shape == (4, G)
    np.testing.assert_array_equal(tokens["serve"], tokens["lockstep"])
    assert len(calls["serve"]) == len(calls["lockstep"]) == P + G - 1
    for (a, pa), (b, pb) in zip(calls["serve"], calls["lockstep"]):
        assert pa == pb
        np.testing.assert_array_equal(a, b)


class Program:
    """A step that answers token + 1 and counts its calls."""

    def __init__(self):
        self.calls = 0

    def step(self, params, cache, tok, pos):
        self.calls += 1
        return None, tok + 1, cache + 1


class Served:
    def __init__(self, n_batches, B=1, P=2):
        self.program = Program()
        self.step, self.params = self.program.step, None
        self.pos = [jnp.int32(t) for t in range(16)]
        self.prompts = [[jnp.full((B,), 100 * i + t, jnp.int32)
                         for t in range(P)] for i in range(n_batches)]

    def fresh_cache(self):
        return jnp.int32(0)

    def put_cache(self, c):
        self.cache = c


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_window_stops_taking_batches_once_its_seconds_pass(driver):
    s = Served(10)
    tick = itertools.count()
    # each clock read advances one second; a batch reads it 3 times
    win = DRIVERS[driver].run_window(s, {"prompt_len": 2, "gen_len": 2},
                                     seconds=5.0,
                                     clock=lambda: float(next(tick)))
    assert len(win.batches) == 2
    assert win.t_close >= win.t_open + 5.0
    assert s.program.calls == 2 * 3
    assert int(s.cache) == 3


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_window_starts_at_first_batch_and_ends_with_the_prompts(driver):
    s = Served(4)
    win = DRIVERS[driver].run_window(s, {"prompt_len": 2, "gen_len": 3},
                                     seconds=1e9, first_batch=1)
    assert [b.index for b in win.batches] == [1, 2, 3]
    assert [int(t[0]) for t in win.batches[0].tokens] == [102, 103, 104]


def test_phase_counters_time_dispatch_and_sync():
    prog = Program()
    prompt = [jnp.full((3,), t, jnp.int32) for t in range(5)]
    pos = [jnp.int32(t) for t in range(9)]
    tok, cache, pre = serve.prefill(prog.step, None, jnp.int32(0), prompt,
                                    pos)
    assert prog.calls == 5 and int(cache) == 5
    np.testing.assert_array_equal(tok, [5, 5, 5])
    out, cache, dec = serve.decode(prog.step, None, cache, tok, pos, 5, 3)
    assert prog.calls == 8 and int(cache) == 8
    assert [int(t[0]) for t in out] == [6, 7, 8]
    for t in (pre, dec):
        assert t.dispatch_s >= 0 and t.sync_s >= 0
        assert t.seconds == t.dispatch_s + t.sync_s


def test_program_serve_reports_its_phases(capsys):
    res = serve.serve("minitron-4b", smoke=True, batch=2, prompt_len=4,
                      gen=3)
    assert res.tokens.shape == (2, 3)
    assert isinstance(res.prefill, serve.PhaseTimes)
    out = capsys.readouterr().out
    assert out.count("dispatch") == 2 and out.count("sync") == 2

