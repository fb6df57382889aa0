"""The lockstep driver's token and timing bookkeeping, on the CPU."""
import itertools

import jax.numpy as jnp
import numpy as np

import tpu_bench_helpers as h
from benchmarks.tpu.drivers import lockstep


class Program:
    """A step that records what it is fed and answers token + 1."""

    def __init__(self):
        self.calls = []

    def step(self, params, cache, tok, pos):
        self.calls.append((np.asarray(tok).copy(), int(pos)))
        return None, jnp.asarray(tok) + 1, cache + 1


class Served:
    def __init__(self, program, n_batches, B, P):
        self.step, self.params = program.step, None
        self.pos = [jnp.int32(t) for t in range(64)]
        self.prompts = [[jnp.full((B,), 100 * i + t, jnp.int32)
                         for t in range(P)] for i in range(n_batches)]
        self.cache = jnp.int32(0)
        self.resets = 0

    def fresh_cache(self):
        self.cache = None
        self.resets += 1
        return jnp.int32(0)

    def put_cache(self, c):
        self.cache = c


def test_batch_replays_prompt_then_decodes_greedily():
    B, P, G = 2, 3, 4
    prog = Program()
    served = Served(prog, 5, B, P)
    tick = itertools.count()
    win = lockstep.run_window(served, {"prompt_len": P, "gen_len": G},
                              seconds=1e9, max_batches=2,
                              clock=lambda: float(next(tick)))
    assert len(win.batches) == 2
    assert len(prog.calls) == 2 * lockstep.steps_per_batch(
        {"prompt_len": P, "gen_len": G}) == 2 * (P + G - 1)
    # positions 0..P+G-2 in each batch, prompt fed first
    assert [p for _, p in prog.calls] == list(range(P + G - 1)) * 2
    assert [int(t[0]) for t, _ in prog.calls[:P]] == [0, 1, 2]
    b = win.batches[1]
    assert b.index == 1
    # first token answers the last prompt position, then each feeds next
    assert [int(t[0]) for t in b.tokens] == [103, 104, 105, 106]
    assert len(b.tokens) == G
    assert b.t_start < b.t_first < b.t_end
    # a fresh cache at every batch, handed back after it
    assert served.resets == 2 and int(served.cache) == P + G - 1


def test_window_stops_taking_batches_once_its_seconds_pass():
    B, P, G = 1, 2, 2
    served = Served(Program(), 10, B, P)
    tick = itertools.count()
    # each clock read advances one second; a batch reads it 3 times
    win = lockstep.run_window(served, {"prompt_len": P, "gen_len": G},
                              seconds=5.0, clock=lambda: float(next(tick)))
    assert len(win.batches) == 2
    assert win.t_close >= win.t_open + 5.0


def test_window_ends_when_the_prompts_run_out():
    served = Served(Program(), 2, 1, 2)
    win = lockstep.run_window(served, {"prompt_len": 2, "gen_len": 2},
                              seconds=1e9)
    assert [b.index for b in win.batches] == [0, 1]


def test_end_to_end_metrics_from_batches():
    from benchmarks.tpu import harness
    cell, _ = h.tiny("minitron-4b.chat-b64", batch=4, gen_len=5)
    win = lockstep.Window(t_open=10.0)
    win.batches = [lockstep.Batch(0, 10.0, 11.0, 13.0, []),
                   lockstep.Batch(1, 13.0, 15.0, 16.0, [])]
    m = harness.end_to_end(cell, win, setup_s=7.5)
    assert m["output_tok_s"] == 2 * 4 * 5 / 6.0
    assert m["ttft_p95_ms"] == 2000.0          # slowest batch's 4 requests
    assert m["tpot_p95_ms"] == 500.0           # (13 - 11) / 4
    assert m["setup_s"] == 7.5
    assert harness.quantile_p95(list(range(1, 101))) == 95
