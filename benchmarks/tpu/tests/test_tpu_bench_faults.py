"""A run at a size the CPU holds, with the look for a chip skipped and
the timed path broken underneath: ``correct`` must come out false for
each fault the cell can have, and true with none."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import tpu_bench_helpers as h


def state_unchanged(step):
    """The step answers but hands back the cache it was given."""
    def f(params, cache, tok, pos):
        keep = jax.tree_util.tree_map(jnp.copy, cache)
        logits, t, _ = step(params, cache, tok, pos)
        return logits, t, keep
    return f


def token_altered(step):
    """The greedy pick is changed where it is produced."""
    def f(params, cache, tok, pos):
        logits, t, cache = step(params, cache, tok, pos)
        return logits, (t + 1) % logits.shape[-1], cache
    return f


@pytest.mark.parametrize("name", h.CELLS[:2])
def test_sound_run_is_correct(name):
    r = h.run_tiny(name)
    assert r["correct"], r["check"]
    assert list(r)[-1] == "check"
    assert r["attempted"] == 4 and r["failed"] == 0     # one batch of 4


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
@pytest.mark.parametrize("name", h.CELLS[:2])
def test_fault_is_caught(name, fault):
    r = h.run_tiny(name, fault=fault)
    assert not r["correct"], r["check"]
    assert r["failed"] > 0


FOUR = r"""
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses
import jax, jax.numpy as jnp
import tpu_bench_helpers as h
name = "granite-20b.tp4.chat-b32"
if sys.argv[2] == "control":
    from benchmarks.tpu import control
    cell, cfg = h.tiny(name)
    lim = {"max_logit_gap": float(sys.argv[3])}
    cell = dataclasses.replace(cell, limits=lim)
    with h.program(cfg):
        r = next(control.readings(cell, [3_000_000_321], jax.devices()[:4]))
    print("READINGS", r["program"], r["control"])
    print("CORRECT", r["control_correct"] if r["program_correct"] else "program")
    sys.exit(0)
if sys.argv[2] == "no_exchange":
    # each chip keeps its own partial of the sequence-sharded attention
    jax.lax.all_gather = lambda x, axis_name, **kw: jnp.broadcast_to(
        x[None], (4,) + x.shape)
r = h.run_tiny(name)
print("CORRECT", r["correct"], r["check"]["max_logit_gap"]["value"])
"""


def _four(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", FOUR,
                        os.path.dirname(__file__), mode, str(h.TINY_LIMIT)],
                       env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("CORRECT")]
    assert line, r.stdout[-2000:]
    return line[-1].split()[1], r.stdout[-2000:]


@pytest.mark.parametrize("mode,want", [("sound", True),
                                       ("no_exchange", False)])
def test_four_chip_exchange(mode, want):
    got, out = _four(mode)
    assert got == str(want), out


def test_four_chip_control_is_not_correct():
    """The control in the program's place on four virtual devices, held
    to the small size's limit (``tpu_bench_helpers.TINY_LIMIT``)."""
    got, out = _four("control")
    assert got == "False", out
