"""The control at a size the CPU holds, put in the program's place: the
run's own check (``harness.check``) must decide it not correct, and the
program correct, on the same sequences.

The committed limits are set at each cell's own size, where the control
reads far above them (``control.py`` on the chip; ``PERF.md``).  At this
size both readings are smaller, so the check here is held to the limit
``tpu_bench_helpers.TINY_LIMIT``, set from this size's readings.
"""
import dataclasses

import jax
import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import control

@pytest.mark.parametrize("name", h.CELLS[:2])
def test_control_reads_above_the_program(name):
    cell, cfg = h.tiny(name)
    cell = dataclasses.replace(cell, limits={"max_logit_gap": h.TINY_LIMIT})
    with h.program(cfg):
        r = next(control.readings(cell, [3_000_000_321],
                                  jax.devices()[:1]))
    assert r["tokens"] == 3 * 8
    assert r["control"] > 3 * r["program"], r
    assert r["program_correct"], r
    assert not r["control_correct"], r
