"""Shared by the benchmark's tests: a cell cut to a size the CPU holds.

The program's configuration is the registry's ``reduced()`` preset of
the cell's architecture; the configuration file's sizes are replaced by
its sizes, so that the benchmark's weights, reference and counts follow.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import types
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.tpu import harness, spec  # noqa: E402

CELLS = ("minitron-4b.chat-b64", "mamba2-370m.chat-b256",
         "granite-20b.tp4.chat-b32")

#: the correctness limit of the control's tests at the small size, set
#: from that size's readings (CPU): the program's widest gap read
#: 0.0-0.0133 and the control's 0.068-0.312 over three seeds of each of
#: the two one-chip cells; on four virtual devices 0.0 and 0.078
TINY_LIMIT = 0.04


def tiny(name: str, **traffic):
    """(cell, program config) at a size the CPU holds."""
    from repro.models.registry import get_arch
    cell = spec.cell(name)
    cfg = get_arch(cell.config["arch"]).reduced()
    conf = dict(cell.config)
    for k in harness.sizes(conf):
        conf[k] = getattr(cfg, k)
    t = dict(cell.traffic, batch=4, prompt_len=8, gen_len=8,
             max_batches=3, check_requests=3)
    t.update(traffic)
    return dataclasses.replace(cell, config=conf, traffic=t), cfg


@contextlib.contextmanager
def program(cfg, fault=None):
    """The harness runs the program at ``cfg``, the registry's reduced
    preset, and with ``fault`` the compiled step that ``fault`` wraps:
    the timed path broken underneath the harness."""
    from repro.launch import serve
    make = serve.make_step

    def make_step(*a, **kw):
        jitted = make(*a, **kw)
        if fault is None:
            return jitted

        class Faulty:
            def lower(self, *args):
                lowered = jitted.lower(*args)
                return types.SimpleNamespace(
                    compile=lambda: fault(lowered.compile()))
        return Faulty()

    with mock.patch.object(harness, "program_config", lambda config: cfg), \
            mock.patch.object(serve, "make_step", make_step):
        yield


def run_tiny(name: str, seed: int = 3_000_000_123, fault=None, trace=False,
             **traffic):
    import time

    import jax
    cell, cfg = tiny(name, **traffic)
    with program(cfg, fault):
        return harness.run(cell, seed, 0.0, trace,
                           jax.devices()[:cell.chips], time.monotonic())
