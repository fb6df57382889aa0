"""The Pallas kernels compiled by Mosaic for a described TPU v5e.

No chip is attached: the TPU compiler compiles each kernel at the real
widths of the models it serves (minitron-4b, granite-20b's sequence
shards, mamba2-370m) for a ``v5e:2x2`` topology, and the compiled HLO
must hold the kernel (``tpu_custom_call``).  This catches what interpret
mode cannot: misaligned slices, VMEM overruns, ops Mosaic lacks.

Only one process may load the TPU library at a time, so the topology is
described inside a module fixture, never while a module is imported.
Nothing here runs a kernel; ``chip_smoke.py`` does that on the chip.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.neutron_matmul import neutron_matmul
from repro.kernels.ssd_scan import ssd_scan

ROOT = Path(__file__).resolve().parents[1]
BF, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32

# name -> (kernel call, operand (shape, dtype) list)
CASES = {
    # minitron-4b decode: 4 streams, 24 q heads over 8 kv, hd 128, 96 slots
    "flash_decode_minitron": (
        lambda q, k, v, n: flash_decode(q, k, v, kv_len=n),
        [((4, 24, 128), BF), ((4, 8, 96, 128), BF), ((4, 8, 96, 128), BF),
         ((4,), I32)]),
    # granite-20b decode on a 4-way model axis: 48 heads over 1 kv head,
    # each chip holds 16 of 64 cache slots and returns its log-sum-exp
    "flash_decode_granite_shard": (
        lambda q, k, v, n: flash_decode(q, k, v, kv_len=n, return_lse=True),
        [((4, 48, 128), BF), ((4, 1, 16, 128), BF), ((4, 1, 16, 128), BF),
         ((4,), I32)]),
    # the benchmark cells' decode shapes: minitron-4b.chat-b64 (a 256-row
    # cache), granite-20b.tp4.chat-b32's per-chip shard of 48 rows, and
    # minitron-4b.chat-b4 (320 rows, two blocks of 160)
    "flash_decode_chat_b64": (
        lambda q, k, v, n: flash_decode(q, k, v, kv_len=n),
        [((64, 24, 128), BF), ((64, 8, 256, 128), BF),
         ((64, 8, 256, 128), BF), ((64,), I32)]),
    "flash_decode_granite_chat_b32": (
        lambda q, k, v, n: flash_decode(q, k, v, kv_len=n, return_lse=True),
        [((32, 48, 128), BF), ((32, 1, 48, 128), BF), ((32, 1, 48, 128), BF),
         ((32,), I32)]),
    "flash_decode_chat_b4": (
        lambda q, k, v, n: flash_decode(q, k, v, kv_len=n),
        [((4, 24, 128), BF), ((4, 8, 320, 128), BF), ((4, 8, 320, 128), BF),
         ((4,), I32)]),
    # minitron-4b prefill: 64 tokens, 32 (padded) heads, kv expanded
    "flash_attention_minitron": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_k=512),
        [((4, 32, 64, 128), BF)] * 3),
    # a 2k-token prompt with 8 kv heads: several q and k blocks
    "flash_attention_2k_gqa": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, 32, 2048, 128), BF), ((1, 8, 2048, 128), BF),
         ((1, 8, 2048, 128), BF)]),
    # mamba2-370m: 32 heads of 64, state 128, chunk 128, 512 tokens
    "ssd_scan_mamba2": (
        lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, chunk=128),
        [((4, 512, 32, 64), BF), ((4, 512, 32), F32), ((32,), F32),
         ((4, 512, 128), BF), ((4, 512, 128), BF)]),
    # minitron-4b MLP up-projection, 256 tokens
    "neutron_matmul_bf16": (
        lambda x, w: neutron_matmul(x, w, act="sqrelu"),
        [((256, 3072), BF), ((3072, 9216), BF)]),
    # the same widths in int8 with per-channel scale and requantization
    "neutron_matmul_int8": (
        lambda x, w, s: neutron_matmul(x, w, scale=s, act="relu",
                                       out_scale=0.5),
        [((256, 3072), I8), ((3072, 9216), I8), ((9216,), F32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, operands = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# kernel -> the case above that compiles it
NAMED = {"flash_decode": "flash_decode_minitron",
         "flash_attention": "flash_attention_minitron",
         "ssd_scan": "ssd_scan_mamba2",
         "neutron_matmul": "neutron_matmul_bf16"}


def custom_calls(text):
    """The compiled HLO's Mosaic kernels, each as the profiler's device
    trace names an operation: its instruction text."""
    return [line.strip().removeprefix("ROOT ")
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("kernel", sorted(NAMED))
def test_kernel_carries_its_name(kernel, one_chip):
    """Each ``pallas_call`` is named, so a refactor of its wrapper cannot
    rename what a device trace shows of it."""
    fn, operands = CASES[NAMED[kernel]]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in operands]
    calls = custom_calls(jax.jit(fn).lower(*args).compile().as_text())
    assert calls and all(f"/{kernel}/pallas_call" in c for c in calls)


def test_step_keeps_the_names_the_benchmark_reads(one_chip):
    """The serving step compiles to the module ``jit_step`` and holds the
    ``flash_decode`` kernel under a name the benchmark's readers find
    (``harness.STEP_MODULE``, ``flash_decode.roofline``'s ``is_kernel``):
    minitron-4b at its widths, cut to two layers, on one described v5e.
    The kernels are picked as on a TPU, which this process is not."""
    import dataclasses
    from unittest import mock

    from benchmarks.tpu import harness, spec
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh, named_shardings
    from repro.launch.serve import make_step
    from repro.models.registry import (abstract_cache, abstract_params,
                                       cache_specs, get_arch, state_specs)

    cfg = dataclasses.replace(get_arch("minitron-4b"), n_layers=2)
    B, S = 4, 96
    mesh = make_mesh(1, 1, devices=list(one_chip.device_set))

    def shaped(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            tree, named_shardings(mesh, specs))

    params = abstract_params(cfg)
    params = shaped(params, state_specs(cfg, params))
    cache = abstract_cache(cfg, B, S)
    cache = shaped(cache, cache_specs(cfg, cache, "decode_32k",
                                      n_model=1))
    repl = named_shardings(mesh, None)
    tok = jax.ShapeDtypeStruct((B,), I32, sharding=repl)
    pos = jax.ShapeDtypeStruct((), I32, sharding=repl)
    on_tpu = {"auto": "pallas"}
    with mock.patch.object(ops, "_resolve",
                           lambda impl, interpret: on_tpu.get(impl, impl)):
        text = make_step(cfg, mesh, cache).lower(
            params, cache, tok, pos).compile().as_text()
    assert text.startswith(f"HloModule {harness.STEP_MODULE},")
    roofline = spec.load_module(
        ROOT / "benchmarks" / "tpu" / "metrics" / "flash_decode.roofline.py",
        "bench_metric_flash_decode_roofline")
    calls = custom_calls(text)
    assert calls and all(roofline.is_kernel(c) for c in calls)
    assert all("/flash_decode/pallas_call" in c for c in calls)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(where, tmp_path):
    """Off the chip, or away from the repository, the smoke script exits
    non-zero and never prints its result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        (tmp_path / script.name).write_bytes(script.read_bytes())
        script = tmp_path / script.name
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
