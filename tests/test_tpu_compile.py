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
