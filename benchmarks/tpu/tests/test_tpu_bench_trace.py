"""The trace reader and the metric readers on a small device trace.

``data/small_decode.xplane.pb`` was recorded on one TPU v5e: a jitted
``step`` (the Pallas ``flash_decode`` on 2 streams of 8 query heads over
2 KV heads of 128, a 512-row cache, then a matmul) run at kv_len 100,
300 and 512 inside a ``bench.prompt`` span and at 512 inside
``bench.decode``.  34 KB.
"""
import os

import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import harness, xplane
from benchmarks.tpu.peaks import peaks
from benchmarks.tpu.spec import load_module

PATH = os.path.join(os.path.dirname(__file__), "data",
                    "small_decode.xplane.pb")
METRICS = os.path.join(h.REPO, "benchmarks", "tpu", "metrics")


@pytest.fixture(scope="module")
def trace():
    return xplane.read(PATH)


def test_planes_lines_and_spans(trace):
    assert list(trace.devices) == [0]
    dev = trace.devices[0]
    assert len(dev.ops) == 28
    assert [m.name.split("(")[0] for m in dev.modules] == ["jit_step"] * 4
    assert sorted(trace.spans) == ["bench.decode", "bench.prompt"]
    assert len(trace.spans["bench.prompt"]) == 1
    kern = [o for o in dev.ops if xplane.op_name(o.name).startswith(
        "flash_decode")]
    assert [round(o.dur * 1e9) for o in kern] == [16468, 18602, 18747,
                                                   18790]
    lo, hi = trace.window()
    assert lo == pytest.approx(0.048653359)
    assert hi == pytest.approx(0.050141639)


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (10.0, 11.0)]
    assert xplane.union(iv, 0.0, 5.0) == 3.0
    assert xplane.union(iv, 1.5, 3.5) == 1.0
    assert xplane.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert xplane.gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def _ctx(trace):
    """The four steps as one decode position each, one layer, with the
    device's own span of the steps as the window."""
    dev = trace.devices[0]
    config = {"family": "dense", "dtype": "bfloat16", "n_layers": 1,
              "n_heads": 8, "n_kv_heads": 2, "head_dim": 128,
              "d_model": 1024, "d_ff": 0, "gated_mlp": False,
              "vocab": 1024}
    window = (dev.modules[0].start, dev.modules[-1].end)
    return harness.TraceContext(config, {"batch": 2, "prompt_len": 3},
                                1, peaks("TPU v5 lite"), trace, window,
                                [99, 299, 511, 511])


def test_flash_decode_roofline_by_hand(trace):
    ctx = _ctx(trace)
    read = load_module(os.path.join(METRICS, "flash_decode.roofline.py"),
                       "bench_metric_flash_decode_roofline").read
    # K and V rows of 2 heads x 128 in bf16, plus q and out of 8 heads
    need = sum((2 * 2 * 2 * n * 128 + 2 * 2 * 8 * 128) * 2
               for n in (100, 300, 512, 512))
    kernel_s = (16468 + 18602 + 18747 + 18790) * 1e-9
    assert read(ctx) == pytest.approx(100 * need / 819e9 / kernel_s,
                                      rel=1e-3)


def test_device_idle_and_prompt_span(trace):
    ctx = _ctx(trace)
    idle = load_module(os.path.join(METRICS, "device_idle.py"),
                       "bench_metric_device_idle").read(ctx)
    lo, hi = ctx.window
    busy = xplane.union([(o.start, o.end) for o in trace.devices[0].ops],
                        lo, hi)
    assert 0.0 < idle < 100.0
    assert idle == pytest.approx(100 * (1 - busy / (hi - lo)))
    pre = load_module(os.path.join(METRICS, "prefill_ms_per_tok.py"),
                      "bench_metric_prefill_ms_per_tok").read(ctx)
    assert pre == pytest.approx(1e3 * 0.000886271 / 3)


def test_collective_share_reads_nothing_on_one_chip(trace):
    read = load_module(os.path.join(METRICS, "collective_share.py"),
                       "bench_metric_collective_share").read
    assert read(_ctx(trace)) is None


def test_breakdown_names_ops_and_gaps(trace):
    b = harness.breakdown(_ctx(trace))
    names = [n for n, _ in b["device_ops"]]
    assert names[0].startswith(("flash_decode", "fusion", "copy"))
    assert all(not n.startswith("while") for n in names)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("text,coll", [
    ("%all-gather-start.3 = (bf16[8,128]{1,0}, bf16[32,128]{1,0}) "
     "all-gather-start(bf16[8,128]{1,0} %p), dimensions={0}", True),
    ("%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x), to_apply=%add",
     True),
    ("%fusion.9 = bf16[4]{0} fusion(bf16[4]{0} %all-reduce.1), "
     "kind=kLoop", False),
    ("%flash_decode.1 = (bf16[2,8,1,128]{3,2,1,0}) custom-call("
     "bf16[2,8,1,128]{3,2,1,0} %q), custom_call_target=\"tpu_custom_call\"",
     False),
])
def test_collectives_are_named_by_the_op_itself(text, coll):
    mod = load_module(os.path.join(METRICS, "collective_share.py"),
                      "bench_metric_collective_share")
    kern = load_module(os.path.join(METRICS, "flash_decode.roofline.py"),
                       "bench_metric_flash_decode_roofline")
    assert mod.is_collective(text) is coll
    assert kern.is_kernel(text) is text.startswith("%flash_decode")
    # a consumer of the kernel's output names it only among its operands
    assert not kern.is_kernel("%get-tuple-element.2 = bf16[2]{0} "
                              "get-tuple-element(%flash_decode.1), index=0")
