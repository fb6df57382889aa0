"""Operations and bytes of a decode step against hand-worked values."""
import pytest

import tpu_bench_helpers as h  # noqa: F401  (puts the repo on the path)
from benchmarks.tpu import counts, spec
from benchmarks.tpu.peaks import peaks

# (cell, pos) -> (flops, bytes), worked by hand from the published sizes
HAND = {
    # head 2*64*3072*256000 + 32 layers * (2*64*81,788,928 matmul
    # weights + 4*64*24*1*128 attention); weights 6,808,147,968 bytes
    # (embedding rows only, nominal heads) + logits 64*256000*2 + cache
    # 32*2*64*8*(pos+2)*128*2
    ("minitron-4b.chat-b64", 0): (435_695_910_912, 6_857_693_184),
    ("minitron-4b.chat-b64", 127): (435_695_910_912
                                    + 32 * 4 * 64 * 24 * 127 * 128,
                                    6_808_147_968 + 32_768_000
                                    + 1_082_130_432),
    # head 2*256*1024*50280 + 48 layers * 256 * (2*6,586,368 + conv
    # 2*4*2304 + SSD 6*32*64*128); weights 736,989,184 + logits
    # 25,743,360 + state 48*256*2*269,056*2 read and written
    ("mamba2-370m.chat-b256", 0): (207_781_625_856, 13_987_373_056),
    ("mamba2-370m.chat-b256", 200): (207_781_625_856, 13_987_373_056),
    # head 2*32*6144*49152 + 52 layers * (2*32*379,060,224 + 4*32*48*128);
    # weights 40,027,926,528 + logits 3,145,728 + cache 52*2*32*2*128*2
    ("granite-20b.tp4.chat-b32", 0): (1_280_880_672_768, 40_032_776_192),
}


@pytest.mark.parametrize("cell,pos", sorted(HAND))
def test_decode_step_counts(cell, pos):
    c = spec.cell(cell)
    w = counts.decode_step(c.config, c.traffic["batch"], pos)
    flops, nbytes = HAND[(cell, pos)]
    assert w.flops == flops
    assert w.bytes == nbytes


def test_attention_kernel_counts():
    c = spec.cell("minitron-4b.chat-b64").config
    w = counts.attention_kernel(c, 64, 200)
    assert w.flops == 4 * 64 * 24 * 200 * 128
    # K and V rows of 8 heads, q read and out written for 24 heads
    assert w.bytes == (2 * 64 * 8 * 200 * 128 + 2 * 64 * 24 * 128) * 2


def test_least_time_takes_the_larger_bound():
    p = peaks("TPU v5 lite")
    w = counts.Work(flops=197e12, bytes=819e9 * 2)
    assert w.least_s(p) == 2.0
    assert w.least_s(p, chips=4) == 0.5
    with pytest.raises(KeyError):
        peaks("no such chip")
