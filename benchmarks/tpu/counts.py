"""Operations and bytes that a decode step needs, from shapes alone.

The counts are of the model's work, whatever implements it: nominal
heads (no padding), each weight read once (the embedding only for the
rows looked up), cache rows up to the position attended and the new row
written, SSM state read and written, the logits written.  Matmuls count
2 operations a multiply-add; what else a family's layers count is in
their file (``families/<family>.py``).  Norms, activations, RoPE and
softmax are left out: they are a small share and no peak is stated for
them.

A step's least time on ``chips`` chips is the larger of its operations
over ``chips`` times the peak and its bytes over ``chips`` times the HBM
bandwidth: the work spread evenly, with nothing read twice.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def least_s(self, peaks, chips: int = 1) -> float:
        return max(self.flops / (chips * peaks.bf16_flops),
                   self.bytes / (chips * peaks.hbm_bw))


def weight_bytes(c: Dict[str, Any], batch: int) -> float:
    """Bytes of weights one decode step reads: every matrix once in each
    layer of its stack, the embedding's looked-up rows."""
    from .weights import leaves, stack_counts, stack_of
    layers = stack_counts(c)
    total = 0.0
    for lf in leaves(c):
        n = 1
        for s in lf.shape:
            n *= s
        if lf.path == "embed":
            n = batch * c["d_model"]
        total += n * BYTES[lf.dtype] * (layers[stack_of(lf)] if lf.stacked
                                        else 1)
    return total


def attention_kernel(c: Dict[str, Any], batch: int, kv_len: int) -> Work:
    """One layer's decode attention: K and V rows up to ``kv_len`` read
    once, q read and the output written."""
    H, Hkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    b = BYTES[c["dtype"]]
    return Work(flops=4.0 * batch * H * kv_len * hd,
                bytes=(2.0 * batch * Hkv * kv_len * hd
                       + 2.0 * batch * H * hd) * b)


def decode_step(c: Dict[str, Any], batch: int, pos: int) -> Work:
    """The whole model's decode step for ``batch`` streams at ``pos``:
    the head's operations, every weight read, the logits written, and
    the family's layer stack (``families/<family>.py`` ``step_work``)."""
    from .spec import family
    own = Work(flops=2.0 * batch * c["d_model"] * c["vocab"],
               bytes=weight_bytes(c, batch)
               + batch * c["vocab"] * BYTES[c["dtype"]])
    return own + family(c["family"]).step_work(c, batch, pos)
