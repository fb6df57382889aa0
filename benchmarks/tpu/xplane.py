"""Reads a JAX profiler trace (``.xplane.pb``) into plain intervals.

Device planes (``/device:TPU:<n>``) give two lines: ``XLA Ops``, one
event for each operation the chip ran, and ``XLA Modules``, one event
for each execution of a compiled program (named ``jit_<function>``).
The host plane gives the benchmark's own spans, written with
``jax.profiler.TraceAnnotation`` and named ``bench.*``, and what the
host threads were doing.  All times are in seconds on the profiler's
clock, which the device and host planes share.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start, end), seconds


@dataclass
class Op:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Device:
    ops: List[Op] = field(default_factory=list)
    modules: List[Op] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Op]                      # every host event, all threads
    spans: Dict[str, List[Op]]          # bench.* spans by name

    def window(self) -> Interval:
        """From the first ``bench.*`` span's start to the last's end."""
        allspans = [s for v in self.spans.values() for s in v]
        return (min(s.start for s in allspans),
                max(s.end for s in allspans))


_OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9-]*)\(")


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text:
    ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def opcode(text: str) -> str:
    """``%fusion.12 = (bf16[...], ...) fusion(...)`` -> ``fusion``; the
    name where the text is not an HLO instruction."""
    m = _OPCODE.search(text)
    return m.group(1) if m else op_name(text)


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _device_index(plane_name: str) -> Optional[int]:
    prefix = "/device:TPU:"
    if not plane_name.startswith(prefix):
        return None
    rest = plane_name[len(prefix):]
    return int(rest) if rest.isdigit() else None


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    host: List[Op] = []
    spans: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            dev = devices.setdefault(idx, Device())
            for line in plane.lines:
                dest = {"XLA Ops": dev.ops,
                        "XLA Modules": dev.modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dest.append(Op(e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    op = Op(e.name, s, s + e.duration_ns * 1e-9)
                    host.append(op)
                    if e.name.startswith("bench."):
                        spans.setdefault(e.name, []).append(op)
    for dev in devices.values():
        dev.ops.sort(key=lambda o: o.start)
        dev.modules.sort(key=lambda o: o.start)
    return Trace(devices, host, spans)


def union(intervals: List[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle stretches of [lo, hi] that ``intervals`` leave."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]
