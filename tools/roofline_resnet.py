#!/usr/bin/env python
"""ResNet-50 roofline exhaustion table (r4 verdict weak #1 / next #3).

Profiles the fused training step per-HLO and, for every op above a
time threshold, estimates HBM traffic from the tensor types in the
HLO expression (operands + results; fusion intermediates stay on-chip)
to report achieved GB/s against the chip's ~745 GB/s achievable HBM
bandwidth and the op's share of step time.  The output is the
"remaining sinks are within X% of achievable bandwidth" evidence for
PERF.md — or the pointer at which op still has slack.

Usage: BENCH_BATCH=128 python tools/roofline_resnet.py
"""

import os
import re
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

from mxnet_tpu.config import place_compile_cache

place_compile_cache()

import numpy as np

from profile_step import find_xplane, parse_xplane, run_trace

ACHIEVABLE_GBS = 745.0  # measured STREAM-like ceiling on this v5e (PERF.md)
PEAK_TFLOPS = 197.0

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "s64": 8, "u64": 8}
_TENSOR_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|"
                        r"pred)\[([0-9,]*)\]")


def tensor_bytes(expr):
    """Sum the bytes of every tensor type named in an HLO expression —
    operands + results ≈ the op's HBM traffic (fusion internals never
    appear in the signature)."""
    total = 0
    for dt, dims in _TENSOR_RE.findall(expr):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def main():
    tdir = tempfile.mkdtemp(prefix="roofline_")
    steps, _batch = run_trace(tdir)  # profile_step's exact recipe
    (mod_ms, mod_n), busy_ms, rows = parse_xplane(find_xplane(tdir))
    step_ms = busy_ms / steps
    print(f"\ndevice busy {step_ms:.3f} ms/step (module span {mod_ms:.3f})")

    table = []
    for name, cls, ms_total in rows:
        ms = ms_total / steps
        if ms < 0.2:
            continue
        nbytes = tensor_bytes(name)
        gbs = nbytes / (ms / 1e3) / 1e9 if ms > 0 else 0.0
        table.append((ms, cls, gbs, nbytes / 1e6, name))
    table.sort(reverse=True)

    print(f"\n{'ms/step':>8} {'share':>6} {'MB':>8} {'GB/s':>7} "
          f"{'%BW':>5}  op")
    covered = 0.0
    for ms, cls, gbs, mb, name in table:
        covered += ms
        short = re.sub(r"\{[^}]*\}", "", name)[:95]
        print(f"{ms:8.3f} {ms / step_ms:6.1%} {mb:8.1f} {gbs:7.0f} "
              f"{min(gbs / ACHIEVABLE_GBS, 9.99):5.0%}  [{cls}] {short}")
    rest = step_ms - covered
    print(f"{rest:8.3f} {rest / step_ms:6.1%} {'':>8} {'':>7} {'':>5}  "
          f"(all ops < 0.2 ms/step)")
    mem_floor = sum(mb for ms, cls, gbs, mb, name in table) / 1e3 \
        / ACHIEVABLE_GBS * 1e3
    print(f"\nsum of listed traffic / achievable BW = {mem_floor:.1f} ms "
          f"floor for the listed ops")


if __name__ == "__main__":
    main()
