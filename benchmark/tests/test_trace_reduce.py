"""The reduction from a trace to numbers: on hand-made intervals, and on
a small trace recorded on the chip (``data/trace_small.json.gz``: the
first 1,500 device operations of a traced window of
``gpt2-large.serve-doc-closed``, PR 23, cut by ``trace_dump.py``)."""

import os

import numpy as np

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 12), (20, 30), (30, 31), (50, 60)]
    assert tr.union_seconds(iv) == 12 + 11 + 10
    assert tr.gaps(iv, 0, 70) == [(12, 20), (31, 50), (60, 70)]
    assert tr.gaps(iv, 6, 25) == [(12, 20)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_busy_idle_and_gap_attribution_by_hand():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [["jit_step(1)", 10.0, 30.0],
                            ["jit_step(1)", 60.0, 30.0]],
            "XLA Ops": [["%a", 10.0, 10.0], ["%b", 15.0, 25.0],
                        ["%a", 60.0, 30.0]]},
        "/host:CPU": {"python3": [
            ["bench:window", 0.0, 100.0], ["bench:submit", 0.0, 8.0],
            ["bench:wait", 41.0, 18.0], ["other", 0.0, 100.0]]}}
    t = tr.Trace(planes)
    busy, window = t.busy_and_window()
    assert (busy, window) == (60e-9, 100e-9)  # [10,40] and [60,90]
    assert t.modules() == {"jit_step(1)": (2, 60e-9)}
    assert t.op_seconds() == {"%a": 40e-9, "%b": 25e-9}
    # gaps: [40,60] 20 ns under `wait`; [0,10] and [90,100] 10 ns
    assert t.idle_gaps(3) == [["wait", 20e-9], ["submit", 10e-9],
                              ["unattributed", 10e-9]]


def test_kernel_names_and_shapes():
    full = ('%prefill.36 = bf16[1,1024,1280]{2,1,0:T(8,128)(2,1)} '
            'custom-call(bf16[1,1024,3840]{2,1,0:T(8,128)(2,1)} %x), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    short = tr.short_name(full)
    assert short == "%prefill.36 [tpu_custom_call bf16[1,1024,3840]]"
    assert tr.short_name("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "%fusion.1"
    t = tr.Trace({"/device:TPU:0": {"XLA Ops": [[short, 0.0, 500.0]]}})
    assert t.kernels() == [(short, [1, 1024, 3840], 500e-9)]


def test_recorded_trace():
    t = tr.Trace.from_json(os.path.join(HERE, "data",
                                        "trace_small.json.gz"))
    busy, window = t.busy_and_window()
    # an independent count: paint the operations on a 100 ns grid
    lo, hi = t.window()
    ops = t.planes["/device:TPU:0"]["XLA Ops"]
    grid = np.zeros(int((hi - lo) / 100) + 1, bool)
    for _, s, d in ops:
        grid[int((s - lo) / 100):int((s + d - lo) / 100)] = True
    assert abs(grid.sum() * 100e-9 - busy) < 0.01 * busy
    assert 0 < busy < window and abs(window - (hi - lo) / 1e9) < 1e-12
    # the longest gap is the trace's own (5.4 ms between two programs,
    # the benchmark waiting for a result); then the two the fixture was
    # cut with, 2 ms after the last operation and 1 ms before the first
    gaps = t.idle_gaps(3)
    assert gaps[0][0] == "wait_result" and abs(gaps[0][1] - 5.38e-3) < 1e-5
    assert abs(gaps[1][1] - 2e-3) < 1e-5 and abs(gaps[2][1] - 1e-3) < 1e-5
    mods = t.modules()
    assert any(k.startswith("jit_prefill(") for k in mods)
    shapes = {tuple(s) for _, s, _ in t.kernels()}
    assert (1, 1024, 3840) in shapes      # flash prefill: packed q|k|v
    assert all(name.startswith("%") for name, _ in
               t.breakdown()["device_ops"])
