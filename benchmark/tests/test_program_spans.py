"""The readers of the program's own spans and counters: on hand-made
spans, on a small trace recorded on the chip
(``data/program_spans_small.json.gz``: the device operations and the
engine thread's spans of a stretch of a traced window of
``gpt2-large.serve-doc-closed``, PR 25), and end to end in a tiny cell
whose root is built in ``tmp_path``."""

import json
import os
import shutil
import types

import pytest

from benchmark import trace_reduce as tr
from benchmark.reducers import (compile_seconds, engine_host_ms,
                                engine_prefill_share, idle_attributed,
                                module_ms, paged_roofline)
from benchmark.reducers import program_spans as ps

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY_ROOT = os.path.join(HERE, "data", "root")
KERNEL = "%paged_attention.7 [tpu_custom_call s32[4,8]]"


def by_hand():
    """A window [0, 1000]: one prefill, a plain step, a pipelined pair,
    an idle wait.  Device busy [100, 300] (prefill), [420, 480],
    [520, 700] (two programs back to back), idle elsewhere."""
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [["jit_prefill_t8(1)", 100.0, 200.0],
                            ["jit_step_decode_b4x8(2)", 420.0, 60.0],
                            ["jit_step_decode_b4x8(2)", 520.0, 90.0],
                            ["jit_step_decode_b4x8(2)", 610.0, 90.0]],
            "XLA Ops": [["%fusion.1", 100.0, 200.0],
                        [KERNEL, 420.0, 30.0], ["%fusion.2", 450.0, 30.0],
                        [KERNEL, 520.0, 40.0], ["%fusion.2", 560.0, 50.0],
                        [KERNEL, 610.0, 50.0], ["%fusion.2", 660.0, 40.0]]},
        "/host:CPU": {"python#0": [["bench:window", 0.0, 1000.0],
                                   ["bench:wait_result", 0.0, 1000.0]]}}
    threads = {
        "mx-decode-loop#1": [
            ["serving.admit", 50.0, 320.0, {"pending": "1"}],
            ["serving.prefill.t8", 60.0, 310.0, {"sids": "3"}],
            ["serving.d2h_sync", 110.0, 305.0, {"sids": "3"}],
            # a plain step: 100 long, 50 of it waiting for the device
            ["serving.step", 400.0, 500.0, {"active": "4"}],
            ["serving.stage", 405.0, 415.0, {}],
            ["serving.decode_step.b4x8", 415.0, 425.0, {}],
            ["serving.d2h_sync", 430.0, 480.0, {}],
            ["serving.absorb", 481.0, 499.0, {"retired": "0"}],
            # a pipelined pair: 210 long, 120 + 40 of it waiting
            ["serving.step", 500.0, 710.0, {"active": "4"}],
            ["serving.stage", 502.0, 510.0, {}],
            ["serving.decode_step.b4x8", 510.0, 518.0, {}],
            ["serving.stage", 518.0, 524.0, {}],
            ["serving.decode_step.b4x8", 524.0, 530.0, {}],
            ["serving.d2h_sync", 530.0, 650.0, {}],
            ["serving.absorb", 650.0, 660.0, {"retired": "0"}],
            ["serving.d2h_sync", 660.0, 700.0, {}],
            ["serving.absorb", 700.0, 709.0, {"retired": "1"}],
            ["serving.idle", 720.0, 990.0, {}]],
        "python#0": [["Executor.forward", 10.0, 20.0, {}]]}
    spans = ps.ProgramSpans({t: [tuple(e) for e in ev]
                             for t, ev in threads.items()})
    return tr.Trace(planes), spans


def sources(trace, spans, **more):
    return dict({"trace": trace, "program_spans": spans}, **more)


def test_self_time_with_nested_children_and_a_pipelined_pair():
    trace, spans = by_hand()
    assert spans.engine_thread()[0][0] == "serving.admit"
    # (100 - 50) + (210 - 120 - 40) ns of self time over 1 + 2 programs
    got = engine_host_ms.read(sources(trace, spans))
    assert got == pytest.approx((50 + 50) / 1e6 / 3)


def test_prefill_share_of_the_engine_threads_working_time():
    trace, spans = by_hand()
    got = engine_prefill_share.read(sources(trace, spans))
    assert got == pytest.approx(100.0 * 250 / (250 + 100 + 210))


def test_idle_under_serving_idle_is_named_but_not_attributed():
    trace, spans = by_hand()
    # gaps: [0,100] [300,420] [480,520] [700,1000]; covered by working
    # spans: 50 (admit) + 20 + 20 (admit, step) + 40 + 10 (step)
    got = idle_attributed.read(sources(trace, spans))
    idle = 100 + 120 + 40 + 300
    assert got == pytest.approx(100.0 * (50 + 40 + 40 + 10) / idle)
    holes = tr.gaps([(a, b) for _, a, b in
                     trace.ops("/device:TPU:0")], 0.0, 1000.0)
    named = idle_attributed.name_gaps(holes, spans.all_spans(), top=4)
    assert [n for n, _ in named] == [
        "serving.idle", "serving.step", "serving.admit", "serving.step"]
    assert named[0][1] == pytest.approx(300e-9)


def test_paged_roofline_by_hand():
    trace, spans = by_hand()
    cell = types.SimpleNamespace(
        config={"n_layer": 2, "n_embd": 64},
        workload={"dtype": "bfloat16"})
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    stats = {"context_tokens": 3000, "steps": 3, "stream_steps": 12}
    got = paged_roofline.read(sources(trace, spans, cell=cell, run=run,
                                      engine_stats=stats))
    # per step: (2 x 1000 tokens + 2 x 4 rows) x 64 x 2 B x 2 layers
    nbytes = (2 * 1000 + 2 * 4) * 64 * 2 * 2
    spent = (30 + 40 + 50) * 1e-9 / 3
    assert got == pytest.approx(100.0 * (nbytes / 819e9) / spent)
    # a program that does not count its context reports nothing
    stats = {"steps": 3, "stream_steps": 12}
    assert paged_roofline.read(sources(
        trace, spans, cell=cell, run=run, engine_stats=stats)) is None


def test_decode_and_prefill_programs_by_their_names():
    trace, _ = by_hand()
    assert module_ms.read({"trace": trace}, ["jit_step"]) == \
        pytest.approx(1e3 * 240e-9 / 3)
    assert module_ms.read({"trace": trace}, ["jit_prefill"]) == \
        pytest.approx(1e3 * 200e-9)


def test_a_program_without_spans_reports_nothing():
    trace, _ = by_hand()
    none = ps.ProgramSpans({})
    for reader in (engine_host_ms, engine_prefill_share, idle_attributed):
        assert reader.read(sources(trace, none)) is None
    # an untraced run: no trace, no spans
    run = types.SimpleNamespace(traced=False, trace_dir=None)
    assert ps.load({"run": run}) is None
    assert engine_host_ms.read({"run": run, "trace": None}) is None


def test_compile_events_after_the_window_opened_are_left_out(
        monkeypatch):
    from mxnet_tpu import profiler

    events = [(10.0, "trace", 2.0), (11.0, "lower", 3.0),
              (12.0, "backend", 0.5), (12.5, "cache_fetch", 0.4),
              (50.0, "trace", 7.0), (51.0, "backend", 9.0)]
    monkeypatch.setattr(profiler, "compile_events", lambda: events)
    run = types.SimpleNamespace(t0=20.0)
    assert compile_seconds.read({"run": run}, ["trace", "lower"]) == 5.0
    assert compile_seconds.read({"run": run}, ["backend"]) == 0.5
    # nor what was built before this run's own clock started (another
    # run in the same process: the tests)
    run = types.SimpleNamespace(t0=20.0, t_process=10.5)
    assert compile_seconds.read({"run": run}, ["trace", "lower"]) == 3.0
    # a program that keeps no such log
    monkeypatch.delattr(profiler, "compile_events")
    assert compile_seconds.read({"run": run}, ["backend"]) is None


def test_recorded_trace():
    """What the chip wrote (PR 25): the engine thread's spans beside
    the device's operations, on one clock."""
    path = os.path.join(HERE, "data", "program_spans_small.json.gz")
    spans = ps.ProgramSpans.from_json(path)
    trace = tr.Trace.from_json(os.path.join(
        HERE, "data", "trace_spans_small.json.gz"))
    thread = spans.engine_thread()
    names = {e[0] for e in thread}
    assert {"serving.step", "serving.stage", "serving.d2h_sync",
            "serving.absorb", "serving.admit"} <= names
    assert any(n.startswith("serving.decode_step.b48x64") for n in names)
    assert any(n.startswith("serving.prefill.t1024") for n in names)
    src = sources(trace, spans)
    host = engine_host_ms.read(src)
    assert 1.0 < host < 100.0            # ms a program, of 230
    assert 20.0 < engine_prefill_share.read(src) < 80.0
    assert idle_attributed.read(src) > 80.0
    # the kernels under their own names, the programs under theirs
    assert any("paged_attention" in n for n, _, _ in trace.kernels())
    assert any("flash_fwd_packed" in n for n, _, _ in trace.kernels())
    assert module_ms.read(src, ["jit_step_decode"]) > 100.0
    assert module_ms.read(src, ["jit_prefill_t1024"]) > 100.0


def test_tiny_cell_reports_the_new_metrics(run_cell, tmp_path):
    """A tiny closed-loop cell, traced, in a root of its own: a copy of
    ``tests/data/root`` with this PR's entries and reader files added
    (the CPU has no device plane: the device's readers report nothing
    and are left out)."""
    root = str(tmp_path / "root")
    shutil.copytree(TINY_ROOT, root)
    cell = "gpt2-tiny.serve-tiny-closed"
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench_path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    have = {m["name"] for m in bench["per_layer"]}
    for m in real["per_layer"]:
        if m["name"] in have:
            continue
        bench["per_layer"].append(dict(m, workloads=[cell]))
        name = m["name"] + ".json"
        shutil.copy(os.path.join(ROOT, "benchmark", "layer_metrics", name),
                    os.path.join(root, "benchmark", "layer_metrics", name))
    json.dump(bench, open(bench_path, "w"))
    result, lines = run_cell(cell, root=root, trace=1)
    assert result["correct"] is True
    got = result["metrics"]
    assert {"engine_host_ms_per_step.closed",
            "engine_prefill_share.closed", "trace_lower_s.setup",
            "executable_fetch_s.setup"} <= set(got)
    assert "paged_roofline.serve" not in got
    assert "device_idle_attributed.closed" not in got
    assert got["engine_host_ms_per_step.closed"]["value"] > 0
    assert 0 < got["engine_prefill_share.closed"]["value"] < 100
    assert got["trace_lower_s.setup"]["value"] > 0
    assert got["executable_fetch_s.setup"]["value"] > 0
    log = [ln for ln in lines if "engine" in ln][0]["engine"]
    assert log["steps"] > 0
