"""Monitor / profiler / visualization / telemetry tests (reference:
monitor usage in docs, test_viz.py, profiler dump format; plus the
observability layer: trace args, metrics registry + exporters, the
straggler watchdog, and the tools/ parsers)."""

import json
import logging
import math
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_monitor_collects_stats():
    np.random.seed(0)
    X = np.random.randn(40, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=10)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mon = mx.Monitor(interval=1, pattern=".*")
    mod.install_monitor(mon)
    b = next(iter(it))
    mon.tic()
    mod.forward(b, is_train=False)
    res = mon.toc()
    names = [k for _, k, _ in res]
    assert any("fc" in n for n in names), names
    assert any("weight" in n for n in names), names  # weights stat'd too
    for _, _, v in res:
        assert "nan" not in v.lower()


def test_monitor_finds_nan():
    """The NaN-hunt workflow: a poisoned weight shows up in the stats."""
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    bad = mod._exec.arg_dict["fc_weight"].asnumpy().copy()
    bad[0, 0] = np.nan
    mod._exec.arg_dict["fc_weight"][:] = bad
    mon = mx.Monitor(interval=1, pattern=".*fc.*")
    mod.install_monitor(mon)
    mon.tic()
    mod.forward(mx.io.DataBatch([mx.nd.zeros((4, 6))],
                                [mx.nd.zeros((4,))]), is_train=False)
    res = mon.toc()
    assert any("nan" in v.lower() for _, _, v in res), res


def test_profiler_chrome_trace(tmp_path):
    fname = str(tmp_path / "trace.json")
    mx.profiler.profiler_set_config(mode="all", filename=fname)
    mx.profiler.profiler_set_state("run")
    X = np.random.randn(30, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=10)
    mod = mx.mod.Module(_mlp_binary(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd")
    mx.profiler.profiler_set_state("stop")
    assert os.path.isfile(fname)
    with open(fname) as f:
        trace = json.load(f)
    events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(events) > 0
    names = {e["name"] for e in events}
    assert any("fused_step" in n or "forward" in n for n in names), names
    for e in events:
        assert "ts" in e and "dur" in e
    # process metadata + clock anchor ride every dump (trace_merge input)
    meta = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    assert "clock_sync" in trace["metadata"]


def _mlp_binary():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_print_summary():
    out = mx.viz.print_summary(_mlp(), shape={"data": (8, 6)})
    assert "fc1(FullyConnected)" in out
    assert "Total params" in out
    # fc1: 6*8+8 = 56; fc2: 8*3+3 = 27
    assert "Total params: 83" in out


def test_plot_network():
    dot = mx.viz.plot_network(_mlp(), shape={"data": (8, 6)},
                              save_format="dot")
    src = dot.source
    assert "fc1" in src and "relu1" in src and "softmax" in src
    assert "fc1_weight" not in src  # weights hidden
    assert "->" in src or "--" in src


def test_xla_trace_smoke(tmp_path):
    """jax.profiler passthrough writes an XPlane trace directory."""
    logdir = str(tmp_path / "xla")
    mx.profiler.start_xla_trace(logdir)
    mx.nd.dot(mx.nd.ones((32, 32)), mx.nd.ones((32, 32))).asnumpy()
    mx.profiler.stop_xla_trace()
    found = []
    for root, _, files in os.walk(logdir):
        found.extend(files)
    assert found, "no trace files written"


def test_monitor_fires_during_training():
    """The fused path must yield to the tap: training forwards are monitored."""
    np.random.seed(1)
    X = np.random.randn(20, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=10)
    mod = mx.mod.Module(_mlp_binary(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd")
    mon = mx.Monitor(interval=1, pattern=".*output.*")
    mod.install_monitor(mon)
    b = next(iter(it))
    mon.tic()
    mod.forward(b, is_train=True)
    mod.backward()
    mod.update()
    res = mon.toc()
    assert any("output" in k for _, k, _ in res), res


def test_env_var_catalog():
    """Every env var the code reads is declared in the config catalog."""
    import re

    cat = {v.name for v in mx.config.list_env()}
    # scan the source for MXNET_* reads
    used = set()
    pkg = os.path.dirname(mx.__file__)
    for root, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py") or f == "config.py":
                continue
            src = open(os.path.join(root, f)).read()
            used.update(re.findall(r"MXNET_[A-Z_]+", src))
    # family-wildcard mentions in docs/comments ("MXNET_CKPT_*",
    # "MXNET_CHAOS_*") regex-capture as a trailing-underscore token —
    # they reference a declared family, not an undeclared var
    used = {u for u in used if not u.endswith("_")}
    missing = used - cat
    assert not missing, f"undeclared env vars: {sorted(missing)}"
    # catalog answers queries
    v = mx.config.describe("MXNET_BACKWARD_DO_MIRROR")
    assert v.default == 0 and "recompute" in v.doc
    cur = mx.config.current()
    assert "MXNET_FUSED_STEP" in cur


# ---------------------------------------------------------------------------
# telemetry layer: trace args, metrics registry, exporters, watchdog
# ---------------------------------------------------------------------------


def test_trace_event_args(tmp_path):
    """scope/add_event carry an args dict into the trace viewer."""
    fname = str(tmp_path / "trace.json")
    mx.profiler.profiler_set_config(mode="all", filename=fname)
    mx.profiler.profiler_set_state("run")
    with mx.profiler.scope("unit.work", "test",
                           args={"step": 7, "bytes": 128}):
        pass
    t0 = time.perf_counter()
    mx.profiler.add_event("unit.xthread", t0, 0.001, "test",
                          args={"bucket": 32})
    mx.profiler.profiler_set_state("stop")
    with open(fname) as f:
        trace = json.load(f)
    evs = {e["name"]: e for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert evs["unit.work"]["args"] == {"step": 7, "bytes": 128}
    assert evs["unit.xthread"]["args"]["bucket"] == 32
    # rank metadata + the clock anchor trace_merge aligns with
    assert trace["metadata"]["rank"] == 0
    assert "wall_time_s" in trace["metadata"]["clock_sync"]
    assert "perf_counter_s" in trace["metadata"]["clock_sync"]


def test_autostart_guard(tmp_path):
    """MXNET_PROFILER_AUTOSTART must be optional-out-able: test suites
    import the package without an env var flipping global state."""
    prof = mx.profiler
    assert not prof._profiler.running
    assert not prof._env_autostart({})
    assert not prof._env_autostart({"MXNET_PROFILER_AUTOSTART": "0"})
    assert not prof._env_autostart({"MXNET_PROFILER_AUTOSTART": "1",
                                    "MXNET_PROFILER_NO_AUTOSTART": "1"})
    assert not prof._profiler.running
    prof.profiler_set_config(mode="all", filename=str(tmp_path / "a.json"))
    try:
        assert prof._env_autostart({"MXNET_PROFILER_AUTOSTART": "1"})
        assert prof._profiler.running
    finally:
        prof.profiler_set_state("stop")
    assert not prof._profiler.running


def test_metrics_summary_p90_and_rates():
    mx.profiler.reset_metrics()
    mx.profiler.inc_counter("unit.count", 5)
    for v in range(1, 101):
        mx.profiler.observe("unit.lat_ms", float(v))
    s = mx.profiler.metrics_summary()
    assert s["counters"]["unit.count"] == 5
    h = s["histograms"]["unit.lat_ms"]
    assert h["count"] == 100
    assert 88 <= h["p90"] <= 92
    assert h["p50"] <= h["p90"] <= h["p99"]
    # per-counter rate since reset (the reporter/bench shared schema)
    assert s["rates"]["unit.count"] > 0
    assert s["elapsed_s"] > 0
    mx.profiler.reset_metrics()


def test_gauges():
    mx.profiler.reset_metrics()
    mx.profiler.set_gauge("unit.depth", 3)
    mx.profiler.inc_gauge("unit.bytes", 100)
    mx.profiler.inc_gauge("unit.bytes", -40)
    g = mx.profiler.metrics_summary()["gauges"]
    assert g["unit.depth"] == 3.0
    assert g["unit.bytes"] == 60.0
    mx.profiler.reset_metrics()


def test_gauge_decrement_dropped_after_reset():
    """A delta-gauge decrement that outlives reset_metrics() (executor
    finalizer) must be dropped, not drive the gauge negative."""
    reg = mx.profiler.MetricsRegistry()
    gen = reg.inc_gauge("live.bytes", 100)  # returns the generation
    assert reg.summary()["gauges"]["live.bytes"] == 100.0
    reg.reset()
    assert reg.inc_gauge("live.bytes", -100, gen=gen) is None  # dropped
    assert reg.summary()["gauges"].get("live.bytes", 0.0) == 0.0
    gen2 = reg.inc_gauge("live.bytes", 7)
    assert gen2 == reg.generation  # current: applied
    assert reg.summary()["gauges"]["live.bytes"] == 7.0


def test_prometheus_text():
    mx.profiler.reset_metrics()
    mx.profiler.inc_counter("serving.requests", 3)
    mx.profiler.set_gauge("executor.live_buffer_bytes", 1024)
    for v in (1.0, 2.0, 3.0):
        mx.profiler.observe("serving.latency_ms", v)
    text = mx.profiler.prometheus_text()
    assert "# TYPE mxnet_serving_requests counter" in text
    assert 'mxnet_serving_requests{rank="0"} 3' in text
    assert "# TYPE mxnet_executor_live_buffer_bytes gauge" in text
    assert 'mxnet_executor_live_buffer_bytes{rank="0"} 1024' in text
    # PR 12: registry histograms export as REAL Prometheus histograms
    # (cumulative _bucket series over the fixed ladder + _sum/_count)
    assert "# TYPE mxnet_serving_latency_ms histogram" in text
    assert 'mxnet_serving_latency_ms_bucket{rank="0",le="1"} 1' in text
    assert 'mxnet_serving_latency_ms_bucket{rank="0",le="2.5"} 2' in text
    assert 'mxnet_serving_latency_ms_bucket{rank="0",le="+Inf"} 3' in text
    assert 'mxnet_serving_latency_ms_count{rank="0"} 3' in text
    assert 'mxnet_serving_latency_ms_sum{rank="0"} 6' in text
    # the one-release deprecated _pNN quantile gauges are RETIRED:
    # histogram_quantile() over the _bucket series replaces them
    assert "_p50" not in text
    assert "_p90" not in text
    assert "_p99" not in text
    # the pre-PR-12 summary form is GONE (a histogram family plus a
    # same-name summary would be an invalid exposition)
    assert "summary" not in text
    assert 'quantile=' not in text
    mx.profiler.reset_metrics()


def test_jsonl_reporter(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = mx.profiler.MetricsRegistry()
    reg.inc("unit.count", 2)
    reg.observe("unit.ms", 5.0)
    rep = mx.profiler.start_reporter(path, interval=0.05, registry=reg)
    time.sleep(0.25)
    rep.stop()
    rep.stop()  # idempotent
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) >= 2  # periodic lines + the final flush
    for ln in lines:
        assert ln["counters"]["unit.count"] == 2
        assert ln["histograms"]["unit.ms"]["p90"] == 5.0
        assert "rates" in ln and "t" in ln and "rank" in ln


def test_executor_compile_metrics():
    """First program run per executor counts as the compile; bind
    registers its buffers in the live-buffer-bytes gauge."""
    import gc

    gc.collect()  # flush pending executor finalizers from earlier tests
    mx.profiler.reset_metrics()
    before = mx.profiler.metrics_summary()["gauges"].get(
        "executor.live_buffer_bytes", 0.0)
    mod = mx.mod.Module(_mlp_binary(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    after = mx.profiler.metrics_summary()["gauges"].get(
        "executor.live_buffer_bytes", 0.0)
    assert mod._exec._buffer_bytes > 0
    assert after - before == mod._exec._buffer_bytes
    batch = mx.io.DataBatch([mx.nd.zeros((4, 6))], [mx.nd.zeros((4,))])
    mod.forward(batch, is_train=False)
    mod.forward(batch, is_train=False)
    s = mx.profiler.metrics_summary()
    # exactly one compile: the second forward hit XLA's cache
    assert s["counters"]["executor.compiles"] == 1
    assert s["histograms"]["executor.compile_ms"]["count"] == 1
    mx.profiler.reset_metrics()


def test_fit_step_timeline(tmp_path):
    """fit() emits the step timeline: io.next (input wait) and
    fit.step spans with epoch/step args."""
    fname = str(tmp_path / "trace.json")
    mx.profiler.profiler_set_config(mode="all", filename=fname)
    mx.profiler.profiler_set_state("run")
    np.random.seed(3)
    X = np.random.randn(30, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=10)
    mod = mx.mod.Module(_mlp_binary(), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd")
    mx.profiler.profiler_set_state("stop")
    with open(fname) as f:
        trace = json.load(f)
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    steps = [e for e in evs if e["name"] == "fit.step"]
    waits = [e for e in evs if e["name"] == "io.next"]
    assert steps and waits
    for e in steps:
        assert "step" in e["args"] and "epoch" in e["args"]
    assert {e["args"]["epoch"] for e in steps} == {0, 1}
    # the fused step event carries its step number and compile flag:
    # some first-run-per-module compiles, then cached steady state
    # (the global profiler accumulates events across modules)
    fused = [e for e in evs if e["name"] == "Module.fused_step"]
    assert fused and all("step" in e["args"] for e in fused)
    assert any(e["args"]["compile"] for e in fused)
    assert any(not e["args"]["compile"] for e in fused)


def test_ps_sync_watchdog_names_straggler(caplog):
    """A sync round missing one worker's push past the deadline logs
    WHO is late — instead of the 600 s wait_for hanging silently."""
    from mxnet_tpu.ps import ParameterServer, PSClient

    srv = ParameterServer(num_workers=2, sync=True, watchdog_deadline=0.3)
    try:
        c0 = PSClient("127.0.0.1", srv.port, worker=0)
        c0.init("w", np.zeros((3,), np.float32))
        with caplog.at_level(logging.WARNING):
            c0.push_sync("w", np.ones((3,), np.float32))
            time.sleep(1.2)
        msgs = [r.getMessage() for r in caplog.records
                if "[watchdog]" in r.getMessage()]
        assert any("arrived workers [0]" in m
                   and "waiting on workers [1]" in m for m in msgs), msgs
        # the late worker arrives; the round completes and state clears
        c1 = PSClient("127.0.0.1", srv.port, worker=1)
        c1.push_sync("w", np.ones((3,), np.float32))
        out = c0.pull("w", min_round=1)
        np.testing.assert_allclose(out, np.full((3,), 2.0))
        assert not srv._round_open_t and not srv._arrivals
        # round spread was measured for the completed round
        spread = mx.profiler.metrics_summary()["histograms"].get(
            "ps.round_spread_ms")
        assert spread and spread["count"] >= 1
        c0.close()
        c1.close()
    finally:
        srv.close()


def test_trace_merge_clock_alignment(tmp_path):
    """Unit check of tools/trace_merge.py: wall-clock offsets applied,
    rank-keyed pids, metadata rewritten."""
    import trace_merge

    def mk(rank, wall0, ts):
        return {
            "traceEvents": [
                {"name": "process_name", "ph": "M", "pid": 12345, "tid": 0,
                 "args": {"name": f"rank {rank}"}},
                {"name": "work", "cat": "op", "ph": "X", "ts": ts,
                 "dur": 10.0, "pid": 12345, "tid": 1,
                 "args": {"step": rank}},
            ],
            "displayTimeUnit": "ms",
            "metadata": {"rank": rank, "pid": 12345,
                         "clock_sync": {"wall_time_s": wall0,
                                        "perf_counter_s": 0.0}},
        }

    p0 = tmp_path / "trace_rank0.json"
    p1 = tmp_path / "trace_rank1.json"
    p0.write_text(json.dumps(mk(0, 100.0, 5.0)))
    p1.write_text(json.dumps(mk(1, 100.5, 5.0)))
    merged = trace_merge.merge_traces([
        trace_merge.load_trace(str(p0)), trace_merge.load_trace(str(p1))])
    evs = merged["traceEvents"]
    assert {e["pid"] for e in evs} == {0, 1}
    xs = {e["pid"]: e for e in evs if e.get("ph") == "X"}
    # rank 1's wall clock was 0.5 s ahead → its events shift +0.5e6 us
    assert xs[0]["ts"] == pytest.approx(5.0)
    assert xs[1]["ts"] == pytest.approx(5.0 + 0.5e6)
    assert xs[1]["args"]["step"] == 1  # args survive the merge
    assert merged["metadata"]["merged_ranks"] == [0, 1]
    # directory input collection
    files = trace_merge.collect_inputs([str(tmp_path)])
    assert [os.path.basename(f) for f in files] == [
        "trace_rank0.json", "trace_rank1.json"]


# ---------------------------------------------------------------------------
# tools/parse_log.py
# ---------------------------------------------------------------------------


def test_parse_log_plain_scientific_and_nan(tmp_path):
    import parse_log

    lines = [
        "2026-08-03 INFO Epoch[0] Train-accuracy=0.5\n",
        "2026-08-03 INFO Epoch[0] Validation-accuracy=0.25\n",
        "2026-08-03 INFO Epoch[0] Time cost=12.5\n",
        "2026-08-03 INFO Epoch[1] Train-accuracy=1.5e-01\n",  # scientific
        "2026-08-03 INFO Epoch[1] Validation-accuracy=nan\n",  # diverged
        "2026-08-03 INFO Epoch[1] Time cost=1.2e+01\n",
        "unrelated line\n",
    ]
    data = parse_log.parse(lines)
    assert set(data) == {0, 1}
    # epoch 0: plain decimals
    assert data[0][0] == [0.5, 1]
    assert data[0][1] == [0.25, 1]
    assert data[0][2] == [12.5, 1]
    # epoch 1: scientific notation parsed, nan tolerated (not skipped)
    assert data[1][0][0] == pytest.approx(0.15)
    assert data[1][1][1] == 1 and math.isnan(data[1][1][0])
    assert data[1][2][0] == pytest.approx(12.0)
