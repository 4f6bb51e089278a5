"""PR 12 observability: distributed trace context, the crash flight
recorder, live goodput/MFU accounting, and the ops HTTP surface.

The centerpiece is the two-process stitching test: one traced request
routed through a real Router → wire → ReplicaServer subprocess comes
back as ONE span tree with monotonic, clock-aligned parent/child
bounds across both processes — recovered entirely from the always-on
flight-recorder ring files (no profiler needed)."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------


def test_trace_context_header_roundtrip():
    ctx = profiler.TraceContext()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    header = ctx.to_header()
    assert header == f"00-{ctx.trace_id}-{ctx.span_id}-01"
    back = profiler.TraceContext.from_header(header)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id  # sender's span = my parent
    child = back.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id
    assert child.span_id != ctx.span_id
    for bad in ("", "00-zz-xx-01", "00-abc-def-01", "nonsense"):
        with pytest.raises(ValueError):
            profiler.TraceContext.from_header(bad)


def test_wire_trace_field_roundtrip():
    from mxnet_tpu import wire

    ctx = profiler.TraceContext()
    buf = memoryview(wire.pack_trace(ctx) + b"tail")
    back, off = wire.unpack_trace(buf, 0)
    assert back.trace_id == ctx.trace_id
    assert bytes(buf[off:]) == b"tail"
    # absent = one byte, parses to None
    none_buf = memoryview(wire.pack_trace(None) + b"x")
    assert len(wire.pack_trace(None)) == 1
    got, off = wire.unpack_trace(none_buf, 0)
    assert got is None and bytes(none_buf[off:]) == b"x"
    # a malformed header drops to None instead of failing the request
    raw = bytes([9]) + b"not-a-tp!" + b"y"
    got, off = wire.unpack_trace(memoryview(raw), 0)
    assert got is None and raw[off:] == b"y"


def test_trace_sampling_deterministic(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0.0")
    profiler._TRACE_SAMPLE = None  # re-read the env
    assert profiler.make_trace(key=7) is None
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "1.0")
    profiler._TRACE_SAMPLE = None
    assert profiler.make_trace(key=7) is not None
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0.5")
    profiler._TRACE_SAMPLE = None
    a = [profiler.make_trace(key=k) is not None for k in range(64)]
    b = [profiler.make_trace(key=k) is not None for k in range(64)]
    assert a == b  # deterministic per key: retries keep their verdict
    assert 5 < sum(a) < 60  # and it actually samples
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "banana")
    profiler._TRACE_SAMPLE = None
    with pytest.raises(mx.MXNetError):
        profiler.make_trace()
    monkeypatch.delenv("MXNET_TRACE_SAMPLE")
    profiler._TRACE_SAMPLE = None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_bounded_and_always_on():
    rec = profiler.flight_recorder()
    cap = rec.capacity
    with profiler.trace_span("flight.unit", profiler.TraceContext(),
                             args={"k": 1}):
        pass
    names = [e["name"] for e in rec.snapshot()]
    assert "flight.unit" in names  # recorded with the profiler OFF
    for i in range(cap * 2):
        rec.record({"name": f"fill{i}", "ph": "X", "ts": 0.0,
                    "dur": 0.0, "pid": 0, "tid": 0})
    assert len(rec.snapshot()) == cap  # bounded, oldest dropped
    assert rec.snapshot()[-1]["name"] == f"fill{cap * 2 - 1}"


def test_flight_ring_file_survives_and_reads_back(tmp_path):
    rec = profiler.FlightRecorder(capacity=64,
                                  file_path=str(tmp_path / "t.ring"),
                                  file_bytes=4096)
    for i in range(200):  # force several wraps of the 4 KiB data ring
        rec.record({"name": f"ev{i}", "ph": "X", "ts": float(i),
                    "dur": 1.0, "pid": 1, "tid": 2})
    rec.sync()
    doc = profiler.read_flight_file(str(tmp_path / "t.ring"))
    evs = doc["traceEvents"]
    assert evs and evs[-1]["name"] == "ev199"
    # only whole lines (the torn line at the seam is skipped)
    assert all(e["name"].startswith("ev") for e in evs)
    # newest-first contiguity: recovered ids are the trailing ones
    ids = [int(e["name"][2:]) for e in evs]
    assert ids == sorted(ids)
    assert "clock_sync" in doc["metadata"]
    # trace_merge's standalone reader agrees with the library's
    import trace_merge as tm

    doc2 = tm.load_trace(str(tmp_path / "t.ring"))
    assert [e["name"] for e in doc2["traceEvents"]] == \
        [e["name"] for e in evs]


def test_flight_dump_on_engine_loop_crash(tmp_path, monkeypatch):
    """An injected BaseException in the serving path kills the batch
    loop; the loop's crash handler must leave a post-mortem JSON with
    the recent spans before poisoning the futures."""
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    profiler._flight_dumped.clear()  # defeat cross-test rate limiting

    class Boom(BaseException):  # escapes `except Exception` layers
        pass

    pred = mx.Predictor(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"),
        {"fc_weight": np.zeros((2, 3), np.float32),
         "fc_bias": np.zeros(2, np.float32)},
        {"data": (1, 3)})
    eng = mx.InferenceEngine(pred, buckets=(1,))

    def explode(bucket, donate):
        raise Boom("injected engine-loop crash")

    monkeypatch.setattr(eng._model, "compile", explode)
    fut = eng.submit({"data": np.zeros((1, 3), np.float32)})
    # the future carries the ORIGINAL cause (not a generic closed
    # error): the dispatch failure net catches BaseException too
    with pytest.raises(Boom):
        fut.result(timeout=30)
    deadline = time.time() + 10
    dump = None
    while time.time() < deadline and dump is None:
        found = [f for f in os.listdir(tmp_path)
                 if f.startswith("flightdump_") and "engine_crash" in f
                 and f.endswith(".json")]  # not the .tmp mid-rename
        dump = found[0] if found else None
        time.sleep(0.05)
    assert dump is not None, "no post-mortem dump after loop crash"
    with open(tmp_path / dump) as f:
        doc = json.load(f)
    assert doc["metadata"]["reason"] == "engine_crash"
    assert "Boom" in doc["metadata"]["error"]
    assert "clock_sync" in doc["metadata"]
    assert isinstance(doc["traceEvents"], list)


def test_reporter_lines_carry_clock_anchor(tmp_path):
    """Satellite: Reporter JSONL, flight dumps and rank traces share
    ONE clock_sync convention, so trace_merge aligns all three."""
    path = str(tmp_path / "m.jsonl")
    reg = profiler.MetricsRegistry()
    reg.set_gauge("unit.g", 3.0)
    rep = profiler.start_reporter(path, interval=0.05, registry=reg)
    time.sleep(0.2)
    rep.stop()
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    anchor = profiler.clock_anchor()
    assert lines and all(ln["clock_sync"] == anchor for ln in lines)
    # and trace_merge can merge the JSONL next to a span trace
    import trace_merge as tm

    doc = tm.load_trace(path)
    assert any(e["ph"] == "C" and e["name"] == "unit.g"
               for e in doc["traceEvents"])
    merged = tm.merge_traces([doc])
    assert merged["traceEvents"]


# ---------------------------------------------------------------------------
# goodput / MFU
# ---------------------------------------------------------------------------


def test_goodput_tracker_math():
    reg = profiler.MetricsRegistry()
    g = profiler.GoodputTracker(registry=reg)
    g.set_flops_per_step(2e9)
    g.set_peak_flops(1e12)
    g.set_pp_bubble(0.25)
    for _ in range(4):
        g.add_comm(0.02)
        g.step(0.1, io_s=0.05, ckpt_s=0.01)
    s = g.summary()
    assert s["steps"] == 4
    d = s["decomposition"]
    assert sum(d.values()) == pytest.approx(1.0)
    # comm drained into the step, bubble carved out of the remainder
    assert d["comm"] == pytest.approx(0.02 / 0.16, rel=1e-6)
    assert d["pp_bubble"] == pytest.approx(0.25 * 0.08 / 0.16, rel=1e-6)
    assert d["io_wait"] == pytest.approx(0.05 / 0.16, rel=1e-6)
    # mfu = flops / step_s / peak
    assert s["mfu"] == pytest.approx(2e9 / 0.1 / 1e12, rel=1e-6)
    assert 0 < s["goodput"] <= 1.0
    gauges = reg.summary()["gauges"]
    assert gauges["training.mfu"] == pytest.approx(s["mfu"], rel=0.05)
    assert gauges["training.goodput"] == pytest.approx(s["goodput"],
                                                      rel=0.05)


def test_goodput_lost_time_attribution():
    g = profiler.GoodputTracker(registry=profiler.MetricsRegistry())
    g.step(0.1)
    g.add_lost(2.5, "remesh")
    s = g.summary()
    assert s["lost_s"] == {"remesh": 2.5}


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_TFLOPS", "123.5")
    assert profiler.device_peak_flops() == pytest.approx(123.5e12)
    monkeypatch.setenv("MXNET_PEAK_TFLOPS", "banana")
    with pytest.raises(mx.MXNetError):
        profiler.device_peak_flops()
    monkeypatch.setenv("MXNET_PEAK_TFLOPS", "-1")
    with pytest.raises(mx.MXNetError):
        profiler.device_peak_flops()


def test_one_peak_table_unknown_kind_is_an_error(monkeypatch):
    """profiler.PEAK_BY_DEVICE_KIND is the package's peak table (the
    live MFU gauge divides by it): it holds the one chip there is, and
    a device_kind without a row raises instead of answering None and
    letting MFU drop out in silence."""
    assert profiler.peak_flops("TPU v5 lite") == pytest.approx(197e12)
    with pytest.raises(mx.MXNetError, match="TPU v9000"):
        profiler.peak_flops("TPU v9000")
    # the live gauge: withheld on a CPU backend (no such peak), never
    # guessed
    monkeypatch.delenv("MXNET_PEAK_TFLOPS", raising=False)
    assert profiler.device_peak_flops() is None


def test_fit_exports_live_goodput(monkeypatch):
    """A real (tiny) fit exports training.goodput/mfu gauges whose
    decomposition covers ~100% of wall, with flops from the fused
    program's own cost analysis."""
    monkeypatch.setenv("MXNET_PEAK_TFLOPS", "1")
    profiler.goodput_tracker().reset()
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc"),
        mx.sym.Variable("softmax_label"), name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.rand(32, 8).astype(np.float32),
                           (np.arange(32) % 4).astype(np.float32),
                           batch_size=8)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    s = profiler.goodput_tracker().summary()
    assert s["steps"] == 8
    assert 0 < s["goodput"] <= 1.0
    assert s["flops_per_step"] and s["flops_per_step"] > 0
    assert s["mfu"] and s["mfu"] > 0
    assert sum(s["decomposition"].values()) == pytest.approx(1.0)
    gauges = profiler.metrics_summary()["gauges"]
    assert "training.goodput" in gauges
    assert "training.mfu" in gauges


# ---------------------------------------------------------------------------
# ops surface
# ---------------------------------------------------------------------------


def test_metrics_http_endpoints():
    profiler.set_gauge("unit.http_gauge", 7.0)
    profiler.register_statusz("unit", lambda: {"hello": "world"})
    srv = profiler.start_metrics_server(port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "mxnet_unit_http_gauge" in text
        st = json.loads(urllib.request.urlopen(f"{base}/statusz").read())
        assert st["gauges"]["unit.http_gauge"] == 7.0
        assert st["unit"] == {"hello": "world"}
        assert "training" in st and "clock_sync" in st
        profiler.observe("unit.http_ms", 1.0)
        tz = json.loads(
            urllib.request.urlopen(f"{base}/tracez?n=64").read())
        assert "traceEvents" in tz and "clock_sync" in tz
        assert urllib.request.urlopen(f"{base}/metrics").status == 200
        with pytest.raises(Exception):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        profiler.unregister_statusz("unit")
        srv.close()
    # closing clears the singleton so a fresh server can bind
    srv2 = profiler.start_metrics_server(port=0)
    assert srv2 is not srv
    srv2.close()


def test_statusz_provider_errors_are_contained():
    profiler.register_statusz("bad", lambda: 1 / 0)
    try:
        doc = profiler.statusz()
        assert "error" in doc["bad"]
    finally:
        profiler.unregister_statusz("bad")


# ---------------------------------------------------------------------------
# the two-process stitch (the tier-1 acceptance test)
# ---------------------------------------------------------------------------


def _walk(nodes):
    for n in nodes:
        yield n
        yield from _walk(n["children"])


def test_two_process_trace_stitch(tmp_path):
    """One traced request through Router → wire → a fake-replica
    SUBPROCESS stitches into a single tree: the router.request root
    spans both processes' child spans with monotonic, clock-aligned
    bounds — recovered purely from the two flight-recorder ring
    files."""
    import trace_merge as tm

    from mxnet_tpu import fleet

    fleet_dir = str(tmp_path)
    fleet.write_secret(fleet_dir, b"trace-test")
    profiler.init_flight_recorder(fleet_dir)
    env = dict(os.environ, MXNET_WORKER_ID="1", JAX_PLATFORMS="cpu",
               MXNET_FLIGHT_RECORDER_DIR=fleet_dir)
    worker = os.path.join(os.path.dirname(__file__),
                          "fleet_trace_worker.py")
    proc = subprocess.Popen([sys.executable, worker, fleet_dir],
                            env=env)
    router = None
    try:
        host, port = fleet.read_endpoint(fleet_dir, 0, timeout=120)
        client = fleet.ReplicaClient(0, host, port,
                                     secret=b"trace-test")
        router = fleet.Router([client], fleet_dir=fleet_dir,
                              secret=b"trace-test")
        out = router.submit(
            {"data": np.ones((1, 2), np.float32)}).result(60)
        assert np.allclose(out[0], 2.0)
        time.sleep(0.1)  # let the delivery span land in the ring
    finally:
        if router is not None:
            router.close(stop_replicas=True)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
    profiler.flight_recorder().sync()
    rings = sorted(f for f in os.listdir(fleet_dir)
                   if f.startswith("flight_") and f.endswith(".ring"))
    assert len(rings) == 2, rings
    merged = tm.merge_traces(
        [tm.load_trace(os.path.join(fleet_dir, f)) for f in rings])
    traces = tm.list_traces(merged["traceEvents"])
    roots_of = {tid: tm.trace_tree(merged["traceEvents"], tid)
                for tid in traces}
    # find OUR request: the tree rooted at router.request
    picked = None
    for tid, roots in roots_of.items():
        if len(roots) == 1 and roots[0]["event"]["name"] \
                == "router.request":
            picked = roots
    assert picked is not None, f"no router.request root in {traces}"
    root = picked[0]
    nodes = list(_walk(picked))
    names = {n["event"]["name"] for n in nodes}
    pids = {n["event"]["pid"] for n in nodes}
    # spans from BOTH processes in one tree
    assert len(pids) == 2, names
    assert {"router.request", "router.queue", "wire.send",
            "replica.exec"} <= names
    # every child's bounds sit inside its parent's, on the SHARED
    # wall-clock axis (clock-aligned: same host, sub-ms NTP error;
    # 5 ms tolerance >> observed skew, << the 10 ms replica span)
    tol_us = 5e3
    root_t0 = root["event"]["ts"]
    root_t1 = root_t0 + root["event"]["dur"]

    def check(node, lo, hi):
        ev = node["event"]
        t0, t1 = ev["ts"], ev["ts"] + ev.get("dur", 0.0)
        assert t0 >= lo - tol_us, (ev["name"], t0, lo)
        assert t1 <= hi + tol_us, (ev["name"], t1, hi)
        prev = t0
        for c in node["children"]:
            # children sorted by ts → monotonic
            assert c["event"]["ts"] >= prev - tol_us
            prev = c["event"]["ts"]
            check(c, t0, t1)

    check(root, root_t0, root_t1)
    # the replica's 10 ms exec really happened INSIDE the root span
    exec_node = next(n for n in nodes
                     if n["event"]["name"] == "replica.exec")
    assert exec_node["event"]["pid"] != root["event"]["pid"]
    assert exec_node["event"]["dur"] >= 8e3  # the worker's sleep
    # Perfetto flow arrows were attached for the cross-process edges
    assert any(e.get("cat") == "traceflow"
               for e in merged["traceEvents"])


# ---------------------------------------------------------------------------
# stitcher unit coverage (no processes)
# ---------------------------------------------------------------------------


def test_trace_tree_stitches_and_formats():
    import trace_merge as tm

    root = profiler.TraceContext()
    c1, c2 = root.child(), root.child()
    evs = [
        {"name": "root", "ph": "X", "ts": 0.0, "dur": 100.0, "pid": 1,
         "tid": 0, "args": root.args()},
        {"name": "b", "ph": "X", "ts": 50.0, "dur": 10.0, "pid": 2,
         "tid": 0, "args": c2.args()},
        {"name": "a", "ph": "X", "ts": 10.0, "dur": 10.0, "pid": 1,
         "tid": 0, "args": c1.args()},
        {"name": "other", "ph": "X", "ts": 0.0, "dur": 1.0, "pid": 1,
         "tid": 0, "args": profiler.TraceContext().args()},
    ]
    assert tm.list_traces(evs)[root.trace_id] == 3
    roots = tm.trace_tree(evs, root.trace_id)
    assert len(roots) == 1 and roots[0]["event"]["name"] == "root"
    kids = [n["event"]["name"] for n in roots[0]["children"]]
    assert kids == ["a", "b"]  # sorted by ts
    text = tm.format_tree(roots)
    assert "root" in text and "\n  a" in text
    n_flows = tm.add_flow_events(evs)
    assert n_flows == 1  # only the cross-pid edge (root→b)
