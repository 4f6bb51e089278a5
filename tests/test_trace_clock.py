"""One clock: ``profiler.scope`` writes into a running jax trace, the
engine's thread and the programs carry names, set-up is counted by
phase (``compile.*``), and the training step is lowered once.

The traces here are taken as the benchmark's harness takes them
(``python_tracer_level = 0``: no event per Python call), on the CPU,
with a tiny engine, so the file stays in seconds.
"""
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, profiler

from _engines import DM, H, V, dense_engine, tiny_lm_params

ENGINE_SPANS = ("serving.admit", "serving.prefill", "serving.step",
                "serving.stage", "serving.decode_step",
                "serving.d2h_sync", "serving.absorb", "serving.drain",
                "serving.idle")


def family(name):
    """``serving.prefill.t8`` -> ``serving.prefill``: a span's name
    without its bucket."""
    return re.sub(r"\.(t|b)\d+(x\d+)?$", "", name)


def warmed():
    eng = dense_engine(tiny_lm_params(), prefix_cache=0)
    eng.warmup()
    return eng


@pytest.fixture
def engine(engines):
    """One warmed engine for the file's tests, its counters zeroed."""
    return engines(warmed)


# the scripted run: one caller, three requests one after another, so
# the schedule is the same every time (prompt length, new tokens)
SCRIPT = ((4, 6), (5, 3), (7, 8))


def scripted_run(eng):
    """-> the served tokens of each request."""
    out = []
    for p, n in SCRIPT:
        out.append(eng.generate(np.arange(1, 1 + p, dtype=np.int32), n))
        time.sleep(0.02)  # the loop goes idle between callers
    return out


def host_lines(trace_dir):
    """{line title: [(name, start_ns, end_ns, stats)]} of /host:CPU."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            lines[f"{line.name}#{i}"] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events]
    return lines


def traced(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test:window"):
            out = body()
    finally:
        jax.profiler.stop_trace()
    return out, host_lines(str(tmp_path))


def test_engine_spans_land_in_a_running_jax_trace(engine, tmp_path):
    served, lines = traced(tmp_path, lambda: scripted_run(engine))
    assert [len(s) for s in served] == [n for _, n in SCRIPT]
    (window,) = [e for evs in lines.values() for e in evs
                 if e[0] == "test:window"]
    # every span of the engine's thread is on ONE line, titled with the
    # thread's own name, beside the dispatches jax itself records
    (title, evs), = [(t, evs) for t, evs in lines.items()
                     if any(e[0] == "serving.step" for e in evs)]
    assert title.startswith("mx-decode-loop")
    assert any(e[0].startswith("PjitFunction") or "Execute" in e[0]
               for e in evs)
    by_name = {}
    for e in evs:
        by_name.setdefault(family(e[0]), []).append(e)
    for name in ENGINE_SPANS:
        assert by_name.get(name), f"no {name} span in the trace"
    spans = [e for e in evs if e[0].startswith("serving.")]
    assert all(window[1] <= e[1] and e[2] <= window[2] for e in spans
               if e[0] != "serving.idle")

    def inside(child, parent):
        return parent[1] <= child[1] and child[2] <= parent[2]

    # children lie inside their parents.  A step stages and dispatches
    # its program, THEN fetches and books the one before it: the spans
    # of the fetch (``d2h_sync``, ``absorb``) lie in the step that
    # dispatched the next program, or in the ``serving.drain`` that
    # fetched with nothing left to dispatch (a request's last step)
    steps = by_name["serving.step"]
    drains = by_name["serving.drain"]
    for child in ("serving.stage", "serving.decode_step"):
        assert all(any(inside(c, p) for p in steps)
                   for c in by_name[child]), child
    for child in ("serving.absorb", "serving.d2h_sync"):
        assert all(any(inside(c, p) for p in steps + drains)
                   for c in by_name[child]), child
    assert all(any(inside(c, p) for p in by_name["serving.admit"])
               for c in by_name["serving.prefill"])
    # a prefill span covers the dispatch alone: no fetch inside it
    assert not any(inside(c, p) for c in by_name["serving.d2h_sync"]
                   for p in by_name["serving.prefill"])
    # an idle wait is no part of a step or an admission
    assert not any(inside(i, p) for i in by_name["serving.idle"]
                   for p in steps + by_name["serving.admit"])
    # one prefill per request; one step per decode program: one
    # staging, one dispatch, and — in a later step or a drain — one
    # fetch and one booking; a prefill's first token is one fetch more
    st = engine.stats()
    assert len(by_name["serving.prefill"]) == len(SCRIPT)
    assert len(by_name["serving.decode_step"]) == st["steps"]
    assert len(by_name["serving.absorb"]) == st["steps"]
    assert len(by_name["serving.stage"]) == st["steps"]
    assert len(steps) == st["steps"]
    assert len(by_name["serving.d2h_sync"]) == st["d2h_syncs"] \
        == st["steps"] + st["prefills"]
    # each request's last fetch had no program queued behind it: it is
    # the drain's, and the only one ``d2h_syncs_saved`` leaves out
    assert len(drains) == len(SCRIPT) == st["run_ahead_drains"]
    assert st["d2h_syncs_saved"] == st["d2h_syncs"] - len(SCRIPT)
    assert all(d[3]["reason"] == "idle" for d in drains)
    # what a span is about rides on it
    stage = by_name["serving.stage"][0][3]
    assert {"sids", "active", "ahead"} <= set(stage)
    assert int(stage["active"]) == 1
    assert "retired" in by_name["serving.absorb"][0][3]


def test_untraced_run_books_the_same_spans_and_no_more_syncs(engine):
    """No trace running: the flight recorder gets the same spans, and
    the engine fetches what the parent tree fetched for this script —
    14 steps' tokens and 3 first tokens — all but each request's last
    with a newer program already queued (the parent, commit 0887c7e,
    read 6 of the 17 that way: its pairs)."""
    since = (time.perf_counter()
             - profiler.clock_anchor()["perf_counter_s"]) * 1e6
    served = scripted_run(engine)
    time.sleep(0.05)
    booked = [e for e in profiler.flight_snapshot()
              if e.get("ph") == "X" and e["ts"] >= since]
    assert set(ENGINE_SPANS) <= {family(e["name"]) for e in booked}
    st = engine.stats()
    assert (st["steps"], st["d2h_syncs"], st["d2h_syncs_saved"],
            st["prefills"], st["tokens"]) == (14, 17, 14, 3, 17)
    assert (st["steps_run_ahead"], st["run_ahead_share"],
            st["prefill_first_deferred"], st["overshoot_row_steps"],
            st["run_ahead_drain_reasons"]) == (14, 1.0, 3, 0, {"idle": 3})
    assert [len(s) for s in served] == [6, 3, 8]
    # the recorder's form of a span carries what was known at its END
    absorbed = [e for e in booked if e["name"] == "serving.absorb"]
    assert len(absorbed) == st["steps"]
    assert sum(e["args"]["retired"] for e in absorbed) == len(SCRIPT)


def test_context_tokens_counts_the_live_context_of_every_step(engine):
    scripted_run(engine)
    st = engine.stats()
    # a request of p prompt tokens and n new ones: the prefill emits
    # the first token, then n - 1 decode steps attend p + 1 .. p + n - 1
    want = sum(sum(range(p + 1, p + n)) for p, n in SCRIPT)
    assert st["context_tokens"] == want == 125
    assert st["steps"] == sum(n - 1 for _, n in SCRIPT)
    engine.reset_stats()
    assert engine.stats()["context_tokens"] == 0


def compile_counters():
    c = profiler.metrics_summary()["counters"]
    return {k: c.get(k, 0.0) for k in (
        "compile.trace_s", "compile.lower_s", "compile.backend_s",
        "compile.programs")}


def test_compile_counters_move_when_a_program_is_built():
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 3 + 1)
    x = jnp.ones((5, 7))
    before, t0 = compile_counters(), time.perf_counter()
    f(x).block_until_ready()
    built = compile_counters()
    assert all(built[k] > before[k] for k in before), (before, built)
    assert built["compile.programs"] >= before["compile.programs"] + 1
    f(x).block_until_ready()  # cached: nothing is built
    assert compile_counters() == built
    kinds = {k for t, k, s in profiler.compile_events() if t >= t0}
    assert kinds <= set(profiler.COMPILE_EVENT_KINDS.values())
    assert all(s >= 1e-3 for _, _, s in profiler.compile_events())
    text = profiler.prometheus_text()
    assert "mxnet_compile_lower_s" in text
    assert "mxnet_compile_programs" in text


def test_first_fused_step_lowers_the_step_program_once():
    profiler.goodput_tracker().reset()
    T = 16
    sym = models.transformer_lm(V, T, num_layers=2, num_heads=H,
                                d_model=DM)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, T))],
             label_shapes=[("softmax_label", (2, T))],
             for_training=True)
    mod.init_params(mx.initializer.Normal(0.02))
    mod.init_optimizer(kvstore=None, optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3})
    rng = np.random.default_rng(0)
    batch = mx.io.DataBatch(
        [mx.nd.array(rng.integers(0, V, (2, T)).astype("float32"))],
        [mx.nd.array(rng.integers(0, V, (2, T)).astype("float32"))])

    # jax names the program with every lowering it reports (the names
    # are not in ``profiler.compile_events()``, whose rows the benchmark
    # unpacks as three): the step's is told from the helpers' (device_put,
    # the state's zeros) by name, however long either took
    lowered = []

    def seen(event, duration, fun_name=None, **_kw):
        if profiler.COMPILE_EVENT_KINDS.get(event) == "lower":
            lowered.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(seen)
    try:
        mod.forward_backward(batch)
        mod.update()
        mod.get_outputs()[0].asnumpy()
    finally:
        jax.monitoring.unregister_event_duration_listener(seen)
    assert lowered.count("jit(step_train)") == 1, sorted(set(lowered))
    # the live MFU gauge has its FLOPs, from that one lowering
    profiler.goodput_tracker().step(0.01)
    gauges = profiler.metrics_summary()["gauges"]
    assert gauges["training.flops_per_step"] > 0
    # later steps and the HLO text build nothing more
    t1 = time.perf_counter()
    mod.forward_backward(batch)
    mod.update()
    text = mod.fused_hlo_text()
    mod.fused_memory_analysis()
    built = [(k, s) for t, k, s in profiler.compile_events()
             if t >= t1 and k in ("lower", "backend")]
    assert built == []
    # the program and its ops carry their names
    assert re.search(r"HloModule jit_step_train\b", text)
    assert "optimizer_update/" in text
    assert re.search(r'op_name="jit\(step_train\)/[^"]*layer1_', text)


def test_record_program_keeps_its_compile_accounting(tmp_path):
    profiler.reset_metrics()
    x = mx.sym.Variable("x")
    exe = (x * 2 + 1).simple_bind(mx.cpu(), x=(3,))

    def body():
        exe.forward(is_train=False, x=np.ones(3, "float32"))
        exe.forward(is_train=False, x=np.ones(3, "float32"))

    _, lines = traced(tmp_path, body)
    names = [e[0] for evs in lines.values() for e in evs]
    assert names.count("Executor.compile+forward") == 1
    assert names.count("Executor.forward") == 1
    summ = profiler.metrics_summary()
    assert summ["counters"]["executor.compiles"] == 1
    assert summ["histograms"]["executor.compile_ms"]["count"] == 1


def test_scope_is_an_annotation_even_with_the_recorder_off(
        tmp_path, monkeypatch):
    monkeypatch.setattr(profiler, "_FLIGHT_ENABLED", False)

    def body():
        with profiler.scope("test.span", "test", args={"k": 3}):
            pass

    _, lines = traced(tmp_path, body)
    (ev,) = [e for evs in lines.values() for e in evs
             if e[0] == "test.span"]
    assert int(ev[3]["k"]) == 3


def test_every_pallas_call_is_named():
    """A source-level check: each ``pl.pallas_call(`` of the kernel
    file passes ``name=``, so a device trace shows the kernel under a
    stable word whatever jit it sits in."""
    import inspect

    from mxnet_tpu.ops import pallas_kernels

    src = inspect.getsource(pallas_kernels)
    calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(", src)]
    assert len(calls) >= 12
    names = []
    for at in calls:
        depth, i = 0, at + len("pl.pallas_call")
        while True:  # the call's own parentheses
            depth += {"(": 1, ")": -1}.get(src[i], 0)
            i += 1
            if depth == 0:
                break
        m = re.search(r"\bname=(.+?),\n", src[at:i])
        assert m, src[at:at + 200]
        names.append(m.group(1))
    assert len(set(names)) == len(names)  # one name per kernel
    # the accepted flash_roofline reader tells backward from forward
    # by `transpose` in the kernel's name
    # (the windowed and the latent prefill kernels serve only: a
    # forward, no backward)
    flash = [n for n in names if "flash" in n and "window" not in n
             and "mla" not in n]
    assert sum("transpose" in n for n in flash) == 2 * sum(
        "fwd" in n for n in flash)
