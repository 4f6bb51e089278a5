#!/usr/bin/env python
"""Fleet benchmark + chaos drill: closed-loop client sweep over
replica counts, and the kill-one-replica acceptance drill.

Kept as the drill harness of the ``slow`` fleet tests and of
``fleet.py``'s script builder.  Its req/s and latencies are the host's,
over a toy MLP: a judgement of the drill, never a performance record
(that is ``benchmark/run.py`` and ``PERF_LEDGER.jsonl``).

Prints ONE JSON line per mode:

Sweep (default):
  {"metric": "fleet_throughput", "value": N, "unit": "req/s",
   "req_s": N, "p50_ms": N, "p90_ms": N, "p99_ms": N,
   "shed_rate": N, "vs_single_replica": N, "sweep": [...], ...}

Drill (--drill):
  {"metric": "fleet_drill", "lost": 0, "mismatched": 0,
   "replica_deaths": 1, "p99_trace_ms": [...], "swap_ok": true,
   "swap_shed": 0, ...}

Disaggregated serving (--disagg): the same mixed chat + long-prompt-
hammer workload against a prefill/decode role-split fleet AND the
classic mixed fleet; every disagg answer is bit-checked against a
local never-migrated reference engine (same params, same seeds):
  {"metric": "fleet_disagg", "disagg": {"ttft_p99_ms": N,
   "decode_p99_ms_per_token": N, "migration_ms": {"p50": N, "p99": N},
   "migration_bytes": {"total": N, "frames": N, "avg_per_frame": N},
   ...}, "mixed": {...}, "ttft_isolation_vs_mixed": N}
plus one companion {"metric": "fleet_disagg_<headline>", "value": N}
line per headline (ttft_p99 / decode_p99_per_token / migration_p50),
so a reader can take each headline on its own.

Per-role kill drill (--disagg-drill prefill|decode): kill -9 the
replica of that role mid-stream under disaggregated load; zero lost,
zero mismatched, and the stitched trace must show the router.migrate
cross-process edge:
  {"metric": "fleet_disagg_drill_<role>", "lost": 0, "mismatched": 0,
   "re_prefills": N, "migration_edge_in_trace": true, ...}

Methodology:
- Replicas are REAL subprocesses, each wrapping a prewarmed
  InferenceEngine over a deterministic tiny MLP (seeded weights, so
  every replica — and the local reference — computes identical
  outputs; a retried answer is checkable bit-for-bit).
- Closed loop: C client threads each submit one request, block on the
  future, submit the next — offered load scales with C, latency is
  client-side submit→result wall.
- The drill kills -9 one of two replicas MID-STREAM, then asserts:
  zero lost requests (every future resolves), zero mismatches
  (retried answers equal the reference — "match a single-replica
  run"), bounded p99 (the per-second p99 trace is in the JSON), and a
  rolling Router.swap_weights completes with zero shed/dropped
  requests.

Env knobs: FLEET_REPLICAS (CSV sweep, default "1,2"),
FLEET_CLIENTS (default 4), FLEET_REQUESTS (per client, default 32),
MXNET_FLEET_* (config.py), MXNET_DEAD_RANK_TIMEOUT /
MXNET_HEARTBEAT_INTERVAL (conviction latency).
"""

import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np

_DIM, _HIDDEN, _CLASSES = 16, 64, 8


def log(msg):
    print(f"[bench_fleet] {msg}", file=sys.stderr, flush=True)


def _mlp_symbol():
    import mxnet_tpu as mx

    return mx.sym.FullyConnected(
        mx.sym.Activation(
            mx.sym.FullyConnected(mx.sym.Variable("data"),
                                  num_hidden=_HIDDEN, name="fc1"),
            act_type="relu"),
        num_hidden=_CLASSES, name="fc2")


def _mlp_params(scale=1.0):
    rng = np.random.RandomState(0)
    return {
        "fc1_weight": (rng.randn(_HIDDEN, _DIM) * 0.1 * scale
                       ).astype(np.float32),
        "fc1_bias": np.zeros(_HIDDEN, np.float32),
        "fc2_weight": (rng.randn(_CLASSES, _HIDDEN) * 0.1 * scale
                       ).astype(np.float32),
        "fc2_bias": np.zeros(_CLASSES, np.float32),
    }


def build_replica():
    """Replica builder (runs INSIDE each replica process): identical
    seeded weights everywhere, prewarmed buckets — a lazily compiled
    bucket inside the drill would smear the p99 it measures."""
    import mxnet_tpu as mx

    pred = mx.Predictor(_mlp_symbol(), _mlp_params(),
                        {"data": (1, _DIM)})
    return mx.InferenceEngine(pred, buckets=(1, 4, 16),
                              batch_timeout_ms=2.0, prewarm=True)


def _reference():
    import mxnet_tpu as mx

    return mx.Predictor(_mlp_symbol(), _mlp_params(), {"data": (4, _DIM)})


# -- disaggregated prefill/decode fleet (--disagg / --disagg-drill) -------

_V, _KVB, _NL, _NH, _DMODEL, _MAXLEN = 61, 4, 2, 2, 32, 64


def _lm_params():
    """Deterministic tiny-transformer params: every replica process
    (and the local never-migrated reference) initializes IDENTICAL
    weights, so a migrated stream's tokens are checkable bit-for-bit
    against a single-engine run of the same seeds."""
    import mxnet_tpu as mx
    from mxnet_tpu import models

    np.random.seed(0)  # initializers draw from the global numpy RNG
    sym = models.transformer_lm(_V, _MAXLEN, num_layers=_NL,
                                num_heads=_NH, d_model=_DMODEL,
                                block_size=_KVB)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, _MAXLEN))],
             label_shapes=[("softmax_label", (2, _MAXLEN))],
             for_training=False)
    mod.init_params(mx.initializer.Xavier(factor_type="in",
                                          magnitude=2.0))
    arg, aux = mod.get_params()
    return {**arg, **aux}


def build_decode_replica():
    """Decode-replica builder (runs INSIDE each replica process)."""
    import mxnet_tpu as mx

    return mx.DecodeEngine(_lm_params(), vocab_size=_V,
                           num_layers=_NL, num_heads=_NH,
                           d_model=_DMODEL, max_len=_MAXLEN,
                           kv_block=_KVB, max_streams=8,
                           decode_buckets=[1, 2, 4, 8],
                           temperature=0.0)


def _disagg_jobs(n_chat, n_hammer):
    """Mixed chat + long-prompt-hammer workload: (i, prompt, max_new)
    jobs, deterministic in i.  The hammer's near-max-length prompts
    are what poison TTFT on a mixed fleet — each one monopolizes a
    prefill slot while short chat turns queue behind it."""
    jobs = []
    for i in range(n_chat):
        rng = np.random.RandomState(2000 + i)
        jobs.append(("chat", i, rng.randint(
            1, _V - 1, size=int(rng.randint(4, 9))).astype(np.int32), 12))
    for i in range(n_hammer):
        rng = np.random.RandomState(7000 + i)
        jobs.append(("hammer", n_chat + i, rng.randint(
            1, _V - 1, size=int(rng.randint(40, 49))).astype(np.int32), 6))
    return jobs


def _gen_closed_loop(router, jobs, clients, expect=None,
                     lat_split=None):
    """Closed-loop router.generate over the job list; returns
    (errs, wall_s).  ``expect[i]`` (when given) is the reference token
    array — any delivered mismatch is a bit-identity violation."""
    errs = {"lost": 0, "mismatched": 0, "shed": 0}
    lock = threading.Lock()
    qi = {"n": 0}

    def client():
        from mxnet_tpu.fleet import ShedError

        while True:
            with lock:
                if qi["n"] >= len(jobs):
                    return
                kind, i, prompt, max_new = jobs[qi["n"]]
                qi["n"] += 1
            t0 = time.perf_counter()
            try:
                out = router.generate(prompt, max_new_tokens=max_new,
                                      temperature=0.8,
                                      seed=5000 + i).result(120)
            except ShedError:
                with lock:
                    errs["shed"] += 1
                continue
            except BaseException as exc:  # noqa: BLE001
                log(f"stream {i} LOST: {exc}")
                with lock:
                    errs["lost"] += 1
                continue
            ms = (time.perf_counter() - t0) * 1e3
            with lock:
                if lat_split is not None:
                    lat_split.setdefault(kind, []).append(ms)
                if expect is not None \
                        and not np.array_equal(np.asarray(out),
                                               expect[i]):
                    log(f"stream {i} MISMATCH: {out} != {expect[i]}")
                    errs["mismatched"] += 1

    threads = [threading.Thread(target=client) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errs, time.perf_counter() - t0


def _expected_tokens(jobs):
    """Never-migrated reference: one local engine, same params, same
    (engine seed, stream seed, position) sampling keys."""
    log("computing local never-migrated reference tokens")
    ref = build_decode_replica()
    try:
        futs = {i: ref.submit(prompt, max_new, temperature=0.8,
                              seed=5000 + i)
                for _, i, prompt, max_new in jobs}
        return {i: np.asarray(f.result(120)) for i, f in futs.items()}
    finally:
        ref.close()


def _engine_ttft_p99(router):
    """Max engine-side TTFT p99 across live replicas (the mixed
    baseline has no router-side TTFT observation point)."""
    worst = None
    for state in router._replicas.values():
        if state.dead:
            continue
        try:
            st = state.handle.stats()
        except Exception:  # noqa: BLE001
            continue
        p99 = ((st.get("latency_breakdown") or {}).get("ttft")
               or {}).get("p99_ms")
        if p99 is not None and (worst is None or p99 > worst):
            worst = p99
    return worst


def main_disagg(args):
    """TTFT-isolation benchmark: the same mixed chat + long-prompt-
    hammer workload against (a) a disaggregated prefill/decode fleet
    and (b) the classic mixed fleet, with every disagg answer
    bit-checked against a local never-migrated reference."""
    jobs = _disagg_jobs(
        int(os.environ.get("FLEET_CHAT", str(args.requests))),
        int(os.environ.get("FLEET_HAMMER",
                           str(max(4, args.requests // 4)))))
    clients = int(os.environ.get("FLEET_CLIENTS", "6"))
    expect = _expected_tokens(jobs)
    builder = os.path.abspath(__file__) + ":build_decode_replica"
    out = {"metric": "fleet_disagg", "replicas": args.replicas,
           "clients": clients,
           "jobs": {"chat": sum(1 for j in jobs if j[0] == "chat"),
                    "hammer": sum(1 for j in jobs if j[0] == "hammer")}}
    for mode in ("disagg", "mixed"):
        roles = (["prefill"] + ["decode"] * (args.replicas - 1)
                 if mode == "disagg" else None)
        fleet_dir = tempfile.mkdtemp(prefix=f"fleet-{mode}-")
        from mxnet_tpu import fleet

        router, procs = fleet.launch_local_fleet(
            args.replicas, fleet_dir, builder, roles=roles,
            replica_depth=8)
        try:
            # warm every replica's executables + the route
            warm = [("warm", 10_000 + k,
                     np.asarray([1 + k, 2, 3], np.int32), 2)
                    for k in range(args.replicas * 2)]
            _gen_closed_loop(router, warm, 2)
            router.reset_stats()
            lat_split = {}
            errs, wall = _gen_closed_loop(router, jobs, clients,
                                          expect=expect,
                                          lat_split=lat_split)
            s = router.stats()
            point = {
                "lost": errs["lost"], "mismatched": errs["mismatched"],
                "shed": errs["shed"],
                "streams_per_s": round(len(jobs) / wall, 2),
                "chat": _pcts(lat_split.get("chat", [])),
                "hammer": _pcts(lat_split.get("hammer", [])),
                "engine_ttft_p99_ms": _engine_ttft_p99(router),
            }
            if mode == "disagg":
                point.update({
                    "ttft_p99_ms": s["ttft_p99_ms"],
                    "ttft_p50_ms": s["ttft_p50_ms"],
                    "decode_p99_ms_per_token":
                        s["decode_per_token_p99_ms"],
                    "migrations": s["migrations"],
                    "re_prefills": s["re_prefills"],
                    "migration_ms": {"p50": s["migration_p50_ms"],
                                     "p99": s["migration_p99_ms"]},
                    "migration_bytes": {
                        "total": s["migration_bytes"],
                        "frames": s["migrations"],
                        "avg_per_frame": (
                            round(s["migration_bytes"]
                                  / s["migrations"], 1)
                            if s["migrations"] else None)},
                })
            out[mode] = point
            log(f"{mode}: {point}")
        finally:
            router.close(stop_replicas=True)
            for p in procs:
                p.terminate()
    d, m = out["disagg"], out["mixed"]
    out["value"] = d["ttft_p99_ms"]
    out["unit"] = "ms"
    out["ttft_isolation_vs_mixed"] = (
        round(m["engine_ttft_p99_ms"] / d["engine_ttft_p99_ms"], 2)
        if d.get("engine_ttft_p99_ms") and m.get("engine_ttft_p99_ms")
        else None)
    print(json.dumps(out))
    # companion one-metric lines: each disagg headline on its own
    for metric, value in (
            ("fleet_disagg_ttft_p99", d["ttft_p99_ms"]),
            ("fleet_disagg_decode_p99_per_token",
             d["decode_p99_ms_per_token"]),
            ("fleet_disagg_migration_p50", d["migration_ms"]["p50"])):
        if value is not None:
            print(json.dumps({"metric": metric, "value": round(value, 3),
                              "unit": "ms", "backend": "cpu",
                              "model": "transformer_lm"}))
    ok = (d["lost"] == 0 and d["mismatched"] == 0 and d["shed"] == 0
          and d["migrations"] > 0)
    return 0 if ok else 1


def main_disagg_drill(args, role):
    """kill -9 the replica of ONE role mid-stream under disagg load:
    zero lost, zero mismatched (answers bit-checked against the local
    never-migrated reference), and the stitched trace shows the
    router.migrate cross-process edge."""
    from mxnet_tpu import fleet, profiler

    fleet_dir = args.fleet_dir or tempfile.mkdtemp(
        prefix=f"fleet-disagg-{role}-")
    os.environ.setdefault("MXNET_FLIGHT_RECORDER_DIR", fleet_dir)
    ring_dir = os.environ["MXNET_FLIGHT_RECORDER_DIR"]
    profiler.init_flight_recorder(ring_dir)
    n = max(3, args.replicas)
    roles = ["prefill"] + ["decode"] * (n - 1)
    jobs = _disagg_jobs(max(12, args.requests), 4)
    expect = _expected_tokens(jobs)
    builder = os.path.abspath(__file__) + ":build_decode_replica"
    router, procs = fleet.launch_local_fleet(
        n, fleet_dir, builder, roles=roles, replica_depth=8)
    # rid order == roles order: rid 0 is THE prefill replica
    victim = 0 if role == "prefill" else 1
    try:
        warm = [("warm", 10_000 + k, np.asarray([1 + k, 2], np.int32), 2)
                for k in range(n * 2)]
        _gen_closed_loop(router, warm, 2)
        router.reset_stats()
        # every delivered answer calls lat_split.setdefault once —
        # count them so the killer fires genuinely MID-STREAM
        done = {"n": 0}

        class _Counting(dict):
            def setdefault(self, k, v):
                done["n"] += 1
                return super().setdefault(k, v)

        lat_counting = _Counting()

        def killer():
            while done["n"] < max(2, len(jobs) // 4):
                time.sleep(0.005)
            log(f"kill -9 {role}-role replica rid {victim} "
                f"(pid {procs[victim].pid})")
            os.kill(procs[victim].pid, signal.SIGKILL)

        kt = threading.Thread(target=killer)
        kt.start()
        errs, wall = _gen_closed_loop(router, jobs, 6, expect=expect,
                                      lat_split=lat_counting)
        kt.join()
        deadline = time.monotonic() + 15.0
        while router.stats()["replica_deaths"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        s = router.stats()
        stitched = _stitch_drill_trace(fleet_dir, ring_dir,
                                       procs[victim].pid)
        # the migration edge must be visible in the merged trace
        mig_edge = False
        if stitched.get("stitched_trace"):
            with open(stitched["stitched_trace"]) as f:
                merged = json.load(f)
            mig_edge = any(e.get("name") == "router.migrate"
                           for e in merged["traceEvents"])
        verdict = {
            "metric": f"fleet_disagg_drill_{role}",
            "replicas": n, "requests": len(jobs),
            "lost": errs["lost"], "mismatched": errs["mismatched"],
            "shed": errs["shed"],
            "replica_deaths": s["replica_deaths"],
            "retries": s["retries"], "re_prefills": s["re_prefills"],
            "migrations": s["migrations"],
            "duplicates": s["duplicates"],
            "migration_edge_in_trace": bool(mig_edge),
            **stitched, "wall_s": round(wall, 2),
        }
        print(json.dumps(verdict))
        return 0 if (verdict["lost"] == 0 and verdict["mismatched"] == 0
                     and verdict["replica_deaths"] >= 1
                     and verdict["migrations"] > 0) else 1
    finally:
        router.close(stop_replicas=True)
        for p in procs:
            try:
                p.kill()
            except OSError:
                pass


def _request(i):
    rng = np.random.RandomState(1000 + i)
    return rng.rand(1, _DIM).astype(np.float32)


def _launch(n, fleet_dir, **router_kw):
    from mxnet_tpu import fleet

    log(f"launching {n} replica process(es) under {fleet_dir}")
    router, procs = fleet.launch_local_fleet(
        n, fleet_dir, os.path.abspath(__file__) + ":build_replica",
        **router_kw)
    return router, procs


def _closed_loop(router, clients, per_client, lat_sink=None,
                 check=None, deadline_ms=None):
    """C closed-loop clients; returns (answered, lost, mismatched,
    shed, latencies_ms sorted)."""
    from mxnet_tpu.fleet import ShedError

    lats, errs = [], {"lost": 0, "mismatched": 0, "shed": 0}
    lock = threading.Lock()

    def client(cid):
        for k in range(per_client):
            i = cid * per_client + k
            x = _request(i)
            t0 = time.perf_counter()
            try:
                out = router.submit({"data": x},
                                    deadline_ms=deadline_ms).result(120)
            except ShedError:
                with lock:
                    errs["shed"] += 1
                continue
            except BaseException as exc:  # noqa: BLE001
                log(f"request {i} LOST: {exc}")
                with lock:
                    errs["lost"] += 1
                continue
            ms = (time.perf_counter() - t0) * 1e3
            with lock:
                lats.append(ms)
                if lat_sink is not None:
                    lat_sink.append((time.perf_counter(), ms))
                if check is not None and not check(i, out[0]):
                    errs["mismatched"] += 1

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return lats, errs, wall


def _pcts(lats):
    if not lats:
        return {"p50_ms": None, "p90_ms": None, "p99_ms": None}
    a = np.asarray(lats)
    return {"p50_ms": round(float(np.percentile(a, 50)), 3),
            "p90_ms": round(float(np.percentile(a, 90)), 3),
            "p99_ms": round(float(np.percentile(a, 99)), 3)}


def main_sweep(args):
    from mxnet_tpu import fleet

    counts = [int(x) for x in
              os.environ.get("FLEET_REPLICAS", "1,2").split(",")]
    clients = int(os.environ.get("FLEET_CLIENTS", "4"))
    per_client = int(os.environ.get("FLEET_REQUESTS", "32"))
    sweep = []
    for n in counts:
        fleet_dir = tempfile.mkdtemp(prefix=f"fleet-bench-{n}r-")
        router, procs = _launch(n, fleet_dir)
        try:
            # warm the route (and the cost model) before timing
            _closed_loop(router, 2, 4)
            router.reset_stats()
            lats, errs, wall = _closed_loop(router, clients, per_client)
            stats = router.stats()
            point = {"replicas": n, "clients": clients,
                     "requests": len(lats),
                     "req_s": round(len(lats) / wall, 2),
                     "shed_rate": round(stats["shed_rate"], 4),
                     "lost": errs["lost"], **_pcts(lats),
                     "latency_breakdown": stats["latency_breakdown"]}
            sweep.append(point)
            log(f"point: {point}")
        finally:
            router.close(stop_replicas=True)
            for p in procs:
                p.terminate()
    best = max(sweep, key=lambda p: p["req_s"])
    single = next((p for p in sweep if p["replicas"] == 1), None)
    print(json.dumps({
        "metric": "fleet_throughput", "value": best["req_s"],
        "unit": "req/s", "req_s": best["req_s"],
        "p50_ms": best["p50_ms"], "p90_ms": best["p90_ms"],
        "p99_ms": best["p99_ms"], "shed_rate": best["shed_rate"],
        "vs_single_replica": (round(best["req_s"] / single["req_s"], 2)
                              if single and single["req_s"] else None),
        "latency_breakdown": best["latency_breakdown"],
        "clients": clients, "model": "mlp", "sweep": sweep,
    }))
    return 0


def _stitch_drill_trace(fleet_dir, ring_dir, killed_pid):
    """Merge the fleet dir's flight rings (incl. the kill -9'd
    replica's — its mmap pages survived the process) into one
    Perfetto trace, and pull out a RETRIED request's stitched tree:
    the acceptance artifact whose timeline visibly spans the dead
    replica, the conviction window (the router.retry span), and the
    surviving replica."""
    import glob

    from mxnet_tpu import profiler

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_merge as tm

    profiler.flight_recorder().sync()
    # rings live where the recorder was pointed — the fleet dir by
    # default, or the operator's MXNET_FLIGHT_RECORDER_DIR (replicas
    # inherit the same env, so both cases are one glob)
    rings = sorted(glob.glob(os.path.join(ring_dir, "flight_*.ring")))
    traces = []
    for f in rings:
        try:
            traces.append(tm.load_trace(f))
        except Exception as exc:  # noqa: BLE001
            log(f"unreadable flight ring {f}: {exc}")
    out = {"stitched_trace": None, "retried_trace": None,
           "postmortem_from_killed": False}
    killed = glob.glob(os.path.join(
        ring_dir, f"flight_rank*_pid{killed_pid}.ring"))
    if killed:
        try:
            doc = tm.load_trace(killed[0])
            out["postmortem_from_killed"] = \
                len(doc["traceEvents"]) > 0
            out["killed_ring_events"] = len(doc["traceEvents"])
        except Exception as exc:  # noqa: BLE001
            log(f"killed replica ring unreadable: {exc}")
    if not traces:
        return out
    merged = tm.merge_traces(traces)
    path = os.path.join(fleet_dir, "drill_trace.json")
    with open(path, "w") as f:
        json.dump(merged, f)
    out["stitched_trace"] = path
    retry_tids = [e["args"]["trace_id"] for e in merged["traceEvents"]
                  if e.get("name") == "router.retry"
                  and (e.get("args") or {}).get("trace_id")]
    if retry_tids:
        tid = retry_tids[0]
        roots = tm.trace_tree(merged["traceEvents"], tid)

        def _walk(nodes):
            for n in nodes:
                yield n
                yield from _walk(n["children"])

        nodes = list(_walk(roots))
        pids = {n["event"].get("pid") for n in nodes}
        out["retried_trace"] = {
            "trace_id": tid, "spans": len(nodes),
            "processes": len(pids),
            "has_retry_span": any(
                n["event"]["name"] == "router.retry" for n in nodes),
        }
        log("retried request's stitched tree:\n"
            + tm.format_tree(roots))
    return out


def main_drill(args):
    """kill -9 one of two replicas under load; then a rolling swap."""
    from mxnet_tpu import checkpoint as ckpt_mod
    from mxnet_tpu import profiler

    fleet_dir = args.fleet_dir or tempfile.mkdtemp(prefix="fleet-drill-")
    # flight recorder: router + replicas all ring-file into the fleet
    # dir (replicas inherit the env), so the kill -9'd process leaves
    # its post-mortem where the stitcher looks
    os.environ.setdefault("MXNET_FLIGHT_RECORDER_DIR", fleet_dir)
    ring_dir = os.environ["MXNET_FLIGHT_RECORDER_DIR"]
    profiler.init_flight_recorder(ring_dir)
    router, procs = _launch(args.replicas, fleet_dir,
                            replica_depth=4)
    ref = _reference()
    expect = {}

    def check(i, out):
        if i not in expect:
            ref.forward(data=np.repeat(_request(i), 4, axis=0))
            expect[i] = ref.get_output(0)[:1]
        return np.allclose(out, expect[i], rtol=1e-5, atol=1e-6)

    trace = []
    try:
        # warm routes + cost model
        _closed_loop(router, 2, 4, check=check)
        router.reset_stats()

        clients = int(os.environ.get("FLEET_CLIENTS", "4"))
        per_client = max(8, args.requests // clients)
        total = clients * per_client
        done_flag = threading.Event()

        def killer():
            # fire MID-STREAM: once a quarter of the answers landed
            while len(trace) < max(2, total // 4) \
                    and not done_flag.is_set():
                time.sleep(0.005)
            log(f"kill -9 replica pid {procs[0].pid}")
            os.kill(procs[0].pid, signal.SIGKILL)

        kt = threading.Thread(target=killer)
        kt.start()
        lats, errs, wall = _closed_loop(router, clients, per_client,
                                        lat_sink=trace, check=check)
        done_flag.set()
        kt.join()
        # the conviction may trail the last answer by a scan interval
        deadline = time.monotonic() + 15.0
        while router.stats()["replica_deaths"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        stats = router.stats()
        log(f"post-kill stats: {stats}")

        # per-second p99 trace (the kill-one-replica figure)
        t_start = trace[0][0] if trace else time.perf_counter()
        buckets = {}
        for t, ms in trace:
            buckets.setdefault(int(t - t_start), []).append(ms)
        p99_trace = [round(float(np.percentile(v, 99)), 2)
                     for _, v in sorted(buckets.items())]

        # rolling weight swap under fresh load: zero shed, zero lost
        pub_dir = os.path.join(fleet_dir, "pub")
        ckpt_mod.publish_params(pub_dir, _mlp_params(), step=2)
        swap_errs = {}
        stop = threading.Event()

        def swap_load():
            i = 0
            while not stop.is_set():
                try:
                    router.submit({"data": _request(i)}).result(120)
                except BaseException as exc:  # noqa: BLE001
                    swap_errs[i] = str(exc)
                i += 1

        loaders = [threading.Thread(target=swap_load) for _ in range(2)]
        shed_before = router.stats()["shed"]
        for t in loaders:
            t.start()
        time.sleep(0.2)
        try:
            swap = router.swap_weights(pub_dir)
            swap_ok = swap["step"] == 2 and len(swap["replicas"]) >= 1
        except BaseException as exc:  # noqa: BLE001
            log(f"swap failed: {exc}")
            swap, swap_ok = {}, False
        time.sleep(0.2)
        stop.set()
        for t in loaders:
            t.join()
        swap_shed = router.stats()["shed"] - shed_before \
            + len(swap_errs)

        # observability artifacts: the stitched per-request trace and
        # the killed replica's flight-recorder post-mortem
        stitched = _stitch_drill_trace(fleet_dir, ring_dir,
                                       procs[0].pid)

        verdict = {
            "metric": "fleet_drill",
            "replicas": args.replicas,
            "requests": len(lats) + errs["lost"] + errs["shed"],
            "lost": errs["lost"],
            "mismatched": errs["mismatched"],
            "shed": errs["shed"],
            "replica_deaths": stats["replica_deaths"],
            "retries": stats["retries"],
            "duplicates": stats["duplicates"],
            **_pcts(lats),
            "p99_trace_ms": p99_trace,
            "latency_breakdown": stats["latency_breakdown"],
            **stitched,
            "swap_ok": bool(swap_ok),
            "swap_shed": int(swap_shed),
            "swap_report": swap,
            "wall_s": round(wall, 2),
        }
        print(json.dumps(verdict))
        return 0 if (verdict["lost"] == 0 and verdict["mismatched"] == 0
                     and verdict["replica_deaths"] == 1 and swap_ok
                     and swap_shed == 0
                     and verdict["postmortem_from_killed"]) else 1
    finally:
        router.close(stop_replicas=True)
        for p in procs:
            try:
                p.kill()
            except OSError:
                pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--drill", action="store_true",
                    help="kill-one-replica acceptance drill")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode TTFT-isolation "
                         "bench (vs the mixed baseline)")
    ap.add_argument("--disagg-drill", choices=("prefill", "decode"),
                    default=None, metavar="ROLE",
                    help="kill -9 the replica of ROLE mid-stream under "
                         "disaggregated load")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--fleet-dir", default=None)
    args = ap.parse_args()
    import jax

    from mxnet_tpu.config import place_compile_cache

    place_compile_cache()
    if args.disagg_drill:
        if args.replicas == 2:
            args.replicas = 3  # a drill needs a survivor of each role
        return main_disagg_drill(args, args.disagg_drill)
    if args.disagg:
        if args.replicas == 2:
            args.replicas = 3
        return main_disagg(args)
    return main_drill(args) if args.drill else main_sweep(args)


if __name__ == "__main__":
    sys.exit(main())
