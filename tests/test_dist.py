"""Multi-process dist_sync tests: tools/launch.py spawns 2 real
processes sharing one JAX distributed runtime (the reference tests
multi-node the same way: ``tools/launch.py -n 3 --launcher local``,
``tests/nightly/dist_sync_kvstore.py``)."""

import os
import subprocess
import sys
import time

import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env():
    """Env for launcher-spawned workers: one CPU device per process
    (the realistic per-process topology).  conftest.py's 8-virtual-
    device XLA_FLAGS would otherwise be inherited — 16 virtual devices
    across 2 processes plus the PS handler thread oversubscribe this
    sandbox's single core to a crawl."""
    env = dict(os.environ, PYTHONPATH=REPO)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return env


def test_launch_two_process_dist_sync():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable, os.path.join(REPO, "tests", "dist_worker.py")],
        capture_output=True, text=True, timeout=180,
        cwd=REPO, env=_worker_env())
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert "worker 0/2: dist_sync kvstore OK" in out
    assert "worker 1/2: dist_sync kvstore OK" in out


def test_heartbeat_dead_node_detection(tmp_path, monkeypatch):
    """A stale heartbeat file counts as a dead worker."""
    hb = tmp_path / "hb"
    hb.mkdir()
    monkeypatch.setenv("MXNET_KVSTORE_HEARTBEAT_DIR", str(hb))
    monkeypatch.setenv("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "0.2")
    kv = mx.kv.create("dist_sync")  # single-process: no coordinator env

    class TwoWorkerView(type(kv)):
        @property
        def num_workers(self):
            return 2

    kv.__class__ = TwoWorkerView
    time.sleep(0.5)  # our own heartbeat fires
    # rank 0 (us) alive, rank 1 never wrote -> 1 dead
    assert kv.get_num_dead_node(timeout=5) == 1
    # a fresh rank-1 heartbeat brings it back
    (hb / "hb_1").write_text(str(time.time()))
    assert kv.get_num_dead_node(timeout=5) == 0
    # stale rank-1 heartbeat dies again
    old = time.time() - 100
    os.utime(hb / "hb_1", (old, old))
    assert kv.get_num_dead_node(timeout=5) == 1


def test_launcher_propagates_failure():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu", sys.executable, "-c", "import sys; sys.exit(3)"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 1
    assert "failed" in r.stderr


def test_launch_module_fit_dist_sync(tmp_path):
    """Module.fit across 2 real processes (kvstore='dist_sync',
    update_on_kvstore) must produce the same final weights as a
    single-process run on the union data — the reference's
    tests/nightly/dist_lenet.py check."""
    import numpy as np

    out = str(tmp_path / "dist_params")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable, os.path.join(REPO, "tests", "dist_module_worker.py"),
         out],
        capture_output=True, text=True, timeout=180, cwd=REPO)
    o = r.stdout + r.stderr
    assert r.returncode == 0, o
    assert "worker 0/2: module fit dist_sync OK" in o
    assert "worker 1/2: module fit dist_sync OK" in o

    # single-process reference: same data, global batch, local updater
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import dist_module_worker as W
    X, y = W.make_data()
    single = W.train(X, y, W.GLOBAL_BATCH, kvstore=None)

    d0 = dict(np.load(out + ".rank0.npz"))
    d1 = dict(np.load(out + ".rank1.npz"))
    assert set(d0) == set(single)
    for k in single:
        # both workers identical (replicated updater)
        np.testing.assert_allclose(d0[k], d1[k], rtol=1e-6, atol=1e-7,
                                   err_msg=f"worker disagreement on {k}")
        # and equal to the single-process run
        np.testing.assert_allclose(d0[k], single[k], rtol=1e-4, atol=1e-5,
                                   err_msg=f"dist != single for {k}")


def test_launch_module_fit_tpu_mesh(tmp_path):
    """The north star's execution model: Module.fit(kvstore='tpu') jits
    the fused step over ONE global mesh spanning 2 processes × 4
    virtual devices (dp=8).  Each process supplies only its host-local
    batch (staged via host_local_array_to_global_array); gradients are
    psum'd INSIDE the jitted program across the process boundary.
    Final weights must equal a single-process dp=8 run on the union
    data (reference: kvstore_dist.h:28-318 multi-node story +
    tests/nightly/dist_lenet.py check)."""
    import numpy as np

    out = str(tmp_path / "mesh_params")
    env = dict(os.environ, PYTHONPATH=REPO)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable, os.path.join(REPO, "tests", "dist_tpu_mesh_worker.py"),
         out],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    o = r.stdout + r.stderr
    assert r.returncode == 0, o
    assert "worker 0/2: module fit tpu mesh OK" in o
    assert "worker 1/2: module fit tpu mesh OK" in o
    # dp=4 x tp=2 phase: both ranks train through the tensor-sharded
    # weight and read back identical replicated weights
    import re as _re
    tp_digests = _re.findall(r"tp mesh OK digest=(-?[\d.]+)", o)
    assert len(tp_digests) == 2, o
    assert tp_digests[0] == tp_digests[1], tp_digests

    # single-process reference: same union data, global batch, dp=8 mesh
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import dist_tpu_mesh_worker as W
    X, y = W.make_data()
    single = W.train(X, y, W.GLOBAL_BATCH, kvstore="tpu", seed=7)

    d0 = dict(np.load(out + ".rank0.npz"))
    d1 = dict(np.load(out + ".rank1.npz"))
    assert set(d0) == set(single)
    for k in single:
        # both workers read identical replicated weights off the mesh
        np.testing.assert_allclose(d0[k], d1[k], rtol=1e-6, atol=1e-7,
                                   err_msg=f"worker disagreement on {k}")
        # and equal to the single-process dp=8 run
        np.testing.assert_allclose(d0[k], single[k], rtol=1e-4, atol=1e-5,
                                   err_msg=f"mesh != single for {k}")

    # tp phase ground truth: the 2-process dp=4×tp=2 weights must also
    # equal a single-process dp=4×tp=2 run on the union data in the
    # staged global order — rank agreement alone can't catch a
    # consistently-wrong sharded matmul
    _, tp_single = W.train_tp(None)
    t0 = dict(np.load(out + ".tp.rank0.npz"))
    t1 = dict(np.load(out + ".tp.rank1.npz"))
    assert set(t0) == set(tp_single)
    for k in tp_single:
        np.testing.assert_allclose(t0[k], t1[k], rtol=1e-6, atol=1e-7,
                                   err_msg=f"tp worker disagreement on {k}")
        np.testing.assert_allclose(t0[k], tp_single[k], rtol=1e-4, atol=1e-5,
                                   err_msg=f"tp mesh != single for {k}")


def test_launch_module_fit_dist_sync_on_server(tmp_path):
    """Server-side sync updates (MXNET_KVSTORE_SYNC_ON_SERVER=1): the
    optimizer runs on the sharded servers once NumWorkers pushes arrive,
    workers stateless, pulls wait for the round; FC weights exceed the
    (lowered) big-array bound so split keys are exercised in training.
    Final weights must equal the replicated-path single-process run
    (reference: kvstore_dist_server.h:136-219)."""
    import numpy as np

    out = str(tmp_path / "srv_params")
    env = _worker_env()
    env["MXNET_KVSTORE_SYNC_ON_SERVER"] = "1"
    env["MXNET_KVSTORE_BIGARRAY_BOUND"] = "1000"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable,
         os.path.join(REPO, "tests", "dist_sync_server_worker.py"), out],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    o = r.stdout + r.stderr
    assert r.returncode == 0, o
    assert "worker 0/2: module fit dist_sync on-server OK" in o
    assert "worker 1/2: module fit dist_sync on-server OK" in o

    sys.path.insert(0, os.path.join(REPO, "tests"))
    import dist_module_worker as W
    X, y = W.make_data()
    single = W.train(X, y, W.GLOBAL_BATCH, kvstore=None)

    d0 = dict(np.load(out + ".rank0.npz"))
    d1 = dict(np.load(out + ".rank1.npz"))
    assert set(d0) == set(single)
    for k in single:
        np.testing.assert_allclose(d0[k], d1[k], rtol=1e-6, atol=1e-7,
                                   err_msg=f"worker disagreement on {k}")
        np.testing.assert_allclose(d0[k], single[k], rtol=1e-4, atol=1e-5,
                                   err_msg=f"server-sync != single for {k}")


def test_telemetry_traces_and_watchdog(tmp_path):
    """The observability acceptance path: 2 real processes trace their
    kvstore traffic, dump per-rank Chrome traces, tools/trace_merge.py
    merges them into ONE valid timeline with both pids — and a
    deliberately delayed worker is NAMED by the barrier watchdog log
    within the deadline (instead of the job hanging silently)."""
    import json

    trace_dir = str(tmp_path / "traces")
    env = _worker_env()
    env["MXNET_WATCHDOG_DEADLINE"] = "1"
    env["STRAGGLER_SLEEP_S"] = "4"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable,
         os.path.join(REPO, "tests", "dist_telemetry_worker.py"), trace_dir],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert "worker 0/2: telemetry OK" in out
    assert "worker 1/2: telemetry OK" in out

    # the watchdog named the straggler while rank 1 was still sleeping
    assert "[watchdog] kvstore barrier" in out, out
    assert "waiting on ranks [1]" in out, out

    # per-rank traces exist and merge into one valid Chrome trace
    for rank in (0, 1):
        assert os.path.isfile(
            os.path.join(trace_dir, f"trace_rank{rank}.json"))
    merged = str(tmp_path / "merged.json")
    rm = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_merge.py"),
         trace_dir, "-o", merged],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert rm.returncode == 0, rm.stdout + rm.stderr
    with open(merged) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert pids == {0, 1}, pids  # both ranks present, rank-keyed pids
    names = {e["name"] for e in evs if e.get("ph") == "X"}
    assert any("kvstore" in n for n in names), names
    for pid in (0, 1):  # both ranks contributed real span events
        assert any(e.get("ph") == "X" and e["pid"] == pid for e in evs)
    # spans carry args (bytes moved) for the trace viewer detail pane
    assert any(e.get("args", {}).get("bytes")
               for e in evs if e.get("ph") == "X"), names


def test_comm_overlap_trace(tmp_path):
    """The bucketed-async-comm acceptance path: 2 real processes push
    through the comm scheduler under a small bucket cap; the merged
    trace must show ``kvstore.bucket`` spans (comm thread) running
    WHILE the main thread is inside compute spans — the explicit
    overlap.compute window first (impossible on the blocking path,
    where every allgather completes before push() returns), then under
    Module.fit's fit.step timeline — and both ranks end with identical
    weights.  A bf16-wire phase inside the worker checks compressed
    payloads still sum exactly."""
    import json
    import re

    trace_dir = str(tmp_path / "traces")
    env = _worker_env()
    env["MXNET_KVSTORE_BUCKET_BYTES"] = "65536"  # force several buckets
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable,
         os.path.join(REPO, "tests", "dist_overlap_worker.py"), trace_dir],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    digests = re.findall(r"comm overlap OK digest=([\d.]+)", out)
    assert len(digests) == 2, out
    assert digests[0] == digests[1], f"weight digests differ: {digests}"

    merged = str(tmp_path / "merged.json")
    rm = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_merge.py"),
         trace_dir, "-o", merged],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert rm.returncode == 0, rm.stdout + rm.stderr
    with open(merged) as f:
        evs = [e for e in json.load(f)["traceEvents"]
               if e.get("ph") == "X"]

    def spans(pid, name):
        return [(e["ts"], e["ts"] + e["dur"], e.get("tid"))
                for e in evs if e["pid"] == pid and e["name"] == name]

    for pid in (0, 1):
        buckets = spans(pid, "kvstore.bucket")
        assert buckets, f"rank {pid}: no kvstore.bucket spans"
        # bucket spans carry byte counts for the viewer detail pane
        assert any(e.get("args", {}).get("bytes")
                   for e in evs if e["pid"] == pid
                   and e["name"] == "kvstore.bucket")
        # (1) comm runs on another thread DURING the explicit compute
        # window issued after the pushes already returned
        (c0, c1, ctid), = spans(pid, "overlap.compute")
        overlapping = [b for b in buckets
                       if b[0] < c1 and b[1] > c0 and b[2] != ctid]
        assert overlapping, (
            f"rank {pid}: no comm-thread kvstore.bucket span inside "
            f"the overlap.compute window [{c0}, {c1}]: {buckets}")
        # (2) comm rides under the training-step timeline too
        steps = spans(pid, "fit.step")
        assert steps, f"rank {pid}: no fit.step spans"
        assert any(b[0] < s1 and b[1] > s0
                   for b in buckets for (s0, s1, _t) in steps), (
            f"rank {pid}: no kvstore.bucket span overlaps any fit.step")


def test_launch_two_process_dist_async():
    """Real async consistency: unequal push rates, pulls without
    rendezvous, every push applied on arrival (reference:
    kvstore_dist_server.h:199-207)."""
    env = _worker_env()
    env["MXNET_KVSTORE_BIGARRAY_BOUND"] = "5000"  # (120,120) must split
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable, os.path.join(REPO, "tests", "dist_async_worker.py")],
        capture_output=True, text=True, timeout=180, cwd=REPO,
        env=env)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert "worker 0/2: dist_async update-on-arrival OK" in out
    assert "worker 1/2: dist_async update-on-arrival OK" in out


def test_launch_module_fit_dist_async():
    """Module.fit over the async parameter server: 2 workers at
    different cadences, both converge, and after the final barrier both
    pull identical server weights (digest printed and compared)."""
    import re

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu",
         sys.executable,
         os.path.join(REPO, "tests", "dist_async_module_worker.py")],
        capture_output=True, text=True, timeout=180, cwd=REPO,
        env=_worker_env())
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    digests = re.findall(r"dist_async Module\.fit OK acc=[\d.]+ "
                         r"digest=([\d.]+)", out)
    assert len(digests) == 2, out
    assert digests[0] == digests[1], f"worker weight digests differ: {digests}"


@pytest.mark.slow
def test_elastic_chaos_drill_2_1_2(tmp_path):
    """ISSUE 8 acceptance: the 2→1→2 elastic drill.  Rank 1 SIGKILLed
    mid-epoch; rank 0 must reach the DeadRankError verdict within the
    dead-rank timeout, re-mesh to dp'=1, re-scatter the last committed
    checkpoint onto the surviving shard, resume with no dropped or
    duplicated samples, re-admit the restarted rank at a checkpoint
    boundary, and converge to an uninterrupted run — zero operator
    actions (tier-1 runs the single-process smoke instead:
    tests/test_elastic.py::test_dead_rank_rollback_resume_bitexact)."""
    import json

    out = str(tmp_path / "drill")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_drill.py"),
         "--out", out, "--kill-step", "10"],
        capture_output=True, text=True, timeout=180, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    verdict = json.loads(r.stdout.strip().splitlines()[-1])
    assert verdict["converged"], verdict
    assert verdict["rebuilds"] >= 1, verdict       # a re-mesh happened
    assert verdict["rejoined"], verdict            # scale back up 1→2
    # rollback replay is bounded by the checkpoint cadence (plus the
    # admission re-shard of the joiner counting from its restore point)
    assert 0 <= verdict["steps_lost"] <= 2 * verdict["ckpt_every_n_steps"], \
        verdict
    # no barrier/sync hung past its deadline: downtime (the largest
    # step-to-step gap on the survivor) stays within detection +
    # recovery bounds
    assert verdict["downtime_s"] < 3 * verdict["dead_timeout_s"], verdict


def test_ckpt_kill_and_resume(tmp_path):
    """Acceptance: kill -9 both workers of a 2-proc dist_sync fit
    EXACTLY between the checkpoint barrier and rank 0's COMMIT, then
    relaunch with resume='auto' — the torn checkpoint must be ignored,
    and the resumed run's final weights (params + replicated-updater
    momentum + iterator position all restored) must bit-match an
    uninterrupted 2-proc run."""
    import numpy as np

    worker = os.path.join(REPO, "tests", "dist_ckpt_worker.py")
    launch = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
              "-n", "2", "--cpu", sys.executable, worker]

    # uninterrupted reference
    ckpt_a, out_a = str(tmp_path / "ckpt_a"), str(tmp_path / "a")
    r = subprocess.run(launch + [ckpt_a, out_a], capture_output=True,
                      text=True, timeout=180, cwd=REPO, env=_worker_env())
    o = r.stdout + r.stderr
    assert r.returncode == 0, o
    assert "worker 0/2: ckpt dist fit OK" in o

    # crash run: all ranks die after the barrier, before COMMIT, on the
    # 2nd save (step 8 of 16)
    ckpt_b, out_b = str(tmp_path / "ckpt_b"), str(tmp_path / "b")
    env = _worker_env()
    env["MXNET_CKPT_CRASH"] = "before_commit:2"
    r = subprocess.run(launch + [ckpt_b, out_b], capture_output=True,
                      text=True, timeout=180, cwd=REPO, env=env)
    assert r.returncode != 0, r.stdout + r.stderr

    from mxnet_tpu import checkpoint as C
    infos = C.list_checkpoints(ckpt_b)
    committed = [i.step for i in infos if i.committed]
    torn = [i.step for i in infos if not i.committed]
    assert committed == [4], infos   # step-8 attempt never committed
    assert torn == [8], infos        # ...and its shards are all there
    # both ranks' shards made it to durable storage before the kill —
    # the crash window is precisely barrier -> COMMIT
    torn_dir = [i.path for i in infos if not i.committed][0]
    assert sorted(f for f in os.listdir(torn_dir) if f.endswith(".ok")) == \
        ["shard-00000.ok", "shard-00001.ok"]
    assert "COMMIT" not in os.listdir(torn_dir)

    # resume run: picks the last committed checkpoint (step 4),
    # replays, and lands on the uninterrupted run's exact weights
    r = subprocess.run(launch + [ckpt_b, out_b], capture_output=True,
                      text=True, timeout=180, cwd=REPO, env=_worker_env())
    o = r.stdout + r.stderr
    assert r.returncode == 0, o
    assert "resuming from" in o and "step 4" in o

    for rank in (0, 1):
        ref = dict(np.load(out_a + f".rank{rank}.npz"))
        res = dict(np.load(out_b + f".rank{rank}.npz"))
        assert set(ref) == set(res)
        for k in ref:
            np.testing.assert_array_equal(
                ref[k], res[k],
                err_msg=f"rank{rank} {k}: resume diverged from the "
                        "uninterrupted run")
