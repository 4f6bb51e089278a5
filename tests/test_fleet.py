"""Fleet-router semantics with in-process fake replicas (tier-1 fast):
retry-exactly-once on replica death, typed deadline shedding with
oldest-deadline-first ordering, zero-drop rolling weight swap, the
fleet wire (HMAC'd control frames), and the engine-side inflight/
drain/swap hooks.  The real multi-process kill -9 drill lives in
tools/bench_fleet.py and runs under the `slow` marker."""

import json
import os
import queue
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt_mod
from mxnet_tpu import fleet
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic import HeartbeatWriter
from mxnet_tpu.fleet import (FleetClient, ReplicaClient, ReplicaServer,
                             Router, ShedError)
from mxnet_tpu.serving import ReplicaHarness


class FakeReplica:
    """In-process replica handle: one worker thread answering requests
    after ``service_ms``.  Implements the Router's handle duck type
    exactly (submit→Future-of-list, inflight, drain, resume, swap,
    stats, close) plus fault injection: ``freeze()`` stops answering
    (responses are HELD, like a replica that wedged), ``kill()``
    additionally stops the heartbeat, ``flush()`` releases held
    answers late (the zombie's last gasp)."""

    def __init__(self, rid, service_ms=2.0, hb_dir=None,
                 hb_interval=0.05, scale=1.0):
        self.rid = rid
        self.scale = scale
        self.service_s = service_ms / 1e3
        self.served = []          # specs answered (distribution asserts)
        self.swapped = []         # (step, inflight_at_swap)
        self.weights_step = -1
        self._q = queue.Queue()
        self._held = []
        self._frozen = threading.Event()
        self._lock = threading.Lock()
        self._inflight = set()
        self._accepting = True
        self._hb = HeartbeatWriter(hb_dir, rid, interval=hb_interval) \
            if hb_dir else None
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    # -- handle surface -------------------------------------------------
    def submit(self, spec):
        fut = Future()
        with self._lock:
            if not self._accepting:
                raise ConnectionError(f"replica {self.rid} is down")
            self._inflight.add(fut)
        self._q.put((spec, fut))
        return fut

    def inflight(self):
        with self._lock:
            return len(self._inflight)

    def drain(self, timeout=30.0):
        deadline = time.monotonic() + timeout
        while self.inflight() and time.monotonic() < deadline:
            time.sleep(0.002)
        return self.inflight()

    def resume(self):
        pass

    def swap(self, ckpt_dir, drain_timeout=60.0):
        _params, step, path = ckpt_mod.load_latest_params(ckpt_dir)
        self.swapped.append((step, self.inflight()))
        if self.inflight():
            raise MXNetError(
                f"swap with {self.inflight()} in flight — the router "
                "failed to drain this replica")
        self.weights_step = step
        return {"step": step, "path": path}

    def stats(self):
        return {"rid": self.rid, "served": len(self.served)}

    def close(self):
        pass

    # -- fault injection ------------------------------------------------
    def freeze(self):
        self._frozen.set()

    def kill(self):
        """kill -9 equivalent: stop answering AND stop heartbeating."""
        self.freeze()
        with self._lock:
            self._accepting = False
        if self._hb is not None:
            self._hb.stop(remove=True)

    def flush(self):
        """Release answers held while frozen — the zombie's late
        responses arriving after conviction."""
        held, self._held = self._held, []
        for spec, fut, result in held:
            self._finish(spec, fut, result)

    # -- worker ---------------------------------------------------------
    def _run(self):
        while True:
            spec, fut = self._q.get()
            if spec is None:
                return
            time.sleep(self.service_s)
            result = self._answer(spec)
            if self._frozen.is_set():
                self._held.append((spec, fut, result))
                continue
            self._finish(spec, fut, result)

    def _finish(self, spec, fut, result):
        with self._lock:
            self._inflight.discard(fut)
        self.served.append(spec)
        if fut.set_running_or_notify_cancel():
            fut.set_result(result)

    def _answer(self, spec):
        if spec["kind"] == "infer":
            x = next(iter(spec["inputs"].values()))
            return [np.asarray(x, np.float64) * self.scale]
        # decode: deterministic in (prompt, seed) — replica-independent,
        # like the real engines' seeded sampling
        p = np.asarray(spec["prompt"])
        seed = int(spec["seed"])
        return [np.asarray([(int(p.sum()) * 7 + seed * 31 + i) % 997
                            for i in range(int(spec["max_new"]))],
                           np.int32)]


def _router(replicas, **kw):
    kw.setdefault("retry_budget", 2)
    kw.setdefault("default_deadline_ms", 0)
    return Router(replicas, **kw)


def _results(futs, timeout=30.0):
    return [f.result(timeout) for f in futs]


# ---------------------------------------------------------------------------
# routing + spreading
# ---------------------------------------------------------------------------


def test_router_spreads_and_answers_correctly():
    reps = [FakeReplica(0), FakeReplica(1)]
    with _router(reps) as r:
        futs = [r.submit({"x": np.full((1, 3), i, np.float64)})
                for i in range(16)]
        outs = _results(futs)
        for i, out in enumerate(outs):
            assert np.array_equal(out[0], np.full((1, 3), i))
        assert len(reps[0].served) + len(reps[1].served) == 16
        # least-depth routing with 2 idle replicas must use both
        assert len(reps[0].served) > 0 and len(reps[1].served) > 0
        s = r.stats()
        assert s["responses"] == 16 and s["shed"] == 0
        assert s["retries"] == 0 and s["replica_deaths"] == 0


def test_decode_routes_and_unwraps_tokens():
    reps = [FakeReplica(0)]
    with _router(reps) as r:
        out = r.generate(np.asarray([3, 5], np.int32),
                         max_new_tokens=4).result(10)
        assert out.dtype == np.int32 and out.shape == (4,)


# ---------------------------------------------------------------------------
# replica death: transparent retry, exactly-once
# ---------------------------------------------------------------------------


def test_retry_exactly_once_on_replica_death(tmp_path):
    hb = str(tmp_path)
    reps = [FakeReplica(0, hb_dir=hb), FakeReplica(1, hb_dir=hb)]
    with _router(reps, fleet_dir=hb, dead_timeout=0.3,
                 replica_depth=4) as r:
        # kill replica 0 with work in flight: its requests must retry
        # on replica 1 and every client future must still resolve
        reps[0].service_s = 0.2
        futs = [r.submit({"x": np.full((1, 2), i, np.float64)})
                for i in range(8)]
        time.sleep(0.05)
        reps[0].kill()
        outs = _results(futs, timeout=30.0)
        for i, out in enumerate(outs):
            assert np.array_equal(out[0], np.full((1, 2), i))
        s = r.stats()
        assert s["replica_deaths"] == 1
        assert s["retries"] >= 1
        assert s["responses"] == 8 and s["failures"] == 0
        assert r.alive_replicas() == [1]
        # every request answered exactly once client-side
        assert all(f.done() for f in futs)


def test_zombie_late_answer_is_dropped_not_double_delivered(tmp_path):
    hb = str(tmp_path)
    reps = [FakeReplica(0, hb_dir=hb), FakeReplica(1, hb_dir=hb)]
    with _router(reps, fleet_dir=hb, dead_timeout=0.3,
                 replica_depth=8) as r:
        # slow enough that most of replica 0's share is still in
        # service when the freeze lands (held, not yet answered)
        reps[0].service_s = 0.04
        futs = [r.submit({"x": np.full((1, 2), i, np.float64)})
                for i in range(8)]
        time.sleep(0.06)
        reps[0].kill()
        outs = _results(futs, timeout=30.0)
        # the survivors may answer the retries before the zombie's worker
        # is through the request it was serving when the freeze landed:
        # wait until it holds every answer it still owes
        deadline = time.monotonic() + 5.0
        while len(reps[0]._held) < reps[0].inflight() \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        held = len(reps[0]._held)
        assert held > 0, "zombie held nothing — the fault never fired"
        # now the zombie's held answers arrive late
        reps[0].flush()
        time.sleep(0.3)
        s = r.stats()
        # exactly-once: every late answer was for an already-delivered
        # ticket — counted as a duplicate and DROPPED, responses stay 8
        assert s["responses"] == 8
        assert s["duplicates"] == held
        for i, out in enumerate(outs):
            assert np.array_equal(out[0], np.full((1, 2), i))


def test_retry_budget_exhaustion_fails_loudly(tmp_path):
    hb = str(tmp_path)
    reps = [FakeReplica(0, hb_dir=hb)]
    with _router(reps, fleet_dir=hb, dead_timeout=0.3,
                 retry_budget=0) as r:
        reps[0].service_s = 0.5
        fut = r.submit({"x": np.ones((1, 2))})
        time.sleep(0.05)
        reps[0].kill()
        with pytest.raises(MXNetError, match="retry budget"):
            fut.result(30.0)


def test_decode_retry_is_bit_identical(tmp_path):
    """The acceptance property: a retried decode yields the SAME
    tokens a single-replica run yields — the router's deterministic
    seed stamp + seed-keyed sampling."""
    hb = str(tmp_path)
    prompts = [np.asarray([2 + i, 9], np.int32) for i in range(6)]

    # single-replica reference run
    ref_rep = FakeReplica(0)
    with _router([ref_rep]) as r:
        ref = [r.generate(p, max_new_tokens=5).result(10) for p in prompts]

    reps = [FakeReplica(0, hb_dir=hb), FakeReplica(1, hb_dir=hb)]
    with _router(reps, fleet_dir=hb, dead_timeout=0.3) as r:
        reps[0].service_s = 0.15
        futs = [r.generate(p, max_new_tokens=5) for p in prompts]
        time.sleep(0.05)
        reps[0].kill()
        outs = _results(futs, timeout=30.0)
    for a, b in zip(ref, outs):
        assert np.array_equal(a, b), "retried decode re-sampled tokens"


# ---------------------------------------------------------------------------
# admission control + shedding
# ---------------------------------------------------------------------------


def _prime_cost(router, n=4, units=1):
    """Teach the cost model its first EMA samples."""
    futs = [router.submit({"x": np.zeros((units, 2))}) for _ in range(n)]
    _results(futs)


def test_deadline_provably_unmeetable_sheds_typed():
    rep = FakeReplica(0, service_ms=60.0)
    with _router([rep], replica_depth=2) as r:
        _prime_cost(r)
        # occupy the replica, then ask for the impossible
        bg = [r.submit({"x": np.zeros((1, 2))}) for _ in range(2)]
        fut = r.submit({"x": np.zeros((1, 2))}, deadline_ms=5.0)
        with pytest.raises(ShedError) as ei:
            fut.result(10)
        assert ei.value.reason in ("deadline", "expired")
        _results(bg)  # in-flight work unaffected by the shed
        assert r.stats()["shed"] == 1


def test_no_measurement_means_no_shed():
    """'Provably' requires measurements: an unmeasured bucket admits
    (measure instead of assume — the PR-1 exploration rule)."""
    rep = FakeReplica(0, service_ms=1.0)
    with _router([rep]) as r:
        out = r.submit({"x": np.zeros((1, 2))},
                       deadline_ms=10_000).result(10)
        assert out[0].shape == (1, 2)
        assert r.stats()["shed"] == 0


def test_overload_sheds_oldest_deadline_first():
    rep = FakeReplica(0, service_ms=80.0)
    with _router([rep], replica_depth=1, max_pending=2) as r:
        _prime_cost(r, n=2)
        # one in flight; then flood with staggered deadlines.  The
        # queue bound is 2, so the EARLIEST deadlines must shed first.
        deadlines = [5000.0, 500.0, 3000.0, 1000.0, 9000.0]
        futs = [r.submit({"x": np.full((1, 2), i)}, deadline_ms=d)
                for i, d in enumerate(deadlines)]
        shed, ok = [], []
        for d, f in zip(deadlines, futs):
            try:
                f.result(30)
                ok.append(d)
            except ShedError:
                shed.append(d)
        assert shed, "overload never shed"
        # ordering property: every shed deadline <= every survivor's
        assert max(shed) <= min(ok) + 1e-9
        s = r.stats()
        assert s["shed"] == len(shed) and s["shed"] >= 1


def test_fleet_env_validation_garbage_raises(monkeypatch):
    rep = FakeReplica(0)
    monkeypatch.setenv("MXNET_FLEET_RETRY_BUDGET", "banana")
    with pytest.raises(MXNetError, match="MXNET_FLEET_RETRY_BUDGET"):
        Router([rep])
    monkeypatch.setenv("MXNET_FLEET_RETRY_BUDGET", "-3")
    with pytest.raises(MXNetError, match="MXNET_FLEET_RETRY_BUDGET"):
        Router([rep])
    monkeypatch.delenv("MXNET_FLEET_RETRY_BUDGET")
    monkeypatch.setenv("MXNET_FLEET_SHED_DEADLINE_MS", "-1")
    with pytest.raises(MXNetError, match="MXNET_FLEET_SHED_DEADLINE_MS"):
        Router([rep])
    monkeypatch.delenv("MXNET_FLEET_SHED_DEADLINE_MS")
    monkeypatch.setenv("MXNET_FLEET_SWAP_DRAIN_TIMEOUT", "0")
    with pytest.raises(MXNetError,
                       match="MXNET_FLEET_SWAP_DRAIN_TIMEOUT"):
        Router([rep])


# ---------------------------------------------------------------------------
# rolling weight swap
# ---------------------------------------------------------------------------


def test_swap_weights_drains_zero_requests(tmp_path):
    pub = ckpt_mod.publish_params(
        str(tmp_path / "pub"), {"w": np.arange(4.0)}, step=7)
    reps = [FakeReplica(0, service_ms=3.0), FakeReplica(1, service_ms=3.0)]
    with _router(reps, replica_depth=4) as r:
        stop = threading.Event()
        errors, answered = [], []

        def client():
            i = 0
            while not stop.is_set():
                try:
                    out = r.submit(
                        {"x": np.full((1, 2), i, np.float64)}).result(30)
                    assert np.array_equal(out[0], np.full((1, 2), i))
                    answered.append(i)
                except BaseException as exc:  # noqa: BLE001
                    errors.append(exc)
                i += 1

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        report = r.swap_weights(str(tmp_path / "pub"))
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, f"swap dropped/shed requests: {errors[:3]}"
        assert len(answered) > 20
        assert report["step"] == 7
        assert sorted(report["replicas"]) == [0, 1]
        for rep in reps:
            # each replica swapped exactly once, with ZERO in flight
            assert [s for s, _ in rep.swapped] == [7]
            assert [n for _, n in rep.swapped] == [0]
            assert rep.weights_step == 7
        s = r.stats()
        assert s["swaps"] == 1 and s["shed"] == 0 \
            and s["failures"] == 0
        assert s["weights_step"] == 7
        assert pub == report["path"]


def test_swap_weights_refuses_bad_checkpoint(tmp_path):
    reps = [FakeReplica(0)]
    with _router(reps) as r:
        with pytest.raises(MXNetError, match="committed"):
            r.swap_weights(str(tmp_path / "nope"))
        assert reps[0].swapped == []  # nothing was taken out of rotation


# ---------------------------------------------------------------------------
# the wire: router server + client, replica server + handle
# ---------------------------------------------------------------------------


def test_router_wire_roundtrip_and_hmac(tmp_path):
    secret = b"wire-secret"
    reps = [FakeReplica(0), FakeReplica(1)]
    with _router(reps, secret=secret) as r:
        port = r.serve()
        with FleetClient("127.0.0.1", port, secret=secret) as cl:
            # infer
            out = cl.submit({"x": np.full((2, 3), 4.5)}).result(30)
            assert np.array_equal(out[0], np.full((2, 3), 4.5))
            # decode (tokens unwrapped client-side)
            toks = cl.generate(np.asarray([1, 2, 3], np.int32),
                               max_new_tokens=4).result(30)
            assert toks.dtype == np.int32 and toks.shape == (4,)
            # stats over the signed control channel
            s = cl.stats()
            assert s["responses"] >= 2
            # swap over the wire
            ckpt_mod.publish_params(str(tmp_path / "pub"),
                                    {"w": np.zeros(2)}, step=3)
            rep = cl.swap_weights(str(tmp_path / "pub"))
            assert rep["step"] == 3
        # a client with the wrong secret: tensor traffic still works
        # (never pickled), CONTROL is refused before parsing
        with FleetClient("127.0.0.1", port, secret=b"evil") as cl2:
            out = cl2.submit({"x": np.ones((1, 2))}).result(30)
            assert np.array_equal(out[0], np.ones((1, 2)))
            with pytest.raises(MXNetError, match="HMAC"):
                cl2.stats()


def test_wire_shed_travels_typed():
    rep = FakeReplica(0, service_ms=60.0)
    with _router([rep], replica_depth=1) as r:
        _prime_cost(r)
        port = r.serve()
        with FleetClient("127.0.0.1", port) as cl:
            bg = [cl.submit({"x": np.zeros((1, 2))}) for _ in range(3)]
            fut = cl.submit({"x": np.zeros((1, 2))}, deadline_ms=1.0)
            with pytest.raises(ShedError):
                fut.result(30)
            for f in bg:
                f.result(30)


def test_replica_server_real_engine_roundtrip(tmp_path):
    """ReplicaServer over a real InferenceEngine: submit, inflight,
    drain/resume, weight swap through a published checkpoint — the
    single-replica slice of the fleet, no subprocess."""
    from tests.test_serving import _mlp_predictor

    pred, net, (arg, aux) = _mlp_predictor()
    eng = mx.InferenceEngine(pred, buckets=(1, 4), batch_timeout_ms=1.0)
    secret = b"replica-secret"
    srv = ReplicaServer(ReplicaHarness(eng), rid=0,
                        fleet_dir=str(tmp_path / "fleet"), secret=secret)
    try:
        handle = ReplicaClient(0, "127.0.0.1", srv.port, secret=secret)
        x = np.random.RandomState(3).rand(1, 6).astype(np.float32)
        pred_ref = mx.Predictor(net, {**arg, **aux}, {"data": (1, 6)})
        pred_ref.forward(data=x)
        want = pred_ref.get_output(0)
        out = handle.submit({"kind": "infer",
                             "inputs": {"data": x}}).result(60)
        np.testing.assert_allclose(out[0], want, rtol=1e-6)
        assert handle.inflight() == 0
        # heartbeat file exists (the PR-8 liveness plane)
        assert os.path.exists(str(tmp_path / "fleet" / "hb_0"))

        # weight swap: publish scaled weights, swap, outputs change
        new_params = {k: np.asarray(v.asnumpy() if hasattr(v, "asnumpy")
                                    else v) * 2.0
                      for k, v in {**arg, **aux}.items()}
        ckpt_mod.publish_params(str(tmp_path / "pub"), new_params, step=11)
        rep = handle.swap(str(tmp_path / "pub"))
        assert rep["step"] == 11
        out2 = handle.submit({"kind": "infer",
                              "inputs": {"data": x}}).result(60)
        assert not np.allclose(out2[0], want), \
            "swap did not change served weights"
        pred_ref.set_params(new_params)
        pred_ref.forward(data=x)
        np.testing.assert_allclose(out2[0], pred_ref.get_output(0),
                                   rtol=1e-5)

        # bad HMAC on control
        evil = ReplicaClient(0, "127.0.0.1", srv.port, secret=b"evil")
        with pytest.raises(MXNetError, match="HMAC"):
            evil.inflight()
        evil.close()
        handle.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# multi-process: spawn real replicas, kill -9 one (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif((os.cpu_count() or 1) < 2, reason="needs >= 2 cores")
def test_fleet_kill9_drill_loses_nothing(tmp_path):
    """The acceptance drill, in-repo: 2 real replica processes under
    closed-loop load, kill -9 one mid-stream — zero lost requests,
    answers match, then a rolling swap with zero sheds."""
    drill = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "bench_fleet.py"),
         "--drill", "--replicas", "2", "--requests", "40",
         "--fleet-dir", str(tmp_path / "fleet")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "MXNET_DEAD_RANK_TIMEOUT": "3.0",
             "MXNET_HEARTBEAT_INTERVAL": "0.2"})
    assert drill.returncode == 0, drill.stderr[-4000:]
    verdict = json.loads(drill.stdout.strip().splitlines()[-1])
    assert verdict["lost"] == 0
    assert verdict["mismatched"] == 0
    assert verdict["replica_deaths"] == 1
    assert verdict["swap_ok"]
    assert verdict["swap_shed"] == 0
