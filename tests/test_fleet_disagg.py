"""Disaggregated prefill/decode serving: live KV page migration
(engine-level splice bit-identity across pool dtypes), the Router's
replica roles + phase machinery (in-process fake replicas), role
autoscaling, and the chaos/env surface.

The real multi-process per-role kill -9 drills live in
tools/bench_fleet.py (--disagg-drill prefill|decode) and run under the
``slow`` marker here.
"""

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

from mxnet_tpu import wire
from mxnet_tpu.base import MXNetError
from mxnet_tpu.fleet import REPLICA_ROLES, Router, roles_env
from mxnet_tpu.kv_cache import BlockAllocator
from mxnet_tpu.serving import ReplicaHarness

from _engines import KVB, DM, H, L, dense_engine as _engine, tiny_lm_params


@pytest.fixture(scope="module")
def lm_params():
    return tiny_lm_params()


def _fp8_available():
    try:
        import ml_dtypes  # noqa: F401

        np.dtype(ml_dtypes.float8_e4m3fn)
        return True
    except Exception:  # noqa: BLE001
        return False


# ---------------------------------------------------------------------------
# engine-level migration: export → import splice is bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", [
    "fp32", "int8",
    pytest.param("fp8", marks=pytest.mark.skipif(
        not _fp8_available(), reason="ml_dtypes float8 unavailable")),
])
def test_migration_splice_bit_identity(lm_params, kv_dtype):
    """A stream prefilled on one engine, exported, and spliced into a
    second engine's pool decodes BIT-IDENTICALLY to the same seeds on
    a single never-migrated engine — quantized pools ship their value
    slabs at wire dtype plus their scale slabs, so the splice is exact
    regardless of pool storage."""
    prompt = np.asarray([7, 3, 11, 2, 5], np.int32)
    ref = _engine(lm_params, kv_dtype=kv_dtype)
    try:
        want = np.asarray(
            ref.submit(prompt, 10, temperature=0.9, seed=5).result(120))
    finally:
        ref.close()
    pre = _engine(lm_params, kv_dtype=kv_dtype)
    dec = _engine(lm_params, kv_dtype=kv_dtype)
    try:
        pay = pre.submit(prompt, 10, temperature=0.9, seed=5,
                         prefill_only=True).result(120)
        meta, arrays = pay["meta"], pay["kv_arrays"]
        assert meta["n_pages"] > 0 and meta["kv_dtype"] == kv_dtype
        # pages left the exporter's pool (not leaked, not still live)
        assert pre.stats()["migrations_out"] == 1
        got = np.asarray(dec.import_stream(meta, arrays).result(120))
        assert np.array_equal(got, want), (got, want)
        # the exporter produced the first token; the importer decoded
        # the rest from the spliced pages — bit-identity proves the
        # (engine seed, stream seed, position) sampling contract held
        assert dec.stats()["migrations_in"] == 1
    finally:
        pre.close()
        dec.close()


def test_golden_page_frame_imports_bit_identically(lm_params):
    """The pools are (P, KVB, H·D) but a migration frame declares its
    value pages (n, KVB, H, D), as every frame exported before the
    pools went lane-dense did: the same bytes.  A golden frame built
    from row-major (n, KVB, H, D) arrays must pack to the bytes the
    exporter's own frame packs to, and its pages must land in the
    importer's pools bit for bit."""
    D = DM // H
    prompt = np.arange(1, 2 * KVB + 1, dtype=np.int32)  # 2 full pages
    pre = _engine(lm_params)
    dec = _engine(lm_params)
    try:
        pay = pre.submit(prompt, 6, temperature=0.0, seed=1,
                         prefill_only=True).result(120)
        meta, arrays = pay["meta"], pay["kv_arrays"]
        n = meta["n_pages"]
        assert n == 2 and len(arrays) == 2 + 2 * L
        assert all(a.shape == (n, KVB, H, D) for a in arrays[2:])
        # a frame from before: the same meta, slabs made as (KVB, H, D)
        # pages — here holding the exporter's own values ...
        old = [np.ascontiguousarray(
            np.asarray(a).reshape(n * KVB, H, D).reshape(n, KVB, H, D))
            for a in arrays[2:]]
        secret = b"k" * 32
        assert wire.pack_page_frame(secret, meta, arrays[:2] + old) \
            == wire.pack_page_frame(secret, meta, arrays)
        # ... and here values no pool has ever held
        rng = np.random.RandomState(7)
        golden = [rng.randn(n, KVB, H, D).astype(np.float32)
                  for _ in range(2 * L)]
        m2, a2 = wire.unpack_page_frame(
            secret, memoryview(wire.pack_page_frame(
                secret, meta, arrays[:2] + golden)))
        assert np.asarray(dec.import_stream(m2, a2).result(120)).size
        for pool, want in zip(dec._pools, golden):
            pool = np.asarray(pool)
            assert pool.shape[1:] == (KVB, H * D)
            for page in want.reshape(n, KVB, H * D):
                # the stream decoded on from position 2*KVB: it wrote
                # a THIRD page and left these two as they came
                assert (pool == page).all(axis=(1, 2)).sum() == 1
    finally:
        pre.close()
        dec.close()


def test_migration_cost_conservation(lm_params):
    """sum(per-stream CostRecords) == stats() for the new
    migration_bytes/migration_ms fields — the PR-13 conservation
    contract extends to the migration counters (same increment site)."""
    prompt = np.asarray([9, 4, 1, 8], np.int32)
    pre = _engine(lm_params)
    try:
        pay = pre.submit(prompt, 6, temperature=0.8, seed=3,
                         prefill_only=True).result(120)
        assert pay["meta"]["migration_bytes"] > 0
        s = pre.stats()
        recs = pre.cost_records()
        assert sum(r["migration_bytes"] for r in recs) \
            == s["migration_bytes"] > 0
        assert abs(sum(r["migration_ms"] for r in recs)
                   - s["migration_ms"]) < 1e-3
        assert s["migrations_out"] == 1
        # export_ms rides the meta so the router can fold the engine-
        # side export cost into its end-to-end migration histogram
        assert pay["meta"]["export_ms"] > 0
    finally:
        pre.close()


def test_import_stream_validation_refuses_mismatches(lm_params):
    eng = _engine(lm_params)
    imp = _engine(lm_params, kv_dtype="int8")
    try:
        pay = eng.submit(np.asarray([5, 2, 7], np.int32), 6,
                         temperature=0.8, seed=2,
                         prefill_only=True).result(120)
        meta, arrays = pay["meta"], pay["kv_arrays"]
        with pytest.raises(MXNetError, match="kv_dtype"):
            imp.import_stream(meta, arrays)
        bad = dict(meta, kv_block=KVB * 2, kv_dtype="fp32")
        eng2 = _engine(lm_params)
        try:
            with pytest.raises(MXNetError, match="kv_block"):
                eng2.import_stream(bad, arrays)
            with pytest.raises(MXNetError, match="fmt"):
                eng2.import_stream(dict(meta, fmt=99), arrays)
            with pytest.raises(MXNetError):
                eng2.import_stream(meta, arrays[:-1])  # slab missing
        finally:
            eng2.close()
    finally:
        eng.close()
        imp.close()


def test_prefill_only_refused_on_meshed_engine(lm_params):
    eng = _engine(lm_params)
    try:
        eng._mesh = object()  # pretend tp/pp mesh
        with pytest.raises(MXNetError, match="mesh"):
            eng.submit(np.asarray([1, 2], np.int32), 4,
                       prefill_only=True)
    finally:
        eng._mesh = None
        eng.close()


# ---------------------------------------------------------------------------
# allocator: export/import page accounting
# ---------------------------------------------------------------------------


def test_block_allocator_export_import_pages():
    a = BlockAllocator(8, 4)  # capacity 7 (1 scratch)
    pages = a.alloc(3, owner=1)
    a.export_pages(pages)  # pages leave: slots return to the free list
    assert a.free_blocks == 7
    back = a.import_pages(3, owner=2)
    assert len(back) == 3 and a.free_blocks == 4
    with pytest.raises(MXNetError):
        a.export_pages([99])  # never allocated
    shared = a.alloc(1, owner=3)
    a.share(shared[0])  # refcount 2: a shared page must NOT export
    with pytest.raises(MXNetError, match="live references"):
        a.export_pages(shared)


# ---------------------------------------------------------------------------
# wire: signed page frames
# ---------------------------------------------------------------------------


def test_page_frame_roundtrip_and_mac():
    secret = b"s3cret"
    meta = {"fmt": 1, "sid": 4, "n_pages": 2, "kv_dtype": "int8"}
    arrays = [np.arange(6, dtype=np.int32),
              np.ones((2, 3), np.int8),
              np.full((2, 1), 0.5, np.float32)]  # scale slab
    frame = wire.pack_page_frame(secret, meta, arrays)
    m2, a2 = wire.unpack_page_frame(secret, memoryview(frame))
    assert m2 == meta and len(a2) == 3
    for x, y in zip(arrays, a2):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    # the MAC covers the SLABS, not just the meta: flip one payload
    # byte and the whole frame must be refused
    tampered = bytearray(frame)
    tampered[len(frame) // 2] ^= 0xFF
    with pytest.raises(MXNetError, match="HMAC"):
        wire.unpack_page_frame(secret, memoryview(bytes(tampered)))
    with pytest.raises(MXNetError):
        wire.unpack_page_frame(b"", memoryview(frame))  # no secret


# ---------------------------------------------------------------------------
# Router roles + phase machinery (in-process fakes)
# ---------------------------------------------------------------------------


class RoleFake:
    """Role-aware in-process replica handle: phase-1 decode submits
    answer with a {"meta", "arrays"} payload, "migrate" specs continue
    deterministically from the meta — so router-level bit-identity is
    checkable without processes."""

    def __init__(self, rid, service_ms=2.0, blocks=64):
        self.rid = rid
        self.role = "mixed"
        self.service_s = service_ms / 1e3
        self.blocks = blocks
        self.served = []
        self.role_sets = []
        self._q = queue.Queue()
        self._lock = threading.Lock()
        self._inflight = set()
        self._accepting = True
        threading.Thread(target=self._run, daemon=True).start()

    def set_role(self, role):
        self.role = role
        self.role_sets.append(role)

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

    def stats(self):
        return {"rid": self.rid, "role": self.role,
                "cache_blocks_free": self.blocks, "kv_block": KVB,
                "cache_util": 0.1}

    def close(self):
        pass

    def kill(self):
        with self._lock:
            self._accepting = False

    def _run(self):
        while True:
            spec, fut = self._q.get()
            time.sleep(self.service_s)
            try:
                res = self._answer(spec)
            except BaseException as exc:  # noqa: BLE001
                if fut.set_running_or_notify_cancel():
                    fut.set_exception(exc)
                continue
            with self._lock:
                self._inflight.discard(fut)
            self.served.append(spec)
            if fut.set_running_or_notify_cancel():
                fut.set_result(res)

    @staticmethod
    def _tokens(prompt_sum, seed, max_new):
        return [(prompt_sum * 7 + seed * 31 + i) % 997
                for i in range(max_new)]

    def _answer(self, spec):
        if spec["kind"] == "decode" and spec.get("phase"):
            p = np.asarray(spec["prompt"])
            toks = self._tokens(int(p.sum()), int(spec["seed"]),
                                int(spec["max_new"]))
            done = int(spec["max_new"]) <= 1
            n_pages = 0 if done else -(-(p.size + len(toks)) // KVB)
            meta = {"fmt": 1, "done": done, "n_pages": n_pages,
                    "migration_bytes": n_pages * 512, "export_ms": 0.05,
                    "seed": int(spec["seed"]),
                    "max_new": int(spec["max_new"]),
                    "prompt_sum": int(p.sum())}
            return {"meta": meta,
                    "arrays": [p.astype(np.int64),
                               np.asarray(toks[:1], np.int32)]}
        if spec["kind"] == "migrate":
            m = spec["meta"]
            return [np.asarray(self._tokens(m["prompt_sum"], m["seed"],
                                            m["max_new"]), np.int32)]
        if spec["kind"] == "decode":
            p = np.asarray(spec["prompt"])
            return [np.asarray(self._tokens(int(p.sum()),
                                            int(spec["seed"]),
                                            int(spec["max_new"])),
                               np.int32)]
        x = next(iter(spec["inputs"].values()))
        return [np.asarray(x, np.float64)]


def _expect(got, prompt, seed, max_new):
    s = int(np.asarray(prompt).sum())
    want = [(s * 7 + seed * 31 + i) % 997 for i in range(max_new)]
    assert np.array_equal(np.asarray(got), np.asarray(want, np.int32)), \
        (got, want)


def _router(reps, roles, **kw):
    kw.setdefault("retry_budget", 2)
    kw.setdefault("default_deadline_ms", 0)
    return Router(reps, roles=roles, **kw)


def test_router_disagg_routes_by_role_and_stays_bit_identical():
    reps = [RoleFake(0), RoleFake(1), RoleFake(2)]
    with _router(reps, ["prefill", "decode", "decode"]) as r:
        futs = [(i, r.generate(np.asarray([3, 5 + i], np.int32),
                               max_new_tokens=6, seed=11 + i))
                for i in range(8)]
        for i, f in futs:
            _expect(f.result(20), [3, 5 + i], 11 + i, 6)
        s = r.stats()
        assert s["migrations"] == 8 and s["migration_bytes"] > 0
        assert s["disagg"] is True and s["re_prefills"] == 0
        assert s["replicas"][0]["role"] == "prefill"
        assert s["migration_p50_ms"] is not None
        assert s["ttft_p99_ms"] is not None
        assert s["decode_per_token_p50_ms"] is not None
        # hard split: the prefill replica saw ONLY phase-1 work, the
        # decode replicas ONLY migrations
        assert all(sp.get("phase") for sp in reps[0].served)
        assert all(sp["kind"] == "migrate"
                   for sp in reps[1].served + reps[2].served)


def test_router_disagg_done_at_prefill_short_circuits():
    reps = [RoleFake(0), RoleFake(1)]
    with _router(reps, ["prefill", "decode"]) as r:
        out = r.generate(np.asarray([9], np.int32), max_new_tokens=1,
                         seed=3).result(20)
        _expect(out, [9], 3, 1)
        assert r.stats()["migrations"] == 0  # nothing shipped


def test_router_disagg_decode_death_re_prefills_exactly_once():
    reps = [RoleFake(0, service_ms=1.0), RoleFake(1, service_ms=60.0),
            RoleFake(2, service_ms=1.0)]
    with _router(reps, ["prefill", "decode", "decode"],
                 replica_depth=2) as r:
        reps[2].kill()  # all migrations pile onto slow decoder 1
        futs = [(i, r.generate(np.asarray([2, i], np.int32),
                               max_new_tokens=4, seed=7 + i))
                for i in range(6)]
        time.sleep(0.08)  # first migrations in service on replica 1,
        reps[1].kill()    # the rest queued behind its depth
        reps[2]._accepting = True  # re-prefill target lives again
        for i, f in futs:
            _expect(f.result(30), [2, i], 7 + i, 4)
        s = r.stats()
        # a dead decode replica's spliced pages are gone: delivery ran
        # through the re-prefill retry path, and still exactly once
        assert s["responses"] == 6 and s["re_prefills"] >= 1


def test_router_disagg_prefill_death_degrades_to_classic():
    reps = [RoleFake(0), RoleFake(1)]
    with _router(reps, ["prefill", "decode"]) as r:
        reps[0].kill()
        out = r.generate(np.asarray([4, 4], np.int32), max_new_tokens=3,
                         seed=5).result(30)
        # the lone decode-role survivor serves the stream end-to-end
        _expect(out, [4, 4], 5, 3)
        assert any(sp["kind"] == "decode" and not sp.get("phase")
                   for sp in reps[1].served)


def test_router_set_role_flips_and_guards():
    reps = [RoleFake(0), RoleFake(1), RoleFake(2)]
    with _router(reps, ["prefill", "decode", "decode"]) as r:
        rep = r.set_role(2, "prefill")
        assert rep["flipped"] and reps[2].role == "prefill"
        assert r.stats()["role_flips"] == 1
        assert r.stats()["replicas"][2]["role"] == "prefill"
        with pytest.raises(MXNetError, match="last"):
            r.set_role(1, "prefill")  # would strip the decode side
        with pytest.raises(MXNetError, match="must be one of"):
            r.set_role(0, "turbo")
        assert r.set_role(2, "prefill")["flipped"] is False  # no-op


def test_router_autoscale_flips_under_decode_pressure():
    """Shifting workload drill: long-prompt streams pile migrations
    onto the single slow decode replica; one autoscale evaluation must
    flip a prefill replica to decode (and shed nothing)."""
    reps = [RoleFake(0, service_ms=1.0), RoleFake(1, service_ms=1.0),
            RoleFake(2, service_ms=80.0)]
    with _router(reps, ["prefill", "prefill", "decode"],
                 replica_depth=2) as r:
        r._cost[("decode", 4)] = 2.0
        r._cost[("migrate", 4)] = 80.0
        futs = [(i, r.generate(np.asarray([6, i], np.int32),
                               max_new_tokens=4, seed=3 + i))
                for i in range(8)]
        # wait until migrations queue behind the lone decoder's depth
        deadline = time.monotonic() + 10.0
        flip = None
        while time.monotonic() < deadline:
            flip = r.autoscale_once()
            if flip is not None:
                break
            time.sleep(0.02)
        assert flip is not None and flip["role"] == "decode"
        assert flip["pressure"]["decode"] > flip["pressure"]["prefill"]
        for i, f in futs:
            _expect(f.result(60), [6, i], 3 + i, 4)
        s = r.stats()
        assert s["role_flips"] >= 1 and s["shed"] == 0


def test_roles_env_parses_and_refuses_garbage(monkeypatch):
    monkeypatch.delenv("MXNET_FLEET_ROLES", raising=False)
    assert roles_env() is None
    monkeypatch.setenv("MXNET_FLEET_ROLES", "prefill,decode,mixed")
    assert roles_env() == ["prefill", "decode", "mixed"]
    monkeypatch.setenv("MXNET_FLEET_ROLES", "prefill,turbo")
    with pytest.raises(MXNetError, match="turbo"):
        roles_env()
    monkeypatch.setenv("MXNET_FLEET_ROLES", "prefill,prefill")
    with pytest.raises(MXNetError, match="one-sided|BOTH"):
        roles_env()
    for role in REPLICA_ROLES:
        monkeypatch.setenv("MXNET_FLEET_ROLES", f"{role}" if role ==
                           "mixed" else "prefill,decode")
        assert roles_env() is not None


def test_router_roles_kwarg_validation():
    reps = [RoleFake(0), RoleFake(1)]
    with pytest.raises(MXNetError, match="every replica"):
        Router(reps, roles=["prefill"])
    for rep in reps:
        rep.close()
    reps = [RoleFake(0), RoleFake(1)]
    with pytest.raises(MXNetError, match="BOTH"):
        Router(reps, roles=["prefill", "prefill"])


def test_harness_role_surface(lm_params):
    eng = _engine(lm_params)
    h = ReplicaHarness(eng)
    try:
        assert "role" not in h.stats()  # roles never enabled
        h.set_role("prefill")
        assert h.stats()["role"] == "prefill"
        with pytest.raises(MXNetError, match="must be one of"):
            h.set_role("turbo")
        h.set_role("decode")
        with pytest.raises(MXNetError, match="prefill-role"):
            h.submit_prefill_export(np.asarray([1, 2], np.int32))
        h.set_role("prefill")
        with pytest.raises(MXNetError, match="prefill"):
            h.submit_import({"fmt": 1}, [])
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# chaos: the migration-tear fault point
# ---------------------------------------------------------------------------


def test_chaos_migration_tear_validated_and_armed(monkeypatch):
    from mxnet_tpu import chaos

    monkeypatch.setenv("MXNET_CHAOS_MIGRATION_TEAR", "garbage")
    chaos.reset_chaos()
    with pytest.raises(MXNetError, match="MXNET_CHAOS_MIGRATION_TEAR"):
        chaos.get_chaos()
    monkeypatch.setenv("MXNET_CHAOS_MIGRATION_TEAR", "0")
    chaos.reset_chaos()
    with pytest.raises(MXNetError):
        chaos.get_chaos()  # minimum is 1: the 0th frame cannot exist
    monkeypatch.setenv("MXNET_CHAOS_MIGRATION_TEAR", "2")
    chaos.reset_chaos()
    ch = chaos.get_chaos()
    assert ch.armed and ch.migration_tear == 2

    class Sock:
        def __init__(self):
            self.sent = b""
            self.dead = False

        def sendall(self, b):
            self.sent += b

        def shutdown(self, how):
            self.dead = True

        def close(self):
            pass

    frame = b"x" * 100
    s1, s2 = Sock(), Sock()
    assert ch.torn_migration_send(s1, frame) is False  # frame 1 passes
    assert ch.torn_migration_send(s2, frame) is True   # frame 2 torn
    assert s1.sent == b"" and s2.dead
    # torn = length header promising 100 bytes, only half delivered
    assert s2.sent == wire.U32.pack(100) + frame[:50]
    monkeypatch.delenv("MXNET_CHAOS_MIGRATION_TEAR")
    chaos.reset_chaos()


# ---------------------------------------------------------------------------
# multi-process per-role kill -9 drills (slow)
# ---------------------------------------------------------------------------


def _run_disagg_drill(role, tmp_path):
    drill = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "bench_fleet.py"),
         "--disagg-drill", role, "--replicas", "3", "--requests", "12",
         "--fleet-dir", str(tmp_path / "fleet")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "MXNET_DEAD_RANK_TIMEOUT": "3.0",
             "MXNET_HEARTBEAT_INTERVAL": "0.2"})
    assert drill.returncode == 0, drill.stderr[-4000:]
    verdict = json.loads(drill.stdout.strip().splitlines()[-1])
    assert verdict["lost"] == 0
    assert verdict["mismatched"] == 0
    assert verdict["replica_deaths"] >= 1
    assert verdict["migrations"] > 0
    return verdict


@pytest.mark.slow
@pytest.mark.skipif((os.cpu_count() or 1) < 3, reason="needs >= 3 cores")
def test_disagg_kill9_decode_role_loses_nothing(tmp_path):
    """kill -9 a decode-role replica mid-stream: spliced pages die
    with it; every stream re-prefills and delivers bit-identically."""
    verdict = _run_disagg_drill("decode", tmp_path)
    assert verdict["re_prefills"] >= 0  # may be 0 if kill landed between migrations
    assert verdict["migration_edge_in_trace"]


@pytest.mark.slow
@pytest.mark.skipif((os.cpu_count() or 1) < 3, reason="needs >= 3 cores")
def test_disagg_kill9_prefill_role_loses_nothing(tmp_path):
    """kill -9 THE prefill-role replica mid-stream: in-flight prefills
    retry on the survivors (the fleet degrades to classic routing)."""
    _run_disagg_drill("prefill", tmp_path)
