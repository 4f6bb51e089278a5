"""Gradient-comm scheduler tests: deterministic bucketing (pack → sum
→ unpack bitwise-identical to per-key sums), priority ordering,
failure propagation, the windowed PS pipeline + multi-key wire frames,
bf16 wire compression with fp32 accumulation (convergence-tolerance
"small fit"), the kvstore rescale hook, and the overlap's structure (a
bucket on the wire while the caller packs the next)."""

import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import comm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ps import ParameterServer, ShardedPSClient


def _entries(arrays, priority=0):
    out, off = [], 0
    for i, a in enumerate(arrays):
        out.append(comm.BucketEntry(i, a.shape, a.dtype, a.size, off,
                                    priority))
        off += a.size
    return out


# -- deterministic bucketing --------------------------------------------
def test_pack_unpack_roundtrip_bitwise():
    rng = np.random.RandomState(0)
    arrays = [rng.randn(*s).astype(np.float32)
              for s in [(3, 4), (7,), (2, 2, 2), (1,)]]
    flat = np.asarray(comm.pack_bucket(arrays))
    assert flat.shape == (sum(a.size for a in arrays),)
    out = [np.asarray(x) for x in comm.unpack_bucket(flat, _entries(arrays))]
    for a, b in zip(arrays, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()  # bitwise


def test_bucketed_sum_bitwise_equals_per_key_sum():
    """The sync-semantics invariant: pack → elementwise sum over
    workers → unpack must be BITWISE identical to the per-key sums the
    blocking path computed, and stable across repeated runs."""
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    w0 = [rng.randn(64, 3).astype(np.float32) * 10,
          rng.randn(17).astype(np.float32) * 1e-3]
    w1 = [rng.randn(64, 3).astype(np.float32),
          rng.randn(17).astype(np.float32)]
    entries = _entries(w0)
    # per-key reference: exactly the old blocking path's reduction
    ref = [np.asarray(jnp.sum(jnp.stack([jnp.asarray(a), jnp.asarray(b)]),
                              axis=0)) for a, b in zip(w0, w1)]
    runs = []
    for _ in range(2):
        summed = jnp.sum(jnp.stack([comm.pack_bucket(w0),
                                    comm.pack_bucket(w1)]), axis=0)
        out = [np.asarray(x) for x in comm.unpack_bucket(summed, entries)]
        runs.append(out)
        for r, o in zip(ref, out):
            assert r.tobytes() == o.tobytes()
    for a, b in zip(*runs):  # run-to-run bitwise stability
        assert a.tobytes() == b.tobytes()


# -- scheduler behavior --------------------------------------------------
def _wait_depth_zero(s, timeout=5.0):
    t0 = time.time()
    while s.depth > 0 and time.time() - t0 < timeout:
        time.sleep(0.01)


def test_scheduler_seals_by_bucket_bytes():
    buckets = []

    def launch(b):
        buckets.append([e.key for e in b.entries])

    s = comm.CommScheduler(launch, strict_order=True, max_bucket_bytes=40)
    try:
        for i in range(5):
            s.submit(i, np.ones(4, np.float32))  # 16 B each
        s.flush()
        s.drain()
    finally:
        s.close()
    assert buckets == [[0, 1], [2, 3], [4]]


def test_scheduler_priority_heap_order():
    order = []
    gate = threading.Event()

    def launch(b):
        gate.wait(10)
        order.append(b.entries[0].key)

    s = comm.CommScheduler(launch, strict_order=False, max_bucket_bytes=1)
    try:
        s.submit("first", np.ones(4, np.float32), priority=0)
        _wait_depth_zero(s)  # comm thread holds 'first' at the gate
        s.submit("a", np.ones(4, np.float32), priority=-3)
        s.submit("b", np.ones(4, np.float32), priority=5)
        s.submit("c", np.ones(4, np.float32), priority=1)
        gate.set()
        s.drain()
    finally:
        s.close()
    assert order[0] == "first"
    assert order[1:] == ["b", "c", "a"]  # higher priority first


def test_scheduler_strict_order_is_submission_order():
    order = []
    gate = threading.Event()

    def launch(b):
        gate.wait(10)
        order.append(b.entries[0].key)

    s = comm.CommScheduler(launch, strict_order=True, max_bucket_bytes=1)
    try:
        s.submit("first", np.ones(4, np.float32), priority=0)
        _wait_depth_zero(s)
        s.submit("a", np.ones(4, np.float32), priority=-3)
        s.submit("b", np.ones(4, np.float32), priority=5)
        s.submit("c", np.ones(4, np.float32), priority=1)
        gate.set()
        s.drain()
    finally:
        s.close()
    # collective transports must launch in submission order on every
    # rank regardless of priority
    assert order == ["first", "a", "b", "c"]


def test_scheduler_dtype_groups_split_buckets():
    buckets = []

    def launch(b):
        buckets.append({e.key: e.dtype for e in b.entries})

    s = comm.CommScheduler(launch, strict_order=True,
                           max_bucket_bytes=1 << 20)
    try:
        s.submit("f32", np.ones(4, np.float32))
        s.submit("f64", np.ones(4, np.float64))
        s.submit("i32", np.ones(4, np.int32))
        s.flush()
        s.drain()
    finally:
        s.close()
    assert len(buckets) == 3  # one bucket per dtype group
    for b in buckets:
        assert len(set(b.values())) == 1


def test_scheduler_failure_surfaces_at_wait_and_poisons_submit():
    def launch(b):
        raise RuntimeError("transport down")

    s = comm.CommScheduler(launch, strict_order=True, max_bucket_bytes=1)
    s.submit("k", np.ones(2, np.float32))
    with pytest.raises(RuntimeError, match="transport down"):
        s.wait("k")
    with pytest.raises(MXNetError, match="comm thread failed"):
        s.submit("k2", np.ones(2, np.float32))


def test_scheduler_wait_unknown_key_is_noop():
    s = comm.CommScheduler(lambda b: None, strict_order=True)
    try:
        s.wait("never-pushed")
        s.drain()
    finally:
        s.close()


def test_scheduler_bucket_on_the_wire_while_caller_packs_the_next():
    """The overlap's STRUCTURE, with no clock compared: bucket k is
    launched and not complete — the comm thread holds it in its
    finisher, the ``kvstore.inflight`` gauge counts the work behind it
    — all the while the caller submits the keys of bucket k+1, and what
    comes back is bit for bit the per-key sums."""
    rng = np.random.RandomState(3)
    mine = [rng.randn(4).astype(np.float32) for _ in range(6)]
    peer = [rng.randn(4).astype(np.float32) for _ in range(6)]
    launched = [threading.Event() for _ in range(3)]
    release = [threading.Event() for _ in range(3)]
    summed = {}

    def launch(b):  # an all-reduce with one peer, collected later
        flat = comm.pack_bucket(b.arrays) + comm.pack_bucket(
            [peer[e.key] for e in b.entries])
        launched[b.seq].set()

        def finish():
            assert release[b.seq].wait(10)
            for e, a in zip(b.entries, comm.unpack_bucket(flat, b.entries)):
                summed[e.key] = np.asarray(a)
        return finish

    s = comm.CommScheduler(launch, strict_order=True,
                           max_bucket_bytes=32, window=2)
    handles = []
    try:
        for k in range(3):
            # two 16-byte keys fill a bucket: the second submit seals it
            pair = [s.submit(i, mine[i]) for i in (2 * k, 2 * k + 1)]
            assert pair[0] is pair[1] and pair[0] not in handles
            handles.append(pair[0])
            if k:
                # bucket k-1 stayed on the wire while bucket k was packed
                assert launched[k - 1].is_set() and not handles[k - 1].done
                assert mx.profiler.metrics_summary()["gauges"][
                    "kvstore.inflight"] >= 1
                release[k - 1].set()
                handles[k - 1].wait(10)
            assert launched[k].wait(10)
        release[2].set()
        s.drain()
    finally:
        for r in release:
            r.set()
        s.close()
    assert all(h.done for h in handles)
    for i in range(6):
        assert summed[i].tobytes() == (mine[i] + peer[i]).tobytes()


# -- windowed PS pipeline + multi-key frames ----------------------------
def _cluster(n=2, secret=b"s3cret", big_bound=100, **kw):
    servers = [ParameterServer(secret=secret, **kw) for _ in range(n)]
    client = ShardedPSClient([("127.0.0.1", s.port) for s in servers],
                             secret=secret, big_bound=big_bound, worker=0)
    return servers, client


def test_psclient_windowed_inflight_pipeline():
    from mxnet_tpu.ps import _body_pull, _unpack_tensor

    servers, cl = _cluster(n=1)
    try:
        c = cl.clients[0]
        for i in range(4):
            cl.init(f"k{i}", np.full(3, float(i), np.float32))
        # 4 requests on the wire before the first response is collected
        fins = [c._begin(_body_pull(f"k{i}", 0)) for i in range(4)]
        assert c._sent - c._recvd == 4
        for i, fin in enumerate(fins):
            arr, _ = _unpack_tensor(fin(), 1 + 8)
            np.testing.assert_array_equal(arr, np.full(3, float(i)))
        assert c._sent == c._recvd
    finally:
        cl.close()
        [s.close() for s in servers]


def test_psclient_out_of_order_finish_waits_for_turn():
    from mxnet_tpu.ps import _body_pull, _unpack_tensor

    servers, cl = _cluster(n=1)
    try:
        c = cl.clients[0]
        cl.init("a", np.ones(2, np.float32))
        cl.init("b", 2 * np.ones(2, np.float32))
        fin_a = c._begin(_body_pull("a", 0))
        fin_b = c._begin(_body_pull("b", 0))
        got_b = {}

        def later():
            arr, _ = _unpack_tensor(fin_b(), 1 + 8)
            got_b["v"] = np.array(arr)

        t = threading.Thread(target=later, daemon=True)
        t.start()
        time.sleep(0.2)
        assert "v" not in got_b  # ticket b must wait for ticket a
        arr, _ = _unpack_tensor(fin_a(), 1 + 8)
        np.testing.assert_array_equal(arr, np.ones(2))
        t.join(10)
        np.testing.assert_array_equal(got_b["v"], 2 * np.ones(2))
    finally:
        cl.close()
        [s.close() for s in servers]


def test_push_pull_multi_roundtrip_with_split_key():
    servers, cl = _cluster(n=2, big_bound=100)
    try:
        rng = np.random.RandomState(3)
        smalls = {f"s{i}": rng.randn(5).astype(np.float32)
                  for i in range(6)}
        big = rng.randn(30, 10).astype(np.float32)  # 300 > big_bound
        for k in smalls:
            cl.init(k, np.zeros(5, np.float32))
        cl.init("big", np.zeros_like(big))
        entries = list(smalls.items()) + [("big", big)]
        cl.push_multi(entries)  # no updater: servers assign the values
        specs = [(k, v.shape, v.dtype, 0) for k, v in smalls.items()]
        specs.append(("big", big.shape, big.dtype, 0))
        outs = cl.pull_multi(specs)
        for (k, v), got in zip(entries, outs):
            np.testing.assert_array_equal(got, v, err_msg=k)
        # the split key really landed on both shards
        assert sum("part" in str(kk) for s in servers
                   for kk in s._store) == 2
    finally:
        cl.close()
        [s.close() for s in servers]


def test_bf16_tensor_wire_roundtrip():
    import ml_dtypes

    servers, cl = _cluster(n=1)
    try:
        v32 = np.linspace(-3, 3, 16, dtype=np.float32)
        v = v32.astype(ml_dtypes.bfloat16)
        cl.init("b", np.zeros(16, np.float32))
        cl.push("b", v)  # bf16 payload on the wire; server stores fp32
        out = cl.pull("b")
        np.testing.assert_array_equal(out, v.astype(np.float32))
    finally:
        cl.close()
        [s.close() for s in servers]


# -- wire compression: bf16 "small fit" ---------------------------------
def _fit_quadratic(wire, steps=60, lr=0.1):
    """Server-side SGD descends 0.5*||w - target||^2; gradients travel
    through the bucketed scheduler with the given wire dtype."""
    rng = np.random.RandomState(13)
    targets = {"w0": rng.uniform(-1, 1, 48).astype(np.float32),
               "w1": rng.uniform(-1, 1, 9).astype(np.float32)}
    old = os.environ.get("MXNET_KVSTORE_GRAD_DTYPE")
    os.environ["MXNET_KVSTORE_GRAD_DTYPE"] = wire
    servers, cl = _cluster(n=2, big_bound=10**6)
    sched = comm.CommScheduler(comm.make_ps_launch(cl), strict_order=False,
                               max_bucket_bytes=1 << 20)
    try:
        ws = {k: np.zeros_like(t) for k, t in targets.items()}
        for k in targets:
            cl.init(k, ws[k])
        cl.set_optimizer(mx.optimizer.SGD(learning_rate=lr,
                                          rescale_grad=1.0, wd=0.0))
        for _ in range(steps):
            for k, t in targets.items():
                sched.submit(k, ws[k] - t)  # dL/dw
            sched.flush()
            sched.drain()
            for k in targets:
                ws[k] = cl.pull(k)
        return ws, targets
    finally:
        sched.close()
        cl.close()
        [s.close() for s in servers]
        if old is None:
            os.environ.pop("MXNET_KVSTORE_GRAD_DTYPE", None)
        else:
            os.environ["MXNET_KVSTORE_GRAD_DTYPE"] = old


def test_bf16_wire_converges_within_tolerance():
    w32, targets = _fit_quadratic("fp32")
    wbf, _ = _fit_quadratic("bf16")
    for k, t in targets.items():
        # fp32 wire: tight convergence
        np.testing.assert_allclose(w32[k], t, atol=2e-3, err_msg=k)
        # bf16 wire: converges to the same optimum within the bf16
        # noise floor (~0.4% relative), nowhere near divergence
        np.testing.assert_allclose(wbf[k], t, atol=2e-2, err_msg=k)
        np.testing.assert_allclose(wbf[k], w32[k], atol=2e-2, err_msg=k)


def test_wire_dtype_knob_parses():
    old = os.environ.get("MXNET_KVSTORE_GRAD_DTYPE")
    try:
        for val, want in [("fp32", None), ("bf16", "bfloat16"),
                          ("fp16", "float16")]:
            os.environ["MXNET_KVSTORE_GRAD_DTYPE"] = val
            got = comm.wire_dtype()
            assert (got is None) == (want is None)
            if want:
                assert got.name == want
        os.environ["MXNET_KVSTORE_GRAD_DTYPE"] = "int7"
        with pytest.raises(MXNetError):
            comm.wire_dtype()
    finally:
        if old is None:
            os.environ.pop("MXNET_KVSTORE_GRAD_DTYPE", None)
        else:
            os.environ["MXNET_KVSTORE_GRAD_DTYPE"] = old


# -- kvstore satellites --------------------------------------------------
def test_set_rescale_scales_pushes_once():
    kv = mx.kv.create("local")
    kv.init(0, mx.nd.zeros((4,)))
    kv.set_rescale(0.5)
    kv.push(0, mx.nd.ones((4,)) * 4)
    out = mx.nd.empty((4,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((4,), 2.0))
    # applied BEFORE the updater (the wire-side scale), exactly once
    kv2 = mx.kv.create("local")
    kv2.init(0, mx.nd.ones((4,)))
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=1.0,
                                       rescale_grad=1.0, wd=0.0))
    kv2.set_rescale(0.25)
    kv2.push(0, mx.nd.ones((4,)) * 4)  # updater sees 4*0.25 = 1
    out2 = mx.nd.empty((4,))
    kv2.pull(0, out=out2)
    np.testing.assert_allclose(out2.asnumpy(), np.zeros((4,)))  # 1 - 1*1


def test_get_num_dead_node_unified_default():
    import inspect

    from mxnet_tpu.kvstore import DistKVStore, KVStore

    # the staleness threshold is unified in the config catalog
    # (MXNET_DEAD_RANK_TIMEOUT): every consumer defaults to None and
    # resolves through it — no scattered literals
    for cls in (KVStore, DistKVStore):
        sig = inspect.signature(cls.get_num_dead_node)
        assert sig.parameters["timeout"].default is None, cls
    assert inspect.signature(
        DistKVStore.dead_ranks).parameters["timeout"].default is None
    from mxnet_tpu import config

    assert config.describe("MXNET_DEAD_RANK_TIMEOUT").default == 60.0
    assert config.describe("MXNET_HEARTBEAT_INTERVAL").default == 1.0
    assert mx.kv.create("local").get_num_dead_node() == 0
