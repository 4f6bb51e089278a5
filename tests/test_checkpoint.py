"""Fault-tolerant checkpointing tests: atomic writes, the sharded
commit protocol, corruption fallback, auto-resume bit-exactness,
fault injection (writer killed mid-shard), SIGTERM preemption, env-var
validation, and the inspect/bench tools."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests"))

import ckpt_crash_worker as W  # noqa: E402


def _subproc_env():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("MXNET_CKPT_CRASH", None)
    return env


# ---------------------------------------------------------------------------
# satellite: atomic model.save_checkpoint / clear load_checkpoint errors
# ---------------------------------------------------------------------------

def _small_sym():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_save_checkpoint_atomic_and_loadable(tmp_path):
    prefix = str(tmp_path / "model")
    args = {"fc_weight": mx.nd.ones((4, 3)), "fc_bias": mx.nd.zeros((4,))}
    mx.model.save_checkpoint(prefix, 3, _small_sym(), args, {})
    # no temp litter: a crash mid-write must never shadow the real files
    leftovers = [f for f in os.listdir(tmp_path) if ".part." in f]
    assert leftovers == []
    sym, args2, aux2 = mx.model.load_checkpoint(prefix, 3)
    np.testing.assert_array_equal(args2["fc_weight"].asnumpy(),
                                  args["fc_weight"].asnumpy())
    assert aux2 == {}


def test_load_checkpoint_missing_file_names_it(tmp_path):
    prefix = str(tmp_path / "nope")
    with pytest.raises(mx.MXNetError, match="missing symbol file.*nope"):
        mx.model.load_checkpoint(prefix, 0)
    # symbol present, params missing
    _small_sym().save(prefix + "-symbol.json")
    with pytest.raises(mx.MXNetError, match=r"missing params file.*0007"):
        mx.model.load_checkpoint(prefix, 7)


def test_load_checkpoint_corrupt_params_names_file(tmp_path):
    prefix = str(tmp_path / "model")
    args = {"fc_weight": mx.nd.ones((4, 3)), "fc_bias": mx.nd.zeros((4,))}
    mx.model.save_checkpoint(prefix, 1, _small_sym(), args, {})
    pfile = prefix + "-0001.params"
    blob = open(pfile, "rb").read()
    with open(pfile, "wb") as f:
        f.write(blob[:len(blob) // 2])  # truncate: crash-mid-write relic
    with pytest.raises(mx.MXNetError, match="0001.params"):
        mx.model.load_checkpoint(prefix, 1)
    with open(pfile, "wb") as f:
        f.write(b"garbage not a params file")
    with pytest.raises(mx.MXNetError, match="0001.params"):
        mx.model.load_checkpoint(prefix, 1)


# ---------------------------------------------------------------------------
# manager: roundtrip, commit protocol, GC, corruption fallback
# ---------------------------------------------------------------------------

def test_manager_roundtrip_and_gc(tmp_path):
    d = str(tmp_path / "ckpt")
    params = W.train(ckpt_dir=d, num_epoch=2, every_n=3)
    infos = [i for i in C.list_checkpoints(d) if i.committed]
    # 24 steps, every 3 -> saves at 3..24; keep=10 in the worker
    assert [i.step for i in infos] == [3, 6, 9, 12, 15, 18, 21, 24]
    assert C.verify_checkpoint(infos[-1].path) == []
    state = C.load_shard(infos[-1].path, 0)
    assert state["step"] == 24 and state["epoch"] == 1
    assert state["nbatch"] == 11  # 12 batches/epoch
    for k, v in state["arg_params"].items():
        np.testing.assert_array_equal(v, params[k])
    assert state["optimizer"]["kind"] == "fused"
    assert "fc1_weight" in state["optimizer"]["states"]
    assert state["iter_state"]["kind"] == "NDArrayIter"
    assert state["rng"] is not None


def test_manager_keep_gc(tmp_path):
    d = str(tmp_path / "ckpt")
    mgr = mx.CheckpointManager(d, keep=2, async_save=False)

    class FakeModule:
        optimizer_initialized = False

        def get_params(self):
            return {"w": mx.nd.ones((2, 2))}, {}

    mod = FakeModule()
    for s in range(1, 6):
        mgr.save(module=mod, epoch=0, nbatch=s, step=s)
    infos = [i for i in C.list_checkpoints(d) if i.committed]
    assert [i.step for i in infos] == [4, 5]


def test_restore_falls_back_on_corruption(tmp_path, caplog):
    d = str(tmp_path / "ckpt")
    W.train(ckpt_dir=d, num_epoch=1, every_n=6)  # commits steps 6, 12
    infos = [i for i in C.list_checkpoints(d) if i.committed]
    assert [i.step for i in infos] == [6, 12]
    # corrupt the NEWEST shard (bit flip)
    shard = os.path.join(infos[-1].path, "shard-00000.bin")
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(shard, "wb") as f:
        f.write(bytes(blob))
    assert C.verify_checkpoint(infos[-1].path) != []
    mgr = mx.CheckpointManager(d)
    state = mgr.load_latest()
    assert state is not None and state["step"] == 6  # fell back


def test_restore_ignores_torn_tmp(tmp_path):
    d = str(tmp_path / "ckpt")
    W.train(ckpt_dir=d, num_epoch=1, every_n=12)  # commits step 12
    # a torn, never-committed attempt with a HIGHER step
    torn = os.path.join(d, "ckpt-000000000099.tmp")
    os.makedirs(torn)
    with open(os.path.join(torn, "shard-00000.bin"), "wb") as f:
        f.write(b"half a shard")
    mgr = mx.CheckpointManager(d)
    state = mgr.load_latest()
    assert state["step"] == 12
    # restore-side GC retired the torn attempt
    assert not os.path.isdir(torn)


def test_uncommitted_dir_without_marker_is_not_latest(tmp_path):
    d = str(tmp_path / "ckpt")
    W.train(ckpt_dir=d, num_epoch=1, every_n=12)
    # a renamed dir whose COMMIT marker is missing (e.g. deleted)
    good = [i for i in C.list_checkpoints(d) if i.committed][0]
    fake = os.path.join(d, "ckpt-000000000050")
    os.makedirs(fake)
    state = mx.CheckpointManager(d).load_latest()
    assert state["step"] == good.step


# ---------------------------------------------------------------------------
# auto-resume bit-exactness (single process, fused path)
# ---------------------------------------------------------------------------

def test_fit_resume_auto_bitexact_mid_epoch(tmp_path):
    ref = W.train(ckpt_dir=None, num_epoch=2)

    d = str(tmp_path / "ckpt")

    class Stop(Exception):
        pass

    # interrupted run: dies mid-epoch 0 (after batch 7; ckpt at step 6)
    mx.random.seed(11)
    np.random.seed(11)
    X, y = W.make_data()
    it = mx.io.NDArrayIter(X, y, batch_size=W.BATCH, shuffle=True)
    mod = mx.mod.Module(W.build_sym(), context=mx.cpu())
    mgr = mx.CheckpointManager(d, every_n_steps=6, async_save=True, keep=10)

    def boom(param):
        if param.epoch == 0 and param.nbatch == 7:
            raise Stop()

    with pytest.raises(Stop):
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
                initializer=mx.initializer.Xavier(rnd_type="gaussian"),
                eval_metric="acc", checkpoint=mgr, resume="auto",
                batch_end_callback=boom)
    mgr.close()
    committed = [i.step for i in C.list_checkpoints(d) if i.committed]
    assert committed == [6]

    # resumed run: DIFFERENT ambient seeds — everything that matters
    # (params, momentum, shuffle order, RNG key, batch position) must
    # come from the checkpoint
    mx.random.seed(555)
    np.random.seed(555)
    resumed = W.train(ckpt_dir=d, num_epoch=2, every_n=6)
    for k in ref:
        np.testing.assert_array_equal(
            ref[k], resumed[k],
            err_msg=f"{k}: resumed weights diverge from uninterrupted run")


def test_fit_resume_requires_manager():
    X, y = W.make_data()
    it = mx.io.NDArrayIter(X, y, batch_size=W.BATCH)
    mod = mx.mod.Module(W.build_sym(), context=mx.cpu())
    with pytest.raises(mx.MXNetError, match="resume"):
        mod.fit(it, num_epoch=1, resume="auto")


# ---------------------------------------------------------------------------
# fault injection: writer killed mid-shard; SIGTERM preemption
# ---------------------------------------------------------------------------

def test_kill_background_writer_mid_shard_then_resume(tmp_path):
    """The background writer dies HALFWAY through a shard write; the
    torn attempt must be invisible to restore, and the resumed run must
    bit-match an uninterrupted one."""
    d = str(tmp_path / "ckpt")
    env = _subproc_env()
    env["MXNET_CKPT_CRASH"] = "mid_shard:2"  # 2nd save (step 12) tears
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "ckpt_crash_worker.py"),
         "--ckpt-dir", d, "--epochs", "2", "--every-n", "6"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert r.returncode == 9, r.stdout + r.stderr  # the injected exit
    infos = C.list_checkpoints(d)
    committed = [i for i in infos if i.committed]
    torn = [i for i in infos if not i.committed]
    assert [i.step for i in committed] == [6]
    assert [i.step for i in torn] == [12]
    assert C.verify_checkpoint(committed[0].path) == []

    # restore picks the committed step-6 checkpoint, ignoring the torn
    # one, and replays to the same final weights as an untouched run
    mx.random.seed(321)
    np.random.seed(321)
    resumed = W.train(ckpt_dir=d, num_epoch=2, every_n=6)
    ref = W.train(ckpt_dir=None, num_epoch=2)
    for k in ref:
        np.testing.assert_array_equal(ref[k], resumed[k])


def test_sigterm_triggers_emergency_checkpoint(tmp_path):
    """Preemption notice: SIGTERM mid-fit must produce a committed
    emergency checkpoint and still kill the process with SIGTERM
    semantics."""
    d = str(tmp_path / "ckpt")
    p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "ckpt_crash_worker.py"),
         "--ckpt-dir", d, "--epochs", "50", "--every-n", "0",
         "--sleep", "0.05", "--progress"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=_subproc_env())
    out_lines = []
    try:
        # wait for a few completed steps, then deliver the preemption
        deadline = time.time() + 180
        while time.time() < deadline:
            line = p.stdout.readline()
            if not line:
                break
            out_lines.append(line)
            if "BATCH 3" in line:
                break
        assert any("BATCH 3" in l for l in out_lines), "".join(out_lines)
        p.send_signal(signal.SIGTERM)
        out, _ = p.communicate(timeout=120)
        out_lines.append(out or "")
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    out = "".join(out_lines)
    assert p.returncode == -signal.SIGTERM, out
    infos = [i for i in C.list_checkpoints(d) if i.committed]
    assert len(infos) == 1, out
    state = C.load_shard(infos[0].path, 0)
    assert state["reason"] == "preempt"
    assert state["step"] > 0


# ---------------------------------------------------------------------------
# env-var catalog + loud validation
# ---------------------------------------------------------------------------

def test_ckpt_env_vars_registered():
    names = {v.name for v in mx.config.list_env()}
    for var in ("MXNET_CKPT_DIR", "MXNET_CKPT_EVERY_N_STEPS",
                "MXNET_CKPT_KEEP", "MXNET_CKPT_ASYNC",
                "MXNET_CKPT_COMMIT_TIMEOUT", "MXNET_CKPT_CRASH"):
        assert var in names
        assert mx.config.describe(var).doc


@pytest.mark.parametrize("var,bad,msg", [
    ("MXNET_CKPT_EVERY_N_STEPS", "banana", "expected int"),
    ("MXNET_CKPT_EVERY_N_STEPS", "-3", "must be >="),
    ("MXNET_CKPT_KEEP", "0", "must be >="),
    ("MXNET_CKPT_KEEP", "2.5", "expected int"),
    ("MXNET_CKPT_COMMIT_TIMEOUT", "soon", "expected float"),
    ("MXNET_CKPT_CRASH", "sometimes", "MXNET_CKPT_CRASH"),
    ("MXNET_CKPT_CRASH", "mid_shard:x", "MXNET_CKPT_CRASH"),
])
def test_invalid_ckpt_env_fails_loudly(tmp_path, monkeypatch, var, bad, msg):
    monkeypatch.setenv(var, bad)
    with pytest.raises(mx.MXNetError, match=msg):
        mx.CheckpointManager(str(tmp_path / "c"))


def test_explicit_args_override_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_CKPT_EVERY_N_STEPS", "7")
    monkeypatch.setenv("MXNET_CKPT_KEEP", "9")
    mgr = mx.CheckpointManager(str(tmp_path / "c"), every_n_steps=2)
    assert mgr.every_n_steps == 2  # arg wins
    assert mgr.keep == 9           # env fills the rest


# ---------------------------------------------------------------------------
# metrics + tools
# ---------------------------------------------------------------------------

def test_ckpt_metrics_recorded(tmp_path):
    mx.profiler.reset_metrics()
    W.train(ckpt_dir=str(tmp_path / "c"), num_epoch=1, every_n=12)
    s = mx.profiler.metrics_summary()
    assert s["counters"]["ckpt.saves"] >= 1
    assert s["counters"]["ckpt.bytes"] > 0
    assert s["gauges"]["ckpt.last_step"] == 12.0
    assert s["histograms"]["ckpt.blocking_ms"]["count"] >= 1
    assert s["histograms"]["ckpt.save_ms"]["count"] >= 1


def test_ckpt_inspect_tool(tmp_path):
    d = str(tmp_path / "ckpt")
    W.train(ckpt_dir=d, num_epoch=1, every_n=6)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ckpt_inspect.py"),
         d, "--verify"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=_subproc_env())
    assert r.returncode == 0, r.stdout + r.stderr
    assert "step=6 committed" in r.stdout
    assert "checksums=OK" in r.stdout
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ckpt_inspect.py"),
         d, "--manifest"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=_subproc_env())
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "fc1_weight" in r2.stdout
    assert "kind=fused" in r2.stdout
    # corrupt a shard -> --verify exits non-zero and says CORRUPT
    info = [i for i in C.list_checkpoints(d) if i.committed][-1]
    shard = os.path.join(info.path, "shard-00000.bin")
    with open(shard, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff")
    r3 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ckpt_inspect.py"),
         d, "--verify"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=_subproc_env())
    assert r3.returncode == 1
    assert "CORRUPT" in r3.stdout


def test_bucketing_module_optimizer_snapshot_roundtrip():
    """BucketingModule delegates the checkpoint payload to the active
    bucket (which owns the adopted fused state)."""
    from mxnet_tpu.io import DataBatch, DataDesc

    def sym_gen(key):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
        return (mx.sym.SoftmaxOutput(net, name="softmax"),
                ("data",), ("softmax_label",))

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8)
    mod.bind(data_shapes=[DataDesc("data", (4, 6))],
             label_shapes=[DataDesc("softmax_label", (4,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    rng = np.random.RandomState(0)
    for _ in range(2):
        batch = DataBatch(
            [mx.nd.array(rng.randn(4, 6).astype(np.float32))],
            [mx.nd.array(rng.randint(0, 8, 4).astype(np.float32))],
            pad=0, bucket_key=8,
            provide_data=[DataDesc("data", (4, 6))],
            provide_label=[DataDesc("softmax_label", (4,))])
        mod.forward_backward(batch)
        mod.update()
    payload = mod._optimizer_states_to_host()
    assert payload["kind"] == "fused"
    assert "fc_weight" in payload["states"]
    import jax

    before = np.asarray(
        jax.tree_util.tree_leaves(payload["states"]["fc_weight"])[0])
    assert np.abs(before).sum() > 0  # real momentum, not zeros
    from mxnet_tpu.checkpoint import _to_host_tree
    mod._install_optimizer_states(_to_host_tree(payload))
    after = mod._optimizer_states_to_host(lazy=False)
    np.testing.assert_allclose(
        np.asarray(jax.tree_util.tree_leaves(after["states"]["fc_weight"])[0]),
        before)
