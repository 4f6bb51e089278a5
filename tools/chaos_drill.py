#!/usr/bin/env python
"""Elastic-training chaos drill: the 2→1→2 rank-death acceptance run.

Spawns a 2-process elastic ``dist_sync`` training job (the
tools/launch.py environment plus ``MXNET_ELASTIC=1``), SIGKILLs rank 1
mid-epoch via ``MXNET_CHAOS_KILL_STEP``, lets rank 0 detect the death
(heartbeat staleness + sync-round timeout → DeadRankError), re-mesh to
dp'=1, roll back to the last committed checkpoint and keep training —
then respawns rank 1 with ``MXNET_ELASTIC_JOIN=1`` so it is re-admitted
at the next checkpoint boundary (scale back up 1→2).  No step needs
operator action; this tool only supervises and judges.

Verdict: final weights must converge to an uninterrupted
single-process run on the union data within ``--rtol``.  Emits ONE
JSON line::

    {"converged": true, "downtime_s": 12.3, "steps_lost": 2,
     "rebuilds": 1, "max_rel_err": 1.2e-6, ...}

Exit status 0 iff converged and the protocol ran (rank death detected,
re-mesh committed, rank re-admitted).

    python tools/chaos_drill.py --kill-step 10 --out /tmp/drill
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_elastic_worker.py")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def base_env(hb_dir: str, dead_timeout: float, hb_interval: float) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
    env["MXNET_KVSTORE_HEARTBEAT_DIR"] = hb_dir
    env["MXNET_ELASTIC"] = "1"
    env["MXNET_HEARTBEAT_INTERVAL"] = str(hb_interval)
    env["MXNET_DEAD_RANK_TIMEOUT"] = str(dead_timeout)
    env["MXNET_WATCHDOG_DEADLINE"] = str(dead_timeout)
    env["ELASTIC_CKPT_EVERY"] = os.environ.get("ELASTIC_CKPT_EVERY", "4")
    return env


def run_drill(args) -> dict:
    out_prefix = args.out
    ckpt_dir = out_prefix + ".ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    hb_dir = tempfile.mkdtemp(prefix="mxnet_tpu_chaos_hb_")
    port = free_port()
    procs: dict = {}
    t_kill = None
    t_rejoin = None
    rebuild_lines = []
    try:
        env = base_env(hb_dir, args.dead_timeout, args.hb_interval)
        env["MXNET_COORDINATOR"] = f"127.0.0.1:{port}"
        env["MXNET_NUM_WORKERS"] = "2"
        for rank in (0, 1):
            e = dict(env)
            e["MXNET_WORKER_ID"] = str(rank)
            if rank == 1:
                e["MXNET_CHAOS_KILL_STEP"] = str(args.kill_step)
                e["MXNET_CHAOS_RANK"] = "1"
            logf = open(f"{out_prefix}.rank{rank}.log", "w")
            procs[rank] = (subprocess.Popen(
                [sys.executable, WORKER, ckpt_dir, out_prefix],
                env=e, cwd=REPO, stdout=logf, stderr=subprocess.STDOUT),
                logf)

        deadline = time.time() + args.timeout
        respawned = False
        while time.time() < deadline:
            rc0 = procs[0][0].poll()
            rc1 = procs[1][0].poll()
            if rc1 is not None and not respawned:
                # the victim died (SIGKILL): wait out the restart delay,
                # then bring it back as a JOINER — a fresh process with
                # no jax.distributed, discovering the run from the
                # membership ledger
                t_kill = time.time()
                print(f"[drill] rank 1 exited rc={rc1}; respawning as "
                      f"joiner in {args.restart_delay:.0f}s",
                      file=sys.stderr, flush=True)
                time.sleep(args.restart_delay)
                e = base_env(hb_dir, args.dead_timeout, args.hb_interval)
                e["MXNET_ELASTIC_JOIN"] = "1"
                e["MXNET_WORKER_ID"] = "1"
                e.pop("MXNET_COORDINATOR", None)
                e.pop("MXNET_NUM_WORKERS", None)
                logf = open(f"{out_prefix}.rank1b.log", "w")
                procs[1] = (subprocess.Popen(
                    [sys.executable, WORKER, ckpt_dir, out_prefix],
                    env=e, cwd=REPO, stdout=logf,
                    stderr=subprocess.STDOUT), logf)
                t_rejoin = time.time()
                respawned = True
                continue
            if rc0 is not None and rc0 != 0:
                raise RuntimeError(f"survivor (rank 0) failed rc={rc0}")
            if rc0 == 0 and respawned and procs[1][0].poll() == 0:
                break
            time.sleep(0.3)
        else:
            raise RuntimeError("drill timed out")
    finally:
        for p, logf in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
            logf.close()
        shutil.rmtree(hb_dir, ignore_errors=True)

    # -- judge ---------------------------------------------------------
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "tests"))
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("MXNET_ELASTIC", "MXNET_COORDINATOR"):
        os.environ.pop(var, None)
    import dist_elastic_worker as W

    ref = W.train_reference()
    logs = ""
    for suffix in ("rank0", "rank1", "rank1b"):
        path = f"{out_prefix}.{suffix}.log"
        if os.path.exists(path):
            logs += open(path).read()
    stats = {}
    for line in logs.splitlines():
        if line.startswith("ELASTIC_WORKER rank=0"):
            stats = dict(kv.split("=") for kv in line.split()[1:])
    expected = W.EPOCHS * (W.N_SAMPLES // W.GLOBAL_BATCH)
    steps_run = int(stats.get("steps", 0))
    rebuilds = int(stats.get("remesh", 0))
    max_rel = 0.0
    converged = True
    got = dict(np.load(out_prefix + ".rank0.npz"))
    got1 = dict(np.load(out_prefix + ".rank1.npz"))
    for k, v in ref.items():
        rel = float(np.max(np.abs(got[k] - v)
                           / (np.abs(v) + 1e-6)))
        max_rel = max(max_rel, rel)
        if not np.allclose(got[k], v, rtol=args.rtol, atol=1e-5):
            converged = False
        if not np.allclose(got1[k], got[k], rtol=1e-6, atol=1e-7):
            converged = False  # re-admitted rank must agree bit-tightly
    verdict = {
        "converged": bool(converged),
        "downtime_s": round(float(stats.get("max_gap_s", -1)), 2),
        "steps_lost": steps_run - expected,
        "rebuilds": rebuilds,
        "rejoined": "joins=1" in logs,
        "max_rel_err": max_rel,
        "steps_run": steps_run,
        "kill_to_rejoin_s": round(t_rejoin - t_kill, 2)
        if t_rejoin and t_kill else None,
        "dead_timeout_s": args.dead_timeout,
        "ckpt_every_n_steps": args.ckpt_every,
    }
    return verdict


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--kill-step", type=int, default=10,
                    help="fit step at which rank 1 is SIGKILLed")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="checkpoint cadence in steps (default 4); the "
                         "rollback-replay bound of the drill")
    ap.add_argument("--restart-delay", type=float, default=2.0)
    ap.add_argument("--dead-timeout", type=float, default=12.0,
                    help="MXNET_DEAD_RANK_TIMEOUT for the run")
    ap.add_argument("--hb-interval", type=float, default=0.5)
    ap.add_argument("--rtol", type=float, default=1e-4)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--out", default=os.path.join(
        tempfile.gettempdir(), "mxnet_tpu_chaos_drill"))
    args = ap.parse_args()
    if args.ckpt_every is not None:
        os.environ["ELASTIC_CKPT_EVERY"] = str(args.ckpt_every)
    args.ckpt_every = int(os.environ.get("ELASTIC_CKPT_EVERY", "4"))
    verdict = run_drill(args)
    print(json.dumps(verdict))
    ok = (verdict["converged"] and verdict["rebuilds"] >= 1
          and verdict["rejoined"])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
