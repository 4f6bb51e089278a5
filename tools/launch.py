#!/usr/bin/env python
"""Distributed job launcher — parity with the reference's
``tools/launch.py`` (dmlc-tracker) ``--launcher local`` mode: spawn N
worker processes on this machine wired into one JAX distributed
runtime, used both for real multi-host-style runs and for testing
``kvstore='dist_sync'`` semantics without a cluster
(``tests/nightly/dist_sync_kvstore.py`` model).

    python tools/launch.py -n 2 python examples/train_mnist.py \
        --kv-store dist_sync

Each worker gets:
  MXNET_COORDINATOR      host:port of the JAX coordination service
  MXNET_NUM_WORKERS      n
  MXNET_WORKER_ID        0..n-1
  MXNET_KVSTORE_HEARTBEAT_DIR  shared dir for liveness files
(`DistKVStore` reads these and calls jax.distributed.initialize.)
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import tempfile


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_local(n, cmd, env_extra=None, cpu=False, grace=20.0):
    """Spawn n local processes; returns the list of return codes.

    If any worker exits nonzero, the survivors are terminated after
    ``grace`` seconds — a crashed peer otherwise leaves the rest
    blocked in a collective until the coordinator's long timeout."""
    import shutil
    import time

    port = free_port()
    hb_dir = tempfile.mkdtemp(prefix="mxnet_tpu_hb_")
    procs = []
    try:
        for rank in range(n):
            env = dict(os.environ)
            env.update(env_extra or {})
            env["MXNET_COORDINATOR"] = f"127.0.0.1:{port}"
            env["MXNET_NUM_WORKERS"] = str(n)
            env["MXNET_WORKER_ID"] = str(rank)
            env["MXNET_KVSTORE_HEARTBEAT_DIR"] = hb_dir
            if cpu:
                # a CPU-only runtime (multi-process CPU collectives
                # need the plain CPU client)
                env["JAX_PLATFORMS"] = "cpu"
                env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
            else:
                # one process for each chip: n workers that all target
                # the TPU without a chip of their own are refused, not
                # hung (importing the package creates no jax backend)
                sys.path.insert(0, os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
                from mxnet_tpu.config import refuse_shared_chip

                refuse_shared_chip(env, f"launch.py worker {rank} of {n}")
            procs.append(subprocess.Popen(cmd, env=env))

        deadline = None
        while True:
            rcs = [p.poll() for p in procs]
            if all(rc is not None for rc in rcs):
                return rcs
            if any(rc not in (None, 0) for rc in rcs):
                if deadline is None:
                    bad = [i for i, rc in enumerate(rcs)
                           if rc not in (None, 0)]
                    print(f"worker(s) {bad} failed — terminating the rest "
                          f"in {grace:.0f}s", file=sys.stderr)
                    deadline = time.time() + grace
                elif time.time() > deadline:
                    for p in procs:
                        if p.poll() is None:
                            p.terminate()
                    for p in procs:
                        try:
                            p.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            p.kill()
                    return [p.poll() for p in procs]
            time.sleep(0.2)
    finally:
        shutil.rmtree(hb_dir, ignore_errors=True)


def main():
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--launcher", choices=["local"], default="local")
    parser.add_argument("--cpu", action="store_true",
                        help="force a clean CPU-only JAX runtime")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    rcs = launch_local(args.num_workers, args.command, cpu=args.cpu)
    bad = [i for i, rc in enumerate(rcs) if rc != 0]
    if bad:
        print(f"workers {bad} failed (rcs={rcs})", file=sys.stderr)
        sys.exit(1)
    print(f"all {args.num_workers} workers finished successfully")


if __name__ == "__main__":
    main()
