"""Common example plumbing: repo path bootstrap, fit argument group,
synthetic datasets (the zero-egress stand-ins for MNIST/ImageNet/PTB).

Reference analogue: ``example/image-classification/common/fit.py`` +
``common/data.py`` (argument groups, kvstore/optimizer wiring, data
iterators).  Synthetic data keeps every script runnable end-to-end on
a machine with no datasets while still being *learnable* (class-
dependent signal), so accuracy/perplexity improvements are real.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx


def setup_logging():
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s %(message)s")


def get_device():
    """The training device: the TPU when this process has one, else the
    host CPU — chosen here, in the open, and logged by the examples;
    ``mx.tpu()`` itself never falls back (it raises without a chip).
    These are tutorials, not measurements: a script that reports device
    numbers asks for ``mx.tpu()`` outright."""
    return mx.tpu() if mx.context.num_devices("tpu") else mx.cpu()


def add_fit_args(parser: argparse.ArgumentParser):
    """reference: common/fit.py add_fit_args"""
    train = parser.add_argument_group("Training")
    train.add_argument("--network", type=str, default="lenet")
    train.add_argument("--batch-size", type=int, default=64)
    train.add_argument("--num-epochs", type=int, default=3)
    train.add_argument("--lr", type=float, default=0.05)
    train.add_argument("--lr-factor", type=float, default=0.1)
    train.add_argument("--lr-step-epochs", type=str, default="")
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=0.0001)
    train.add_argument("--kv-store", type=str, default="local",
                       help="local | device | tpu | dist_sync | dist_async")
    train.add_argument("--disp-batches", type=int, default=20)
    train.add_argument("--model-prefix", type=str, default=None)
    train.add_argument("--load-epoch", type=int, default=None)
    train.add_argument("--monitor", type=int, default=0,
                       help="monitor interval (0 = off)")
    train.add_argument("--profile", type=str, default=None,
                       help="write a Chrome trace to this file")
    return train


def lr_scheduler(args, epoch_size):
    if not args.lr_step_epochs:
        return None
    steps = [int(x) for x in args.lr_step_epochs.split(",") if x]
    return mx.lr_scheduler.MultiFactorScheduler(
        step=[max(1, epoch_size * s) for s in steps], factor=args.lr_factor)


def fit(args, network, train_iter, val_iter=None, label_names=None,
        initializer=None, epoch_size=None):
    """reference: common/fit.py fit — the standard training run."""
    setup_logging()
    kv = args.kv_store
    devs = get_device()
    mod = mx.mod.Module(network, context=devs,
                        label_names=label_names or ("softmax_label",))
    if args.load_epoch is not None and args.model_prefix:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)
    else:
        arg_params = aux_params = None
    epoch_size = epoch_size or 1000
    optimizer_params = {
        "learning_rate": args.lr,
        "wd": args.wd,
    }
    if args.optimizer in ("sgd", "nag"):
        optimizer_params["momentum"] = args.mom
    sched = lr_scheduler(args, epoch_size)
    if sched is not None:
        optimizer_params["lr_scheduler"] = sched
    monitor = mx.Monitor(args.monitor, pattern=".*") if args.monitor > 0 \
        else None
    if args.profile:
        mx.profiler.profiler_set_config(mode="all", filename=args.profile)
        mx.profiler.profiler_set_state("run")
    checkpoint = mx.callback.do_checkpoint(args.model_prefix) \
        if args.model_prefix else None
    mod.fit(train_iter,
            eval_data=val_iter,
            begin_epoch=args.load_epoch or 0,
            num_epoch=args.num_epochs,
            eval_metric="acc",
            kvstore=kv,
            optimizer=args.optimizer,
            optimizer_params=optimizer_params,
            initializer=initializer or mx.initializer.Xavier(
                rnd_type="gaussian", factor_type="in", magnitude=2),
            arg_params=arg_params,
            aux_params=aux_params,
            batch_end_callback=mx.callback.Speedometer(
                args.batch_size, args.disp_batches),
            epoch_end_callback=checkpoint,
            monitor=monitor)
    if args.profile:
        mx.profiler.profiler_set_state("stop")
        print(f"wrote profile to {args.profile}")
    return mod


# ---------------------------------------------------------------------------
# Synthetic datasets (learnable, deterministic)
# ---------------------------------------------------------------------------

def synthetic_mnist(num=2048, seed=0):
    """28x28 digit-like data: class k = bright kxk-ish block pattern."""
    rng = np.random.RandomState(seed)
    X = rng.rand(num, 1, 28, 28).astype(np.float32) * 0.25
    y = rng.randint(0, 10, size=num).astype(np.float32)
    for i in range(num):
        k = int(y[i])
        r, c = divmod(k, 4)
        X[i, 0, 2 + r * 8:8 + r * 8, 2 + c * 6:8 + c * 6] += 0.75
    return X, y


def mnist_iters(args, data_dir=None):
    """Real MNIST idx files when present, else synthetic."""
    if data_dir:
        timg = os.path.join(data_dir, "train-images-idx3-ubyte")
        tlbl = os.path.join(data_dir, "train-labels-idx1-ubyte")
        vimg = os.path.join(data_dir, "t10k-images-idx3-ubyte")
        vlbl = os.path.join(data_dir, "t10k-labels-idx1-ubyte")
        if all(os.path.exists(p) or os.path.exists(p + ".gz")
               for p in (timg, tlbl, vimg, vlbl)):
            fix = lambda p: p if os.path.exists(p) else p + ".gz"
            train = mx.io.MNISTIter(image=fix(timg), label=fix(tlbl),
                                    batch_size=args.batch_size, shuffle=True)
            val = mx.io.MNISTIter(image=fix(vimg), label=fix(vlbl),
                                  batch_size=args.batch_size, shuffle=False)
            return train, val
    logging.info("MNIST files not found — using a synthetic learnable set")
    X, y = synthetic_mnist(4096)
    Xv, yv = synthetic_mnist(512, seed=7)
    train = mx.io.NDArrayIter(X, y, batch_size=args.batch_size, shuffle=True,
                              last_batch_handle="discard")
    val = mx.io.NDArrayIter(Xv, yv, batch_size=args.batch_size,
                            last_batch_handle="discard")
    return train, val


def synthetic_image_iter(batch_size, image_shape, num_classes=1000,
                         num_batches=50):
    """The reference's --benchmark 1 path: random device-side batches."""
    c, h, w = image_shape
    rng = np.random.RandomState(0)
    n = batch_size * 2
    X = rng.rand(n, c, h, w).astype(np.float32)
    y = rng.randint(0, num_classes, size=n).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch_size)
    return mx.io.ResizeIter(it, num_batches, reset_internal=False)
