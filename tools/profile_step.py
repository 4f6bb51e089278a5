#!/usr/bin/env python
"""Profile the fused ResNet-50 training step on the TPU.

Captures a ``jax.profiler`` trace around a window of fused steps, then
parses the XPlane protobuf (via tensorboard_plugin_profile) to report:

* total device time per step (the XLA executable's on-device span) —
  the ``step_ms_device`` cross-check for bench.py's wall-clock claim;
* the top HLO op categories / individual ops by self time — where the
  step's milliseconds actually go (matmuls? transposes? BN reductions?).

Usage:  BENCH_BATCH=256 python tools/profile_step.py [trace_dir]

Reference methodology parity: /root/reference/docs/how_to/perf.md:105-138
(the reference profiles with nvprof; this is the TPU-native equivalent).
"""

import glob
import gzip
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

from mxnet_tpu.config import place_compile_cache

place_compile_cache()

import numpy as np


def build_module(batch, precision="bf16"):
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models

    sym = models.resnet(num_classes=1000, num_layers=50,
                        image_shape=(3, 224, 224),
                        stem=os.environ.get("BENCH_STEM", "s2d"))
    ctx = mx.tpu()  # no chip: an error, never CPU numbers
    data_dtype = jnp.bfloat16 if precision == "bf16" else np.float32
    rng = np.random.RandomState(0)
    X = mx.nd.array(rng.rand(batch, 3, 224, 224).astype(np.float32)
                    .astype(data_dtype), ctx=ctx)
    y = mx.nd.array(rng.randint(0, 1000, size=batch).astype(np.float32),
                    ctx=ctx)
    batch_obj = mx.io.DataBatch([X], [y])
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc("data", (batch, 3, 224, 224),
                                         dtype=data_dtype)],
             label_shapes=[mx.io.DataDesc("softmax_label", (batch,))],
             for_training=True)
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.34))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.005,
                                         "momentum": 0.9})
    return mod, batch_obj


def run_trace(trace_dir, steps=10, batch=None, precision=None):
    batch = batch or int(os.environ.get("BENCH_BATCH", "32"))
    precision = precision or os.environ.get("BENCH_PRECISION", "bf16")
    mod, b = build_module(batch, precision)
    for _ in range(3):  # warmup + compile
        mod.forward_backward(b)
        mod.update()
    mod.get_outputs()[0].wait_to_read()
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            mod.forward_backward(b)
            mod.update()
        mod.get_outputs()[0].wait_to_read()
    return steps, batch


def find_xplane(trace_dir):
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


import re

_OP_CLASSES = [
    # NOTE: any name containing "convolution" is classified "conv" by
    # the pre-check in _op_class before this table is consulted
    ("conv", re.compile(r"^%?conv_general")),
    ("dot", re.compile(r"^%?(dot|gemm)")),
    ("pool_bwd", re.compile(r"^%?select_and_scatter")),
    ("reduce_window", re.compile(r"^%?reduce_window")),
    ("bn_reduce", re.compile(r"^%?\w*(multiply_reduce|convert_reduce)_fusion")),
    ("copy/transpose", re.compile(r"^%?(copy|transpose|bitcast)")),
    ("collective", re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                              r"collective)")),
    ("other_fusion", re.compile(r"^%?\w*fusion")),
]


def _op_class(name):
    # conv-named *fusions* are weight/data-grad convs fused with
    # elementwise ops — classify before the generic fusion bucket
    if re.match(r"^%?\w*convolution\w*", name):
        return "conv"
    for cls, rx in _OP_CLASSES:
        if rx.match(name):
            return cls
    return "other"


def parse_xplane(path):
    """Return (module_ms_per_occurrence, busy_ms_total, rows) where rows
    are (op_name, class, total_ms) aggregated over the trace, from the
    device plane of an XPlane protobuf (parsed by tools/xplane_parse)."""
    from xplane_parse import load_xspace

    planes = load_xspace(path)
    dev = None
    for p in planes:
        if "/device:TPU" in p.name or ("/device:" in p.name
                                       and "CUSTOM" not in p.name):
            dev = p
            break
    if dev is None:
        raise SystemExit(f"no device plane in {path}: "
                         f"{[p.name for p in planes]}")
    module_ms, module_n = 0.0, 0
    ops = {}
    for line in dev.lines:
        if line.name == "XLA Modules":
            for ev in line.events:
                module_ms += ev.duration_ps / 1e9
                module_n += 1
        elif line.name == "XLA Ops":
            for ev in line.events:
                name = dev.event_names.get(ev.metadata_id, "?")
                ops[name] = ops.get(name, 0.0) + ev.duration_ps / 1e9
    busy_ms = sum(ops.values())
    rows = sorted(((n, _op_class(n), ms) for n, ms in ops.items()),
                  key=lambda r: -r[2])
    return (module_ms / max(module_n, 1), module_n), busy_ms, rows


def main():
    trace_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/mxtpu_trace"
    steps = int(os.environ.get("PROFILE_STEPS", "10"))
    if not os.environ.get("PROFILE_PARSE_ONLY"):
        steps, batch = run_trace(trace_dir, steps=steps)
        print(f"[profile] traced {steps} steps (batch {batch}) -> {trace_dir}",
              file=sys.stderr)
    xp = find_xplane(trace_dir)
    print(f"[profile] parsing {xp}", file=sys.stderr)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    (module_ms, module_n), busy_ms, rows = parse_xplane(xp)
    print(f"XLA module executions: {module_n}; device time/exec "
          f"{module_ms:.3f} ms; op-busy total {busy_ms:.2f} ms "
          f"({busy_ms/max(module_n,1):.3f} ms/exec)")
    cats = {}
    for name, cls, ms in rows:
        cats[cls] = cats.get(cls, 0.0) + ms
    print("\n-- by op class (ms total, % of busy) --")
    for c, ms in sorted(cats.items(), key=lambda kv: -kv[1]):
        print(f"{ms:9.2f}  {100*ms/busy_ms:5.1f}%  {c}")
    print("\n-- top 25 ops by total time (ms across trace) --")
    for name, cls, ms in rows[:25]:
        print(f"{ms:9.3f}  {100*ms/busy_ms:5.1f}%  [{cls}] {name[:110]}")


if __name__ == "__main__":
    main()
