#!/usr/bin/env python
"""Profile the fused transformer-LM training step on the TPU — the
per-HLO breakdown behind the MFU work (PERF.md "Transformer LM").

Usage: python tools/profile_transformer.py [trace_dir] [--layers N ...]
"""

import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

from mxnet_tpu.config import place_compile_cache

place_compile_cache()

import numpy as np

from profile_step import find_xplane, parse_xplane


def build(layers=12, d_model=768, heads=12, T=1024, batch=8, vocab=32768,
          head="softmax"):
    import mxnet_tpu as mx
    from mxnet_tpu import models

    sym = models.transformer_lm(vocab_size=vocab, seq_len=T,
                                num_layers=layers, num_heads=heads,
                                d_model=d_model, dtype="bfloat16",
                                head=head)
    ctx = mx.tpu()  # no chip: an error, never CPU numbers
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc("data", (batch, T))],
             label_shapes=[mx.io.DataDesc("softmax_label", (batch, T))],
             for_training=True)
    mx.random.seed(0)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="avg", magnitude=3))
    mod.init_optimizer(kvstore=None, optimizer="adam",
                       optimizer_params={"learning_rate": 3e-4})
    rng = np.random.RandomState(0)
    toks = rng.randint(1, vocab, size=(batch, T + 1))
    b = mx.io.DataBatch(
        [mx.nd.array(toks[:, :T].astype(np.float32), ctx=ctx)],
        [mx.nd.array(toks[:, 1:].astype(np.float32), ctx=ctx)])
    return mod, b


def main():
    trace_dir = sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith("--") \
        else tempfile.mkdtemp(prefix="tf_trace_")
    mod, b = build(head=os.environ.get("BENCH_HEAD", "softmax"))
    steps = 8
    for _ in range(3):
        mod.forward_backward(b)
        mod.update()
    mod.get_outputs()[0].wait_to_read()
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            mod.forward_backward(b)
            mod.update()
        mod.get_outputs()[0].wait_to_read()

    (mod_ms, mod_n), busy_ms, rows = parse_xplane(find_xplane(trace_dir))
    print(f"\nXLA module span: {mod_ms:.3f} ms x {mod_n} occurrences")
    print(f"device busy: {busy_ms / steps:.3f} ms/step over {steps} steps")
    by_cls = {}
    for name, cls, ms in rows:
        by_cls[cls] = by_cls.get(cls, 0.0) + ms
    print("\nper-class ms/step:")
    for cls, ms in sorted(by_cls.items(), key=lambda kv: -kv[1]):
        print(f"  {cls:16s} {ms / steps:8.3f}")
    print("\ntop 25 ops (ms/step):")
    for name, cls, ms in rows[:25]:
        print(f"  {ms / steps:8.3f}  [{cls}] {name[:90]}")


if __name__ == "__main__":
    main()
