"""Runner ``train_lm_mesh``: ``train_lm``'s language model trained
through ``mx.mod.Module`` under a ``MeshPlan`` over the cell's chips —
data-parallel replicas x tensor-parallel shards, the optimizer state
sharded over the replicas by ``kvstore="tpu"`` (ZeRO-1).

What it measures exists only across chips: the gradient and ZeRO
collectives, hidden under the tensor-parallel matmuls or exposed.  The
readings, the window and the comparison are ``train_lm``'s, by import;
what differs is how the module is built (the workload's ``mesh``: dp,
tp, the partition rules' overrides) and where the float32 reference
lives: its w, m, v and g (16 bytes a parameter) fit no one chip beside
anything, so they are split over the cell's chips — every stacked leaf
along its last axis — and ``reference/gpt2.train_step`` runs on them as
it is, partitioned by the compiler.

``CONTROLS`` (empty in a benchmark run) names precisions of the
reference that are put through the same checks against the same limits
after the program's: each must read ``correct: false``
(``benchmark/tests/test_mesh_cell.py`` sets it, beside the faults it
plants in the step).
"""

import gc
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, peaks
from benchmark.runners import train_lm as base

CONTROLS = ()
VERDICTS = {}       # control -> did it pass every check (it must not)


class MeshTrainer(base.Trainer):
    """``train_lm.Trainer`` with the module laid over a mesh."""

    def __init__(self, run, weights):
        import mxnet_tpu as mx
        from mxnet_tpu import models, parallel
        from mxnet_tpu.models import transformer

        cfg, wl, mix = run.cell.config, run.cell.workload, run.cell.traffic
        mesh = wl["mesh"]
        self.mx = mx
        tpu = run.devices[0].platform == "tpu"
        self.ctx = mx.tpu(0) if tpu else mx.cpu()
        B, T = int(mix["batch"]), int(mix["seq_len"])
        sym = models.transformer_lm(
            vocab_size=cfg["vocab_size"], seq_len=T,
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            d_model=cfg["n_embd"], dtype=wl["dtype"],
            head=wl.get("head", "softmax"))
        self.mod = mx.mod.Module(sym, context=self.ctx)
        self.mod.bind(
            data_shapes=[mx.io.DataDesc("data", (B, T))],
            label_shapes=[mx.io.DataDesc("softmax_label", (B, T))],
            for_training=True)
        self.mod.init_params(
            initializer=None,
            arg_params={k: mx.nd.NDArray(v, self.ctx)
                        for k, v in weights.items()})
        # the family's rules, with the workload's overrides first (first
        # match wins): 50257 vocabulary rows divide by no axis
        rules = tuple((k, v) for k, v in mesh.get("rules", {}).items()) \
            + tuple(transformer.lm_partition_rules())
        self.mod.set_mesh_plan(parallel.MeshPlan(
            list(run.devices), dp=int(mesh["dp"]), tp=int(mesh["tp"]),
            rules=rules))
        self.mod.init_optimizer(
            kvstore=wl["kvstore"], optimizer=wl["optimizer"],
            optimizer_params=dict(wl["optimizer_params"]))
        self.steps = 0


def split_over(devices, tree):
    """Every leaf of the reference's tree on all the chips: split along
    its last axis where the chips divide it, whole on each otherwise
    (the biases of 50257, scalars)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)

    def put(a):
        spec = P(*([None] * (a.ndim - 1) + ["x"])) \
            if a.ndim and a.shape[-1] % n == 0 else P()
        return jax.device_put(a, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, tree)


def reference_three(run, ref, tokens, steps, precision="float32"):
    """``reference/gpt2.train_three``, its tensors split over the
    cell's chips: the same draws, the same ``train_step``, the same
    readings."""
    cfg, wl = run.cell.config, run.cell.workload
    heads = ref.sizes(cfg)[2]
    lr = wl["optimizer_params"]["learning_rate"]
    micro = int(wl.get("reference_micro", 2))

    def seeded():
        return split_over(run.devices, ref.to_float32(
            ref.draw(cfg, run.seed, embed_dtype="float32")))

    w = seeded()
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    tokens = split_over(run.devices, tokens)      # whole on every chip
    losses, grad_norm, first_token_losses = [], None, None
    for i in range(steps):
        w, m, v, loss, gsq, per_token = ref.train_step(
            w, m, v, jnp.float32(i + 1), tokens[i % len(tokens)],
            jnp.float32(lr), heads=heads, precision=precision, micro=micro)
        losses.append(float(loss))
        if i == 0:
            grad_norm = {k: math.sqrt(x) for k, x in
                         ref.by_program_names(gsq).items()}
            first_token_losses = jax.device_get(per_token)
    del m, v
    change = {k: math.sqrt(x) for k, x in
              ref.by_program_names(ref.change_sq(w, seeded())).items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change, "token_losses": first_token_losses}


def check(program, reference, limits, last_loss):
    """``train_lm.check`` and the one number a mesh adds.  A ZeRO
    gather left out leaves the other replicas' rows of EVERY parameter
    stale: each leaf's change reads 1/sqrt(dp) of the reference's, a
    gap of 0.29 at dp = 2, which the worst leaf's limit (three times
    what bfloat16 rounding gives a sound run's smallest bias) lets
    pass.  The MEDIAN leaf of a sound run changes as the reference's
    does, so the median leaf's gap is held as well."""
    ok = base.check(program, reference, limits, last_loss)
    skip = tuple(limits.get("change_skip", ()))
    want = {k: v for k, v in reference["change_norm"].items()
            if not (skip and k.endswith(skip))}
    floor = statistics.median(want.values())
    gaps = [abs(program["change_norm"][k] - v) / max(v, floor)
            for k, v in want.items()]
    return harness.check("change_norm_median_leaf_gap",
                         statistics.median(gaps),
                         limits["change_norm_median_gap"], []) and ok


def controls(run, ref, tokens, reference):
    """The reference recomputed in each precision of ``CONTROLS``, in
    the program's place: the same checks, the same limits."""
    for p in CONTROLS:
        harness.log(control_begins=p)
        low = reference_three(run, ref, tokens, base.FIRST_STEPS, p)
        VERDICTS[p] = check(low, reference, run.cell.workload["limits"],
                            0.0)
        harness.log(control=p, correct=VERDICTS[p])


def run(run):
    ref = harness.plugin("reference", run.cell.config["family"])
    flops = harness.plugin("flops", run.cell.config["family"])
    generate = harness.plugin("traffic", run.cell.traffic["generator"])
    cfg, wl, mix = run.cell.config, run.cell.workload, run.cell.traffic
    B, T = int(mix["batch"]), int(mix["seq_len"])
    weights = base.seeded_weights(run, ref)
    run.mark("weights_drawn")
    trainer = MeshTrainer(run, weights)
    del weights
    run.mark("module_bound")
    tokens = generate.token_batches(mix, run.seed, cfg["vocab_size"])
    trainer.feed(tokens)
    program = base.first_steps(run, trainer, tokens, ref)
    run.mark("first_steps_read")
    harness.log(first_steps=program["losses"])

    # ---- the window: no read-back inside; one step in flight ---------
    t0 = run.start_window()
    prev, steps0 = None, trainer.steps
    while time.perf_counter() - t0 < run.seconds:
        run.tick()
        with run.span("forward_backward_update"):
            out = trainer.step()
        if prev is not None:
            with run.span("wait_previous_step"):
                prev.block_until_ready()
        prev = out
    with run.span("wait_last_step"):
        prev.block_until_ready()
    window_s = run.end_window()
    steps = trainer.steps - steps0

    peak = run.memory_peak()
    last_batch = tokens[(trainer.steps - 1) % len(tokens), :, 1:]
    last_loss = float(base.token_loss(prev, last_batch).mean())
    del prev, out
    text = trainer.mod.fused_hlo_text()
    kernel_ok = True
    if run.devices[0].platform == "tpu":
        kernel_ok = base.KERNEL in text
        harness.log(check="kernel_in_fused_step", marker=base.KERNEL,
                    ok=kernel_ok)
    from mxnet_tpu import hlo

    run.extras["overlap_report"] = hlo.overlap_report(text)
    harness.log(overlap_report=run.extras["overlap_report"],
                memory_peak_bytes_per_chip=[
                    int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in run.devices])

    per_token = flops.train_flops_per_token(cfg, T)
    rate = per_token * B * T * steps / window_s
    kind = run.devices[0].device_kind
    peak_flops = peaks.PEAKS.get(kind, {}).get("bf16_flops")
    mfu = None if peak_flops is None else \
        100.0 * rate / (run.cell.chips * peak_flops)
    harness.log(steps=steps, window_s=window_s,
                step_ms=1e3 * window_s / steps,
                tokens_per_s=B * T * steps / window_s,
                train_flops_per_token=per_token, train_mfu=mfu)

    # ---- free the program, then the reference ------------------------
    del trainer, text
    gc.collect()
    t_ref = time.perf_counter()
    reference = reference_three(run, ref, tokens, base.FIRST_STEPS)
    correct = check(program, reference, wl["limits"], last_loss)
    harness.log(reference_s=time.perf_counter() - t_ref)
    controls(run, ref, tokens, reference)
    metrics = {}
    if mfu is not None:
        metrics["train_mfu"] = mfu
    return {"correct": correct and kernel_ok, "attempted": steps,
            "failed": 0, "metrics": metrics, "memory_peak_bytes": peak}
