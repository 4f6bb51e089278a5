"""A hybrid model's kernel's share of its roofline, in the programs it
serves in.

Spent: the summed device time of the Mosaic kernels whose name holds
``kernel`` that ran inside a WHOLE execution of a program whose name
holds ``program`` (``XLA Modules`` of the first chip), over those
executions.  Need, per execution, from the engine's counters over the
window (``flops/<family>.py``), by ``kernel``:

* ``moe_gmm``: the held experts hit and the token-expert pairs computed
  here, per decode step (``moe_experts_hit``, ``moe_pairs_here``: sums
  over layers and steps);
* ``kda_step``: the live rows of a decode step (``stream_steps /
  steps``), in each kda layer;
* ``kda_chunk``: the prompt positions of a prefill (``prefill_tokens /
  prefills``), in each kda layer;
* ``paged_attention``: the live context of a decode step
  (``context_tokens / steps``) at the K/V width, in each attention
  layer (``flops/paged_attention.py``).

The least time is the larger of operations / peak FLOP/s and bytes /
peak bytes/s.  Counters are averaged over the window and kernels over
the traced slice at its end; the closed loop is steady.  A program that
has no such kernel or counter (the parent of the PR that brought them)
gives nothing to read: ``None``.
"""

from benchmark import harness, peaks
from benchmark import trace_reduce as tr
from benchmark.flops import paged_attention


def kernel_seconds_in(trace, kernel, program):
    """(seconds of ``kernel`` inside whole ``program`` executions, the
    number of those executions), first chip."""
    planes = trace.device_planes()
    if not planes:
        return 0.0, 0
    lo, hi = trace.window()
    runs = sorted((s, s + d) for name, s, d in
                  trace.planes[planes[0]].get(tr.MODULES_LINE, [])
                  if program in name and s >= lo and s + d <= hi)
    if not runs:
        return 0.0, 0
    spent, at = 0.0, 0
    events = sorted((a, b) for name, a, b in trace.ops(planes[0])
                    if tr.KERNEL_TAG in name and kernel in name)
    for a, b in events:
        while at < len(runs) and runs[at][1] < a:
            at += 1
        if at < len(runs) and runs[at][0] <= a and b <= runs[at][1]:
            spent += (b - a) / 1e9
    return spent, len(runs)


def need(kernel, stats, cfg, flops, itemsize):
    """(operations, bytes) per execution, or ``None``."""
    att, kda, moe = flops.layer_counts(cfg)
    steps = stats.get("steps")
    if kernel == "kda_chunk":
        n = stats.get("prefills")
        if not n or not stats.get("prefill_tokens"):
            return None
        ops, nbytes = flops.kda_chunk(stats["prefill_tokens"] / n, 1, cfg)
        return kda * ops, kda * nbytes
    if not steps:
        return None
    if kernel == "moe_gmm":
        if not stats.get("moe_pairs_here"):
            return None
        return flops.moe_gmm(stats["moe_experts_hit"] / steps,
                             stats["moe_pairs_here"] / steps, cfg,
                             itemsize)
    if kernel == "kda_step":
        ops, nbytes = flops.kda_step(stats["stream_steps"] / steps, cfg)
        return kda * ops, kda * nbytes
    if kernel == "paged_attention":
        if not stats.get("context_tokens"):
            return None
        return paged_attention.decode_step(
            context_tokens=stats["context_tokens"] / steps,
            rows=stats["stream_steps"] / steps, layers=att,
            width=int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
            itemsize=itemsize)
    raise ValueError(f"no count for kernel {kernel!r}")


def read(sources, kernel, program):
    trace, stats = sources.get("trace"), sources.get("engine_stats")
    if trace is None or not stats or not trace.device_planes():
        return None
    cell, run = sources["cell"], sources["run"]
    flops = harness.plugin("flops", cell.config["family"])
    counted = need(kernel, stats, cell.config, flops,
                   paged_attention.ITEMSIZE[cell.workload["dtype"]])
    spent, runs = kernel_seconds_in(trace, kernel, program)
    if counted is None or spent <= 0 or not runs:
        return None
    ops, nbytes = counted
    peak = peaks.lookup(run.devices[0].device_kind)
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    least, each = max(t_ops, t_mem), spent / runs
    harness.log(roofline_of=kernel, in_program=program, executions=runs,
                bound_by="compute" if t_ops >= t_mem else "memory",
                need_ops=ops, need_bytes=nbytes, least_ms=1e3 * least,
                kernel_ms=1e3 * each)
    return 100.0 * least / each
