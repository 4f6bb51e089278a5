"""The paged attention (decode) kernel's share of its roofline.

Need per decode step: the engine counts the live context each step
attends (``context_tokens``, the sum of the live rows' lengths, over
the window) — per token of context the kernel reads K and V of every
layer; per row it reads q and writes o (``flops/paged_attention.py``).
The least time is the larger of operations / peak FLOP/s and bytes /
peak bytes/s (the bytes bind: one operation per byte).  Spent per
step: the summed device time of the ``paged_attention`` kernels in the
traced slice over the decode programs executed in it.  The context is
averaged over the window and the kernel over the slice at its end; the
closed loop is steady, so the two agree (PERF.md section 5 checks one
shape by hand).
"""

from benchmark import harness, peaks
from benchmark.flops import paged_attention

KERNEL_NAME = "paged_attention"
DECODE_PROGRAM = "jit_step_decode"


def read(sources):
    trace, stats = sources.get("trace"), sources.get("engine_stats")
    if trace is None or not stats or not trace.device_planes():
        return None
    context, steps = stats.get("context_tokens"), stats.get("steps")
    if not context or not steps:
        return None  # a program that does not count its context
    spent = sum(t for name, _, t in trace.kernels()
                if KERNEL_NAME in name)
    programs = sum(n for name, (n, _) in trace.modules().items()
                   if DECODE_PROGRAM in name)
    if spent <= 0 or not programs:
        return None
    cell, run = sources["cell"], sources["run"]
    cfg = cell.config
    peak = peaks.lookup(run.devices[0].device_kind)
    ops, nbytes = paged_attention.decode_step(
        context_tokens=context / steps,
        rows=stats["stream_steps"] / steps,
        layers=int(cfg["n_layer"]), width=int(cfg["n_embd"]),
        itemsize=paged_attention.ITEMSIZE[cell.workload["dtype"]])
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    need, spent_per_step = max(t_ops, t_mem), spent / programs
    harness.log(paged_roofline_bound_by="compute" if t_ops >= t_mem
                else "memory", context_tokens_per_step=context / steps,
                need_bytes_per_step=nbytes, least_ms_per_step=1e3 * need,
                kernel_ms_per_step=1e3 * spent_per_step,
                decode_programs=programs)
    return 100.0 * need / spent_per_step
