"""A kernel's share of its roofline, the need counted by the FAMILY's
own ``flops/<family>.py``: ``need(kernel, stats, cfg, itemsize)`` gives
(operations, bytes) per execution of the program the kernel runs in,
from the engine's counters over the window.

Spent: the summed device time of the Mosaic kernels whose name holds
``kernel`` inside WHOLE executions of the programs whose name holds
``program`` (``spec_kernel_roofline.kernel_seconds_in``), over those
executions.  The least time is the larger of operations / peak FLOP/s
and bytes / peak bytes/s.  A family without ``need``, a program without
the kernel or the counters (the parent of the PR that brought them)
gives nothing to read: ``None``.
"""

from benchmark import harness, peaks
from benchmark.flops import paged_attention
from benchmark.reducers.spec_kernel_roofline import kernel_seconds_in


def read(sources, kernel, program):
    trace, stats = sources.get("trace"), sources.get("engine_stats")
    if trace is None or not stats or not trace.device_planes():
        return None
    cell, run = sources["cell"], sources["run"]
    flops = harness.plugin("flops", cell.config["family"])
    if not hasattr(flops, "need"):
        return None
    counted = flops.need(kernel, stats, cell.config,
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
