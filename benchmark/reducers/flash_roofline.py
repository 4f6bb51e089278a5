"""The flash attention kernel's share of its roofline.

The kernels are the Mosaic events whose first operand is the packed
``[q | k | v]`` activation, (rows, positions, 3 x width): forward, and
under ``transpose`` in their name the two of the backward (dq; dk and
dv), which share the backward's need.  Each event's need comes from its
OWN shape through the family's counter (``flops/<family>.py``), so a mix
of prefill buckets is counted as it ran: the least time is the larger of
operations / peak FLOP/s and bytes / peak bytes/s.  The share is the
summed least time over the summed device time of those events.
"""

from benchmark import harness, peaks


def read(sources):
    trace = sources.get("trace")
    if trace is None or not trace.device_planes():
        return None
    cell, run = sources["cell"], sources["run"]
    cfg = cell.config
    width, heads = int(cfg["n_embd"]), int(cfg["n_head"])
    flops = harness.plugin("flops", cfg["family"])
    peak = peaks.lookup(run.devices[0].device_kind)
    need = spent = 0.0
    binds = {"compute": 0, "memory": 0}
    for name, shape, seconds in trace.kernels():
        if len(shape) != 3 or shape[2] != 3 * width:
            continue  # another kernel (the paged decode one)
        rows, positions = shape[0], shape[1]
        backward = "transpose" in name
        count = flops.flash_backward if backward else flops.flash_forward
        ops, nbytes = count(rows, positions, heads, width // heads)
        t_ops = ops / peak["bf16_flops"]
        t_mem = nbytes / peak["hbm_bytes_per_s"]
        binds["compute" if t_ops >= t_mem else "memory"] += 1
        need += max(t_ops, t_mem) * (0.5 if backward else 1.0)
        spent += seconds
    if spent <= 0:
        return None
    harness.log(flash_roofline_bound_by=binds, kernel_seconds=spent,
                least_seconds=need)
    return 100.0 * need / spent
