"""The share of a program's device time that the operations of some
named GROUPS take: the booking and the tables of ``scope_time`` (whole
``XLA Modules`` executions of the first chip, an operation at its self
time, named by ``mxnet_tpu.profiler.program_scopes()``), summed over the
records whose ``group`` — the scope without the layer's number,
``layer*_q_up``, ``layer*_attn/...`` — ends, at its first path
component, in one of ``suffixes``.

``read`` = 100 x (device time of those operations) / (device time of
the whole executions of the programs whose name holds ``program``).
The default ``suffixes`` are the nodes of a latent-attention mixer
(``models/hybrid_lm.py _mla``): the query's and the latent's down- and
up-projections, the absorbed forms of the up-projection, the attention
itself (kernels, rotation, the cache write) and the output projection.
``None`` without a trace, without tables, without a whole execution, or
where no operation of the program carries such a name (a program of
another family)."""

from benchmark.reducers import scope_time

MLA_NODES = ("_attn", "_q_down", "_q_norm", "_q_up", "_kv_down", "_kv_norm",
             "_kv_up", "_absorb_k", "_absorb_v", "_o")


def read(sources, program, suffixes=MLA_NODES):
    trace = sources.get("trace")
    if trace is None or not trace.device_planes():
        return None
    rows, tables = scope_time.booked(trace)
    if not rows or not tables:
        return None
    total = spent = 0.0
    for name, row in rows.items():
        table = tables.get(name)
        if program not in name or table is None:
            continue
        total += row["seconds"]
        for key, t in row["ops"].items():
            rec = table.get(key)
            node = ((rec or {}).get("group") or "").split("/", 1)[0]
            if node.startswith("layer*") and node.endswith(tuple(suffixes)):
                spent += t
    if total <= 0 or spent <= 0:
        return None
    return 100.0 * spent / total
