"""Compiled-HLO inspection — structural proof of comm/compute overlap.

The fused training step's collectives only overlap compute if the
COMPILED program says so: on TPU/GPU the async-collective passes split
each collective into ``<op>-start`` / ``<op>-done`` pairs and the
latency-hiding scheduler moves real compute between them; on backends
that emit synchronous collectives (this sandbox's CPU build) the same
property shows up as per-bucket collectives *interleaved* with compute
in the scheduled instruction order instead of one monolithic clump at
the end of backward.

This module parses the scheduled HLO text (``is_scheduled=true``
modules, the form ``jitted.lower(...).compile().as_text()`` returns)
and answers both questions, so the bench tools, the dryrun and the
tests can gate on structure rather than on wall-clock luck:

- :func:`collective_summary` — ordered per-op classification of the
  entry computation;
- :func:`overlap_report` — async start/done pairs with compute between
  them, and the sync-collective interleaving measure (how many
  collective groups are separated by compute);
- :func:`collective_bytes` — bytes written by collective ops (the
  numerator of the in-program comm fraction the GoodputTracker books);
- :func:`shape_bytes` — size of one HLO shape literal.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

__all__ = ["collective_summary", "overlap_report", "collective_bytes",
           "shape_bytes", "COLLECTIVE_OPS"]

# synchronous collective op names (scheduled HLO, SPMD-partitioned)
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

# ops that represent real device compute in a scheduled module (fusions
# subsume elementwise chains; dot/convolution are the MXU work)
_COMPUTE_OPS = ("fusion", "dot", "convolution", "custom-call")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# one scheduled-HLO instruction: "%name = <shape> <op>(...)" — the
# shape may be a tuple for -start/-done/tuple-output ops
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[a-z0-9]+\[[^=]*?)\s+"
    r"([a-z][a-z0-9\-]*(?:-start|-done)?)\(")


def shape_bytes(shape_text: str) -> int:
    """Total bytes of every array literal in an HLO shape string
    (handles tuple shapes: sums the components)."""
    total = 0
    for dtype, dims in re.findall(r"([a-z0-9]+)\[([0-9,]*)\]",
                                  shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _entry_lines(hlo_text: str) -> List[str]:
    """Lines of the ENTRY computation only (in schedule order for an
    ``is_scheduled=true`` module)."""
    lines = hlo_text.splitlines()
    out: List[str] = []
    depth = 0
    in_entry = False
    for line in lines:
        if not in_entry and line.lstrip().startswith("ENTRY "):
            in_entry = True
        if in_entry:
            out.append(line)
            depth += line.count("{") - line.count("}")
            if depth <= 0 and len(out) > 1:
                break
    return out


def collective_summary(hlo_text: str) -> List[Tuple[str, str, int]]:
    """Ordered (op_kind, shape_text, line_index) classification of the
    entry computation's collective and compute instructions.

    ``op_kind`` is the HLO opcode (``all-gather``,
    ``all-gather-start``, ``fusion``, ...).  Only collective ops, their
    async start/done forms, and compute ops are returned — the rest of
    the schedule (copies, bitcasts, parameters) is noise for the
    overlap question."""
    rows: List[Tuple[str, str, int]] = []
    for i, line in enumerate(_entry_lines(hlo_text)):
        # long tuple shapes carry /*index=5*/ markers — the combined
        # gradient all-reduce is such a tuple
        m = _INSTR.match(re.sub(r"/\*.*?\*/", "", line))
        if not m:
            continue
        shape, op = m.group(1), m.group(2)
        base = re.sub(r"-(start|done)$", "", op)
        if base in COLLECTIVE_OPS or op in ("async-start", "async-done"):
            rows.append((op, shape, i))
        elif op in _COMPUTE_OPS:
            rows.append((op, shape, i))
    return rows


def overlap_report(hlo_text: str) -> Dict[str, object]:
    """Structural overlap evidence from one scheduled HLO module.

    Returns a dict with:

    - ``collectives``: {opcode: count} over the entry computation;
    - ``async_pairs``: number of ``*-start`` instructions whose
      matching ``*-done`` appears later with >= 1 compute op scheduled
      between them — the literal async-overlap proof on TPU/GPU
      toolchains;
    - ``interleaved_groups``: number of maximal runs of collective ops
      separated by at least one compute op, counting only collectives
      AFTER the first compute (so a leading all-gather of an input
      doesn't count as a group).  >= 2 means the collectives are
      distributed through the compute schedule instead of fused into
      one monolithic clump;
    - ``compute_between``: compute ops scheduled strictly between the
      first and last collective;
    - ``overlapped``: the verdict — async pairs exist, or the sync
      schedule interleaves >= 2 collective groups with compute between
      them.
    """
    rows = collective_summary(hlo_text)
    counts: Dict[str, int] = {}
    coll_idx: List[int] = []
    starts: List[Tuple[str, int]] = []
    async_pairs = 0
    for pos, (op, _shape, _line) in enumerate(rows):
        if op in _COMPUTE_OPS:
            continue
        counts[op] = counts.get(op, 0) + 1
        coll_idx.append(pos)
        if op.endswith("-start"):
            starts.append((op[:-6], pos))
        elif op.endswith("-done"):
            base = op[:-5]
            for j, (b, spos) in enumerate(starts):
                if b == base:
                    between = [r for r in rows[spos + 1:pos]
                               if r[0] in _COMPUTE_OPS]
                    if between:
                        async_pairs += 1
                    starts.pop(j)
                    break
    # interleaving measure on the (possibly sync) schedule
    first_compute = next((i for i, r in enumerate(rows)
                          if r[0] in _COMPUTE_OPS), None)
    groups = 0
    prev_was_coll = False
    compute_between = 0
    if coll_idx:
        lo, hi = coll_idx[0], coll_idx[-1]
        compute_between = sum(1 for r in rows[lo + 1:hi]
                              if r[0] in _COMPUTE_OPS)
    for pos, (op, _s, _l) in enumerate(rows):
        is_coll = op not in _COMPUTE_OPS
        if is_coll and first_compute is not None and pos > first_compute:
            if not prev_was_coll:
                groups += 1
        prev_was_coll = is_coll
    return {
        "collectives": counts,
        "async_pairs": async_pairs,
        "interleaved_groups": groups,
        "compute_between": compute_between,
        "overlapped": bool(async_pairs > 0
                           or (groups >= 2 and compute_between > 0)),
    }


def collective_bytes(hlo_text: str) -> int:
    """Bytes produced by collective instructions in the entry
    computation — the static numerator of the in-program communication
    fraction (``GoodputTracker.set_program_comm_fraction``).  Each
    collective's OUTPUT shape is counted once; start/done pairs count
    the start only (the done re-states the same transfer), and a
    start's tuple shape ``(operand..., result)`` counts only its LAST
    component — summing the whole tuple would double-count the
    operand buffers the async form carries along."""
    total = 0
    for op, shape, _line in collective_summary(hlo_text):
        if op in _COMPUTE_OPS or op.endswith("-done") \
                or op == "async-done":
            continue
        if op.endswith("-start") or op == "async-start":
            parts = re.findall(r"[a-z0-9]+\[[0-9,]*\]", shape)
            if parts:
                total += shape_bytes(parts[-1])
                continue
        total += shape_bytes(shape)
    return total
