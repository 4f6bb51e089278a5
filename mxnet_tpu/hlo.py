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
and answers both questions, so the dryrun and the tests can gate on
structure rather than on wall-clock luck:

- :func:`collective_summary` — ordered per-op classification of the
  entry computation;
- :func:`overlap_report` — async start/done pairs with compute between
  them, and the sync-collective interleaving measure (how many
  collective groups are separated by compute);
- :func:`collective_bytes` — bytes written by collective ops (the
  numerator of the in-program comm fraction the GoodputTracker books);
- :func:`shape_bytes` — size of one HLO shape literal;
- :func:`scope_table` — every executed instruction with the name the
  program gave it (``jax.named_scope``), what it is made of and a
  class: what a device trace's ``_fusion.229`` is looked up in.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

__all__ = ["collective_summary", "overlap_report", "collective_bytes",
           "shape_bytes", "COLLECTIVE_OPS", "scope_table", "ScopeTable",
           "RELAYOUT_OPS"]

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


# ---------------------------------------------------------------------
# instruction -> the scope the program gave it
# ---------------------------------------------------------------------

# opcodes that compute nothing: an instruction (or a fusion) made of
# these alone moves or re-types data
RELAYOUT_OPS = frozenset((
    "copy", "copy-start", "copy-done", "transpose", "reshape", "bitcast",
    "convert", "broadcast", "slice", "slice-start", "slice-done",
    "dynamic-slice", "concatenate", "pad", "tuple", "get-tuple-element",
    "parameter", "constant", "iota"))

_COLLECTIVES = frozenset(
    op + half for op in COLLECTIVE_OPS for half in ("", "-start", "-done"))
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s*([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")
_LAYER_NUMBER = re.compile(r"\d+")
_COMMENT = re.compile(r"/\*.*?\*/")    # a long tuple's /*index=5*/
# an instruction that RUNS the computations it names (a fusion's body
# is what the fusion is made of; a reduce's ``to_apply`` is a lambda)
_RUNS = {"while": ("body", "condition"), "call": ("to_apply",),
         "conditional": ("true_computation", "false_computation",
                         "branch"),
         "async-start": ("calls",)}


class ScopeTable(dict):
    """``{instruction: record}`` of one program, with the program's
    own name (the ``HloModule`` line's: what a trace's ``XLA Modules``
    event carries before its fingerprint), the size of the text it was
    read from and, where a caller timed it, the seconds that took."""

    program = ""
    text_bytes = 0
    seconds = 0.0


def _split_path(path: str) -> List[str]:
    """``a/f(b/c)/d`` -> [a, f(b/c), d]: the slashes outside brackets."""
    parts, depth, at = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(0, depth - 1)
        elif ch == "/" and depth == 0:
            parts.append(path[at:i])
            at = i + 1
    parts.append(path[at:])
    return parts


def _components(path: str) -> List[str]:
    """The names of an ``op_name`` path, a transform's brackets taken
    off (``transpose(jvp(layer3_ff1))`` is ``layer3_ff1``'s backward)
    and every ``jit(<function>)`` left out."""
    out: List[str] = []
    for part in _split_path(path):
        m = _WRAPPED.match(part)
        if m is None:
            if part:
                out.append(part)
        elif m.group(1) not in ("jit", "pjit"):
            out += _components(m.group(2))
    return out


def _scope_of(op_name: str) -> str:
    """The path between ``jit(<program>)/`` and the primitive; ``""``
    for a name jax gave no program (a parameter's, a reducer's)."""
    if not op_name.startswith(("jit(", "pjit(")):
        return ""
    return "/".join(_components(op_name)[:-1])


def _group_of(scope: str) -> str:
    """``layer3_ff1`` -> ``layer*_ff1``: the path's first number — the
    layer's — is what a table is printed without (``ff1`` and
    ``layer3_mix/mamba2_step`` keep theirs)."""
    return _LAYER_NUMBER.sub("*", scope, count=1)


def _after_shape(rest: str) -> str:
    """What follows an instruction's shape (a tuple's may hold blanks)."""
    if not rest.startswith("("):
        return rest.partition(" ")[2]
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[i + 1:]
    return ""


def _computations(hlo_text: str):
    """(entry's name, {computation: [(instruction, opcode, scope,
    {attribute: [computations]}, is a Mosaic kernel)]})."""
    entry, comps, rows = None, {}, None
    for line in hlo_text.splitlines():
        if rows is None:
            m = _COMPUTATION.match(line)
            if m and " = " not in line.split("(", 1)[0]:
                rows = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            rows = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        op = _OPCODE.match(_after_shape(
            _COMMENT.sub("", rest) if rest.startswith("(") else rest))
        if op is None:
            continue
        called: Dict[str, List[str]] = {}
        if "=%" in rest or "={%" in rest:
            for attr, comp in _CALLED.findall(rest):
                called.setdefault(attr, []).append(comp)
            b = _BRANCHES.search(rest)
            if b:
                called["branch"] = [c.strip().lstrip("%")
                                    for c in b.group(1).split(",")]
        meta = _OP_NAME.search(rest)
        rows.append((name, op.group(1),
                     _scope_of(meta.group(1)) if meta else "", called,
                     _KERNEL_TARGET in rest))
    return entry, comps


def _klass(opcodes, kernel: bool) -> str:
    if kernel:
        return "kernel"
    if not _COLLECTIVES.isdisjoint(opcodes):
        return "collective"
    if "dot" in opcodes or "convolution" in opcodes:
        return "matmul"
    if RELAYOUT_OPS.issuperset(opcodes):
        return "relayout"
    return "other"


def scope_table(hlo_text: str) -> ScopeTable:
    """``{instruction: record}`` for every instruction a compiled
    program RUNS: the ENTRY computation's and, through them, those of
    the bodies of ``while`` / ``call`` / ``conditional`` (a scanned
    stack is not one opaque row).  The key is the instruction's name
    without its ``%`` — what a device trace's ``XLA Ops`` event is
    called; the record:

    - ``scope``: the ``op_name`` path the program's ``named_scope``s
      wrote, ``jit(<program>)/`` and the primitive taken off, a
      transform's brackets too (``layer3_ff1``,
      ``optimizer_update/layer3_ff1_weight``); where the instruction
      itself has none, the commonest scope inside its fusion; ``""``
      where XLA left no name at all (layout copies);
    - ``group``: ``scope`` without its first number, the layer's
      (``layer*_ff1``, ``optimizer_update/layer*_ff1_weight``);
    - ``opcodes``: a fusion's — the sorted opcodes of the computation
      it ``calls=`` — else the instruction's own;
    - ``scopes``: a fusion's every distinct scope inside it, sorted (a
      matmul fused with its Adam update has two), else ``[scope]``;
    - ``klass``, first match wins: ``kernel`` (a ``tpu_custom_call``),
      ``collective`` (:data:`COLLECTIVE_OPS`, ``-start`` / ``-done``
      too), ``matmul`` (a ``dot`` or ``convolution`` among the
      opcodes), ``relayout`` (nothing but :data:`RELAYOUT_OPS`: it
      computes nothing), ``other``;
    - ``optimizer``: some scope starts with ``optimizer_update``.

    Pure text in, dict out: nothing here touches jax."""
    entry, comps = _computations(hlo_text)
    table = ScopeTable()
    head = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    table.program = head.group(1) if head else ""
    table.text_bytes = len(hlo_text)

    def made_of(comp, seen):
        """(opcodes, {scope: count}) of a fusion's body, nested
        fusions' included."""
        ops, scopes = set(), {}
        for _, op, scope, called, _ in comps.get(comp, ()):
            ops.add(op)
            if scope:
                scopes[scope] = scopes.get(scope, 0) + 1
            for inner in called.get("calls", ()):
                if inner not in seen:
                    seen.add(inner)
                    more, inner_scopes = made_of(inner, seen)
                    ops |= more
                    for k, n in inner_scopes.items():
                        scopes[k] = scopes.get(k, 0) + n
        return ops, scopes

    todo, seen = [entry] if entry else [], {entry}
    while todo:
        for name, op, scope, called, kernel in comps.get(todo.pop(), ()):
            opcodes, inside = {op}, {scope: 1} if scope else {}
            if op == "fusion":
                for comp in called.get("calls", ()):
                    opcodes, inside = made_of(comp, {comp})
                if not scope and inside:
                    scope = max(sorted(inside), key=inside.get)
                if scope:
                    inside.setdefault(scope, 1)
            for attr in _RUNS.get(op, ()):
                for comp in called.get(attr, ()):
                    if comp not in seen:
                        seen.add(comp)
                        todo.append(comp)
            scopes = sorted(inside)
            table[name] = {
                "scope": scope, "group": _group_of(scope),
                "opcodes": sorted(opcodes), "scopes": scopes,
                "klass": _klass(opcodes, kernel),
                "optimizer": any(s.startswith("optimizer_update")
                                 for s in scopes)}
    return table
