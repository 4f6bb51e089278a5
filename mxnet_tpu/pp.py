"""Pipeline parallelism — the 'pp' mesh axis of the 3D (dp × tp × pp)
parallel training program.

Three pieces, all running INSIDE the one fused XLA program:

1. **Stage splitter** (:func:`split_blocks`): partitions a Symbol whose
   repeated trunk is annotated with ``__pp_block__`` attributes (see
   ``models/transformer.py``) into *pre* (embedding), L isomorphic
   *blocks*, and *post* (head + loss).  The cut contract is validated
   loudly: each block exchanges exactly ONE activation tensor with its
   neighbors (the residual stream), and all blocks are structurally
   identical — the GSPMD pipelining requirement.

2. **Schedule builder** (:func:`build_schedule`): a static (tick ×
   stage) timetable for GPipe or interleaved-1F1B microbatch order,
   produced by a greedy dependency-respecting simulation.  Both run in
   the optimal ``2·(M + S − 1)`` ticks; 1F1B (default) interleaves each
   stage's backward of microbatch *m* between forwards of *m+k*, the
   PipeDream-flush order that bounds in-flight activations.

3. **Pipelined step** (:func:`build_pipeline_fn`): per-layer block
   parameters are STACKED along a leading stage dim (each stage's
   contiguous layer slice), per-tick compute is ``vmap``-ed over the
   stage dim, and the activation/cotangent transfers between stages
   are rolls of the stage-stacked buffers — which XLA lowers to
   ``collective-permute`` (the SPMD spelling of ``ppermute``) when the
   stash is 'pp'-sharded (``MXNET_PP_CONSTRAIN=1`` pins it; see below
   for why that defaults off on this jaxlib) — inside a
   ``jax.lax.scan`` over schedule ticks.
   The backward wave is hand-driven: each stage re-materializes its
   block forward from the stashed stage input and applies the incoming
   cotangent through a local ``jax.vjp`` (recompute-in-backward, the
   standard pipeline memory trade).  Gradients accumulate across
   microbatches inside the scan, so ONE optimizer step (the existing
   ZeRO-1 reduce-scatter/update/all-gather over 'dp') consumes the
   summed gradient — numerics match a non-pipelined step up to fp
   reassociation of the microbatch sum.

Activation shardings resolve through the plan's
:class:`~mxnet_tpu.parallel.PartitionRules` table (boundary ops may
carry ``__logical__`` names, e.g. ``('batch', 'length', 'embed')``), so
sequence parallelism composes with the pipeline carries through the
same table as everything else.

Limits (all raise loudly): auxiliary-state ops (BatchNorm moving
stats) are not supported inside a pipelined program; the batch axis
must be dim 0; elastic re-mesh of a pp>1 plan is not implemented
(``Module.remesh``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .base import MXNetError

__all__ = ["build_schedule", "Schedule", "split_blocks", "PipelineGraph",
           "build_pipeline_fn", "build_resident_pipeline_fn",
           "bubble_fraction"]


# ---------------------------------------------------------------------------
# Schedule
# ---------------------------------------------------------------------------

def bubble_fraction(num_micro: int, num_stages: int) -> float:
    """Idle fraction of an optimally-packed flush schedule: each stage
    does 2·M unit works in 2·(M + S − 1) ticks."""
    m, s = int(num_micro), int(num_stages)
    return (s - 1) / (m + s - 1)


class Schedule:
    """Static pipeline timetable.

    ``fwd[t, s]`` / ``bwd[t, s]``: microbatch index stage ``s`` forwards
    / backwards at tick ``t``, or −1 (idle).  ``fwd_dst`` / ``bwd_src``
    are the per-tick routing vectors for the activation / cotangent
    rolls (who receives what this tick produced)."""

    def __init__(self, fwd: np.ndarray, bwd: np.ndarray, kind: str):
        self.fwd = fwd.astype(np.int32)
        self.bwd = bwd.astype(np.int32)
        self.kind = kind
        self.num_ticks, self.num_stages = fwd.shape
        # stage s+1 receives the microbatch stage s forwarded this tick
        self.fwd_dst = np.roll(self.fwd, 1, axis=1)
        self.fwd_dst[:, 0] = -1
        # stage s receives the cotangent stage s+1 backwarded this tick
        self.bwd_src = np.roll(self.bwd, -1, axis=1)
        self.bwd_src[:, -1] = -1

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of this table: each stage owns one op-slot per
        tick; a stage fills 2·M of the ``num_ticks`` slots, so a packed
        flush schedule measures (S−1)/(M+S−1)."""
        work = int((self.fwd >= 0).sum() + (self.bwd >= 0).sum())
        return 1.0 - work / float(self.num_ticks * self.num_stages)


def build_schedule(num_micro: int, num_stages: int,
                   kind: str = "1f1b") -> Schedule:
    """Greedy dependency-respecting simulation → static timetable.

    ``kind='1f1b'`` (default): interleaved PipeDream-flush — past its
    warmup each stage alternates B(m) with F(m+k), bounding in-flight
    forwards per stage at its warmup depth + 1.  ``kind='gpipe'``: all
    forwards, then all backwards.  Both finish in 2·(M + S − 1) ticks.
    """
    M, S = int(num_micro), int(num_stages)
    if M < 1 or S < 1:
        raise MXNetError(f"schedule needs microbatches >= 1 and stages "
                         f">= 1, got M={M} S={S}")
    if kind not in ("1f1b", "gpipe"):
        raise MXNetError(f"unknown pipeline schedule {kind!r}; "
                         "want '1f1b' or 'gpipe'")
    fwd_done = [[-1] * M for _ in range(S)]
    bwd_done = [[-1] * M for _ in range(S)]
    next_f, next_b = [0] * S, [0] * S
    fwd_rows, bwd_rows = [], []
    t = 0
    while any(next_b[s] < M for s in range(S)):
        fvec, bvec = [-1] * S, [-1] * S
        for s in range(S):
            m_b, m_f = next_b[s], next_f[s]
            can_b = (m_b < M and 0 <= fwd_done[s][m_b] < t
                     and (s == S - 1 or 0 <= bwd_done[s + 1][m_b] < t))
            can_f = (m_f < M
                     and (s == 0 or 0 <= fwd_done[s - 1][m_f] < t))
            if kind == "gpipe":
                prefer_b = can_b and next_f[s] >= M
            else:  # 1f1b: warmup of S-1-s forwards, then B-first
                prefer_b = can_b and (next_f[s] - next_b[s] > S - 1 - s
                                      or not can_f)
            if prefer_b:
                bvec[s] = m_b
                bwd_done[s][m_b] = t
                next_b[s] += 1
            elif can_f:
                fvec[s] = m_f
                fwd_done[s][m_f] = t
                next_f[s] += 1
            elif can_b:
                bvec[s] = m_b
                bwd_done[s][m_b] = t
                next_b[s] += 1
        fwd_rows.append(fvec)
        bwd_rows.append(bvec)
        t += 1
        if t > 4 * (M + S) + 8:
            raise MXNetError(
                f"pipeline schedule simulation did not converge "
                f"(M={M}, S={S}, kind={kind})")
    return Schedule(np.asarray(fwd_rows), np.asarray(bwd_rows), kind)


# ---------------------------------------------------------------------------
# Stage splitter
# ---------------------------------------------------------------------------

class PipelineGraph:
    """The splitter's result: pre / L isomorphic blocks / post node
    partitions of one Symbol, with the boundary refs and the block
    template's parameter slot order."""

    def __init__(self, symbol, pre_nodes, block_nodes, post_nodes,
                 boundary_in, block_params, pre_params, post_params,
                 boundary_axes):
        self.symbol = symbol
        self.pre_nodes = pre_nodes          # topo-ordered list
        self.block_nodes = block_nodes      # list of L topo-ordered lists
        self.post_nodes = post_nodes
        self.boundary_in = boundary_in      # (node, idx) entering block 0
        self.block_params = block_params    # (L, n_slots) param names
        self.pre_params = pre_params        # names consumed only pre
        self.post_params = post_params
        self.boundary_axes = boundary_axes  # logical axes or None

    @property
    def num_layers(self) -> int:
        return len(self.block_nodes)

    @property
    def num_slots(self) -> int:
        return len(self.block_params[0]) if self.block_params else 0


def _block_id(node) -> Optional[int]:
    raw = node._meta.get("__pp_block__", node.attrs.get("__pp_block__"))
    if raw is None:
        return None
    try:
        return int(raw)
    except (TypeError, ValueError):
        raise MXNetError(
            f"node {node.name!r}: __pp_block__ attr {raw!r} is not an "
            "integer block index")


def _node_signature(node, local_ref):
    """Structural identity of one op node for the isomorphism check:
    op name, parameter attrs, and the block-local wiring pattern."""
    attrs = {k: v for k, v in node.attrs.items() if k != "__pp_block__"}
    return (node.op, tuple(sorted(attrs.items())),
            tuple(local_ref(i, ix) for i, ix in node.inputs))


def split_blocks(symbol) -> PipelineGraph:
    """Partition ``symbol`` into pre / blocks / post along its
    ``__pp_block__`` annotations, validating the pipeline cut contract
    loudly (see module docstring)."""
    nodes = symbol._topo()
    blocks: Dict[int, List] = {}
    for n in nodes:
        if n.is_variable:
            continue
        b = _block_id(n)
        if b is not None:
            blocks.setdefault(b, []).append(n)
    if not blocks:
        raise MXNetError(
            "pipeline parallelism (pp > 1) needs __pp_block__ "
            "annotations on the repeated trunk of the symbol (see "
            "models/transformer.py); none found")
    L = max(blocks) + 1
    missing = [l for l in range(L) if l not in blocks]
    if missing:
        raise MXNetError(f"__pp_block__ indices must be contiguous from "
                         f"0; missing blocks {missing} of {L}")
    block_of: Dict[int, int] = {}
    for l, ns in blocks.items():
        for n in ns:
            block_of[id(n)] = l

    # variables belong to the block that exclusively consumes them
    var_consumers: Dict[int, set] = {}
    for n in nodes:
        if n.is_variable:
            continue
        tag = block_of.get(id(n), "outside")
        for (i, _ix) in n.inputs:
            if i.is_variable:
                var_consumers.setdefault(id(i), set()).add(tag)
    var_block: Dict[int, Optional[int]] = {}
    for n in nodes:
        if not n.is_variable:
            continue
        tags = var_consumers.get(id(n), set())
        if len(tags) == 1 and "outside" not in tags:
            var_block[id(n)] = next(iter(tags))
        elif any(t != "outside" for t in tags):
            used = sorted(t for t in tags if t != "outside")
            where = (f"shared across pipeline blocks {used}"
                     if "outside" not in tags else
                     f"consumed by pipeline block(s) {used} AND shared "
                     "with the pre/post regions")
            raise MXNetError(
                f"parameter {n.name!r} is {where}; cross-stage shared "
                "parameters are not supported under pp > 1")
        else:
            var_block[id(n)] = None

    # per-block boundary: exactly one non-param tensor enters from
    # outside, exactly one leaves
    def in_block(node, l):
        return block_of.get(id(node)) == l or var_block.get(id(node)) == l

    boundary_in: List[Tuple] = [None] * L
    boundary_out: List[Tuple] = [None] * L
    for l in range(L):
        externals = []
        for n in blocks[l]:
            for ref in n.inputs:
                src, _ix = ref
                if in_block(src, l):
                    continue
                if src.is_variable and var_block.get(id(src)) is None:
                    raise MXNetError(
                        f"pipeline block {l} reads non-block input "
                        f"{src.name!r}; a block may only consume its own "
                        "parameters and the previous block's activation")
                if ref not in externals:
                    externals.append(ref)
        if len(externals) != 1:
            raise MXNetError(
                f"pipeline block {l} must take exactly ONE external "
                f"activation (the residual stream); found "
                f"{[e[0].name for e in externals]}")
        boundary_in[l] = externals[0]
        outs = []
        block_set = {id(n) for n in blocks[l]}
        for n in nodes:
            if id(n) in block_set:
                continue
            for ref in n.inputs:
                if id(ref[0]) in block_set and ref not in outs:
                    outs.append(ref)
        for node_ref in symbol._outputs:
            if id(node_ref[0]) in block_set and node_ref not in outs:
                outs.append(node_ref)
        if len(outs) != 1:
            raise MXNetError(
                f"pipeline block {l} must produce exactly ONE external "
                f"activation; {len(outs)} found")
        boundary_out[l] = outs[0]
    for l in range(1, L):
        src, _ = boundary_in[l]
        if block_of.get(id(src)) != l - 1:
            raise MXNetError(
                f"pipeline block {l}'s input comes from "
                f"{src.name!r}, not from block {l - 1}; blocks must "
                "chain linearly")

    # pre = ancestors of block 0's boundary input; post = the rest
    pre_set = set()

    def mark_pre(node):
        if id(node) in pre_set or id(node) in block_of:
            return
        pre_set.add(id(node))
        for i, _ix in node.inputs:
            mark_pre(i)

    mark_pre(boundary_in[0][0])
    pre_nodes, post_nodes = [], []
    for n in nodes:
        if id(n) in block_of or var_block.get(id(n)) is not None:
            continue
        if id(n) in pre_set:
            pre_nodes.append(n)
        elif n.is_variable and id(n) not in var_consumers:
            pre_nodes.append(n)  # unused inputs (e.g. ignored labels)
        else:
            post_nodes.append(n)
    post_set = {id(n) for n in post_nodes}
    for l in range(L):
        for n in blocks[l]:
            for i, _ix in n.inputs:
                if id(i) in post_set:
                    raise MXNetError(
                        f"node {i.name!r} feeds pipeline block {l} but "
                        "depends on the pipeline output; the graph is "
                        "not a pre → blocks → post chain")
    last_set = {id(n) for n in blocks[L - 1]}
    for n in post_nodes:
        if n.is_variable:
            continue
        for i, _ix in n.inputs:
            if id(i) in pre_set and not i.is_variable:
                raise MXNetError(
                    f"post node {n.name!r} reads pre-pipeline value "
                    f"{i.name!r}; skip connections around the pipelined "
                    "trunk are not supported under pp > 1")
            if id(i) in block_of and id(i) not in last_set:
                raise MXNetError(
                    f"post node {n.name!r} reads block "
                    f"{block_of[id(i)]}'s internals; only the last "
                    "block's output may feed the head under pp > 1")

    # block isomorphism + parameter slot order
    def local_refs(block_list, l):
        index = {id(n): k for k, n in enumerate(block_list)}
        params = [n for n in nodes
                  if n.is_variable and var_block.get(id(n)) == l]
        pindex = {id(n): k for k, n in enumerate(params)}

        def ref(node, ix):
            if id(node) in index:
                return ("n", index[id(node)], ix)
            if id(node) in pindex:
                return ("p", pindex[id(node)], ix)
            return ("x",)  # the boundary input

        return ref, [n.name for n in params]

    ref0, slots0 = local_refs(blocks[0], 0)
    sig0 = [_node_signature(n, ref0) for n in blocks[0]]
    block_params = [slots0]
    for l in range(1, L):
        refl, slotsl = local_refs(blocks[l], l)
        sigl = [_node_signature(n, refl) for n in blocks[l]]
        if sigl != sig0 or len(slotsl) != len(slots0):
            raise MXNetError(
                f"pipeline block {l} is not structurally identical to "
                "block 0 (op sequence, attrs and wiring must match); "
                "pp requires a uniform repeated trunk")
        block_params.append(slotsl)

    # region parameters by CONSUMER, not residence: a variable read by
    # both regions (tied embeddings, shared biases) belongs to both —
    # each region's vjp contributes a gradient and the step sums them
    def region_params(region_nodes):
        names, seen = [], set()
        for n in region_nodes:
            if n.is_variable:
                continue
            for i, _ix in n.inputs:
                if i.is_variable and id(i) not in seen \
                        and var_block.get(id(i)) is None:
                    seen.add(id(i))
                    names.append(i.name)
        return names

    pre_params = region_params(pre_nodes)
    post_params = region_params(post_nodes)

    from .parallel import parse_logical

    bnode = boundary_in[0][0]
    boundary_axes = parse_logical(
        bnode._meta.get("__logical__", bnode.attrs.get("__logical__")))

    return PipelineGraph(symbol, pre_nodes, blocks_list(blocks, L),
                         post_nodes, boundary_in[0], block_params,
                         pre_params, post_params, boundary_axes)


def blocks_list(blocks: Dict[int, List], L: int) -> List[List]:
    return [blocks[l] for l in range(L)]


# ---------------------------------------------------------------------------
# Region executors (pre / block template / post)
# ---------------------------------------------------------------------------

def _run_nodes(node_list, vals, node_index, rng, is_train):
    """Replay a topo-ordered node subset the way
    ``executor.build_graph_fn`` does, reading/writing the shared
    ``vals`` dict keyed by (id(node), out_idx)."""
    import jax

    from .ops.registry import OpContext

    for n in node_list:
        if n.is_variable:
            continue
        op = n.opdef()
        inputs = [vals[(id(i), ix)] for i, ix in n.inputs]
        if n.aux_names():
            raise MXNetError(
                f"op {n.name!r} carries auxiliary state (moving "
                "averages); aux-state ops are not supported inside a "
                "pipelined (pp > 1) program")
        key = None
        if op.needs_rng:
            key = jax.random.fold_in(rng, node_index[id(n)])
        outs = op.compute(OpContext(is_train=is_train, rng=key),
                          n.attrs, inputs, [])
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        for i, o in enumerate(outs):
            vals[(id(n), i)] = o


def _region_fns(pg: PipelineGraph):
    """Build the three pure region functions from the split graph.

    RNG streams: pre/post fold the per-microbatch key by the node's
    position in the FULL symbol topo order (same convention as
    ``build_graph_fn``); the block template folds by the node's
    position within the block, offset by the layer index — every
    (microbatch, layer, node) triple draws a distinct key, and the
    backward recompute replays the identical stream."""
    nodes = pg.symbol._topo()
    node_index = {id(n): i for i, n in enumerate(nodes)}
    out_refs = [(id(n), i) for n, i in pg.symbol._outputs]
    b_node, b_idx = pg.boundary_in

    def pre_fn(args, micro_inputs, rng, is_train):
        vals = {}
        for n in pg.pre_nodes:
            if n.is_variable:
                v = micro_inputs.get(n.name, args.get(n.name))
                if v is not None:
                    vals[(id(n), 0)] = v
        _run_nodes(pg.pre_nodes, vals, node_index, rng, is_train)
        return vals[(id(b_node), b_idx)]

    # block template from block 0
    template = pg.block_nodes[0]
    t_index = {id(n): k for k, n in enumerate(template)}
    slot_of = {}
    for n in nodes:
        if n.is_variable and n.name in pg.block_params[0]:
            slot_of[id(n)] = pg.block_params[0].index(n.name)
    t_out_node, t_out_idx = None, None
    block_set = {id(n) for n in template}
    for n in nodes:
        if id(n) in block_set:
            continue
        for i, ix in n.inputs:
            if id(i) in block_set:
                t_out_node, t_out_idx = i, ix
    if t_out_node is None:  # single-block model: output feeds post only
        for n, i in pg.symbol._outputs:
            if id(n) in block_set:
                t_out_node, t_out_idx = n, i

    def block_fn(slots, x, rng, is_train):
        """One block: ``slots`` are the template's parameters in slot
        order, ``x`` the incoming residual stream."""
        import jax

        from .ops.registry import OpContext

        vals = {(id(b_node), b_idx): x}
        for n in template:
            for (i, ix) in n.inputs:
                if id(i) in slot_of:
                    vals[(id(i), 0)] = slots[slot_of[id(i)]]
        for k, n in enumerate(template):
            op = n.opdef()
            inputs = [vals[(id(i), ix)] for i, ix in n.inputs]
            if n.aux_names():
                raise MXNetError(
                    f"op {n.name!r} carries auxiliary state; not "
                    "supported inside a pipelined (pp > 1) program")
            key = jax.random.fold_in(rng, k) if op.needs_rng else None
            outs = op.compute(OpContext(is_train=is_train, rng=key),
                              n.attrs, inputs, [])
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            for i, o in enumerate(outs):
                vals[(id(n), i)] = o
        return vals[(id(t_out_node), t_out_idx)]

    last_out = None
    last_set = {id(n) for n in pg.block_nodes[-1]}
    for n in nodes:
        if id(n) in last_set:
            continue
        for i, ix in n.inputs:
            if id(i) in last_set:
                last_out = (i, ix)
    if last_out is None:
        for n, i in pg.symbol._outputs:
            if id(n) in last_set:
                last_out = (n, i)

    def post_fn(args, micro_inputs, h, rng, is_train):
        vals = {(id(last_out[0]), last_out[1]): h}
        # seed every variable the post ops READ — including variables
        # residing in the pre region (tied/shared parameters)
        for n in pg.post_nodes:
            if n.is_variable:
                continue
            for i, _ix in n.inputs:
                if i.is_variable and (id(i), 0) not in vals:
                    v = micro_inputs.get(i.name, args.get(i.name))
                    if v is not None:
                        vals[(id(i), 0)] = v
        _run_nodes(pg.post_nodes, vals, node_index, rng, is_train)
        return [vals[r] for r in out_refs]

    return pre_fn, block_fn, post_fn


# ---------------------------------------------------------------------------
# The pipelined forward+backward
# ---------------------------------------------------------------------------

def build_pipeline_fn(pg: PipelineGraph, plan, grad_names: Sequence[str],
                      param_specs: Dict[str, Any],
                      schedule_kind: str = "1f1b"):
    """Compile-time assembly of the pipelined fwd+bwd: returns
    ``f(args, inputs, rng) -> (outputs, grads)`` to be traced inside
    the module's fused step.

    ``args``: every parameter by name (trainable + fixed).  ``inputs``:
    the full-batch data/label arrays.  ``grads`` come back summed over
    microbatches for every name in ``grad_names``.  ``param_specs``
    maps param name → its resolved PartitionSpec (from the rules
    table), so the stacked per-stage views keep tensor shardings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    S = plan.pp
    M = plan.microbatches
    L = pg.num_layers
    if L % S != 0:
        raise MXNetError(
            f"{L} pipeline blocks do not divide into pp={S} stages; "
            "choose pp dividing the layer count")
    Ls = L // S
    if plan.batch_axis != 0:
        raise MXNetError("pipeline parallelism requires batch_axis=0")
    sched = build_schedule(M, S, schedule_kind)
    pre_fn, block_fn, post_fn = _region_fns(pg)
    grad_set = set(grad_names)
    pre_grads = [n for n in pg.pre_params if n in grad_set]
    post_grads = [n for n in pg.post_params if n in grad_set]
    wsc = jax.lax.with_sharding_constraint

    def check_param_spec(name0):
        # the pipeline owns the 'pp' axis for stage placement of the
        # stacked views; a weight dim mapped to 'pp' would collide
        spec = tuple(param_specs.get(name0) or ())
        if "pp" in spec:
            raise MXNetError(
                f"block parameter {name0!r} is sharded over 'pp' by the "
                "rules table; the pipeline already owns that axis for "
                "stage placement — map the logical axis elsewhere")

    def act_spec(ndim):
        """Sharding constraint spec of one (Bm, ...) microbatch
        activation, via the rules table (boundary __logical__ names, or
        batch-only)."""
        axes = pg.boundary_axes
        if axes is None or len(axes) != ndim:
            axes = ("batch",) + (None,) * (ndim - 1)
        return plan.activation_spec(axes, param="<pp-carry>")

    def fn(args, inputs, rng, is_train=True):
        # ---- microbatch the inputs (global batch, dim 0)
        micro = {}
        for k, v in inputs.items():
            B = v.shape[0]
            if B % M:
                raise MXNetError(
                    f"input {k!r} batch {B} not divisible by "
                    f"microbatches={M}")
            micro[k] = v.reshape((M, B // M) + tuple(v.shape[1:]))

        # ---- stacked per-stage block params: (L, ...) -> (S, Ls, ...)
        # NOT explicitly constrained to P('pp', ...): this jaxlib's SPMD
        # partitioner miscompiles a concatenate whose result is
        # constrained along the concatenated dim (values silently
        # corrupt — caught by the pp-vs-single-process equivalence
        # test).  Stage placement of the compute flows from the 'pp'-
        # sharded activation stash instead; the stacked weights follow
        # the partitioner's propagation.
        stacked = []
        for slot in range(pg.num_slots):
            check_param_spec(pg.block_params[0][slot])
            names = [pg.block_params[l][slot] for l in range(L)]
            w = jnp.stack([args[n] for n in names], axis=0)
            stacked.append(w.reshape((S, Ls) + tuple(w.shape[1:])))

        # per-(microbatch) keys; regions fold further by node position
        keys_m = jax.vmap(lambda m: jax.random.fold_in(rng, m))(
            jnp.arange(M))
        # per-(stage, layer, microbatch) block keys: salt by global
        # layer index so no (layer, node) pair collides across stages
        layer_ids = jnp.arange(L).reshape(S, Ls)

        def block_key(m_key, layer_id):
            return jax.random.fold_in(m_key, 1 + layer_id)

        # ---- pre (embedding...) over every microbatch up front
        def run_pre(mi, key):
            return pre_fn(args, mi, key, is_train)

        e = jax.vmap(run_pre)({k: v for k, v in micro.items()}, keys_m)
        carry_sharding = NamedSharding(
            plan.mesh, P(*(None,) + tuple(act_spec(e.ndim - 1))))
        e = wsc(e, carry_sharding)

        def stage_chain(ws, x, m_key, lids):
            for j in range(Ls):
                x = block_fn([w[j] for w in ws], x,
                             block_key(m_key, lids[j]), is_train)
            return x

        # ---- pipeline state
        # The (S, M, ...) activation stash is constrained to
        # P('pp', None, batch...) — the stage-resident placement — only
        # under MXNET_PP_CONSTRAIN=1: this jaxlib's SPMD partitioner
        # miscompiles the roll/one-hot updates of a 'pp'-sharded carry
        # at some shapes (silently wrong values; the equivalence tests
        # catch it).  Off (default here), XLA propagates its own
        # layout: numerics are exact everywhere, the batch dim still
        # shards over 'dp', and newer toolchains can pin the stage
        # placement back on.
        from . import config as _config
        from .base import get_env

        constrain = bool(get_env(
            "MXNET_PP_CONSTRAIN",
            _config.describe("MXNET_PP_CONSTRAIN").default, int))
        Bm_shape = tuple(e.shape[1:])
        stash_sh = NamedSharding(
            plan.mesh, P(*("pp", None) + tuple(act_spec(e.ndim - 1))))
        pin = (lambda a: wsc(a, stash_sh)) if constrain else (lambda a: a)
        stash = jnp.zeros((S, M) + Bm_shape, e.dtype)
        stash = pin(stash.at[0].set(e))
        cot = pin(jnp.zeros((S, M) + Bm_shape, e.dtype))
        h_stash = jnp.zeros((M,) + Bm_shape, e.dtype)
        de_stash = jnp.zeros((M,) + Bm_shape, e.dtype)
        g_stacked = [jnp.zeros_like(w) for w in stacked]
        g_post = {n: jnp.zeros_like(args[n]) for n in post_grads}

        # post outputs: probe one microbatch for shapes/dtypes
        probe = jax.eval_shape(
            lambda h, mi, k: post_fn(args, mi, h, k, is_train),
            jax.ShapeDtypeStruct(Bm_shape, e.dtype),
            {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
             for k, v in micro.items()},
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        for i, p in enumerate(probe):
            if len(p.shape) == 0:
                raise MXNetError(
                    f"pipeline execution requires batch-major outputs; "
                    f"output {i} of {pg.symbol.list_outputs()[i]!r} is a "
                    "scalar — keep per-example loss heads (e.g. "
                    "SoftmaxOutput/SoftmaxCELoss) under pp > 1")
        out_stash = [jnp.zeros((M,) + tuple(p.shape), p.dtype)
                     for p in probe]

        # (S, M, ...) stash access: gathers ride take_along_axis over
        # the UNSHARDED microbatch axis and scatters are one-hot
        # where-selects — never a dynamic scatter/gather crossing the
        # 'pp'-sharded stage dim, which this jaxlib's SPMD partitioner
        # miscompiles at some shapes (silent wrong values; caught by
        # the pp-vs-single-process equivalence tests)
        def gather_m(buf, idx):
            ix = idx.reshape((S,) + (1,) * (buf.ndim - 1))
            return jnp.take_along_axis(buf, ix, axis=1)[:, 0]

        def scatter_m(buf, idx, act, val):
            onehot = (jnp.arange(M)[None, :] == idx[:, None]) \
                & act[:, None]
            mask = onehot.reshape((S, M) + (1,) * (buf.ndim - 2))
            return jnp.where(mask, val[:, None], buf)

        def fwd_wave(state, fvec, fdst):
            stash, h_stash = state
            f_act = fvec >= 0
            f_idx = jnp.clip(fvec, 0, M - 1)
            x_in = gather_m(stash, f_idx)
            y = jax.vmap(stage_chain)(stacked, x_in, keys_m[f_idx],
                                      layer_ids)
            y = jnp.where(f_act.reshape((S,) + (1,) * (y.ndim - 1)),
                          y, jnp.zeros_like(y))
            mS = f_idx[S - 1]
            h_stash = h_stash.at[mS].set(
                jnp.where(f_act[S - 1], y[S - 1], h_stash[mS]))
            # stage s-1's output → stage s's stash slot: a roll of the
            # 'pp'-sharded dim == ppermute between stage shards
            y_shift = jnp.roll(y, 1, axis=0)
            stash = scatter_m(stash, jnp.clip(fdst, 0, M - 1), fdst >= 0,
                              y_shift)
            return pin(stash), h_stash

        def bwd_wave(state, bvec, bsrc):
            (stash, cot, h_stash, de_stash, out_stash, g_stacked,
             g_post) = state
            b_act = bvec >= 0
            b_idx = jnp.clip(bvec, 0, M - 1)
            # the exit stage's cotangent comes from the post (head +
            # loss) vjp of its scheduled microbatch, seeded with the
            # loss-head ones convention (custom VJPs ignore the head).
            # The head is often the heaviest single op (vocab
            # projection), so the vjp runs under lax.cond — only the M
            # ticks with an active exit-stage backward pay for it
            mB = b_idx[S - 1]
            mi_B = {k: v[mB] for k, v in micro.items()}
            lact = b_act[S - 1]

            def post_for(pp_, h):
                merged = dict(args)
                merged.update(pp_)
                return tuple(post_fn(merged, mi_B, h, keys_m[mB],
                                     is_train))

            p_post = {n: args[n] for n in post_grads}

            def run_post(h_in):
                outs_m, post_vjp = jax.vjp(post_for, p_post, h_in)
                heads = tuple(jnp.ones(o.shape, o.dtype)
                              for o in outs_m)
                dpost, dh = post_vjp(heads)
                return tuple(outs_m), dpost, dh.astype(h_in.dtype)

            def skip_post(h_in):
                return (tuple(jnp.zeros(p.shape, p.dtype)
                              for p in probe),
                        {n: jnp.zeros_like(args[n]) for n in post_grads},
                        jnp.zeros_like(h_in))

            outs_m, dpost, dh = jax.lax.cond(lact, run_post, skip_post,
                                             h_stash[mB])
            out_stash = [os.at[mB].set(jnp.where(lact, om, os[mB]))
                         for os, om in zip(out_stash, outs_m)]
            g_post = {n: g + jnp.where(lact, dpost[n],
                                       jnp.zeros_like(g))
                      for n, g in g_post.items()}
            cot_in = gather_m(cot, b_idx)
            cot_in = cot_in.at[S - 1].set(dh.astype(cot_in.dtype))
            x_b = gather_m(stash, b_idx)

            def stage_bwd(ws, xi, ci, m_key, lids):
                # recompute-in-backward: re-materialize this stage's
                # forward from the stashed input, vjp with the incoming
                # cotangent (identical RNG stream as the forward wave)
                _y, vjp = jax.vjp(
                    lambda w, x: stage_chain(w, x, m_key, lids), ws, xi)
                dws, dx = vjp(ci)
                return dws, dx

            dws, dx = jax.vmap(stage_bwd)(stacked, x_b, cot_in,
                                          keys_m[b_idx], layer_ids)
            g_stacked = [
                g + jnp.where(b_act.reshape((S,) + (1,) * (g.ndim - 1)),
                              dw, jnp.zeros_like(g))
                for g, dw in zip(g_stacked, dws)]
            dx = jnp.where(b_act.reshape((S,) + (1,) * (dx.ndim - 1)),
                           dx, jnp.zeros_like(dx))
            m0 = b_idx[0]
            de_stash = de_stash.at[m0].set(
                jnp.where(b_act[0], dx[0], de_stash[m0]))
            # stage s+1's input-cotangent → stage s: reverse ppermute
            dx_shift = jnp.roll(dx, -1, axis=0)
            cot = scatter_m(cot, jnp.clip(bsrc, 0, M - 1), bsrc >= 0,
                            dx_shift)
            return (stash, pin(cot), h_stash, de_stash,
                    out_stash, g_stacked, g_post)

        def tick(state, xs):
            fvec, bvec, fdst, bsrc = xs
            (stash, cot, h_stash, de_stash, out_stash, g_stacked,
             g_post) = state
            stash, h_stash = fwd_wave((stash, h_stash), fvec, fdst)
            state = bwd_wave((stash, cot, h_stash, de_stash, out_stash,
                              g_stacked, g_post), bvec, bsrc)
            return state, None

        xs = (jnp.asarray(sched.fwd), jnp.asarray(sched.bwd),
              jnp.asarray(sched.fwd_dst), jnp.asarray(sched.bwd_src))
        state0 = (stash, cot, h_stash, de_stash, out_stash, g_stacked,
                  g_post)
        state, _ = jax.lax.scan(tick, state0, xs)
        (_stash, _cot, _h, de_stash, out_stash, g_stacked,
         g_post) = state

        # ---- pre backward (all microbatches at once)
        def pre_for(pp_):
            merged = dict(args)
            merged.update(pp_)
            return jax.vmap(lambda mi, k: pre_fn(merged, mi, k, is_train)
                            )({k: v for k, v in micro.items()}, keys_m)

        p_pre = {n: args[n] for n in pre_grads}
        _e, pre_vjp = jax.vjp(pre_for, p_pre)
        (g_pre,) = pre_vjp(de_stash.astype(e.dtype))

        # ---- assemble grads by name; a parameter shared by the pre
        # and post regions (tied embedding) sums both contributions
        grads: Dict[str, Any] = {}
        for src in (g_pre, g_post):
            for n, g in src.items():
                grads[n] = grads[n] + g if n in grads else g
        for slot in range(pg.num_slots):
            flat = g_stacked[slot].reshape(
                (L,) + tuple(g_stacked[slot].shape[2:]))
            for l in range(L):
                name = pg.block_params[l][slot]
                if name in grad_set:
                    grads[name] = flat[l]

        # ---- outputs back to full-batch shape, preserving row order
        outputs = [os.reshape((os.shape[0] * os.shape[1],)
                              + tuple(os.shape[2:])) for os in out_stash]
        return outputs, grads

    fn.schedule = sched
    return fn


# ---------------------------------------------------------------------------
# Stage-resident pipelined forward+backward (MXNET_PP_RESIDENT)
# ---------------------------------------------------------------------------

def _manual_pp(mesh, in_specs, out_specs):
    """Full-manual shard_map over the whole mesh — the stage-axis data
    movement of the resident pipeline runs through these tiny bodies
    (ppermute / psum / per-stage take/select along the microbatch dim)
    so the SPMD partitioner NEVER handles a 'pp'-sharded carry update:
    the documented MXNET_PP_CONSTRAIN miscompile (roll/one-hot updates
    of a 'pp'-sharded scan carry) has no surface to fire on."""
    import jax

    def wrap(f):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    return wrap


def build_resident_pipeline_fn(pg: PipelineGraph, plan,
                               grad_names: Sequence[str],
                               param_specs: Dict[str, Any],
                               slab_shardings: Sequence[Any],
                               schedule_kind: str = "1f1b"):
    """The STAGE-RESIDENT pipelined fwd+bwd: block parameters arrive
    as per-slot slabs stacked (S, L/S, ...) and sharded
    ``P('pp', ...)`` — each pipeline stage's devices hold only their
    own layers' weights (~1/pp the bytes; the placement the
    partitioner bug forfeited).  Returns ``f(args, slabs, inputs,
    rng, is_train) -> (outputs, grads, slab_grads)`` where ``grads``
    covers the pre/post-region parameters and ``slab_grads`` are the
    per-slot gradient slabs, pinned to the slab sharding.

    Correctness strategy vs the documented jaxlib hazard: the stash
    and cotangent carries stay pinned to their stage-resident layout,
    but every operation that MOVES data across or indexes along the
    stage axis — the inter-stage activation roll, the microbatch-slot
    scatter/gather, the exit/entry-stage broadcast — is an explicit
    full-manual ``shard_map`` body (``ppermute``/``psum``/local
    selects), not a partitioned ``jnp.roll``/one-hot update.  The
    compute GSPMD sees is the vmapped stage chain over 'pp'-sharded
    operands plus elementwise masking — patterns it partitions
    trivially.  Equivalence vs the replicated path is pinned by
    tests/test_pp.py.

    Numerics are IDENTICAL to :func:`build_pipeline_fn` by
    construction: same schedule, same per-(microbatch, layer, node)
    RNG streams, same accumulation order."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    S = plan.pp
    M = plan.microbatches
    L = pg.num_layers
    if L % S != 0:
        raise MXNetError(
            f"{L} pipeline blocks do not divide into pp={S} stages; "
            "choose pp dividing the layer count")
    Ls = L // S
    if plan.batch_axis != 0:
        raise MXNetError("pipeline parallelism requires batch_axis=0")
    sched = build_schedule(M, S, schedule_kind)
    pre_fn, block_fn, post_fn = _region_fns(pg)
    grad_set = set(grad_names)
    pre_grads = [n for n in pg.pre_params if n in grad_set]
    post_grads = [n for n in pg.post_params if n in grad_set]
    wsc = jax.lax.with_sharding_constraint
    mesh = plan.mesh

    def act_spec(ndim):
        axes = pg.boundary_axes
        if axes is None or len(axes) != ndim:
            axes = ("batch",) + (None,) * (ndim - 1)
        return tuple(plan.activation_spec(axes, param="<pp-carry>"))

    def fn(args, slabs, inputs, rng, is_train=True):
        # ---- microbatch the inputs (global batch, dim 0)
        micro = {}
        for k, v in inputs.items():
            B = v.shape[0]
            if B % M:
                raise MXNetError(
                    f"input {k!r} batch {B} not divisible by "
                    f"microbatches={M}")
            micro[k] = v.reshape((M, B // M) + tuple(v.shape[1:]))

        keys_m = jax.vmap(lambda m: jax.random.fold_in(rng, m))(
            jnp.arange(M))
        layer_ids = jnp.arange(L).reshape(S, Ls)

        def block_key(m_key, layer_id):
            return jax.random.fold_in(m_key, 1 + layer_id)

        # ---- pre (embedding...) over every microbatch up front
        def run_pre(mi, key):
            return pre_fn(args, mi, key, is_train)

        e = jax.vmap(run_pre)({k: v for k, v in micro.items()}, keys_m)
        aspec = act_spec(e.ndim - 1)
        carry_sh = NamedSharding(mesh, P(*((None,) + aspec)))
        e = wsc(e, carry_sh)

        # stage-axis movement helpers (see _manual_pp): specs of the
        # (S, Bm, ...) wave, the (S, M, Bm, ...) stash, and (S,) vecs
        y_spec = P(*(("pp",) + aspec))
        stash_spec = P(*(("pp", None) + aspec))
        vec_spec = P("pp")
        y_sh = NamedSharding(mesh, y_spec)
        stash_sh = NamedSharding(mesh, stash_spec)

        def ring_shift(y, shift):
            """Stage s's wave row → stage s+shift (wraps; the wrapped
            entry is masked by the caller's scatter vector)."""
            perm = [(i, (i + shift) % S) for i in range(S)]
            body = _manual_pp(mesh, (y_spec,), y_spec)(
                lambda v: jax.lax.ppermute(v, "pp", perm))
            return body(y)

        def stage_bcast(y_masked):
            """(S, Bm, ...) wave with exactly one unmasked stage row →
            that row, replicated over 'pp' (a psum of zeros
            elsewhere)."""
            body = _manual_pp(mesh, (y_spec,), P(*aspec))(
                lambda v: jax.lax.psum(v[0], "pp"))
            return body(y_masked)

        def gather_m(buf, idx):
            """Per-stage pick along the microbatch dim: local
            take_along_axis on each stage's own (1, M, ...) shard."""
            def body(b, i):
                ix = i.reshape((b.shape[0],) + (1,) * (b.ndim - 1))
                return jnp.take_along_axis(b, ix, axis=1)[:, 0]

            return _manual_pp(mesh, (stash_spec, vec_spec),
                              y_spec)(body)(buf, idx)

        def scatter_m(buf, idx, act, val):
            """Per-stage masked write along the microbatch dim: a
            local where-select on each stage's shard."""
            def body(b, i, a, v):
                onehot = (jnp.arange(M)[None, :] == i[:, None]) \
                    & a[:, None]
                mask = onehot.reshape(b.shape[:2]
                                      + (1,) * (b.ndim - 2))
                return jnp.where(mask, v[:, None], b)

            return _manual_pp(
                mesh, (stash_spec, vec_spec, vec_spec, y_spec),
                stash_spec)(body)(buf, idx, act, val)

        def stage_chain(ws, x, m_key, lids):
            for j in range(Ls):
                x = block_fn([w[j] for w in ws], x,
                             block_key(m_key, lids[j]), is_train)
            return x

        # ---- pipeline state: stash[0] seeds from the pre output on
        # the entry stage via an elementwise stage-mask select (no
        # indexed update of the 'pp'-sharded dim)
        Bm_shape = tuple(e.shape[1:])
        first = (jnp.arange(S) == 0).reshape((S,) + (1,) * (e.ndim))
        last_y = (jnp.arange(S) == S - 1).reshape(
            (S,) + (1,) * (e.ndim - 1))
        stash = jnp.zeros((S, M) + Bm_shape, e.dtype)
        stash = wsc(jnp.where(first, e[None], stash), stash_sh)
        cot = wsc(jnp.zeros((S, M) + Bm_shape, e.dtype), stash_sh)
        h_stash = jnp.zeros((M,) + Bm_shape, e.dtype)
        de_stash = jnp.zeros((M,) + Bm_shape, e.dtype)
        g_slabs = [wsc(jnp.zeros_like(w), sh)
                   for w, sh in zip(slabs, slab_shardings)]
        g_post = {n: jnp.zeros_like(args[n]) for n in post_grads}

        probe = jax.eval_shape(
            lambda h, mi, k: post_fn(args, mi, h, k, is_train),
            jax.ShapeDtypeStruct(Bm_shape, e.dtype),
            {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
             for k, v in micro.items()},
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        for i, p in enumerate(probe):
            if len(p.shape) == 0:
                raise MXNetError(
                    f"pipeline execution requires batch-major outputs; "
                    f"output {i} of {pg.symbol.list_outputs()[i]!r} is a "
                    "scalar — keep per-example loss heads (e.g. "
                    "SoftmaxOutput/SoftmaxCELoss) under pp > 1")
        out_stash = [jnp.zeros((M,) + tuple(p.shape), p.dtype)
                     for p in probe]

        def fwd_wave(state, fvec, fdst):
            stash, h_stash = state
            f_act = fvec >= 0
            f_idx = jnp.clip(fvec, 0, M - 1)
            x_in = gather_m(stash, f_idx)
            y = jax.vmap(stage_chain)(slabs, x_in, keys_m[f_idx],
                                      layer_ids)
            y = jnp.where(f_act.reshape((S,) + (1,) * (y.ndim - 1)),
                          y, jnp.zeros_like(y))
            y = wsc(y, y_sh)
            # the exit stage's output must reach the (pp-replicated)
            # h_stash the post vjp reads: one explicit broadcast
            mS = f_idx[S - 1]
            h_val = stage_bcast(jnp.where(last_y, y,
                                          jnp.zeros_like(y)))
            h_stash = h_stash.at[mS].set(
                jnp.where(f_act[S - 1], h_val, h_stash[mS]))
            # stage s-1's output → stage s's stash slot: explicit
            # ppermute instead of a partitioned roll
            y_shift = ring_shift(y, 1)
            stash = scatter_m(stash, jnp.clip(fdst, 0, M - 1),
                              fdst >= 0, y_shift)
            return wsc(stash, stash_sh), h_stash

        def bwd_wave(state, bvec, bsrc):
            (stash, cot, h_stash, de_stash, out_stash, g_slabs,
             g_post) = state
            b_act = bvec >= 0
            b_idx = jnp.clip(bvec, 0, M - 1)
            mB = b_idx[S - 1]
            mi_B = {k: v[mB] for k, v in micro.items()}
            lact = b_act[S - 1]

            def post_for(pp_, h):
                merged = dict(args)
                merged.update(pp_)
                return tuple(post_fn(merged, mi_B, h, keys_m[mB],
                                     is_train))

            p_post = {n: args[n] for n in post_grads}

            def run_post(h_in):
                outs_m, post_vjp = jax.vjp(post_for, p_post, h_in)
                heads = tuple(jnp.ones(o.shape, o.dtype)
                              for o in outs_m)
                dpost, dh = post_vjp(heads)
                return tuple(outs_m), dpost, dh.astype(h_in.dtype)

            def skip_post(h_in):
                return (tuple(jnp.zeros(p.shape, p.dtype)
                              for p in probe),
                        {n: jnp.zeros_like(args[n])
                         for n in post_grads},
                        jnp.zeros_like(h_in))

            outs_m, dpost, dh = jax.lax.cond(lact, run_post, skip_post,
                                             h_stash[mB])
            out_stash = [os.at[mB].set(jnp.where(lact, om, os[mB]))
                         for os, om in zip(out_stash, outs_m)]
            g_post = {n: g + jnp.where(lact, dpost[n],
                                       jnp.zeros_like(g))
                      for n, g in g_post.items()}
            cot_in = gather_m(cot, b_idx)
            # the exit stage's incoming cotangent is the post vjp's dh
            # (pp-replicated): an elementwise stage-mask select
            cot_in = jnp.where(last_y, dh[None].astype(cot_in.dtype),
                               cot_in)
            cot_in = wsc(cot_in, y_sh)
            x_b = gather_m(stash, b_idx)

            def stage_bwd(ws, xi, ci, m_key, lids):
                _y, vjp = jax.vjp(
                    lambda w, x: stage_chain(w, x, m_key, lids), ws, xi)
                dws, dx = vjp(ci)
                return dws, dx

            dws, dx = jax.vmap(stage_bwd)(slabs, x_b, cot_in,
                                          keys_m[b_idx], layer_ids)
            g_slabs = [
                wsc(g + jnp.where(
                    b_act.reshape((S,) + (1,) * (g.ndim - 1)),
                    dw, jnp.zeros_like(g)), sh)
                for g, dw, sh in zip(g_slabs, dws, slab_shardings)]
            dx = jnp.where(b_act.reshape((S,) + (1,) * (dx.ndim - 1)),
                           dx, jnp.zeros_like(dx))
            dx = wsc(dx, y_sh)
            # the entry stage's input-cotangent feeds the (replicated)
            # de_stash the pre backward reads: explicit broadcast
            m0 = b_idx[0]
            first_y = (jnp.arange(S) == 0).reshape(
                (S,) + (1,) * (dx.ndim - 1))
            de_val = stage_bcast(jnp.where(first_y, dx,
                                           jnp.zeros_like(dx)))
            de_stash = de_stash.at[m0].set(
                jnp.where(b_act[0], de_val, de_stash[m0]))
            # stage s+1's input-cotangent → stage s: reverse ppermute
            dx_shift = ring_shift(dx, -1)
            cot = scatter_m(cot, jnp.clip(bsrc, 0, M - 1), bsrc >= 0,
                            dx_shift)
            return (stash, wsc(cot, stash_sh), h_stash, de_stash,
                    out_stash, g_slabs, g_post)

        def tick(state, xs):
            fvec, bvec, fdst, bsrc = xs
            (stash, cot, h_stash, de_stash, out_stash, g_slabs,
             g_post) = state
            stash, h_stash = fwd_wave((stash, h_stash), fvec, fdst)
            state = bwd_wave((stash, cot, h_stash, de_stash, out_stash,
                              g_slabs, g_post), bvec, bsrc)
            return state, None

        xs = (jnp.asarray(sched.fwd), jnp.asarray(sched.bwd),
              jnp.asarray(sched.fwd_dst), jnp.asarray(sched.bwd_src))
        state0 = (stash, cot, h_stash, de_stash, out_stash, g_slabs,
                  g_post)
        state, _ = jax.lax.scan(tick, state0, xs)
        (_stash, _cot, _h, de_stash, out_stash, g_slabs,
         g_post) = state

        # ---- pre backward (all microbatches at once)
        def pre_for(pp_):
            merged = dict(args)
            merged.update(pp_)
            return jax.vmap(lambda mi, k: pre_fn(merged, mi, k, is_train)
                            )({k: v for k, v in micro.items()}, keys_m)

        p_pre = {n: args[n] for n in pre_grads}
        _e, pre_vjp = jax.vjp(pre_for, p_pre)
        (g_pre,) = pre_vjp(de_stash.astype(e.dtype))

        grads: Dict[str, Any] = {}
        for src in (g_pre, g_post):
            for n, g in src.items():
                grads[n] = grads[n] + g if n in grads else g

        outputs = [os.reshape((os.shape[0] * os.shape[1],)
                              + tuple(os.shape[2:])) for os in out_stash]
        return outputs, grads, g_slabs

    fn.schedule = sched
    return fn
