"""Mesh parallelism — the TPU-native multi-device layer.

This module replaces the reference's entire multi-device machinery with
one idea: a ``jax.sharding.Mesh`` + ``NamedSharding`` annotations on
the arrays of the ONE fused training program, letting XLA insert the
collectives the reference performed by hand:

reference capability                         → here
-------------------------------------------------------------------
DataParallelExecutorGroup.decide_slices        batch dim sharded over
  (python/mxnet/module/executor_group.py:195)  the 'dp' mesh axis
KVStoreLocal/CommDevice gradient reduce        psum over 'dp' inserted
  (src/kvstore/comm.h:200-360)                 by XLA from the vjp of
                                               the broadcast params
ctx_group / group2ctx model parallelism        per-parameter
  (src/executor/graph_executor.cc:301)         PartitionSpec from the
                                               partition-rules table
ps-lite multi-host (src/kvstore/kvstore_dist.h) jax.distributed runtime
                                               + DCN collectives

Sharding is declarative (T5X-style): parameters and activations carry
**logical axis names** (``('vocab', 'embed')``, ``('batch', 'length',
'embed')``) and ONE ordered regex-rules table — :class:`PartitionRules`
— maps logical names to mesh axes.  First match wins, scalars stay
unpartitioned, a logical axis no rule matches raises loudly.  Every
placement the framework computes (``param_sharding`` /
``input_sharding`` / ``opt_state_sharding`` / pipeline activation
constraints) resolves through this single table, so data (dp), tensor
(tp), pipeline (pp) and ZeRO shardings compose instead of being wired
per op.

The legacy ``__shard__`` attribute (``"axis:dim"``, e.g. ``"tp:0"``) is
kept as a DEPRECATION SHIM: each attr synthesizes a single-parameter
rule prepended to the table, so old annotations shard identically while
resolving through the same path.  Inputs default to the ``batch``
logical axis over 'dp'.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .base import MXNetError, get_env
from .context import Context

__all__ = ["MeshPlan", "make_plan", "shard_attr", "annotate_shard",
           "logical_axes", "annotate_logical", "parse_logical",
           "PartitionRules", "DEFAULT_RULES"]

MESH_AXES = ("dp", "pp", "tp")

# The plan a graph is being TRACED for.  Module sets it around the
# graph function inside its fused step; an op whose TPU lowering is a
# Mosaic kernel reads it, because such a kernel cannot be partitioned
# by the compiler ("Mosaic kernels cannot be automatically
# partitioned") and has to say, with a shard_map over this plan's
# mesh, which slice each device runs.
_TRACED = threading.local()


@contextlib.contextmanager
def tracing_for(plan):
    prev = getattr(_TRACED, "plan", None)
    _TRACED.plan = plan
    try:
        yield
    finally:
        _TRACED.plan = prev


def traced_plan():
    """The MeshPlan the current trace is for, or None (one device, or
    not inside a Module step)."""
    return getattr(_TRACED, "plan", None)


def shard_attr(axis: str, dim: int = 0) -> Dict[str, str]:
    """DEPRECATED attr dict marking a Variable for tensor-parallel
    sharding: ``mx.sym.Variable('w', attr=parallel.shard_attr('tp', 0))``.

    Prefer logical axis names + a rules table (``logical_axes`` +
    ``MeshPlan(rules=...)``).  Kept as a shim: the attr synthesizes a
    single-param rule at plan-application time, so old annotations
    shard identically through the same resolution point."""
    return {"__shard__": f"{axis}:{dim}"}


def annotate_shard(symbol, arg_name: str, axis: str, dim: int = 0):
    """Mark an existing argument of a built symbol for sharding (the
    post-hoc form of ``shard_attr`` for model-zoo graphs; same
    deprecation shim — prefer ``annotate_logical``)."""
    for n in symbol._topo():
        if n.is_variable and n.name == arg_name:
            n._meta["__shard__"] = f"{axis}:{dim}"
            return symbol
    raise MXNetError(f"argument {arg_name!r} not found in symbol")


def annotate_logical(symbol, arg_name: str, *axes: Optional[str]):
    """Attach logical axis names to an existing argument of a built
    symbol (post-hoc form of ``logical_axes`` for model-zoo graphs)."""
    for n in symbol._topo():
        if n.is_variable and n.name == arg_name:
            n._meta.update(logical_axes(*axes))
            return symbol
    raise MXNetError(f"argument {arg_name!r} not found in symbol")


def logical_axes(*names: Optional[str]) -> Dict[str, str]:
    """Attr dict naming a Variable's logical axes, one entry per dim
    (``None``/``'-'`` = never partitioned)::

        mx.sym.Variable('tok_embed_weight',
                        attr=parallel.logical_axes('vocab', 'embed'))

    The names resolve to mesh axes through the plan's
    :class:`PartitionRules` table."""
    return {"__logical__": ",".join("-" if n is None else str(n)
                                    for n in names)}


def parse_logical(text: Optional[str]) -> Optional[Tuple[Optional[str], ...]]:
    """'vocab,embed' → ('vocab', 'embed'); '-' entries → None."""
    if text is None:
        return None
    out = []
    for tok in str(text).split(","):
        tok = tok.strip()
        out.append(None if tok in ("-", "", "None", "none") else tok)
    return tuple(out)


class PartitionRules:
    """Ordered (regex, mesh-axis) table mapping LOGICAL axis names to
    mesh axes — the fmengine ``match_partition_rules`` / T5X
    logical-axis-rules pattern.

    Resolution of one array: per dimension, take its logical axis name;
    a ``None`` name or a size-1/scalar dim is unpartitioned; otherwise
    the FIRST rule whose regex fully matches the name decides the mesh
    axis (``None`` axis = replicated on purpose).  A named axis that no
    rule matches raises loudly, naming the parameter — silent
    replication of something the model author named is how sharding
    bugs hide.
    """

    def __init__(self, rules: Sequence[Tuple[str, Optional[str]]]):
        self._entries: List[Tuple[str, "re.Pattern", Optional[str]]] = []
        for i, entry in enumerate(rules):
            try:
                pattern, axis = entry
            except (TypeError, ValueError):
                raise MXNetError(
                    f"partition rule #{i} must be a (regex, mesh_axis) "
                    f"pair, got {entry!r}")
            if axis is not None and not isinstance(axis, str):
                raise MXNetError(
                    f"partition rule #{i} ({pattern!r}): mesh axis must "
                    f"be a string or None, got {axis!r}")
            try:
                compiled = re.compile(str(pattern))
            except re.error as e:
                raise MXNetError(
                    f"partition rule #{i} has invalid regex "
                    f"{pattern!r}: {e}")
            self._entries.append((str(pattern), compiled, axis))

    def __len__(self):
        return len(self._entries)

    def __iter__(self):
        return ((p, a) for p, _c, a in self._entries)

    def __repr__(self):
        return "PartitionRules([%s])" % ", ".join(
            f"({p!r}, {a!r})" for p, _c, a in self._entries)

    @classmethod
    def parse(cls, text: str) -> "PartitionRules":
        """Parse the ``MXNET_PARTITION_RULES`` syntax: ``;``-separated
        ``regex:axis`` entries, axis ``-`` meaning replicated::

            batch:dp;vocab|heads|ffn|qkv:tp;layers:pp;embed|length:-

        Malformed entries raise at construction (the loud MXNET_CKPT_*
        validation pattern)."""
        entries = []
        for raw in str(text).split(";"):
            raw = raw.strip()
            if not raw:
                continue
            if ":" not in raw:
                raise MXNetError(
                    f"bad partition rule {raw!r}: want 'regex:axis' "
                    "(axis '-' = replicated), entries ';'-separated")
            pattern, _, axis = raw.rpartition(":")
            pattern, axis = pattern.strip(), axis.strip()
            if not pattern:
                raise MXNetError(f"bad partition rule {raw!r}: empty regex")
            entries.append(
                (pattern, None if axis in ("-", "None", "none") else axis))
        if not entries:
            raise MXNetError(
                f"MXNET_PARTITION_RULES {text!r} contains no rules")
        return cls(entries)

    def validate_axes(self, axis_names: Sequence[str]):
        for pattern, _c, axis in self._entries:
            if axis is not None and axis not in axis_names:
                raise MXNetError(
                    f"partition rule ({pattern!r}, {axis!r}) names an "
                    f"unknown mesh axis; this mesh has {tuple(axis_names)}")

    def prepended(self, rules: Sequence[Tuple[str, Optional[str]]]
                  ) -> "PartitionRules":
        """New table with ``rules`` in front (first match wins — the
        shard_attr shim's synthesized single-param rules go here)."""
        out = PartitionRules(rules)
        out._entries = out._entries + self._entries
        return out

    def _match(self, logical: str, default):
        for _p, compiled, axis in self._entries:
            if compiled.fullmatch(logical):
                return axis
        return default

    def axis_for(self, logical: str, param: str = "<array>") -> Optional[str]:
        """First-match-wins lookup of one logical axis name."""
        axis = self._match(logical, self)  # None is a real answer
        if axis is not self:
            return axis
        raise MXNetError(
            f"no partition rule matches logical axis {logical!r} of "
            f"{param!r}; add a rule (use axis '-'/None to replicate "
            f"explicitly).  Table: {self!r}")

    def axis_or_none(self, logical: str) -> Optional[str]:
        """:meth:`axis_for`, with a name no rule matches replicated —
        for activations an op shards on its own initiative, where a
        table that never mentions the name is no error."""
        return self._match(logical, None)

    def spec(self, axes: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None,
             param: str = "<array>") -> Tuple[Optional[str], ...]:
        """Resolve logical axes → a PartitionSpec-shaped tuple.

        Scalars and size-1 dims never partition; duplicate mesh axes
        across dims are rejected (an invalid PartitionSpec)."""
        if shape is not None and len(shape) != len(axes):
            raise MXNetError(
                f"{param!r}: {len(axes)} logical axes {tuple(axes)} for "
                f"a rank-{len(shape)} array {tuple(shape)}")
        out: List[Optional[str]] = []
        for i, name in enumerate(axes):
            if name is None or (shape is not None and shape[i] <= 1):
                out.append(None)
                continue
            out.append(self.axis_for(str(name), param))
        used = [a for a in out if a is not None]
        if len(used) != len(set(used)):
            raise MXNetError(
                f"{param!r}: logical axes {tuple(axes)} map two dims to "
                f"the same mesh axis ({out}); fix the rules table")
        return tuple(out)


# Framework-internal logical names, appended after every user table so
# user rules can override them (first match wins): the input batch dim
# and the ZeRO-1 flat optimizer-state shard axis.
_BUILTIN_TAIL = (("batch", "dp"), ("zero", "dp"))

# A ready-made table for the transformer-LM family (see
# models/transformer.py for the per-weight logical names).
DEFAULT_RULES = (
    ("batch", "dp"),
    ("layers", "pp"),
    ("vocab", "tp"),
    ("qkv", "tp"),
    ("heads", "tp"),
    ("ffn", "tp"),
    ("embed", None),
    ("length", None),
)


def _env_pos_int(name: str, default=None, minimum: int = 1) -> int:
    """Loud at-read validation for small integer env knobs: garbage
    ('banana'), negatives and zero all raise (MXNET_CKPT_* pattern).
    The default comes from the config catalog — the one place it is
    declared — unless the caller pins one explicitly."""
    raw = get_env(name, None, str)
    if raw is None:
        if default is not None:
            return default
        from . import config

        return config.describe(name).default
    try:
        val = int(raw)
    except (TypeError, ValueError):
        raise MXNetError(
            f"{name}={raw!r} is not an integer (want >= {minimum})")
    if val < minimum:
        raise MXNetError(f"{name}={val} must be >= {minimum}")
    return val


class MeshPlan:
    """A device mesh + the sharding rules for one Module's program.

    Axes: ``dp`` (data/ZeRO), ``pp`` (pipeline stages — see
    ``mxnet_tpu.pp``), ``tp`` (tensor).  ``rules`` is the
    :class:`PartitionRules` table every placement resolves through;
    ``microbatches`` is the pipeline's grad-accumulation depth (the
    global batch must tile dp × microbatches)."""

    def __init__(self, devices: Sequence, dp: Optional[int] = None, tp: int = 1,
                 pp: int = 1, batch_axis: int = 0,
                 group2ctx: Optional[Dict] = None,
                 rules: Optional[Union[PartitionRules, Sequence, str]] = None,
                 microbatches: Optional[int] = None):
        import jax
        from jax.sharding import Mesh

        n = len(devices)
        tp, pp = int(tp), int(pp)
        if tp < 1 or pp < 1:
            raise MXNetError(f"tp ({tp}) and pp ({pp}) must be >= 1")
        if dp is None:
            if n % (tp * pp) != 0:
                raise MXNetError(
                    f"{n} devices not divisible by tp={tp} x pp={pp}")
            dp = n // (tp * pp)
        if dp * tp * pp != n:
            raise MXNetError(
                f"dp({dp}) * pp({pp}) * tp({tp}) != devices({n})")
        self.dp = dp
        self.tp = tp
        self.pp = pp
        self.batch_axis = batch_axis
        self.devices = list(devices)
        # dp outermost (DCN-friendly), tp innermost (fastest ICI), pp
        # between: stage neighbors stay physically close while tp pairs
        # share the tightest links
        self.mesh = Mesh(np.asarray(self.devices).reshape(dp, pp, tp),
                         MESH_AXES)
        if microbatches is None:
            # pipeline default: 2 microbatches per stage keeps the 1F1B
            # bubble at (pp-1)/(2pp+pp-1) without exploding activation
            # stash memory; dp/tp-only plans don't micro-batch
            microbatches = 2 * pp if pp > 1 else 1
        microbatches = int(microbatches)
        if microbatches < 1:
            raise MXNetError(f"microbatches ({microbatches}) must be >= 1")
        self.microbatches = microbatches
        if rules is None:
            rules = ()
        if isinstance(rules, str):
            rules = PartitionRules.parse(rules)
        if not isinstance(rules, PartitionRules):
            rules = PartitionRules(rules)
        # built-ins go last: user rules win by first-match
        self.rules = PartitionRules(list(rules) + list(_BUILTIN_TAIL))
        self.rules.validate_axes(MESH_AXES)
        # ctx_group → placement: the reference's model-parallel layer
        # groups (AttrScope(ctx_group=g) + bind(group2ctx={g: ctx}),
        # graph_executor.cc:301) reinterpreted mesh-natively — each
        # group maps to an "axis:dim" sharding for its parameters
        # instead of a whole device, and XLA inserts the cross-shard
        # transfers the PlaceDevice pass inserted as _CrossDeviceCopy
        self.group2ctx: Dict[str, str] = dict(group2ctx or {})

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.pp

    @property
    def spans_processes(self) -> bool:
        """True when the mesh includes devices of other processes — the
        v5e-pod execution model: ONE jitted program over a global mesh,
        each process feeding its host-local batch shard (reference
        multi-node role: kvstore_dist.h:28-318, re-expressed as XLA
        collectives over ICI/DCN instead of ps-lite push/pull)."""
        import jax

        me = jax.process_index()
        return any(d.process_index != me for d in self.devices)

    @property
    def batch_scale(self) -> int:
        """Global batch = local batch × this (how many process-chunks
        tile the 'dp' axis; 1 on a single-process mesh)."""
        if not self.spans_processes:
            return 1
        import jax

        # every dp row (pp x tp devices) must live entirely on one
        # process: a row co-owned by two processes would have each stage
        # a *different* local batch as the same global chunk — silent
        # divergence.  (This also rejects tp/pp-across-hosts,
        # deliberately: model parallelism belongs on ICI within a host,
        # not DCN.)
        row_owner = {}
        row_size = self.tp * self.pp
        for i, d in enumerate(self.devices):
            row = i // row_size
            prev = row_owner.setdefault(row, d.process_index)
            if prev != d.process_index:
                raise MXNetError(
                    f"dp row {row} spans processes {prev} and "
                    f"{d.process_index}; a process-spanning mesh needs "
                    "each dp row on one host (keep tp/pp within a host)")
        me = jax.process_index()
        local_dp = {r for r, p in row_owner.items() if p == me}
        if not local_dp or self.dp % len(local_dp) != 0:
            raise MXNetError(
                f"process-spanning mesh needs every process to own whole "
                f"dp rows; dp={self.dp}, local rows={sorted(local_dp)}")
        return self.dp // len(local_dp)

    # -- shardings ------------------------------------------------------
    def _named(self, spec):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, spec)

    def replicated(self):
        from jax.sharding import PartitionSpec as P

        return self._named(P())

    def input_sharding(self, ndim: int, axes: Optional[Sequence] = None):
        """Input placement via the rules table.  Default logical axes:
        ``batch`` on the batch dim (rules map it to 'dp'), the rest
        unnamed/replicated."""
        from jax.sharding import PartitionSpec as P

        if axes is None:
            axes = [None] * ndim
            if ndim > 0:
                axes[self.batch_axis] = "batch"
        spec = self.rules.spec(axes, param="<input>")
        return self._named(P(*spec))

    def activation_spec(self, axes: Sequence[Optional[str]],
                        shape: Optional[Sequence[int]] = None,
                        param: str = "<activation>"):
        """PartitionSpec for an in-program activation constraint
        (``jax.lax.with_sharding_constraint``), resolved through the
        SAME table as parameters — the sequence-parallel 'length' axis
        and the pipeline carries use this."""
        from jax.sharding import PartitionSpec as P

        return P(*self.rules.spec(axes, shape=shape, param=param))

    def opt_state_sharding(self):
        """Layout of ZeRO-1 optimizer state: flat (1-D) arrays
        partitioned over the axis the rules table assigns the ``zero``
        logical axis ('dp' unless overridden), so each data-parallel
        rank stores and updates only its 1/dp slice of every
        Adam/momentum slot (Rajbhandari et al., 2020 stage 1).
        Params/grads are flattened and padded to ``zero_padded_size``
        before being pinned to this sharding — see
        Module._make_param_update."""
        from jax.sharding import PartitionSpec as P

        return self._named(P(*self.rules.spec(("zero",),
                                              param="<opt-state>")))

    def zero_padded_size(self, size: int) -> int:
        """Smallest dp-divisible length >= ``size`` — flat params are
        zero-padded to it so every 'dp' rank owns an equal shard."""
        return -(-int(size) // self.dp) * self.dp

    def zero_bucket_sharding(self):
        """Layout of one gradient-collective BUCKET in the ZeRO-1
        update segment: a (dp, columns) array whose row dim partitions
        over the ``zero`` axis ('dp' unless the rules remap it) and
        whose columns — the concatenation of every member param's
        per-rank shard — stay local.  Row r of the bucket is exactly
        the concatenation of rank r's per-param flat shards, so
        per-param column slices never cross shard boundaries: ONE
        reduce-scatter feeds the whole bucket and ONE all-gather
        returns it (MXNET_ZERO_BUCKET_BYTES; Module._make_param_update
        emits buckets in backward order)."""
        from jax.sharding import PartitionSpec as P

        ax = self.rules.spec(("zero",), param="<opt-state>")[0]
        return self._named(P(ax, None))

    def pp_opt_state_sharding(self):
        """ZeRO-1 state layout for a STAGE-RESIDENT slab: (S,
        per-stage-padded-flat) arrays with dim 0 over 'pp' and dim 1
        over the ``zero`` axis — each device stores and updates
        1/(pp*dp) of the slab's Adam/momentum slots."""
        from jax.sharding import PartitionSpec as P

        ax = self.rules.spec(("zero",), param="<opt-state>")[0]
        return self._named(P("pp", ax))

    def pp_param_sharding(self, spec: Sequence[Optional[str]]):
        """Stage-resident placement of one stacked block-parameter
        slab (S, L/S, ...): dim 0 over 'pp', the weight dims keeping
        their rules-table mesh axes (``spec`` is the per-layer param's
        resolved PartitionSpec tuple) — MXNET_PP_RESIDENT storage."""
        from jax.sharding import PartitionSpec as P

        if "pp" in tuple(spec):
            raise MXNetError(
                f"stacked block param already maps a weight dim to "
                f"'pp' ({tuple(spec)}); the slab's stage dim owns that "
                "axis")
        return self._named(P(*(("pp", None) + tuple(spec))))

    def _legacy_shard_axes(self, ndim: int, attr: str, name: str):
        """The ``__shard__`` deprecation shim: synthesize a single-param
        rule from an "axis:dim" attr and return logical axes that hit
        it — old annotations resolve through the SAME table."""
        try:
            axis, dim_s = attr.split(":")
            dim = int(dim_s)
        except ValueError:
            raise MXNetError(f"bad __shard__ attr {attr!r}; want 'axis:dim'")
        if axis not in MESH_AXES:
            raise MXNetError(f"unknown mesh axis {axis!r} in __shard__ attr")
        if dim >= ndim:
            raise MXNetError(f"__shard__ dim {dim} out of range for ndim {ndim}")
        logical = f"__shard__:{name}:{dim}"
        rules = self.rules.prepended([(re.escape(logical), axis)])
        axes = [None] * ndim
        axes[dim] = logical
        return rules, tuple(axes)

    def param_sharding(self, ndim: int, attr: Optional[str] = None,
                       axes: Optional[Sequence[Optional[str]]] = None,
                       shape: Optional[Sequence[int]] = None,
                       name: str = "<param>"):
        """Parameter placement: logical ``axes`` resolve through the
        rules table; a legacy ``__shard__`` ``attr`` resolves through a
        synthesized single-param rule (deprecation shim); neither means
        replicated."""
        from jax.sharding import PartitionSpec as P

        if axes is not None:
            if len(axes) != ndim:
                raise MXNetError(
                    f"parameter {name!r}: {len(axes)} logical axes "
                    f"{tuple(axes)} for a rank-{ndim} array")
            return self._named(
                P(*self.rules.spec(axes, shape=shape, param=name)))
        if not attr:
            return self.replicated()
        rules, axes = self._legacy_shard_axes(ndim, attr, name)
        return self._named(P(*rules.spec(axes, shape=shape, param=name)))

    # -- placement ------------------------------------------------------
    def place(self, value, sharding):
        """Place a host or device array onto the mesh placement.

        On a process-spanning mesh the sharding is not fully addressable
        and ``jax.device_put`` of a local array can't populate remote
        shards — build the global array from this process's addressable
        pieces instead (every process must hold the same full ``value``,
        the replicated-parameter invariant)."""
        import jax

        if getattr(sharding, "is_fully_addressable", True):
            return jax.device_put(value, sharding)
        host = np.asarray(value)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    def stage_input(self, value, ndim: Optional[int] = None):
        """Host-local batch → global mesh array: the process's chunk of
        the 'dp'-sharded global batch rides
        ``multihost_utils.host_local_array_to_global_array`` (the judge
        path for feeding a pod: each host stages only its own rows; no
        host ever materializes the global batch)."""
        from jax.experimental import multihost_utils

        host = np.asarray(value)
        nd = host.ndim if ndim is None else ndim
        sh = self.input_sharding(nd)
        if not self.spans_processes:
            import jax

            return jax.device_put(host, sh)
        return multihost_utils.host_local_array_to_global_array(
            host, self.mesh, sh.spec)

    def local_output(self, garr):
        """Global program output → this process's host-local slice (the
        inverse of ``stage_input``, for per-worker metrics/logging)."""
        from jax.experimental import multihost_utils

        if getattr(garr.sharding, "is_fully_addressable", True):
            return garr
        return multihost_utils.global_array_to_host_local_array(
            garr, self.mesh, garr.sharding.spec)

    def check_batch(self, batch_size: int):
        """``batch_size`` is the PER-PROCESS batch; the global batch
        (batch × batch_scale) must tile dp × microbatches — every
        microbatch must split evenly over the 'dp' axis."""
        global_batch = batch_size * self.batch_scale
        tile = self.dp * self.microbatches
        if global_batch % tile != 0:
            raise MXNetError(
                f"batch size {batch_size} (global {global_batch}) not "
                f"divisible by dp ({self.dp}) x microbatches "
                f"({self.microbatches}) = {tile}; grow the batch to a "
                f"multiple of {tile} or lower microbatches/dp")


def make_plan(contexts: Optional[Sequence[Context]] = None, tp: int = 1,
              pp: Optional[int] = None, batch_axis: int = 0,
              group2ctx: Optional[Dict] = None,
              rules: Optional[Union[PartitionRules, Sequence, str]] = None,
              microbatches: Optional[int] = None) -> MeshPlan:
    """Build a MeshPlan from Module contexts (or every visible device).

    With a context list, each context resolves to its jax device (the
    multi-GPU ``Module(context=[...])`` idiom); with none, all devices
    of the default accelerator platform form the mesh (``kvstore='tpu'``
    idiom).  Environment defaults (validated loudly at construction):
    ``MXNET_PP`` (pipeline degree), ``MXNET_MICROBATCHES``,
    ``MXNET_PARTITION_RULES`` (``regex:axis;...`` — see
    :meth:`PartitionRules.parse`)."""
    import jax

    if contexts:
        devices = [c.jax_device() for c in contexts]
        if len(set(devices)) != len(devices):
            raise MXNetError("duplicate devices in context list")
    else:
        devices = jax.devices()
    if pp is None:
        pp = _env_pos_int("MXNET_PP")
    if microbatches is None and get_env("MXNET_MICROBATCHES", None,
                                        str) is not None:
        microbatches = _env_pos_int("MXNET_MICROBATCHES", 1)
    if rules is None:
        env_rules = get_env("MXNET_PARTITION_RULES", None, str)
        if env_rules is not None:
            rules = PartitionRules.parse(env_rules)
    return MeshPlan(devices, tp=tp, pp=pp, batch_axis=batch_axis,
                    group2ctx=group2ctx, rules=rules,
                    microbatches=microbatches)
