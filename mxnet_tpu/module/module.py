"""Module — the primary training API.

Parity with ``python/mxnet/module/module.py``: bind/init_params/
init_optimizer/forward/backward/update/get_outputs/save_checkpoint.

TPU-first: one Module = one Executor = one XLA program per
(train/infer) phase — there is no per-device executor group.  Data
parallelism over multiple devices is expressed with a
``jax.sharding.Mesh`` + batch sharding on the same single program
(see ``mxnet_tpu.kvstore`` type 'tpu' and ``mxnet_tpu.parallel``);
XLA inserts the gradient all-reduce that the reference's
KVStoreLocal/CommDevice performed (SURVEY §2.4).
"""

from __future__ import annotations

import logging
import pickle
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import ndarray as nd
from .. import optimizer as opt
from .. import profiler as _prof
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..initializer import Uniform
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint, save_checkpoint)
from ..ndarray import NDArray
from .base_module import BaseModule, _as_list

__all__ = ["Module"]


class _PrologueCache:
    """Identity-keyed bounded LRU for per-prologue compiled programs.

    Weak keying cannot reclaim these: each cached program's closure
    strongly references the prologue fn that keys it, so a weak key
    would be kept alive by its own value forever.  A small LRU bounds
    the footprint instead — a job constructing iterators (and thus
    fresh prologue fns) without end evicts the oldest compiled program
    rather than leaking one per iterator; at worst a swap back to an
    evicted prologue re-traces."""

    _CAP = 4

    def __init__(self):
        from collections import OrderedDict
        self._d = OrderedDict()

    def get(self, key, default=None):
        d = self._d
        if key in d:
            d.move_to_end(key)
            return d[key]
        return default

    def put(self, key, value):
        d = self._d
        d[key] = value
        d.move_to_end(key)
        while len(d) > self._CAP:
            d.popitem(last=False)


def _buffer_ids(*trees):
    """Set of id()s of every jax.Array leaf in the given pytrees."""
    import jax

    out = set()
    for t in trees:
        for leaf in jax.tree_util.tree_leaves(t):
            if isinstance(leaf, jax.Array):
                out.add(id(leaf))
    return out


def _copy_donated_aliases(params, protected_ids):
    """Materialize a copy of any param leaf whose buffer is passed to the
    fused program more than once — as another donated param or as any
    non-donated argument (fixed/aux/input/state).

    Donating an aliased buffer either fails ("Attempt to donate the
    same buffer twice") or deletes a buffer another argument still
    reads.  Aliased param buffers are possible here (e.g. arg_params
    initialized from one array, or user ``_set_data`` sharing); after
    the copy the names train as independent parameters — same semantics
    as the reference, where distinct named params own distinct storage
    (tying is expressed by reusing one Variable in the symbol, not by
    aliasing two params' buffers).

    Only ``params`` is scanned per step: optimizer state trees are
    framework-allocated with distinct buffers (see init_state_arrays)
    and in steady state are fresh outputs of the previous donated call.
    """
    import jax
    import jax.numpy as jnp

    seen = set()

    def fix(x):
        if isinstance(x, jax.Array):
            if id(x) in seen or id(x) in protected_ids:
                return jnp.array(x, copy=True)
            seen.add(id(x))
        return x

    return jax.tree_util.tree_map(fix, params)


class Module(BaseModule):
    """reference: module.py Module"""

    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None):
        super().__init__(logger=logger)
        if context is None:
            context = current_context()
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None

        self._exec = None
        self._data_shapes = None
        self._label_shapes = None

        # fused-step state (one XLA program for fwd+bwd+update; the
        # BASELINE north-star "single HLO computation" path)
        import os as _os

        self._use_fused = _os.environ.get("MXNET_FUSED_STEP", "1") != "0"
        self._fused_step = None
        _prof.hold_programs(self)  # weakly: for fused_program_scopes()
        self._fused_warm = False  # first fused run = compile (telemetry)
        self._fused_state = None
        # ZeRO-1 (MXNET_ZERO): optimizer state sharded over the 'dp'
        # mesh axis; grads reduce-scattered, update on the local shard,
        # params all-gathered — all inside the one fused program.
        self._zero = False
        self._zero_meta = None  # {name: (flat_size, dp_padded_size)}
        # optimizer states loaded from a checkpoint before the fused
        # programs were built: host trees, placed at _ensure_fused_built
        self._pending_fused_states = None
        # checkpointed per-run PRNG base key, restored the same way
        self._pending_fused_key = None
        self._pending_batch = None
        self._step_count = 0
        self._flushed_backward = False
        # device-side input prologue (io_pool.make_device_prologue):
        # raw uint8 batches are augmented/normalized INSIDE the fused
        # step under the per-step PRNG key; installed by fit/score from
        # the data iterator's device_prologue
        self._input_prologue = None
        # bounded LRU (see _PrologueCache) so a job constructing eval
        # iterators forever cannot leak one compiled executable per
        # iterator's prologue fn
        self._prologue_host_cache = _PrologueCache()
        # jitted step per installed prologue (None = prologue-free):
        # score()'s per-epoch install/restore swap must not re-trace
        # the fused program every epoch
        self._fused_step_by_prologue = _PrologueCache()
        # mesh data/tensor parallelism (mxnet_tpu.parallel): activated by
        # a multi-context list at bind or kvstore='tpu' at init_optimizer
        self._mesh_plan = None
        # stage-resident pipeline weights (MXNET_PP_RESIDENT): when
        # active, block params live as per-slot (S, L/S, ...) slabs
        # sharded P('pp', ...) and the per-name executor arrays are
        # freed until _materialize_pp_params hands authority back
        self._pp_resident = False
        self._pp_graph = None
        self._pp_slabs = None
        self._pp_slab_zero_meta = None

    # ------------------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """reference: module.py:83 Module.load"""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """reference: module.py:121 save_checkpoint"""
        self._symbol.save(f"{prefix}-symbol.json")
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    # ------------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(name, tuple(arr.shape)) for name, arr in
                zip(self._output_names, self._exec.outputs_cache)] \
            if self._exec.outputs_cache else self._inferred_output_shapes

    def _drain_param_comm(self):
        """Complete any deferred kvstore pulls before parameters are
        consumed — the true dependency point the async gradient comm
        scheduler defers to (update() registered the pulls; the comm
        round-trips have been overlapping everything since)."""
        kv = self._kvstore
        if kv is not None and getattr(kv, "_pending_pulls", None):
            kv.drain_pulls()

    def get_params(self):
        """reference: module.py get_params"""
        assert self.binded and self.params_initialized
        self._drain_param_comm()
        self._materialize_pp_params()
        arg_params = {n: self._exec.arg_dict[n].copy() for n in self._param_names}
        aux_params = {n: self._exec.aux_dict[n].copy() for n in self._aux_names}
        return arg_params, aux_params

    def init_params(self, initializer=Uniform(0.01), arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        """reference: module.py init_params"""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        # land (and discard) any deferred kvstore pulls NOW: they target
        # these same executor arrays, and draining after this write
        # would overwrite the freshly loaded values with stale weights
        self._drain_param_comm()
        # writes go through arg_dict: stage-resident slabs must hand
        # authority back first (and rebuild from these values later)
        self._materialize_pp_params()

        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params is not None and name in arg_params:
                arr[:] = arg_params[name]
            elif self._arg_params is not None and name in self._arg_params:
                arr[:] = self._arg_params[name]
            elif allow_missing and initializer is None:
                raise MXNetError(f"cannot init parameter {name}")
            else:
                if initializer is None:
                    raise MXNetError(
                        f"parameter {name} missing and no initializer given")
                initializer(name, arr)
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params is not None and name in aux_params:
                arr[:] = aux_params[name]
            elif self._aux_params is not None and name in self._aux_params:
                arr[:] = self._aux_params[name]
            elif initializer is not None:
                initializer(name, arr)

        self.params_initialized = True
        self._params_dirty = False

    # ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """reference: module.py:272 bind"""
        if force_rebind:
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        assert not (not for_training and inputs_need_grad)

        # entries are DataDesc or (name, shape) tuples — both index the same
        self._data_shapes = [(d[0], tuple(d[1])) for d in data_shapes]
        self._label_shapes = ([(d[0], tuple(d[1])) for d in label_shapes]
                              if label_shapes else None)

        shape_kwargs = dict(self._data_shapes)
        if self._label_shapes:
            shape_kwargs.update(dict(self._label_shapes))
        # dtype flows from the data descriptors into the bound program
        # (fp16/bf16 training binds fp16 params — reference test_dtype.py);
        # infer_type propagates it into every homogeneous parameter
        type_dict = {}
        for descs in (data_shapes, label_shapes or []):
            for d in descs:
                dt = getattr(d, "dtype", None)
                if dt is not None:
                    type_dict[d[0]] = np.dtype(dt)

        req = {}
        for name in self._symbol.list_arguments():
            if name in self._data_names:
                req[name] = "write" if inputs_need_grad else "null"
            elif name in self._label_names:
                req[name] = "null"
            elif name in self._fixed_param_names or not for_training:
                req[name] = "null"
            else:
                req[name] = grad_req

        shared_exec = shared_module._exec if shared_module is not None else None
        self._exec = self._symbol.simple_bind(
            self._context[0], grad_req=req, type_dict=type_dict or None,
            shared_exec=shared_exec, **shape_kwargs)
        _, out_shapes, _ = self._symbol.infer_shape(**shape_kwargs)
        self._inferred_output_shapes = list(zip(self._output_names, out_shapes))
        self.binded = True

        # multi-context == one mesh program with the batch sharded over
        # 'dp' (replaces the reference's per-device executor group,
        # executor_group.py:195-219)
        if len(self._context) > 1 and self._mesh_plan is None:
            from ..parallel import make_plan

            self._mesh_plan = make_plan(self._context)
        if self._mesh_plan is not None:
            self._apply_mesh_plan()

        # restore cached params into the fresh executor (reference:
        # module.py bind copies _arg_params into the exec group)
        if self.params_initialized:
            if self._arg_params:
                self._exec.copy_params_from(self._arg_params, self._aux_params,
                                            allow_extra_params=True)

        if shared_module is not None and shared_module.params_initialized:
            # simple_bind already reused the donor's param NDArray objects
            # (one storage across bucketed executors).  Params must match
            # the donor exactly — a missing or shape-changed parameter
            # would silently train from zeros / diverge from the shared
            # storage, so fail loudly instead.
            donor = shared_module._exec
            for n in self._param_names:
                arr = self._exec.arg_dict[n]
                donor_arr = donor.arg_dict.get(n)
                if donor_arr is None:
                    raise MXNetError(
                        f"shared_module is missing parameter {n!r}; "
                        "parameters must be identical across shared modules")
                if arr is not donor_arr:
                    raise MXNetError(
                        f"parameter {n!r} ({arr.shape}/{arr.dtype}) does not "
                        f"match the shared module's ({donor_arr.shape}/"
                        f"{donor_arr.dtype}); bucket-specific parameter "
                        "shapes are not supported")
            for n in self._aux_names:
                arr = self._exec.aux_dict[n]
                donor_arr = donor.aux_dict.get(n)
                if donor_arr is not None and arr is not donor_arr \
                        and tuple(arr.shape) == tuple(donor_arr.shape):
                    arr[:] = donor_arr
            self.params_initialized = True

    def _apply_mesh_plan(self):
        """Pin every executor array to its mesh placement, resolved
        through the plan's ONE partition-rules table: inputs carry the
        'batch' logical axis (rules map it to 'dp'), params resolve
        their '__logical__' axis names, and the legacy paths — a
        '__shard__' symbol attr, an op-level '__shard__' hint, or the
        param's ctx_group via the plan's group2ctx mapping — each
        synthesize a single-param rule (deprecation shim) so old
        annotations shard identically through the same table."""
        from ..parallel import parse_logical

        plan = self._mesh_plan
        attrs = self._symbol.attr_dict()
        input_names = set(self._data_names) | set(self._label_names)
        # ctx_group resolution: a param uses its own group attr, else
        # the group of an op consuming it (AttrScope puts the attr on
        # the ops created inside the scope)
        groups = {}
        if plan.group2ctx:
            for n in self._symbol._topo():
                g = n._meta.get("ctx_group", n.attrs.get("ctx_group"))
                if not g:
                    continue
                if n.is_variable:
                    groups[n.name] = g
                else:
                    for (i, _ix) in n.inputs:
                        if i.is_variable:
                            groups.setdefault(i.name, g)
        # a '__shard__' attr on an OP (e.g. FullyConnected(...,
        # attr=shard_attr('tp', 0))) is a hint for the op's own
        # parameters — without this, only explicit Variable attrs
        # shard, and an op-level request silently replicates
        op_shards = {}
        for n in self._symbol._topo():
            s = n._meta.get("__shard__", n.attrs.get("__shard__"))
            if not s or n.is_variable:
                continue
            for (i, _ix) in n.inputs:
                if i.is_variable:
                    op_shards.setdefault(i.name, s)
        for name, shapes in (self._data_shapes or []):
            plan.check_batch(shapes[plan.batch_axis] if shapes else 0)
        spans = plan.spans_processes
        bcast = {}
        if spans:
            from jax.experimental import multihost_utils

            # ONE pytree broadcast for every local param/aux value —
            # per-array broadcasts would be hundreds of sequential
            # cross-host round-trips at bind time
            to_sync = {}
            for name, arr in list(self._exec.arg_dict.items()) + \
                    list(self._exec.aux_dict.items()):
                if name not in input_names and \
                        getattr(arr._data, "is_fully_addressable", True):
                    to_sync[name] = np.asarray(arr._data)
            if to_sync:
                bcast = multihost_utils.broadcast_one_to_all(to_sync)
        for name, arr in self._exec.arg_dict.items():
            if name in input_names:
                sh = plan.input_sharding(arr.ndim)
                if spans:
                    # process-spanning mesh: the jitted program sees the
                    # GLOBAL batch (local × batch_scale); allocate the
                    # executor's input buffer at global shape — each
                    # process's data iter keeps yielding local batches,
                    # staged in forward() via MeshPlan.stage_input
                    if getattr(arr._data, "is_fully_addressable", True):
                        arr._sharding = sh
                        arr._data = plan.stage_input(
                            np.zeros(tuple(arr.shape), arr.dtype), arr.ndim)
                    continue
            else:
                axes = parse_logical(attrs.get(name, {}).get("__logical__"))
                shard = attrs.get(name, {}).get("__shard__")
                if shard is None and name in op_shards:
                    # op-level hint is best-effort per param: a bias
                    # can't shard on the matrix dim — replicate it
                    shard = op_shards[name]
                    parts = str(shard).split(":")
                    if len(parts) == 2 and parts[1].isdigit() \
                            and int(parts[1]) >= arr.ndim:
                        shard = None
                if shard is None and name in groups:
                    shard = plan.group2ctx.get(groups[name])
                    if shard is not None:
                        parts = str(shard).split(":")
                        if len(parts) != 2 or not parts[1].isdigit():
                            raise MXNetError(
                                f"bad group2ctx placement {shard!r} for "
                                f"group {groups[name]!r}; want "
                                "'axis:dim' with a non-negative dim")
                        # group placement is best-effort per param: a
                        # bias can't shard on the matrix dim — replicate
                        if int(parts[1]) >= arr.ndim:
                            shard = None
                # logical axis names win; the __shard__ forms are the
                # deprecation shim (each synthesizes a single-param rule
                # inside param_sharding)
                sh = plan.param_sharding(arr.ndim, attr=shard, axes=axes,
                                         shape=tuple(arr.shape), name=name)
            arr._sharding = sh
            if spans:
                # unify the per-process initializations: rank 0's value
                # wins everywhere (the reference's first-init-wins,
                # kvstore_dist_server.h:150-163) BEFORE the replicated
                # global placement — divergent local inits would
                # otherwise silently violate the replication invariant
                if name in bcast:
                    arr._data = plan.place(np.asarray(bcast[name]), sh)
            else:
                arr._set_data(arr._data)  # re-place via the sharding pin
                if not arr._data.sharding.is_equivalent_to(sh, arr.ndim):
                    # _set_data leaves a value it cannot place where it
                    # was; one parameter left on one device surfaces
                    # later as an "incompatible devices" error that
                    # names neither the parameter nor the cause
                    raise MXNetError(
                        f"parameter {name!r} {tuple(arr.shape)} could "
                        f"not be placed as {sh.spec} on the "
                        f"{dict(plan.mesh.shape)} mesh — a sharded "
                        f"dimension must divide by its mesh axis")
            g = self._exec.grad_dict.get(name)
            if g is not None:
                g._sharding = sh
                if spans:
                    if getattr(g._data, "is_fully_addressable", True):
                        g._data = plan.place(np.asarray(g._data), sh)
                else:
                    g._set_data(g._data)
        for name, arr in self._exec.aux_dict.items():
            arr._sharding = plan.replicated()
            if spans:
                if name in bcast:
                    arr._data = plan.place(np.asarray(bcast[name]),
                                           arr._sharding)
            else:
                arr._set_data(arr._data)

    # ------------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        """reference: module.py:357 init_optimizer"""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), arg_params)

        # kvstore='tpu': data parallelism over the whole visible mesh
        # (or the context list), gradients reduced by XLA collectives
        # inside the fused program — SURVEY §5.8 mapping.  dist_* does
        # NOT build a mesh: each process runs its own local program and
        # the kvstore aggregates over DCN (update_on_kvstore, the
        # reference architecture).
        if kvstore is not None and kvstore.type.startswith("tpu") \
                and self._mesh_plan is None:
            from ..parallel import make_plan

            self._mesh_plan = make_plan(
                self._context if len(self._context) > 1 else None)
            self._apply_mesh_plan()
        if kvstore is not None and self._mesh_plan is not None:
            kvstore.mesh_plan = self._mesh_plan

        if isinstance(optimizer, str):
            batch_size = self._data_shapes[0][1][0]
            if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
                batch_size *= kvstore.num_workers
            elif self._mesh_plan is not None \
                    and self._mesh_plan.spans_processes:
                # ONE global program: the in-program psum sums the
                # GLOBAL batch (local × batch_scale), so the default
                # 1/batch rescale must use the global count — same
                # correction the dist_sync branch above applies
                batch_size *= self._mesh_plan.batch_scale
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            # remember whether the 1/global-batch default was derived
            # here: an elastic re-mesh must recompute it for the new
            # world size, but must never touch a user-pinned value
            self._auto_rescale = "rescale_grad" not in optimizer_params
            if self._auto_rescale:
                optimizer_params["rescale_grad"] = 1.0 / batch_size
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            self._auto_rescale = False

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            kvstore.set_rescale(1.0)
            param_arrays = [self._exec.arg_dict[n] for n in self._param_names]
            _initialize_kvstore(kvstore=kvstore, param_arrays=param_arrays,
                                arg_params=arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # ------------------------------------------------------------------
    def set_mesh_plan(self, plan):
        """Pin this module's arrays to a device-mesh layout (public hook
        for tensor/data-parallel placement built with
        ``parallel.make_plan``/``MeshPlan``).  Call after bind()."""
        assert self.binded, "call bind before set_mesh_plan"
        self._mesh_plan = plan
        self._apply_mesh_plan()

    def remesh(self, plan):
        """Rebuild this module's program on a NEW MeshPlan (dp' < dp
        after losing devices, or dp' > dp after regaining them),
        carrying the complete training state across the layout change.

        The ZeRO-1 optimizer state is the interesting part: under the
        old plan it lives as flat 'dp'-sharded slices.  It is gathered
        to layout-independent param-shaped host values through the
        PR-4 checkpoint path (``_optimizer_states_to_host``), the old
        plan's programs and device state are dropped, and the first
        step under the new plan re-scatters it into dp'-sharded slices
        (``_place_state_tree`` via the pending-states hook) — exactly
        the machinery a cross-layout checkpoint restore uses, so a
        re-mesh is checkpoint-equivalent by construction.  The PRNG
        base key and step counter travel too: a re-meshed run replays
        the same dropout/augmentation streams.

        Not for ``update_on_kvstore`` modules — their re-mesh is the
        kvstore's (``DistKVStore.remesh``)."""
        assert self.binded and self.params_initialized
        if self._update_on_kvstore:
            raise MXNetError(
                "Module.remesh re-shards the in-program (fused/ZeRO) "
                "state; an update_on_kvstore module re-meshes through "
                "DistKVStore.remesh instead")
        old_pp = getattr(self._mesh_plan, "pp", 1) if self._mesh_plan else 1
        new_pp = getattr(plan, "pp", 1)
        if old_pp > 1 or new_pp > 1:
            # elastic re-mesh is dp-only today: the rollback path
            # re-scatters flat 'dp'-sharded ZeRO slices, and silently
            # re-scattering state entangled with a pipeline ('pp') axis
            # (including stage-resident weight slabs) would corrupt it.
            # Fail loudly instead of corrupting.
            raise NotImplementedError(
                f"Module.remesh on a pipeline-parallel plan (pp="
                f"{max(old_pp, new_pp)}) is not implemented: the "
                "elastic re-mesh contract is dp-only (membership "
                "changes re-scatter flat 'dp'-sharded ZeRO slices; a "
                "'pp' axis — and MXNET_PP_RESIDENT weight slabs — "
                "don't re-scatter that way).  Use the layout-"
                "independent checkpoint reshard path instead: "
                "save_checkpoint/CheckpointManager on the old plan, "
                "bind a fresh Module under the new MeshPlan, and "
                "restore — optimizer state and params re-scatter into "
                "ANY dp/tp/pp layout on load (see README '3D "
                "parallelism: checkpoints').")
        opt_payload = None
        if self.optimizer_initialized:
            opt_payload = self._optimizer_states_to_host(lazy=False)
        arg_params, aux_params = self.get_params()
        args = {k: v.asnumpy() for k, v in arg_params.items()}
        auxs = {k: v.asnumpy() for k, v in aux_params.items()}
        # drop every old-layout artifact: programs, device state, caches
        self._mesh_plan = plan
        self._fused_step = None
        self._apply_grads = None
        self._fused_state = None
        self._fused_t = None
        self._fused_key = None
        self._fused_warm = False
        self._fused_step_by_prologue = _PrologueCache()
        self._lr_cache = {}
        self._zero = False
        self._zero_meta = None
        self._zero_buckets = None
        self._pp_resident = False
        self._pp_graph = None
        self._pp_slabs = None
        self._apply_mesh_plan()
        self.set_params(args, auxs)
        if opt_payload is not None:
            # host payload → pending states; the next _ensure_fused_built
            # re-scatters into the NEW dp' layout
            self._install_optimizer_states(opt_payload)
        if self._kvstore is not None:
            self._kvstore.mesh_plan = plan
        _prof.inc_counter("elastic.module_remesh")

    def set_input_prologue(self, fn):
        """Install a device-side input prologue: a jax-traceable
        ``fn(inputs, rng, train) -> inputs`` applied to the batch at
        the START of the (fused) training step — the landing point for
        ``ImageRecordIter(device_augment=1)``'s crop/flip/normalize/
        mixup.  The prologue's randomness derives from the same
        device-resident per-step key as dropout, so checkpoint resume
        replays the augmentation stream bit-exactly.  Non-fused paths
        (eval, monitored runs, plain-path flushes) apply it eagerly via
        a cached jit."""
        if fn is self._input_prologue:
            return
        if fn is not None and self._mesh_plan is not None \
                and self._mesh_plan.spans_processes:
            raise MXNetError(
                "device-side input augmentation is not yet supported on "
                "process-spanning meshes; keep the decode pool "
                "(workers=) with host augmentation (device_augment=0)")
        if self._fused_step is not None:
            self._fused_step_by_prologue.put(self._input_prologue,
                                             self._fused_step)
        self._input_prologue = fn
        if self._fused_step is not None:
            # swap in the step program built around this prologue (or
            # build it once); the optimizer state and step counter
            # carry over untouched
            cached = self._fused_step_by_prologue.get(fn)
            self._fused_step = (cached if cached is not None
                                else self._build_fused_step())

    def _apply_prologue_host(self, kwargs, is_train):
        """Eagerly apply the input prologue for the non-fused paths.
        Train-mode randomness here comes from the module PRNG stream
        (the bit-exact-resume guarantee holds on the fused path, where
        the prologue runs under the checkpointed per-step key)."""
        import jax

        from .. import random as _random
        from ..ndarray import NDArray as _ND

        flag = bool(is_train)
        pro = self._input_prologue
        per_pro = self._prologue_host_cache.get(pro)
        if per_pro is None:
            per_pro = {}
            self._prologue_host_cache.put(pro, per_pro)
        fn = per_pro.get(flag)
        if fn is None:
            fn = jax.jit(lambda inputs, rng: pro(inputs, rng, flag))
            per_pro[flag] = fn
        inputs = {k: (v._data if isinstance(v, _ND) else np.asarray(v))
                  for k, v in kwargs.items()}
        rng = (_random.next_key() if flag
               else np.zeros(2, np.uint32))  # eval branches draw nothing
        out = fn(inputs, rng)
        return {k: _ND(v, self._context[0]) for k, v in out.items()}

    def borrow_optimizer(self, shared_module):
        """Share one optimizer across modules — the BucketingModule
        mechanism (reference: module.py borrow_optimizer)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    def _adopt_fused_state(self, other):
        """Take over the device-resident optimizer state (momentum/Adam
        slots, step counter, PRNG key) from the previously-active bucket
        module so training state is continuous across buckets.  The
        caller must stop using ``other`` as the active module: after the
        next donated step its references are stale."""
        if other is self:
            return
        self._step_count = other._step_count
        if other._fused_step is None:
            return  # nothing device-resident was built yet
        if getattr(other, "_pp_resident", False):
            raise MXNetError(
                "BucketingModule state adoption from a stage-resident "
                "pipeline module is not supported: the donated "
                "optimizer state is keyed by parameter slabs that "
                "don't transfer across symbols.  Set "
                "MXNET_PP_RESIDENT=0 for bucketed pp training.")
        if self._fused_step is None:
            # build only the jitted programs; the state slots come from
            # the donor (allocating fresh ones here would be dead work).
            # The donor's ZeRO mode/layout is inherited verbatim — the
            # adopted state arrays carry its sharded layout, so the
            # programs built here must consume that same layout
            self._grad_param_names = [
                n for n in self._param_names
                if self._exec.grad_req.get(n, "null") != "null"]
            self._zero = other._zero
            self._zero_meta = other._zero_meta
            self._zero_buckets = getattr(other, "_zero_buckets", None)
            self._fused_step = self._build_fused_step()
            self._apply_grads = self._build_apply_grads()
        self._fused_state = other._fused_state
        self._fused_t = other._fused_t
        self._fused_key = other._fused_key
        self._lr_cache = other._lr_cache

    def forward(self, data_batch, is_train=None):
        """reference: module.py forward → executor forward"""
        assert self.binded and self.params_initialized
        # parameters are about to be consumed: land any deferred
        # kvstore pulls from the previous update() first
        self._drain_param_comm()
        if is_train is None:
            is_train = self.for_training
        self._flushed_backward = False
        kwargs = {}
        for name, arr in zip(self._data_names, data_batch.data):
            kwargs[name] = arr
        if self._label_names and data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                kwargs[name] = arr
        if self._input_prologue is not None and \
                not (is_train and self._fused_ready()):
            # non-fused consumption (eval/score/predict, monitored runs):
            # the raw batch must become final-shaped before it reaches
            # the executor's arg buffers
            kwargs = self._apply_prologue_host(kwargs, is_train)
        plan = self._mesh_plan
        if plan is not None and plan.spans_processes:
            # each process supplies its host-local batch; stage it as
            # this process's chunk of the global 'dp'-sharded array
            # (host_local_array_to_global_array under the hood) so the
            # ONE global program sees the full cross-host batch
            from ..ndarray import NDArray as _ND
            for name, v in list(kwargs.items()):
                tgt = self._exec.arg_dict.get(name)
                if tgt is None or not isinstance(v, _ND):
                    continue
                if not getattr(tgt._sharding, "is_fully_addressable", True) \
                        and getattr(v._data, "is_fully_addressable", True):
                    staged = plan.stage_input(
                        v.asnumpy().astype(tgt.dtype), tgt.ndim)
                    kwargs[name] = _ND(staged, sharding=tgt._sharding)
        if is_train and self._fused_ready():
            # defer: the fused program runs in update() with this batch
            self._pending_batch = kwargs
            return
        self._materialize_pp_params()  # plain path reads arg_dict
        self._exec.forward(is_train=is_train, **kwargs)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._pending_batch is not None:
            if out_grads is None:
                return  # handled by the fused step in update()
            self._flush_pending()  # explicit head grads need the plain path
        if self._flushed_backward and out_grads is None:
            # get_outputs() already ran backward for this batch — don't
            # write (or with grad_req='add', accumulate) the grads twice
            self._flushed_backward = False
            return
        self._exec.backward(out_grads=out_grads)

    def _flush_pending(self):
        """Fall back to the plain executor for the deferred batch."""
        if self._pending_batch is not None:
            kwargs = self._pending_batch
            self._pending_batch = None
            if self._input_prologue is not None:
                kwargs = self._apply_prologue_host(kwargs, True)
            self._materialize_pp_params()
            self._exec.forward(is_train=True, **kwargs)

    def update(self):
        """reference: module.py:467 update → model.py:88-115"""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        if self._pending_batch is not None:
            self._run_fused_step()
            return
        if self._fused_ready() and (self._kvstore is None
                                    or self._mesh_plan is not None):
            # batch was flushed through the plain path (get_outputs()
            # before update()): apply its grads through the SAME fused
            # optimizer state rather than a separate eager Updater
            if self._update_with_fused_state():
                return
        param_arrays = [self._exec.arg_dict[n] for n in self._param_names]
        grad_arrays = [self._exec.grad_dict.get(n) for n in self._param_names]
        with _prof.scope("Module.update", "exec",
                         args={"step": self._step_count,
                               "on_kvstore": bool(self._update_on_kvstore)}):
            if self._update_on_kvstore:
                _update_params_on_kvstore(param_arrays, grad_arrays,
                                          self._kvstore)
            else:
                _update_params(param_arrays, grad_arrays,
                               updater=self._updater,
                               num_device=len(self._context),
                               kvstore=self._kvstore)

    # -- fused one-program training step --------------------------------
    def _fused_ready(self):
        return (self._use_fused and self.optimizer_initialized
                and self._exec._monitor_callback is None  # monitored runs
                # must go through Executor.forward so the tap fires
                and not self.inputs_need_grad
                and not self._update_on_kvstore
                and (self._kvstore is None
                     or self._kvstore.type in ("tpu", "local", "device"))
                and self._optimizer is not None
                and hasattr(self._optimizer, "apply")
                and self._exec._outputs_all_loss_heads())

    def _build_fused_step(self):
        """One donated XLA program: forward + vjp + optimizer update.

        Subsumes the reference's per-node engine pushes + kvstore
        push/pull + per-weight optimizer kernels into a single fused
        computation — XLA overlaps backward with updates and keeps all
        buffers on-chip (donated).

        On a pipeline-parallel plan (pp > 1, or microbatches > 1) the
        forward+backward segment is the mxnet_tpu.pp microbatch
        pipeline instead of one whole-graph vjp — same signature, same
        optimizer segment."""
        import functools
        import jax
        import jax.numpy as jnp

        from ..parallel import tracing_for

        plan = self._mesh_plan
        if plan is not None and (plan.pp > 1 or plan.microbatches > 1):
            return self._build_pipelined_step()

        graph_fn = self._exec._graph_fn
        do_mirror = self._exec._do_mirror
        update = self._make_param_update()
        prologue = self._input_prologue

        def step_train(params, fixed, aux, states, inputs, key, lr, t):
            # (the program's name in a device trace: jit_step_train)
            # per-step PRNG derived on device from the base key + int32
            # step counter — no per-step host→device key transfer
            rng = jax.random.fold_in(key, t)
            if prologue is not None:
                # device-side input augmentation fused into the step.
                # Its key folds the BASE key with -1-t: disjoint from
                # every graph op key (executor folds rng with dense
                # node indices >= 0) and from every step key (t >= 0),
                # so the dropout stream stays identical to a
                # prologue-free run, and the checkpointed (key, t) pair
                # makes the augmentation replay bit-exactly on resume
                inputs = prologue(inputs, jax.random.fold_in(key, -1 - t),
                                  True)

            def f(p):
                full = dict(inputs)
                full.update(fixed)
                full.update(p)
                # kernels that the compiler cannot partition shard_map
                # themselves over the plan they are traced for
                with tracing_for(plan):
                    outs, new_aux = graph_fn(full, aux, rng, True)
                return tuple(outs), new_aux

            if do_mirror:
                # MXNET_BACKWARD_DO_MIRROR: recompute activations in
                # backward instead of storing them (memory ↓, FLOPs ↑)
                f = jax.checkpoint(f)

            outs, vjp_fn, new_aux = jax.vjp(f, params, has_aux=True)
            heads = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            grads = vjp_fn(heads)[0]
            t_f = (t + 1).astype(jnp.float32)
            new_params, new_states = update(params, grads, states, lr, t_f)
            return list(outs), new_params, new_aux, new_states, t + 1

        return jax.jit(step_train, donate_argnums=(0, 3, 7))

    def _split_pp_graph(self):
        """Validate + split the symbol for the pipeline executor
        (cached — residency planning and step building both need it)."""
        from .. import pp as _pp

        plan = self._mesh_plan
        if getattr(self, "_pp_graph", None) is not None:
            return self._pp_graph
        if self._aux_names:
            raise MXNetError(
                "pipeline parallelism (pp > 1 / microbatches > 1) does "
                "not support auxiliary-state ops (e.g. BatchNorm moving "
                f"stats); this symbol carries {self._aux_names[:4]}")
        try:
            pg = _pp.split_blocks(self._symbol)
        except MXNetError as e:
            if plan.pp == 1:
                # the user asked only for microbatching; name the real
                # requirement instead of blaming a pp degree they
                # never set
                raise MXNetError(
                    f"microbatches={plan.microbatches} runs the fused "
                    "step through the pipeline executor, which needs "
                    "__pp_block__ annotations on the model's repeated "
                    f"trunk even at pp=1: {e}")
            raise
        input_names = set(self._data_names) | set(self._label_names)
        direct = sorted({n for row in pg.block_params for n in row
                         if n in input_names})
        if direct:
            raise MXNetError(
                f"pipeline block(s) consume graph input(s) {direct} "
                "directly; keep an un-annotated pre region (embedding/"
                "projection) in front of the first __pp_block__")
        self._pp_graph = pg
        return pg

    def _pp_param_specs(self):
        """Per-param resolved PartitionSpec tuples so stacked per-stage
        views keep their rules-table tensor shardings."""
        param_specs = {}
        for n in self._param_names:
            sh = getattr(self._exec.arg_dict[n]._data, "sharding", None)
            spec = getattr(sh, "spec", None)
            param_specs[n] = tuple(spec) if spec is not None else ()
        return param_specs

    def _plan_pp_residency(self):
        """Decide whether this pipelined module stores its block
        parameters STAGE-RESIDENT (MXNET_PP_RESIDENT): per-slot slabs
        stacked (S, L/S, ...) and sharded P('pp', ...), so each
        stage's devices hold only their own layers' weights and
        optimizer state (~1/pp the bytes — the placement the
        partitioner bug forfeited; see mxnet_tpu/pp.py
        build_resident_pipeline_fn for the shard_map workaround).

        Residency needs a uniform slot: every layer of a slot
        trainable with identical lr/wd multipliers (the slab updates
        as ONE array).  A non-uniform model falls back to the
        replicated path with a logged reason rather than failing."""
        from .. import config as _config

        self._pp_resident = False
        plan = self._mesh_plan
        if plan is None or plan.pp <= 1:
            return
        if not (self._use_fused and self.optimizer_initialized):
            return
        if not _config.env_bool("MXNET_PP_RESIDENT"):
            return
        pg = self._split_pp_graph()
        opt = self._optimizer
        slot_names = [[pg.block_params[l][s] for l in range(pg.num_layers)]
                      for s in range(pg.num_slots)]
        for names in slot_names:
            reqs = {self._exec.grad_req.get(n, "null") for n in names}
            if reqs != {"write"}:
                self.logger.warning(
                    "MXNET_PP_RESIDENT: slot %s mixes grad_req %s; "
                    "falling back to replicated block weights",
                    names[0], sorted(reqs))
                return
            mults = {(opt.lr_mult.get(n, 1.0), opt.wd_mult.get(n, 1.0))
                     for n in names}
            if len(mults) != 1:
                self.logger.warning(
                    "MXNET_PP_RESIDENT: slot %s has per-layer lr/wd "
                    "multipliers; the slab updates as one array — "
                    "falling back to replicated block weights",
                    names[0])
                return
        param_specs = self._pp_param_specs()
        self._pp_slot_names = slot_names
        self._pp_slab_keys = [f"__ppslab{s}__"
                              for s in range(pg.num_slots)]
        self._pp_slab_sh = [
            plan.pp_param_sharding(param_specs.get(names[0], ()))
            for names in slot_names]
        slab_members = {n for names in slot_names for n in names}
        self._pp_slab_members = slab_members
        self._pp_nonslab_grad_names = [
            n for n in self._grad_param_names if n not in slab_members]
        self._pp_slab_mults = {
            key: (opt.lr_mult.get(names[0], 1.0),
                  opt.wd_mult.get(names[0], 1.0))
            for key, names in zip(self._pp_slab_keys, slot_names)}
        self._pp_slabs = None  # built lazily (and after materialize)
        self._pp_resident = True

    @property
    def _fused_param_keys(self):
        """Keys of the fused step's donated ``params`` dict: per-name
        trainable params, with block params replaced by their slab
        keys under stage residency."""
        if getattr(self, "_pp_resident", False):
            return self._pp_nonslab_grad_names + self._pp_slab_keys
        return self._grad_param_names

    def _ensure_pp_slabs(self):
        """Switch parameter authority to the stage-resident slabs:
        stack each slot's per-name values into one (S, L/S, ...) slab
        placed at P('pp', ...), then FREE the replicated per-name
        device buffers (their bytes are the whole point).  The
        per-name NDArrays keep answering shape/dtype (jax retains the
        aval of a deleted array) but any data read must go through
        :meth:`_materialize_pp_params` first — get_params, the plain
        executor paths and the checkpoint snapshot all do.

        The stack happens HOST-side on purpose: stacking on device and
        constraining the concatenate to P('pp', ...) is the exact
        pattern the MXNET_PP_CONSTRAIN partitioner bug miscompiles."""
        if not getattr(self, "_pp_resident", False) \
                or self._pp_slabs is not None:
            return
        from ..ndarray import gather_global

        plan = self._mesh_plan
        S = plan.pp
        slabs = []
        for names, sh in zip(self._pp_slot_names, self._pp_slab_sh):
            host = np.stack([
                np.asarray(gather_global(self._exec.arg_dict[n]._data))
                for n in names])
            host = host.reshape((S, len(names) // S) + host.shape[1:])
            slabs.append(plan.place(host, sh))
        for names in self._pp_slot_names:
            for n in names:
                for d in (self._exec.arg_dict.get(n),
                          self._exec.grad_dict.get(n)):
                    if d is not None and not d._data.is_deleted():
                        d._data.delete()
        self._pp_slabs = slabs
        _prof.inc_counter("pp.slab_builds")

    def _materialize_pp_params(self):
        """Switch parameter authority back to the per-name executor
        arrays: gather each slab to host, split per layer, re-place
        every block param (and its zeroed grad buffer) at its bound
        sharding, and DROP the slabs — the next fused step rebuilds
        them.  No-op when slabs aren't active, so every consumer of
        arg_dict (get_params, eval/monitored forward, checkpoint
        snapshot) can call it unconditionally."""
        slabs = getattr(self, "_pp_slabs", None)
        if not slabs:
            return
        from ..ndarray import gather_global

        plan = self._mesh_plan
        for slab, names in zip(slabs, self._pp_slot_names):
            host = np.asarray(gather_global(slab))
            host = host.reshape((len(names),) + host.shape[2:])
            for l, n in enumerate(names):
                arr = self._exec.arg_dict[n]
                arr._data = plan.place(host[l], arr._sharding)
                g = self._exec.grad_dict.get(n)
                if g is not None and g._data.is_deleted():
                    g._data = plan.place(
                        np.zeros(tuple(g.shape), g.dtype), g._sharding)
        self._pp_slabs = None
        _prof.inc_counter("pp.slab_materializes")

    def _collect_fused_params(self):
        """The fused step's donated ``params`` dict — per-name arrays,
        or (under stage residency) per-name non-block arrays plus the
        slab per slot."""
        if getattr(self, "_pp_resident", False):
            self._ensure_pp_slabs()
            params = {n: self._exec.arg_dict[n]._data
                      for n in self._pp_nonslab_grad_names}
            params.update(dict(zip(self._pp_slab_keys, self._pp_slabs)))
            return params
        return {n: self._exec.arg_dict[n]._data
                for n in self._grad_param_names}

    def _store_fused_params(self, new_params):
        """Write a fused step's returned params back to their storage:
        slabs stay slabs (arg_dict's block entries remain freed), the
        rest land in the executor arrays."""
        if getattr(self, "_pp_resident", False):
            idx = {k: i for i, k in enumerate(self._pp_slab_keys)}
            for n, v in new_params.items():
                if n in idx:
                    self._pp_slabs[idx[n]] = v
                else:
                    self._exec.arg_dict[n]._set_data(v)
            return
        for n, v in new_params.items():
            self._exec.arg_dict[n]._set_data(v)

    def param_bytes_per_device(self):
        """Bytes of LIVE parameter storage resident on ONE device —
        slabs count their per-device shard, per-name arrays count
        theirs, freed (slab-covered) buffers count zero.  Stage
        residency drops it ~1/pp for the stacked block weights
        (tests/test_pp.py holds the ratio)."""
        total = 0

        def add(d):
            nonlocal total
            if d is None or getattr(d, "is_deleted", lambda: False)():
                return
            sh = getattr(d, "sharding", None)
            if sh is not None and hasattr(sh, "shard_shape"):
                shard = sh.shard_shape(tuple(d.shape))
                total += int(np.prod(shard, dtype=np.int64)
                             * d.dtype.itemsize)
            else:
                total += int(d.nbytes)

        for n in self._param_names:
            add(self._exec.arg_dict[n]._data)
        for slab in (getattr(self, "_pp_slabs", None) or []):
            add(slab)
        return total

    def _build_pipelined_step(self):
        """The pp>1 fused step: ONE donated XLA program whose
        forward+backward segment is the mxnet_tpu.pp interleaved-1F1B
        microbatch pipeline (vmapped stages over the 'pp' mesh axis,
        collective-permute activation transfers, per-stage
        recompute-backward), whose gradients arrive already ACCUMULATED
        across microbatches, and whose optimizer segment is the very
        same ``_make_param_update`` (ZeRO-1 over 'dp') the non-pipelined
        step uses — 3D parallelism composed, not wired per model.

        Under MXNET_PP_RESIDENT the stacked block weights come in as
        'pp'-sharded slabs (stage-resident storage) and the pipeline
        runs the shard_map-movement variant; otherwise the per-name
        params are stacked in-program and rest replicated over pp (the
        documented pre-residency behavior)."""
        import jax
        import jax.numpy as jnp

        from .. import config as _config
        from .. import pp as _pp
        from ..base import get_env

        plan = self._mesh_plan
        pg = self._split_pp_graph()
        param_specs = self._pp_param_specs()
        kind = get_env("MXNET_PP_SCHEDULE",
                       _config.describe("MXNET_PP_SCHEDULE").default, str)
        update = self._make_param_update()
        prologue = self._input_prologue

        if getattr(self, "_pp_resident", False):
            pipe = _pp.build_resident_pipeline_fn(
                pg, plan, self._grad_param_names, param_specs,
                self._pp_slab_sh, schedule_kind=kind)
            self._pp_schedule = pipe.schedule
            nonslab = list(self._pp_nonslab_grad_names)
            slab_keys = list(self._pp_slab_keys)

            def step_train(params, fixed, aux, states, inputs, key, lr,
                           t):
                rng = jax.random.fold_in(key, t)
                if prologue is not None:
                    inputs = prologue(inputs,
                                      jax.random.fold_in(key, -1 - t),
                                      True)
                slabs = [params[k] for k in slab_keys]
                args = dict(fixed)
                args.update({n: params[n] for n in nonslab})
                outs, grads, g_slabs = pipe(args, slabs, inputs, rng,
                                            True)
                grads = {n: grads.get(n, jnp.zeros_like(params[n]))
                         for n in nonslab}
                grads.update(dict(zip(slab_keys, g_slabs)))
                t_f = (t + 1).astype(jnp.float32)
                new_params, new_states = update(params, grads, states,
                                                lr, t_f)
                return (list(outs), new_params, dict(aux), new_states,
                        t + 1)

            return jax.jit(step_train, donate_argnums=(0, 3, 7))

        pipe = _pp.build_pipeline_fn(pg, plan, self._grad_param_names,
                                     param_specs, schedule_kind=kind)
        self._pp_schedule = pipe.schedule
        pnames = list(self._grad_param_names)

        def step_train(params, fixed, aux, states, inputs, key, lr, t):
            rng = jax.random.fold_in(key, t)
            if prologue is not None:
                inputs = prologue(inputs, jax.random.fold_in(key, -1 - t),
                                  True)
            args = dict(fixed)
            args.update(params)
            outs, grads = pipe(args, inputs, rng, True)
            # a trainable param outside every region (unused) gets a
            # zero gradient rather than a KeyError
            grads = {n: grads.get(n, jnp.zeros_like(params[n]))
                     for n in pnames}
            t_f = (t + 1).astype(jnp.float32)
            new_params, new_states = update(params, grads, states, lr,
                                            t_f)
            return list(outs), new_params, dict(aux), new_states, t + 1

        return jax.jit(step_train, donate_argnums=(0, 3, 7))

    def _make_param_update(self):
        """The optimizer segment of the fused program, shared by
        _build_fused_step, _build_pipelined_step and _build_apply_grads:
        (params, grads, states, lr, t_f) → (new_params, new_states).
        Under pipeline parallelism the incoming ``grads`` are already
        accumulated (summed) across every microbatch by the pp scan, so
        ONE ZeRO update consumes the full-batch gradient — identical
        semantics to the non-pipelined step.

        Replicated mode (default off-mesh): ``optimizer.apply`` runs on
        every full parameter on every device — the state and the update
        FLOPs are duplicated dp times.

        ZeRO-1 mode (``self._zero``): gradients are flattened, padded
        dp-divisible and packed into same-dtype BUCKETS of at most
        ``MXNET_ZERO_BUCKET_BYTES`` emitted in BACKWARD order (the
        reverse of parameter/forward order — the order gradients
        become available during backward), each bucket a (dp, cols)
        array whose row r concatenates every member param's rank-r
        shard.  ONE reduce-scatter per bucket lands the summed shard,
        ``optimizer.apply`` runs per param on its column slice (sharded
        state, 1/dp of the update FLOPs and state bytes per device;
        per-param lr/wd multipliers intact), and ONE all-gather per
        bucket returns the updated columns, re-sliced locally into
        each parameter's own layout (replicated, or 'tp'-sharded).
        Decomposing the collective per bucket is what lets the async-
        collective scheduler (MXNET_ASYNC_COLLECTIVES) run layer i's
        reduce-scatter under layer i-1's backward compute — the
        in-program analogue of the PR-3 CommScheduler.  The pack
        layout is deterministic and per-lane, so bucketed, monolithic
        (MXNET_ZERO_BUCKET_BYTES=0) and per-param programs agree
        bit-for-bit up to fp reassociation of the gradient reduction
        (tests/test_overlap.py pins bucketed == monolithic; see
        tests/test_zero.py for sharded == replicated)."""
        import jax
        import jax.numpy as jnp

        optimizer = self._optimizer
        resident = getattr(self, "_pp_resident", False)
        pnames = list(self._fused_param_keys)
        slab_keys = set(self._pp_slab_keys) if resident else set()
        lr_mult = {n: optimizer.lr_mult.get(n, 1.0) for n in pnames}
        wd_mult = {n: optimizer.wd_mult.get(n, 1.0) for n in pnames}
        if resident:
            for key, (lm, wm) in self._pp_slab_mults.items():
                lr_mult[key], wd_mult[key] = lm, wm
        slab_sh = (dict(zip(self._pp_slab_keys, self._pp_slab_sh))
                   if resident else {})

        if not self._zero:
            wsc0 = jax.lax.with_sharding_constraint

            # a scope of its own in the device trace, each parameter's
            # update under its name (trace-time only)
            @jax.named_scope("optimizer_update")
            def update(params, grads, states, lr, t_f):
                new_params = {}
                new_states = {}
                for n in pnames:
                    with jax.named_scope(n):
                        w, s = optimizer.apply(
                            params[n], grads[n], states[n],
                            lr * lr_mult[n], optimizer.wd * wd_mult[n],
                            t_f)
                        # the f32 lr scalar must not promote
                        # low-precision params
                        w = w.astype(params[n].dtype)
                    if n in slab_keys:
                        # elementwise update of a stage-resident slab:
                        # keep it pinned where it lives
                        w = wsc0(w, slab_sh[n])
                    new_params[n] = w
                    new_states[n] = jax.tree_util.tree_map(
                        lambda new, old: new.astype(old.dtype), s, states[n])
                return new_params, new_states

            return update

        wsc = jax.lax.with_sharding_constraint
        meta = self._zero_meta
        plan = self._mesh_plan
        dp = plan.dp
        dp_sh = plan.opt_state_sharding()
        row_sh = plan.zero_bucket_sharding()
        rep = plan.replicated()
        own_sh = {n: self._exec.arg_dict[n]._data.sharding
                  for n in pnames if n not in slab_keys}
        shapes = {n: tuple(self._exec.arg_dict[n].shape)
                  for n in pnames if n not in slab_keys}
        buckets = self._zero_buckets
        slab_meta = getattr(self, "_pp_slab_zero_meta", None) or {}
        slab_state_sh = (plan.pp_opt_state_sharding() if resident
                         else None)

        def update_slab(key, w, g, st, lr, t_f):
            """ZeRO over a stage-resident slab: per-stage flats
            sharded (pp, dp) — reduce-scatter over 'dp' WITHIN each
            stage, state and update touching 1/(pp*dp) of the slab
            per device."""
            shape, size, padded = slab_meta[key]
            S = shape[0]
            g2 = wsc(jnp.pad(jnp.reshape(g, (S, size)),
                             ((0, 0), (0, padded - size))),
                     slab_state_sh)
            w2 = wsc(jnp.pad(jnp.reshape(w, (S, size)),
                             ((0, 0), (0, padded - size))),
                     slab_state_sh)
            wn, sn = optimizer.apply(w2, g2, st, lr * lr_mult[key],
                                     optimizer.wd * wd_mult[key], t_f)
            new_state = jax.tree_util.tree_map(
                lambda new, old: wsc(new.astype(old.dtype),
                                     slab_state_sh), sn, st)
            wn = jnp.reshape(wn[:, :size], shape).astype(w.dtype)
            return wsc(wn, slab_sh[key]), new_state

        @jax.named_scope("optimizer_update")
        def update(params, grads, states, lr, t_f):
            new_params = {}
            new_states = {}
            # stage-resident slabs first: the trunk's grads are the
            # deepest of the backward
            for key in (k for k in pnames if k in slab_keys):
                new_params[key], new_states[key] = update_slab(
                    key, params[key], grads[key], states[key], lr, t_f)
            for bucket in buckets:  # backward (reverse-param) order
                gcols, wcols, ks = [], [], []
                for n in bucket:
                    size, padded = meta[n]
                    ks.append(padded // dp)
                    gcols.append(jnp.pad(
                        jnp.reshape(grads[n], (size,)),
                        (0, padded - size)).reshape(dp, padded // dp))
                    wcols.append(jnp.pad(
                        jnp.reshape(params[n], (size,)),
                        (0, padded - size)).reshape(dp, padded // dp))
                cat = (lambda xs: xs[0] if len(xs) == 1
                       else jnp.concatenate(xs, axis=1))
                gb = wsc(cat(gcols), row_sh)  # ONE reduce-scatter/bucket
                wb = wsc(cat(wcols), row_sh)  # local rows
                ncols = []
                c = 0
                for n, k in zip(bucket, ks):
                    gf = jax.lax.slice_in_dim(gb, c, c + k, axis=1)
                    wf = jax.lax.slice_in_dim(wb, c, c + k, axis=1)
                    # state stays checkpoint-compatible: stored flat
                    # (padded,) 'dp'-sharded; the (dp, k) view is a
                    # local reshape of the same lanes
                    st = jax.tree_util.tree_map(
                        lambda s, k=k: jnp.reshape(s, (dp, k)), states[n])
                    w, s = optimizer.apply(wf, gf, st,
                                           lr * lr_mult[n],
                                           optimizer.wd * wd_mult[n], t_f)
                    ncols.append(w.astype(params[n].dtype))
                    new_states[n] = jax.tree_util.tree_map(
                        lambda new, old: wsc(
                            jnp.reshape(new.astype(old.dtype), old.shape),
                            dp_sh),
                        s, states[n])
                    c += k
                # ONE all-gather returns the whole updated bucket;
                # per-param extraction below is local slicing
                full = wsc(cat(ncols), rep)
                c = 0
                for n, k in zip(bucket, ks):
                    size, padded = meta[n]
                    flat = jnp.reshape(
                        jax.lax.slice_in_dim(full, c, c + k, axis=1),
                        (padded,))
                    # pad lanes (grad 0, state 0) never reach the weights
                    new_params[n] = wsc(jnp.reshape(flat[:size], shapes[n]),
                                        own_sh[n])
                    c += k
            return new_params, new_states

        return update

    def _ensure_fused_built(self, dev):
        import jax
        import jax.numpy as jnp

        from .. import random as _random

        if self._fused_step is not None:
            return
        self._grad_param_names = [n for n in self._param_names
                                  if self._exec.grad_req.get(n, "null") != "null"]
        self._plan_pp_residency()
        self._init_zero_mode()
        self._fused_step = self._build_fused_step()
        self._apply_grads = self._build_apply_grads()
        if getattr(self, "_pp_resident", False):
            # the slab state builder consumes the slabs: build them now
            # (frees the replicated per-name device buffers)
            self._ensure_pp_slabs()
        self._fused_state = self._build_fused_state(dev)
        _prof.set_gauge("executor.opt_state_bytes",
                        self._opt_state_bytes_per_device())
        # device-resident step counter + base PRNG key: donated and
        # returned by the step so steady state does zero scalar
        # host→device transfers.  On a mesh they live replicated.
        # a checkpointed run resumes with ITS base key (bit-identical
        # per-step dropout masks); otherwise draw a fresh one
        restored_key = self._pending_fused_key
        self._pending_fused_key = None
        if self._mesh_plan is not None:
            plan = self._mesh_plan
            rep = plan.replicated()
            key = (np.asarray(restored_key) if restored_key is not None
                   else _random.next_key())  # raw uint32 (2,) threefry key
            if plan.spans_processes and restored_key is None:
                # one PRNG stream for the ONE global program: rank 0's
                # key wins (identical dropout masks on every host)
                from jax.experimental import multihost_utils
                key = np.asarray(multihost_utils.broadcast_one_to_all(
                    np.asarray(key)))
            self._fused_t = plan.place(np.int32(self._step_count), rep)
            self._fused_key = plan.place(np.asarray(key), rep)
        else:
            self._fused_t = jax.device_put(np.int32(self._step_count), dev)
            self._fused_key = jax.device_put(
                np.asarray(restored_key) if restored_key is not None
                else _random.next_key(), dev)
        self._lr_cache = {}

    def _init_zero_mode(self):
        """Decide whether this module's fused step runs the ZeRO-1
        sharded-optimizer update (MXNET_ZERO, default on whenever a
        MeshPlan with dp>1 is active), precompute the flat dp-padded
        layout of every trainable param, and plan the gradient-
        collective buckets (MXNET_ZERO_BUCKET_BYTES, backward order,
        same-dtype — see _make_param_update)."""
        from ..base import get_env

        plan = self._mesh_plan
        self._zero = bool(plan is not None and plan.dp > 1
                          and get_env("MXNET_ZERO", 1, int))
        self._zero_meta = None
        self._zero_buckets = None
        self._pp_slab_zero_meta = None
        if not self._zero:
            return
        self._zero_meta = {}
        for n in self._grad_param_names:
            size = int(np.prod(self._exec.arg_dict[n].shape, dtype=np.int64))
            self._zero_meta[n] = (size, plan.zero_padded_size(size))
        if getattr(self, "_pp_resident", False):
            # slab keys update as (S, per-stage-flat) arrays sharded
            # pp x dp: state bytes/device shrink by BOTH factors
            self._pp_slab_zero_meta = {}
            for key, names in zip(self._pp_slab_keys,
                                  self._pp_slot_names):
                shape = tuple(self._exec.arg_dict[names[0]].shape)
                Ls = len(names) // plan.pp
                size = Ls * int(np.prod(shape, dtype=np.int64))
                self._pp_slab_zero_meta[key] = (
                    (plan.pp, Ls) + shape, size,
                    plan.zero_padded_size(size))
        self._zero_buckets = self._plan_zero_buckets()

    def _plan_zero_buckets(self):
        """Deterministic same-dtype bucketing of the trainable params
        in BACKWARD (reverse-parameter) order, capped at
        MXNET_ZERO_BUCKET_BYTES per bucket (0 = no cap: one monolithic
        bucket per dtype run — the serialized-collective baseline)."""
        from .. import config as _config
        from ..base import get_env

        raw = get_env("MXNET_ZERO_BUCKET_BYTES", None, str)
        if raw is None:
            cap = int(_config.describe("MXNET_ZERO_BUCKET_BYTES").default)
        else:
            try:
                cap = int(raw)
            except (TypeError, ValueError):
                raise MXNetError(
                    f"MXNET_ZERO_BUCKET_BYTES={raw!r} is not an integer "
                    "(want >= 0 bytes; 0 = one monolithic bucket)")
            if cap < 0:
                raise MXNetError(
                    f"MXNET_ZERO_BUCKET_BYTES={cap} must be >= 0")
        buckets = []
        cur, cur_bytes, cur_dt = [], 0, None
        names = (self._pp_nonslab_grad_names
                 if getattr(self, "_pp_resident", False)
                 else self._grad_param_names)
        for n in reversed(names):
            dt = self._exec.arg_dict[n].dtype
            nbytes = self._zero_meta[n][1] * np.dtype(dt).itemsize
            if cur and (dt != cur_dt
                        or (cap > 0 and cur_bytes + nbytes > cap)):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(n)
            cur_bytes += nbytes
            cur_dt = dt
        if cur:
            buckets.append(cur)
        return buckets

    def _build_fused_state(self, dev):
        """Allocate (or restore from a loaded checkpoint) the device-
        resident optimizer state for every trainable param — flat
        'dp'-sharded in ZeRO mode, weight-shaped otherwise; slab keys
        (stage residency) carry (S, per-stage-flat) pp x dp-sharded
        state restacked from the per-name checkpoint entries."""
        import jax
        import jax.numpy as jnp

        pending = self._pending_fused_states
        self._pending_fused_states = None
        loaded = pending[1] if pending else {}
        states = {}
        fresh = []
        resident = getattr(self, "_pp_resident", False)
        pernames = (self._pp_nonslab_grad_names if resident
                    else self._grad_param_names)
        for n in pernames:
            if n in loaded:
                states[n] = self._place_state_tree(n, loaded[n], dev)
            elif self._zero:
                fresh.append(n)
            else:
                states[n] = self._optimizer.init_state_arrays(
                    self._exec.arg_dict[n]._data)
        if resident:
            fresh_slabs = []
            for key, names in zip(self._pp_slab_keys,
                                  self._pp_slot_names):
                have = [n for n in names if n in loaded]
                if not have:
                    fresh_slabs.append(key)
                elif len(have) != len(names):
                    raise MXNetError(
                        f"optimizer-state restore for pipeline slot "
                        f"{names[0]!r} is incomplete: "
                        f"{sorted(set(names) - set(have))} missing — "
                        "a slab restores all of its layers or none")
                else:
                    states[key] = self._place_slab_state(
                        key, [loaded[n] for n in names])
            if fresh_slabs:
                optimizer = self._optimizer
                slab_idx = {k: i for i, k in
                            enumerate(self._pp_slab_keys)}
                if self._zero:
                    smeta = self._pp_slab_zero_meta
                    pp_sh = self._mesh_plan.pp_opt_state_sharding()

                    def build_slab(slabs_in):
                        out = {}
                        for key, w in slabs_in.items():
                            shape, size, padded = smeta[key]
                            wf = jax.lax.with_sharding_constraint(
                                jnp.pad(
                                    jnp.reshape(w, (shape[0], size)),
                                    ((0, 0), (0, padded - size))),
                                pp_sh)
                            out[key] = optimizer.\
                                init_state_arrays_sharded(wf, pp_sh)
                        return out
                else:
                    slab_sh = dict(zip(self._pp_slab_keys,
                                       self._pp_slab_sh))

                    def build_slab(slabs_in):
                        out = {}
                        for key, w in slabs_in.items():
                            st = optimizer.init_state_arrays(w)
                            out[key] = jax.tree_util.tree_map(
                                lambda a: jax.lax.
                                with_sharding_constraint(a, slab_sh[key]),
                                st)
                        return out

                states.update(jax.jit(build_slab)(
                    {k: self._pp_slabs[slab_idx[k]]
                     for k in fresh_slabs}))
        if fresh:
            # ONE jitted builder for every fresh sharded state — a
            # per-param jit would pay one XLA compile per parameter
            meta = self._zero_meta
            dp_sh = self._mesh_plan.opt_state_sharding()
            optimizer = self._optimizer

            def build(ws):
                out = {}
                for n, w in ws.items():
                    size, padded = meta[n]
                    wf = jax.lax.with_sharding_constraint(
                        jnp.pad(jnp.reshape(w, (size,)),
                                (0, padded - size)), dp_sh)
                    out[n] = optimizer.init_state_arrays_sharded(wf, dp_sh)
                return out

            states.update(jax.jit(build)(
                {n: self._exec.arg_dict[n]._data for n in fresh}))
        return states

    def _place_slab_state(self, key, member_trees):
        """Per-name host state trees (param-shaped, one per layer) →
        this slab's device state: stacked (S, Ls, ...) then flattened
        per stage and scattered pp x dp under ZeRO, or placed slab-
        shaped otherwise."""
        import jax

        plan = self._mesh_plan
        slot = self._pp_slab_keys.index(key)
        S = plan.pp

        if self._zero:
            shape, size, padded = self._pp_slab_zero_meta[key]
            pp_sh = plan.pp_opt_state_sharding()

            def put(*leaves):
                h = np.stack([np.asarray(a) for a in leaves])
                h = np.pad(h.reshape(S, size),
                           ((0, 0), (0, padded - size)))
                return plan.place(h, pp_sh)

            return jax.tree_util.tree_map(put, *member_trees)

        sh = self._pp_slab_sh[slot]

        def put(*leaves):
            h = np.stack([np.asarray(a) for a in leaves])
            h = h.reshape((S, len(member_trees) // S) + h.shape[1:])
            return plan.place(h, sh)

        return jax.tree_util.tree_map(put, *member_trees)

    def _place_state_tree(self, name, host_tree, dev):
        """Host (param-shaped) state tree → device arrays in this
        module's current optimizer-state layout.  Checkpoints always
        store param-shaped full values, so a sharded-mode run re-flattens
        and scatters while a replicated-mode run places directly —
        states saved under either layout load under either."""
        import jax

        plan = self._mesh_plan
        if self._zero:
            size, padded = self._zero_meta[name]
            dp_sh = plan.opt_state_sharding()

            def put(a):
                flat = np.pad(np.asarray(a).reshape(-1),
                              (0, padded - size))
                return plan.place(flat, dp_sh)

            return jax.tree_util.tree_map(put, host_tree)
        if plan is not None:
            sh = self._exec.arg_dict[name]._data.sharding
            return jax.tree_util.tree_map(
                lambda a: plan.place(np.asarray(a), sh), host_tree)
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(np.asarray(a), dev), host_tree)

    def _opt_state_bytes_per_device(self):
        """Bytes of optimizer state resident on ONE device — the
        executor.opt_state_bytes gauge (ZeRO's whole point is shrinking
        this ~dp×)."""
        import jax

        total = 0
        for tree in (self._fused_state or {}).values():
            for leaf in jax.tree_util.tree_leaves(tree):
                sh = getattr(leaf, "sharding", None)
                if sh is not None and hasattr(sh, "shard_shape"):
                    shard = sh.shard_shape(tuple(leaf.shape))
                    total += int(np.prod(shard, dtype=np.int64)
                                 * leaf.dtype.itemsize)
                else:
                    total += int(leaf.nbytes)
        return total

    def _lr_device(self, dev):
        """Device scalar for the current base lr, cached per value."""
        import jax
        import jax.numpy as jnp

        lr = float(self._optimizer.lr_scheduler(self._optimizer.num_update)
                   if self._optimizer.lr_scheduler else self._optimizer.lr)
        lr_dev = self._lr_cache.get(lr)
        if lr_dev is None:
            if len(self._lr_cache) >= 64:
                self._lr_cache.clear()  # per-step schedulers: don't leak
            if self._mesh_plan is not None:
                lr_dev = self._mesh_plan.place(
                    np.float32(lr), self._mesh_plan.replicated())
            else:
                lr_dev = jax.device_put(np.float32(lr), dev)  # committed
            self._lr_cache[lr] = lr_dev
        return lr_dev

    def _update_with_fused_state(self):
        """Apply grad_dict gradients through the fused optimizer state
        (the get_outputs()-fallback companion of _run_fused_step).

        Under stage residency the plain path just ran on materialized
        per-name params/grads; the per-name block grads are re-stacked
        host-side into slab gradients so the ONE slab-keyed optimizer
        state keeps advancing (edge path — the steady state never
        leaves the fused step)."""
        dev = self._context[0].jax_device()
        self._ensure_fused_built(dev)
        grads = {}
        for n in self._grad_param_names:
            g = self._exec.grad_dict.get(n)
            if g is None or g._data.is_deleted():
                return False
            grads[n] = g._data
        if getattr(self, "_pp_resident", False):
            from ..ndarray import gather_global

            plan = self._mesh_plan
            for key, names, sh in zip(self._pp_slab_keys,
                                      self._pp_slot_names,
                                      self._pp_slab_sh):
                host = np.stack([np.asarray(gather_global(grads.pop(n)))
                                 for n in names])
                host = host.reshape((plan.pp, len(names) // plan.pp)
                                    + host.shape[1:])
                grads[key] = plan.place(host, sh)
        params = self._collect_fused_params()
        self._step_count += 1
        self._optimizer._update_count(0)
        params = _copy_donated_aliases(
            params, _buffer_ids(grads, self._fused_state, self._fused_t))
        new_params, self._fused_state, self._fused_t = self._apply_grads(
            params, grads, self._fused_state, self._lr_device(dev),
            self._fused_t)
        self._store_fused_params(new_params)
        return True

    def _build_apply_grads(self):
        """Jitted optimizer-only program over the SAME fused state, used
        when a batch was flushed through the plain executor path (e.g.
        get_outputs() before update()) — keeps momentum/Adam state in one
        place instead of diverging into an eager Updater."""
        import jax
        import jax.numpy as jnp

        update = self._make_param_update()

        def apply_grads(params, grads, states, lr, t):
            t_f = (t + 1).astype(jnp.float32)
            new_params, new_states = update(params, grads, states, lr, t_f)
            return new_params, new_states, t + 1

        return jax.jit(apply_grads, donate_argnums=(0, 2, 4))

    def _run_fused_step(self):
        import jax
        import jax.numpy as jnp

        from .. import random as _random
        from ..ndarray import NDArray

        inputs = {}
        dev = self._context[0].jax_device()
        for k, v in self._pending_batch.items():
            if self._input_prologue is not None:
                # raw wire-format batch (e.g. uint8 NHWC): its shape
                # does not match the executor's arg buffer — stage it
                # straight to the device untouched; the prologue inside
                # the step turns it into the bound shape/dtype.  The
                # uint8 transfer is the 4x H2D cut; stage_array counts
                # the real bytes for io.h2d_bytes
                from ..io import stage_array
                raw = v._data if isinstance(v, NDArray) else np.asarray(v)
                if self._mesh_plan is not None:
                    # place() takes the (possibly already device-
                    # resident) array as-is: a staged batch resharded
                    # device-to-device, never pulled back to host
                    sh = self._mesh_plan.input_sharding(np.ndim(raw))
                    inputs[k] = self._mesh_plan.place(raw, sh)
                else:
                    inputs[k] = stage_array(raw, dev)
                continue
            arr = self._exec.arg_dict[k]
            if isinstance(v, NDArray):
                if arr._sharding is not None:
                    # _set_data re-places onto the batch-sharded mesh layout
                    arr._set_data(v._data.astype(arr.dtype))
                else:
                    # async host→device transfer straight to the target
                    # chip; overlaps with the still-running previous step
                    arr._set_data(jax.device_put(v._data.astype(arr.dtype), dev))
            else:
                arr[:] = v
            inputs[k] = arr._data
        self._pending_batch = None

        self._ensure_fused_built(dev)

        params = self._collect_fused_params()
        fixed = {n: self._exec.arg_dict[n]._data for n in self._param_names
                 if n not in self._grad_param_names}
        aux = {n: a._data for n, a in self._exec.aux_dict.items()}
        self._step_count += 1
        self._optimizer._update_count(0)
        # base lr; per-param lr_mult/wd_mult are folded inside the step.
        # the device scalar is cached per distinct value (schedulers step
        # it rarely relative to the step rate)
        lr_dev = self._lr_device(dev)
        params = _copy_donated_aliases(
            params, _buffer_ids(fixed, aux, inputs, self._fused_state,
                                self._fused_key, self._fused_t))
        if not self._fused_warm and self._mesh_plan is None:
            # First run (after the alias scan, which goes by object
            # identity): COMMIT every argument to the device.  An
            # uncommitted array (fresh from init_params) lowers without
            # a sharding annotation and a committed one (any output of
            # the step) with it, so the first call, the second call and
            # fused_hlo_text's lowering were three different programs:
            # three full XLA compiles of one step — minutes of a cold
            # run on the chip.  Committed, they are one program (and one
            # persistent-cache entry).  No copy: same device.
            params, fixed, aux, self._fused_state = jax.device_put(
                (params, fixed, aux, self._fused_state), dev)
            for n, v in fixed.items():  # re-read from here every step
                self._exec.arg_dict[n]._data = v
        compiled = not self._fused_warm
        self._fused_warm = True
        step_args = (params, fixed, aux, self._fused_state, inputs,
                     self._fused_key, lr_dev, self._fused_t)
        if compiled:
            # first run of this build: feed the live-MFU tracker the
            # program's FLOPs from the ONE lowering the call below
            # reuses (before the call — the donated buffers are gone
            # after it)
            self._account_step_flops(step_args)
        with _prof.record_program("Module.fused_step", compiled,
                                  args={"step": self._step_count}):
            outs, new_params, new_aux, new_states, self._fused_t = \
                self._fused_step(*step_args)
            if _prof._profiler.running:
                jax.block_until_ready(outs)
        self._store_fused_params(new_params)
        for n, v in new_aux.items():
            self._exec.aux_dict[n]._set_data(v)
        self._fused_state = new_states
        if self._mesh_plan is not None and self._mesh_plan.spans_processes:
            # per-worker view: metrics/logging consume this process's
            # slice of the global outputs (same per-shard semantics as
            # the reference's per-worker executor outputs)
            outs = [jnp.asarray(self._mesh_plan.local_output(o))
                    for o in outs]
        self._exec.outputs_cache = [NDArray(o, self._context[0]) for o in outs]

    def _account_step_flops(self, step_args):
        """The live fit path's FLOPs/MFU accounting: XLA's own HLO
        cost analysis of the jitted fused step's lowering yields the
        per-step FLOPs, divided across the mesh so ``training.mfu`` is
        per-chip.
        The step is lowered HERE, from the very arrays the first call
        is about to pass: jax keeps that lowering, so the call traces
        and lowers nothing again and ``_fused_compiled`` hands out the
        executable the call compiled (from shape specs instead, the
        call can lower the whole step a second time: on XLA:CPU it
        does, on the TPU it did not — PERF.md, PR 25).  Also declares the
        pipeline's static bubble fraction.  Best-effort: a toolchain
        without a cost model simply leaves the mfu gauge unexported
        (goodput and the decomposition still work)."""
        import jax
        import jax.numpy as jnp

        tracker = _prof.goodput_tracker()
        plan = self._mesh_plan
        if plan is not None and plan.pp > 1:
            # (pp-1)/(M+pp-1): the GPipe/1F1B fill-drain bubble of the
            # static timetable
            tracker.set_pp_bubble(
                (plan.pp - 1) / (plan.microbatches + plan.pp - 1))
        try:
            # the shapes outlive the donated buffers: a step rebuilt
            # later (a new input prologue) lowers from them
            self._fused_arg_specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    jnp.shape(a), jnp.result_type(a),
                    sharding=getattr(a, "sharding", None)),
                step_args)
            lowered = self._fused_step.lower(*step_args)
            self._fused_lowered = (self._fused_step, lowered)
            cost = lowered.cost_analysis()  # pre-partitioning: global
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            flops = float((cost or {}).get("flops", 0.0))
            if flops > 0:
                ndev = plan.num_devices if plan is not None else 1
                tracker.set_flops_per_step(flops / max(ndev, 1))
        except Exception:  # noqa: BLE001 — accounting must never
            pass  # break the training step

    def _fused_compiled(self):
        """The compiled (SPMD-partitioned) fused step: the executable
        of the lowering kept at the first run — the one the step itself
        runs, so the HLO text, the memory analysis and the
        comm-fraction cost read cost no compile of their own.  Only a
        step rebuilt since (a new input prologue) is lowered again,
        from the arg specs captured then."""
        specs = getattr(self, "_fused_arg_specs", None)
        if specs is None or self._fused_step is None:
            raise MXNetError(
                "needs a built fused step: run one training step "
                "first (forward_backward + update)")
        kept = getattr(self, "_fused_lowered", None)
        if kept is None or kept[0] is not self._fused_step:
            kept = (self._fused_step, self._fused_step.lower(*specs))
            self._fused_lowered = kept
        return kept[1].compile()  # cached on the lowering

    def fused_hlo_text(self):
        """Compiled (scheduled, SPMD-partitioned) HLO text of the
        fused training step — the artifact the comm/compute-overlap
        inspection reads (``mxnet_tpu.hlo.overlap_report``;
        tests/test_overlap.py).

        Reads the executable the step runs (no compile of its own);
        call after at least one fused step has run."""
        return self._fused_compiled().as_text()

    def _programs_held(self) -> dict:
        """The fused step as the profiler's registry asks for it: the
        executable the kept lowering compiled, and nothing lowered
        anew (``__del__`` asks too)."""
        kept = getattr(self, "_fused_lowered", None)
        if kept is None or kept[0] is not getattr(self, "_fused_step",
                                                  None):
            return {}
        return {"fused_step": kept[1].compile()}  # cached on it

    def fused_program_scopes(self) -> dict:
        """{``jit_step_train``: {instruction: record}}: every operation
        of the fused step under the name the symbol's node — or
        ``optimizer_update/<param>`` — gave it (``hlo.scope_table``
        lists the record's fields).  The text is read and parsed when
        this is first asked, once an executable; call after at least
        one fused step has run."""
        self._fused_compiled()  # raises without a built step
        return _prof.holder_scopes(self)

    def __del__(self):
        # a trace taken while the step ran can still be named once the
        # module is gone: the executable alone is handed over
        try:
            _prof.retire_programs(self)
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass

    def fused_memory_analysis(self):
        """Per-device compiled memory breakdown of the fused step
        (argument/temp/output bytes) of the executable the step
        runs."""
        return self._fused_compiled().memory_analysis()

    def account_program_comm(self):
        """Attribute IN-PROGRAM collective time to the goodput
        tracker's step decomposition: the static collective fraction
        = collective bytes / total bytes accessed, both read from the
        compiled fused step (the same XLA cost surface training.mfu
        uses).  Without this, fused-program collectives silently book
        as ``compute`` — only host-side CommScheduler waits were
        counted.  Returns the fraction, or None when it cannot be
        computed (no mesh, program not built, toolchain without a
        cost model).  fit() calls this once per built program (step 8,
        or step 1 when the ops endpoint is live); it reads the
        executable the step runs (its HLO text and cost analysis)."""
        plan = self._mesh_plan
        if plan is None or plan.num_devices <= 1 \
                or self._fused_step is None:
            return None
        # once per BUILT program: a rebuild (new prologue, re-mesh)
        # invalidates this identity and re-accounts at the next call —
        # a stale mesh's fraction must not keep booking
        if getattr(self, "_comm_accounted_for", None) \
                is self._fused_step:
            return self._program_comm_fraction
        from .. import hlo as _hlo

        try:
            compiled = self._fused_compiled()
            cbytes = _hlo.collective_bytes(compiled.as_text())
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            total = float((cost or {}).get("bytes accessed", 0.0))
            # both numbers are per-device (post-partitioning); cap the
            # fraction — a decomposition 100% comm would zero compute
            frac = min(cbytes / max(total, float(cbytes), 1.0), 0.9)
            self._program_comm_fraction = frac
            self._comm_accounted_for = self._fused_step
            _prof.goodput_tracker().set_program_comm_fraction(frac)
            return frac
        except Exception:  # noqa: BLE001 — accounting must never
            return None  # break the training step

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._pending_batch is not None:
            # outputs requested before update(): fall back to the plain
            # forward+backward path for this batch so the deferred-batch
            # optimization stays invisible — outputs and the gradients a
            # later update() consumes come from the SAME program run
            # (same dropout masks, aux updates applied exactly once)
            kwargs = self._pending_batch
            self._pending_batch = None
            if self._input_prologue is not None:
                kwargs = self._apply_prologue_host(kwargs, True)
            self._materialize_pp_params()
            self._exec.forward(is_train=True, **kwargs)
            if all(r in ("write", "null")
                   for r in self._exec.grad_req.values()):
                self._exec.backward()
                self._flushed_backward = True
            # grad_req='add': leave gradients untouched — an output query
            # must not accumulate a contribution; the user's backward()
            # call does it exactly once
        outs = self._exec.outputs
        if self._mesh_plan is not None and self._mesh_plan.spans_processes:
            # plain-path (score/predict/pre-update get_outputs) parity
            # with _run_fused_step: hand back this process's slice of
            # any global output so it pairs with the host-local labels
            import jax.numpy as jnp
            from ..ndarray import NDArray as _ND
            plan = self._mesh_plan
            changed = False
            local = []
            for o in outs:
                if not getattr(o._data, "is_fully_addressable", True):
                    local.append(_ND(jnp.asarray(plan.local_output(o._data)),
                                     self._context[0]))
                    changed = True
                else:
                    local.append(o)
            if changed:
                self._exec.outputs_cache = local
            outs = local
        return outs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        eval_metric.update(labels, self.get_outputs())

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    # ------------------------------------------------------------------
    _FUSED_STATES_FORMAT = "mxnet_tpu-fused-states-v1"

    def save_optimizer_states(self, fname):
        """reference: module.py:543 save_optimizer_states

        Fused-path states are written LAYOUT-INDEPENDENTLY: every slot
        is gathered to its full param-shaped host value (ZeRO shards
        are all-gathered and unpadded), so a checkpoint written by a
        sharded run loads in a replicated run and vice versa."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
            return
        from ..checkpoint import atomic_write_bytes

        if self._fused_state is not None:
            blob = pickle.dumps(self._fused_states_to_host())
        elif self._pending_fused_states is not None:
            # loaded from a checkpoint but no step run yet (the
            # fused programs aren't built): pass the host states
            # through unchanged rather than writing an empty blob
            step, states = self._pending_fused_states
            blob = pickle.dumps(
                {"format": self._FUSED_STATES_FORMAT,
                 "step": int(step), "states": states})
        else:
            blob = self._updater.get_states()
        atomic_write_bytes(fname, blob)

    def _fused_states_to_host(self, lazy=False):
        """Gather the fused optimizer state into the layout-independent
        checkpoint dict: {name: param-shaped host tree} + step count.
        All processes of a spanning mesh call this in lockstep (the
        sharded leaves ride the bulk-synchronous gather_global).

        ``lazy``: fully-addressable leaves come back as DEVICE copies
        (cheap, safe against the next step's donation) instead of host
        numpy — the async checkpointer defers the D2H transfer to its
        background writer so the training thread barely blocks.  Cross-
        host-sharded leaves always gather to host NOW (the collective
        must run with every rank at the same program point)."""
        import jax
        import jax.numpy as jnp

        from ..ndarray import gather_global

        resident = getattr(self, "_pp_resident", False)
        slab_names = (dict(zip(self._pp_slab_keys, self._pp_slot_names))
                      if resident else {})
        states = {}
        for n, tree in self._fused_state.items():
            if n in slab_names:
                # slab state → per-name param-shaped entries, so the
                # checkpoint stays layout-independent (loads into
                # resident, replicated-pp, dp-only or eager runs alike)
                names = slab_names[n]
                L = len(names)
                pshape = tuple(self._exec.arg_dict[names[0]].shape)

                def slab_to_host(a, L=L, pshape=pshape, key=n):
                    h = gather_global(a)
                    if self._zero:
                        _shape, size, _padded = \
                            self._pp_slab_zero_meta[key]
                        h = h[:, :size]
                    return np.asarray(h).reshape((L,) + pshape)

                host = jax.tree_util.tree_map(slab_to_host, tree)
                for l, name in enumerate(names):
                    states[name] = jax.tree_util.tree_map(
                        lambda t, l=l: t[l], host)
                continue
            shape = tuple(self._exec.arg_dict[n].shape)
            size = self._zero_meta[n][0] if self._zero else None

            def to_host(a, shape=shape, size=size):
                if lazy and getattr(a, "is_fully_addressable", True):
                    h = jnp.array(a, copy=True)
                else:
                    h = gather_global(a)
                if size is not None:  # ZeRO: drop pad, restore shape
                    h = h[:size].reshape(shape)
                return h

            states[n] = jax.tree_util.tree_map(to_host, tree)
        return {"format": self._FUSED_STATES_FORMAT,
                "step": int(self._step_count), "states": states}

    def _restore_fused_states(self, step, states_by_name):
        """Install checkpointed optimizer states (host, param-shaped)
        into this module — immediately when the fused programs exist,
        else deferred to _ensure_fused_built, which re-scatters them
        into whatever layout (ZeRO-sharded or replicated) this run
        uses."""
        self._step_count = int(step)
        self._optimizer._index_update_count[0] = self._step_count
        self._optimizer.num_update = max(self._optimizer.num_update,
                                         self._step_count)
        if self._fused_step is None:
            self._pending_fused_states = (self._step_count,
                                          dict(states_by_name))
            return
        import jax
        import jax.numpy as jnp

        dev = self._context[0].jax_device()
        resident = getattr(self, "_pp_resident", False)
        slab_members = self._pp_slab_members if resident else set()
        for n in self._grad_param_names:
            if n in states_by_name and n not in slab_members:
                self._fused_state[n] = self._place_state_tree(
                    n, states_by_name[n], dev)
        if resident:
            for key, names in zip(self._pp_slab_keys,
                                  self._pp_slot_names):
                have = [n for n in names if n in states_by_name]
                if not have:
                    continue
                if len(have) != len(names):
                    raise MXNetError(
                        f"optimizer-state restore for pipeline slot "
                        f"{names[0]!r} is incomplete: "
                        f"{sorted(set(names) - set(have))} missing — "
                        "a slab restores all of its layers or none")
                self._fused_state[key] = self._place_slab_state(
                    key, [states_by_name[n] for n in names])
        if self._mesh_plan is not None:
            self._fused_t = self._mesh_plan.place(
                np.int32(self._step_count), self._mesh_plan.replicated())
        else:
            with jax.default_device(dev):
                self._fused_t = jnp.int32(self._step_count)

    def _install_host_states(self, step, states_by_name):
        """Install layout-independent host optimizer states (the
        fused-checkpoint dict) into this module, whatever update path it
        ends up on.

        ALWAYS populates the eager Updater: even under
        MXNET_FUSED_STEP=1 a module can end up on the plain update path
        for good (monitored run, inputs_need_grad, non-loss output
        heads), and parking the states only in _pending_fused_states
        would silently restart Adam/momentum from zero there.  Keys
        follow model.py _update_params' convention (param_index *
        num_device); leaves stay host numpy — jax commits them on first
        use, so a ZeRO run never materializes the full state on one
        device just for this fallback copy."""
        import jax

        nd_count = len(self._context)
        name2idx = {n: i for i, n in enumerate(self._param_names)}
        if self._updater is not None:
            self._updater.states = {
                name2idx[n] * nd_count:
                    jax.tree_util.tree_map(np.asarray, tree)
                for n, tree in states_by_name.items() if n in name2idx}
            for i in self._updater.states:
                self._optimizer._index_update_count[i] = step
        self._optimizer.num_update = max(
            self._optimizer.num_update, step)
        if self._use_fused:
            self._restore_fused_states(step, states_by_name)

    # -- in-memory optimizer-state snapshot/install (checkpoint.py) ----
    def _optimizer_states_to_host(self, lazy=False):
        """Complete, layout-independent snapshot of the optimizer state
        for the async checkpointer — covers the fused device state, a
        not-yet-built pending restore, the eager Updater, and the
        kvstore-side replicated updater.  See _fused_states_to_host for
        the ``lazy`` contract."""
        assert self.optimizer_initialized
        num_update = int(self._optimizer.num_update)
        if self._update_on_kvstore:
            kv = self._kvstore
            quiesce = getattr(kv, "_sync_comm", None)
            if quiesce is not None:
                quiesce()  # the comm thread may be mid-update
            updater = getattr(kv, "_updater", None)
            if updater is None:
                # server-side updates: the state lives on the shards.
                # A provably STATELESS optimizer (init_state_arrays is
                # None — plain SGD, SGLD) has nothing to lose, so the
                # snapshot degrades to num_update only (the elastic
                # drill's configuration); anything stateful must refuse
                # rather than silently drop momentum on restore
                import jax.numpy as jnp

                try:
                    stateless = self._optimizer.init_state_arrays(
                        jnp.zeros((1,), jnp.float32)) is None
                except Exception:  # noqa: BLE001 — exotic optimizer
                    stateless = False
                if stateless:
                    return {"kind": "updater", "blob": b"",
                            "num_update": num_update}
                raise MXNetError(
                    "cannot snapshot optimizer state: the kvstore keeps "
                    "it server-side (MXNET_KVSTORE_SYNC_ON_SERVER)")
            return {"kind": "updater", "blob": updater.get_states(),
                    "num_update": num_update}
        if self._fused_state is not None:
            d = self._fused_states_to_host(lazy=lazy)
            payload = {"kind": "fused", "step": d["step"],
                       "states": d["states"], "num_update": num_update}
            if self._fused_key is not None:
                from ..ndarray import gather_global

                payload["fused_key"] = gather_global(self._fused_key)
            return payload
        if self._pending_fused_states is not None:
            step, states = self._pending_fused_states
            payload = {"kind": "fused", "step": int(step),
                       "states": dict(states), "num_update": num_update}
            if self._pending_fused_key is not None:
                payload["fused_key"] = np.asarray(self._pending_fused_key)
            return payload
        if self._updater is not None:
            return {"kind": "updater", "blob": self._updater.get_states(),
                    "num_update": num_update}
        return {"kind": "updater", "blob": b"", "num_update": num_update}

    def _install_optimizer_states(self, payload):
        """Inverse of _optimizer_states_to_host (host-numpy payload)."""
        assert self.optimizer_initialized
        kind = payload.get("kind")
        if kind == "updater":
            blob = payload.get("blob")
            if blob:
                if self._update_on_kvstore:
                    updater = getattr(self._kvstore, "_updater", None)
                    if updater is None:
                        raise MXNetError("cannot restore optimizer state: "
                                         "kvstore has no local updater")
                    updater.set_states(blob)
                elif self._updater is not None:
                    self._updater.set_states(blob)
        elif kind == "fused":
            key = payload.get("fused_key")
            if key is not None:
                self._pending_fused_key = np.asarray(key)
            self._install_host_states(int(payload["step"]),
                                      payload["states"])
            if key is not None and self._fused_step is not None:
                # programs already built: place the restored key now
                import jax

                if self._mesh_plan is not None:
                    self._fused_key = self._mesh_plan.place(
                        np.asarray(key), self._mesh_plan.replicated())
                else:
                    self._fused_key = jax.device_put(
                        np.asarray(key), self._context[0].jax_device())
                self._pending_fused_key = None
        else:
            raise MXNetError(
                f"unknown optimizer-state payload kind {kind!r}")
        nu = payload.get("num_update")
        if nu:
            self._optimizer.num_update = max(self._optimizer.num_update,
                                             int(nu))

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            return
        with open(fname, "rb") as f:
            blob = f.read()
        data = pickle.loads(blob)
        if isinstance(data, dict) and \
                data.get("format") == self._FUSED_STATES_FORMAT:
            self._install_host_states(int(data["step"]), data["states"])
            return
        self._updater.set_states(blob)
        if self._use_fused and self._updater.states:
            # legacy index-keyed blob feeding a fused run: map the
            # keys (param_index * num_device, model.py _update_params)
            # back to names so the fused state inherits it
            import jax

            nd_count = len(self._context)
            idx2name = {i * nd_count: n
                        for i, n in enumerate(self._param_names)}
            by_name = {}
            for i, tree in self._updater.states.items():
                n = i if isinstance(i, str) else idx2name.get(i)
                if n in self._param_names:
                    by_name[n] = jax.tree_util.tree_map(
                        lambda a: np.asarray(a), tree)
            if by_name:
                self._restore_fused_states(self._step_count, by_name)
