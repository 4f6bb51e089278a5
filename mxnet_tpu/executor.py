"""Executor — binds a Symbol to buffers and runs it.

Parity with ``include/mxnet/executor.h`` + ``src/executor/graph_executor.cc``
and ``python/mxnet/executor.py``.

TPU-first design (the BASELINE north star): instead of creating one
engine op per graph node (graph_executor.cc:518-648) and pushing them
through a dependency engine, the whole graph is lowered to **one pure
JAX function** and jitted into a **single XLA program**:

* forward (inference)        → ``fwd_infer``  program
* forward+backward (training)→ ``fused``      program — outputs, aux
  updates and all gradients in one XLA computation, so XLA fuses the
  backward with the forward and schedules everything on-chip.  This
  subsumes the reference's Gradient pass, PlanMemory, AttachOpExecs,
  inplace-addto detection and the engine's topo scheduling.

The gradient comes from ``jax.vjp`` over the composed function; MXNet's
"backward ignores head gradients on loss layers" semantics live in the
ops' custom VJPs (ops/nn.py).

grad_req semantics ('write'/'add'/'null') match executor.py /
OpReqType (include/mxnet/op_attr_types.h).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import engine as _engine
from . import profiler as _prof
from .base import MXNetError
from .context import Context
from .ndarray import NDArray, zeros as nd_zeros
from .ops.registry import OpContext
from . import random as _random

__all__ = ["Executor", "simple_bind"]


def build_graph_fn(symbol):
    """Lower a Symbol DAG into a pure function
    ``f(arg_dict, aux_dict, rng, is_train) -> (outputs, new_aux_dict)``.

    This is the NNVM-graph → XLA lowering (replaces per-node engine
    dispatch, SURVEY §3.1 RunOps)."""
    nodes = symbol._topo()
    node_index = {id(n): i for i, n in enumerate(nodes)}
    out_refs = [(id(n), i) for n, i in symbol._outputs]

    def fn(arg_dict, aux_dict, rng, is_train: bool):
        vals: Dict[tuple, Any] = {}
        new_aux: Dict[str, Any] = {}
        for n in nodes:
            if n.is_variable:
                vals[(id(n), 0)] = arg_dict[n.name]
                continue
            op = n.opdef()
            inputs = [vals[(id(i), ix)] for i, ix in n.inputs]
            aux_names = n.aux_names()
            aux_in = [aux_dict[a] for a in aux_names]
            key = None
            if op.needs_rng:
                key = jax.random.fold_in(rng, node_index[id(n)])
            op_ctx = OpContext(is_train=is_train, rng=key)
            # every HLO op carries the symbol's own node name (a
            # name-stack push while tracing; nothing at run time)
            with jax.named_scope(n.name):
                if aux_names:
                    outs, aux_out = op.compute(op_ctx, n.attrs, inputs,
                                               aux_in)
                    for a, v in zip(aux_names, aux_out):
                        new_aux[a] = v
                else:
                    outs = op.compute(op_ctx, n.attrs, inputs, [])
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            for i, o in enumerate(outs):
                vals[(id(n), i)] = o
        outputs = [vals[r] for r in out_refs]
        return outputs, new_aux

    return fn


class Executor:
    """Executable bound graph (reference: python/mxnet/executor.py)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = group2ctx or {}
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        self.arg_dict: Dict[str, NDArray] = self._to_dict(args, self.arg_names, "args")
        self.arg_arrays: List[NDArray] = [self.arg_dict[n] for n in self.arg_names]

        self.aux_dict: Dict[str, NDArray] = self._to_dict(aux_states, self.aux_names, "aux_states") \
            if self.aux_names else {}
        self.aux_arrays: List[NDArray] = [self.aux_dict[n] for n in self.aux_names]

        # grad_req normalization (reference: executor_group / simple_bind)
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}

        if args_grad is None:
            self.grad_dict: Dict[str, NDArray] = {}
        else:
            self.grad_dict = self._to_dict(args_grad, self.arg_names, "args_grad",
                                           allow_missing=True)
        for n in self.arg_names:
            if n not in self.grad_dict:
                self.grad_req[n] = "null"
        self.grad_arrays: List[Optional[NDArray]] = [
            self.grad_dict.get(n) for n in self.arg_names]

        self._grad_names = [n for n in self.arg_names if self.grad_req.get(n, "null") != "null"]
        # gradient mirroring / rematerialization: trade FLOPs for memory
        # by recomputing activations in backward (reference:
        # MXNET_BACKWARD_DO_MIRROR, graph_executor.cc:199-212 → here a
        # jax.checkpoint over the whole forward)
        from .base import get_env
        self._do_mirror = bool(get_env("MXNET_BACKWARD_DO_MIRROR", 0, int))
        self._monitor_callback = None
        self._graph_fn = build_graph_fn(symbol)
        self._jit_fwd = jax.jit(functools.partial(self._fwd, is_train=False))
        self._jit_fwd_train = jax.jit(functools.partial(self._fwd, is_train=True))
        self._jit_fused = jax.jit(self._fused)
        self._jit_fused_ones = jax.jit(self._fused_ones)
        self.outputs_cache: List[NDArray] = []
        self._train_snapshot = None
        self._cached_grads = None
        self._internals_fns: Dict[bool, Any] = {}
        # programs this executor has already run once: first run per
        # tag = trace+compile+run (XLA caches after), telemetered as a
        # compile event.  Shapes are fixed per executor, so a reshape
        # (new Executor) naturally restarts the compile accounting.
        self._warm_programs: set = set()
        # live-buffer-bytes gauge: what this bind pinned on device
        # (args + grads + aux); decremented when the executor dies so
        # bucketed/reshaped executor churn shows up as a sawtooth.
        # Arrays reused from a shared_exec donor (the bucketed shared
        # arena) are the donor's storage — counting them again would
        # overstate live memory by the bucket count.
        import weakref

        donor_ids = set()
        if shared_exec is not None:
            donor_ids = {id(x) for x in (
                list(shared_exec.arg_dict.values())
                + list(shared_exec.grad_dict.values())
                + list(shared_exec.aux_dict.values()))}
        self._buffer_bytes = sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for a in {id(x): x for x in (
                list(self.arg_dict.values()) + list(self.grad_dict.values())
                + list(self.aux_dict.values()))}.values()
            if id(a) not in donor_ids)
        # generation-stamped (returned by the increment itself, so the
        # stamp is atomic with it): a decrement that outlives
        # reset_metrics() must be dropped, not drive the gauge negative
        gen = _prof.inc_gauge("executor.live_buffer_bytes",
                              self._buffer_bytes)
        weakref.finalize(self, _prof.inc_gauge,
                         "executor.live_buffer_bytes", -self._buffer_bytes,
                         gen=gen)

    def _record_program(self, tag, args=None):
        """Span round one program dispatch: first run per tag counts as
        the compile (trace+compile+run — XLA caches afterwards)."""
        compiled = tag not in self._warm_programs
        if compiled:
            self._warm_programs.add(tag)
        ev_args = {"program": tag}
        if args:
            ev_args.update(args)
        return _prof.record_program(
            f"Executor.compile+{tag}" if compiled else f"Executor.{tag}",
            compiled, args=ev_args)

    # ------------------------------------------------------------------
    def _to_dict(self, values, names, what, allow_missing=False) -> Dict[str, NDArray]:
        if values is None:
            raise MXNetError(f"{what} must be provided")
        if isinstance(values, dict):
            d = {}
            for n in names:
                if n in values:
                    d[n] = values[n]
                elif not allow_missing:
                    raise MXNetError(f"{what} missing entry for {n!r}")
            return d
        values = list(values)
        if len(values) != len(names):
            raise MXNetError(f"{what} length {len(values)} != expected {len(names)}")
        return {n: v for n, v in zip(names, values) if v is not None}

    # pure functions to be jitted --------------------------------------
    def _fwd(self, arg_vals, aux_vals, rng, is_train):
        outs, new_aux = self._graph_fn(arg_vals, aux_vals, rng, is_train)
        return outs, new_aux

    def _fused(self, arg_vals, aux_vals, rng, heads):
        grad_names = self._grad_names

        def f(grad_args):
            full = dict(arg_vals)
            full.update(grad_args)
            outs, new_aux = self._graph_fn(full, aux_vals, rng, True)
            return tuple(outs), new_aux

        if self._do_mirror:
            f = jax.checkpoint(f)
        grad_args = {n: arg_vals[n] for n in grad_names}
        (outs, vjp_fn, new_aux) = jax.vjp(f, grad_args, has_aux=True)
        grads = vjp_fn(tuple(heads))[0]
        return list(outs), new_aux, grads

    def _fused_ones(self, arg_vals, aux_vals, rng):
        """Fused fwd+bwd with the default all-ones head gradients (the
        loss-head convention: custom VJPs of loss ops ignore the head).
        One XLA program yields outputs, aux updates and grads."""
        grad_names = self._grad_names

        def f(grad_args):
            full = dict(arg_vals)
            full.update(grad_args)
            outs, new_aux = self._graph_fn(full, aux_vals, rng, True)
            return tuple(outs), new_aux

        if self._do_mirror:
            f = jax.checkpoint(f)
        grad_args = {n: arg_vals[n] for n in grad_names}
        (outs, vjp_fn, new_aux) = jax.vjp(f, grad_args, has_aux=True)
        heads = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
        grads = vjp_fn(heads)[0]
        return list(outs), new_aux, grads

    def _outputs_all_loss_heads(self) -> bool:
        """True when default all-ones head gradients are safe: every
        output is a loss head (custom VJP ignores the head) or a
        BlockGrad (VJP is zero)."""
        from .ops.registry import get_op

        for node, _ in self._symbol._outputs:
            if node.is_variable:
                return False
            op = get_op(node.op)
            if not op.loss_head(node.attrs) and op.name != "BlockGrad":
                return False
        return True

    # ------------------------------------------------------------------
    @property
    def outputs(self) -> List[NDArray]:
        return self.outputs_cache

    def forward(self, is_train: bool = False, **kwargs):
        """reference: MXExecutorForward → GraphExecutor::Forward"""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"unknown forward argument {k!r}")
            if isinstance(v, NDArray):
                self.arg_dict[k]._set_data(v._data.astype(self.arg_dict[k].dtype))
            else:
                self.arg_dict[k][:] = v
        arg_vals = {n: a._data for n, a in self.arg_dict.items()}
        aux_vals = {n: a._data for n, a in self.aux_dict.items()}
        rng = _random.next_key()
        self._train_snapshot = None
        self._cached_grads = None

        if self._monitor_callback is not None:
            self._run_monitor(arg_vals, aux_vals, rng, is_train)

        fused = bool(is_train and self._grad_names
                     and self._outputs_all_loss_heads())
        tag = "fused_fwd_bwd" if fused else \
            "forward/train" if is_train else "forward"
        with self._record_program(tag):
            if fused:
                # training step on a loss-head graph: run the single
                # fused fwd+bwd program now and cache the grads —
                # backward() then just writes them out, so fwd+bwd
                # costs ONE program run
                outs, new_aux, grads = self._jit_fused_ones(
                    arg_vals, aux_vals, rng)
                self._cached_grads = grads
                self._train_snapshot = (arg_vals, aux_vals, rng)
            else:
                fn = self._jit_fwd_train if is_train else self._jit_fwd
                outs, new_aux = fn(arg_vals, aux_vals, rng)
                if is_train and self._grad_names:
                    # stash the *pristine* inputs + rng so a later
                    # backward(out_grads) reproduces this forward
                    # exactly (same dropout masks, same pre-update aux)
                    self._train_snapshot = (arg_vals, aux_vals, rng)
            if _prof._profiler.running:
                jax.block_until_ready(outs)  # real span, not dispatch time
        for name, val in new_aux.items():
            self.aux_dict[name]._set_data(val)
        self.outputs_cache = [NDArray(o, self._ctx) for o in outs]
        _engine.sync_if_naive(self.outputs_cache)
        return self.outputs_cache

    def backward(self, out_grads=None):
        """reference: MXExecutorBackward; writes grads per grad_req.

        With no ``out_grads``, consumes the gradients already computed by
        the fused program ``forward(is_train=True)`` ran — fwd+bwd is ONE
        XLA program run.  With explicit ``out_grads``, re-runs the fused
        program on the snapshotted inputs with those head gradients (same
        PRNG key; aux updates discarded — already applied by forward)."""
        if not self._grad_names:
            return
        if self._train_snapshot is None:
            raise MXNetError("backward() called before forward(is_train=True)")
        if out_grads is None:
            grads = self._cached_grads
            if grads is None:
                # graph has non-loss outputs: all-ones heads would sum
                # unrelated gradients into the params (the reference only
                # attaches gradient to loss heads, graph_executor.cc:167)
                raise MXNetError(
                    "backward() without out_grads requires every output to be "
                    "a loss head (SoftmaxOutput/*RegressionOutput/MakeLoss/"
                    "SVMOutput); pass explicit out_grads for non-loss outputs")
        else:
            arg_vals, aux_vals, rng = self._train_snapshot
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            heads = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                     for g in out_grads]
            if len(heads) != len(self.output_names):
                raise MXNetError(
                    f"out_grads has {len(heads)} entries for "
                    f"{len(self.output_names)} outputs")
            with self._record_program("backward"):
                _, _, grads = self._jit_fused(arg_vals, aux_vals, rng,
                                              heads)
                if _prof._profiler.running:
                    jax.block_until_ready(grads)
        for name in self._grad_names:
            g = grads[name]
            dst = self.grad_dict[name]
            if self.grad_req[name] == "add":
                dst._set_data(dst._data + g.astype(dst.dtype))
            else:
                dst._set_data(g.astype(dst.dtype))
        _engine.sync_if_naive([self.grad_dict[n] for n in self._grad_names])

    def forward_backward(self, **kwargs):
        """Fused one-program training step (TPU fast path)."""
        outs = self.forward(is_train=True, **kwargs)
        self.backward()
        return outs

    # ------------------------------------------------------------------
    def set_monitor_callback(self, callback):
        """reference: MXExecutorSetMonitorCallback (monitor.py tap)"""
        self._monitor_callback = callback

    def _run_monitor(self, arg_vals, aux_vals, rng, is_train):
        internals = self._symbol.get_internals()
        fn = self._internals_fns.get(bool(is_train))
        if fn is None:
            gfn = build_graph_fn(internals)
            fn = jax.jit(functools.partial(gfn, is_train=bool(is_train)))
            self._internals_fns[bool(is_train)] = fn
        outs, _ = fn(arg_vals, aux_vals, rng)
        for name, val in zip(internals.list_outputs(), outs):
            self._monitor_callback(name, NDArray(val, self._ctx))

    # ------------------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        """reference: executor.py copy_params_from"""
        for k, v in arg_params.items():
            if k in self.arg_dict:
                self.arg_dict[k]._set_data(v._data.astype(self.arg_dict[k].dtype)
                                           if isinstance(v, NDArray) else jnp.asarray(v))
            elif not allow_extra_params:
                raise MXNetError(f"Found name {k!r} not in executor arguments")
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    self.aux_dict[k]._set_data(v._data if isinstance(v, NDArray) else jnp.asarray(v))
                elif not allow_extra_params:
                    raise MXNetError(f"Found name {k!r} not in executor aux states")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor with new input shapes (weights shared).
        reference: executor.py reshape.  XLA recompiles per shape and
        caches — the per-bucket executor pattern."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("insufficient shapes for reshape")
        new_args = {}
        new_grads = {}
        for name, sh in zip(self.arg_names, arg_shapes):
            old = self.arg_dict[name]
            if tuple(old.shape) == tuple(sh):
                new_args[name] = old
                if name in self.grad_dict:
                    new_grads[name] = self.grad_dict[name]
            else:
                if not partial_shaping and name not in kwargs:
                    raise MXNetError(
                        f"reshape changed shape of {name!r}; pass partial_shaping=True")
                new_args[name] = nd_zeros(sh, self._ctx, old.dtype)
                if name in self.grad_dict:
                    new_grads[name] = nd_zeros(sh, self._ctx, old.dtype)
        new_aux = {}
        for name, sh in zip(self.aux_names, aux_shapes or []):
            old = self.aux_dict[name]
            new_aux[name] = old if tuple(old.shape) == tuple(sh) else nd_zeros(sh, self._ctx, old.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads or None,
                        self.grad_req, new_aux or None, group2ctx=self._group2ctx)

    def debug_str(self):
        return self._symbol.debug_str()


def simple_bind(symbol, ctx, grad_req="write", type_dict=None, group2ctx=None,
                shared_exec=None, **kwargs) -> Executor:
    """Allocate all buffers from inferred shapes and bind.

    reference: MXExecutorSimpleBind path used by Module
    (graph_executor.cc:697 Bind + InitArguments).
    """
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
    if arg_shapes is None:
        raise MXNetError(f"cannot infer shapes from {kwargs}")
    arg_types, _, aux_types = symbol.infer_type(**(type_dict or {}))
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    ctx = ctx if isinstance(ctx, Context) else Context(ctx)

    args = {}
    args_grad = {}
    if isinstance(grad_req, str):
        req = {n: grad_req for n in arg_names}
    elif isinstance(grad_req, (list, tuple)):
        req = dict(zip(arg_names, grad_req))
    else:
        req = {n: grad_req.get(n, "null") for n in arg_names}

    # shared_exec: reuse the donor executor's arrays where name+shape+dtype
    # match — the same NDArray *objects*, so params/grads stay one storage
    # across bucketed executors (the reference's shared memory pool,
    # graph_executor.cc:330-334/423-515; inputs differ in shape and get
    # fresh buffers)
    def _reusable(pool, name, shape, dt):
        old = pool.get(name) if pool else None
        if old is not None and tuple(old.shape) == tuple(shape) \
                and old.dtype == np.dtype(dt):
            return old
        return None

    sh_args = shared_exec.arg_dict if shared_exec is not None else None
    sh_grads = shared_exec.grad_dict if shared_exec is not None else None
    sh_aux = shared_exec.aux_dict if shared_exec is not None else None
    for name, shape, dt in zip(arg_names, arg_shapes, arg_types):
        shared = _reusable(sh_args, name, shape, dt)
        args[name] = shared if shared is not None else nd_zeros(shape, ctx, dt)
        if req.get(name, "null") != "null":
            shared = _reusable(sh_grads, name, shape, dt)
            args_grad[name] = (shared if shared is not None
                               else nd_zeros(shape, ctx, dt))
    aux = {}
    for name, shape, dt in zip(aux_names, aux_shapes, aux_types):
        shared = _reusable(sh_aux, name, shape, dt)
        aux[name] = shared if shared is not None else nd_zeros(shape, ctx, dt)
    return Executor(symbol, ctx, args, args_grad or None, req, aux or None,
                    group2ctx=group2ctx, shared_exec=shared_exec)
