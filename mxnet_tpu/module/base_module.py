"""BaseModule — training-loop state machine.

Parity with ``python/mxnet/module/base_module.py`` (31-449): the
bind → init_params → init_optimizer lifecycle plus ``fit``, ``score``,
``predict``, ``forward_backward``, ``iter_predict``.
"""

from __future__ import annotations

import logging
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

import numpy as np

from .. import metric as metric_mod
from .. import profiler as _prof
from ..base import MXNetError
from ..model import BatchEndParam
from ..ndarray import NDArray
import mxnet_tpu.ndarray as nd


class BaseModule:
    """reference: base_module.py:31 BaseModule"""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------------------------
    # High-level interface (reference: base_module.py:140-449)
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None, batch_end_callback=None,
              score_end_callback=None, reset=True, epoch=0):
        """reference: base_module.py score"""
        assert self.binded and self.params_initialized
        with self._adopted_prologue(eval_data):
            if reset:
                eval_data.reset()
            if not isinstance(eval_metric, metric_mod.EvalMetric):
                eval_metric = metric_mod.create(eval_metric)
            eval_metric.reset()
            actual_num_batch = 0
            for nbatch, eval_batch in enumerate(eval_data):
                if num_batch is not None and nbatch == num_batch:
                    break
                self.forward(eval_batch, is_train=False)
                self.update_metric(eval_metric, eval_batch.label)
                if batch_end_callback is not None:
                    batch_end_params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                     eval_metric=eval_metric, locals=locals())
                    for callback in _as_list(batch_end_callback):
                        callback(batch_end_params)
                actual_num_batch += 1
            if score_end_callback:
                params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                       eval_metric=eval_metric, locals=locals())
                for callback in _as_list(score_end_callback):
                    callback(params)
            return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        with self._adopted_prologue(eval_data):
            if reset:
                eval_data.reset()
            for nbatch, eval_batch in enumerate(eval_data):
                if num_batch is not None and nbatch == num_batch:
                    break
                self.forward(eval_batch, is_train=False)
                pad = eval_batch.pad
                outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
                yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True, reset=True,
                always_output_list=False):
        """reference: base_module.py:243 predict"""
        assert self.binded and self.params_initialized
        output_list = []
        with self._adopted_prologue(eval_data):
            if reset:
                eval_data.reset()
            for nbatch, eval_batch in enumerate(eval_data):
                if num_batch is not None and nbatch == num_batch:
                    break
                self.forward(eval_batch, is_train=False)
                pad = eval_batch.pad
                outputs = [out[0:out.shape[0] - pad].copy() for out in self.get_outputs()]
                output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the same " + \
                    "in mini-batches. Maybe bucketing is used?"
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint=None, resume=None, elastic_data=None):
        """Train loop (reference: base_module.py:315 fit).

        Fault tolerance: pass a ``mxnet_tpu.checkpoint.CheckpointManager``
        as ``checkpoint`` (or set ``MXNET_CKPT_DIR``) to snapshot the
        full training state on the ``MXNET_CKPT_EVERY_N_STEPS`` cadence
        and on SIGTERM (preemption).  ``resume='auto'`` restores the
        newest committed checkpoint — parameters, optimizer state,
        lr-scheduler step, RNG, and the exact epoch/batch position of
        the data iterator — and continues as if never interrupted.

        Elastic mode (``MXNET_ELASTIC=1``): the loop survives rank
        death.  A :class:`~mxnet_tpu.elastic.DeadRankError` verdict
        (barrier timeout / transport failure + stale heartbeat) makes
        the survivors agree on a shrunk membership epoch, re-scatter
        the weights from the last committed checkpoint, roll their own
        training state back to it, and CONTINUE — no operator action.
        A restarted rank re-joins at the next checkpoint boundary.
        ``elastic_data(active_ranks) -> DataIter`` rebuilds this rank's
        data shard for a new membership (keep the GLOBAL batch layout
        fixed so batch indices stay comparable across epochs of any
        world size); positioning is reset-and-skip to the checkpointed
        batch, so no sample is dropped or double-counted relative to
        the rollback point.
        """
        assert num_epoch is not None, "please specify number of epochs"
        from ..base import get_env
        from ..chaos import get_chaos
        from ..elastic import DeadRankError, elastic_enabled
        from ..initializer import Uniform

        elastic = elastic_enabled()
        chaos = get_chaos()
        if initializer is None:
            initializer = Uniform(0.01)

        if checkpoint is None:
            ckpt_dir = get_env("MXNET_CKPT_DIR", None, str)
            if ckpt_dir:
                from ..checkpoint import CheckpointManager

                checkpoint = CheckpointManager(ckpt_dir, logger=self.logger)
        if resume not in (None, False, True, "auto", "never"):
            raise MXNetError(f"fit: resume must be 'auto'/'never'/bool, "
                             f"got {resume!r}")
        ckpt_state = None
        if resume in (True, "auto"):
            if checkpoint is None:
                raise MXNetError("fit(resume='auto') needs a checkpoint "
                                 "manager (or MXNET_CKPT_DIR)")
            ckpt_state = checkpoint.load_latest()
            if ckpt_state is not None:
                arg_params = ckpt_state["arg_params"]
                aux_params = ckpt_state["aux_params"]
                begin_epoch = ckpt_state["epoch"]
                force_init = True

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        # device-offloaded augmentation: an iterator built with
        # device_augment=1 yields raw uint8 batches plus the fused
        # jitted prologue that finishes them ON DEVICE inside fit.step
        self._install_data_prologue(train_data)

        resume_nbatch = -1
        if checkpoint is not None:
            checkpoint.attach(self, train_data)
            checkpoint.install_signal_handler()
            if ckpt_state is not None:
                if elastic:
                    # the saving rank's iterator snapshot may come from
                    # a DIFFERENT membership (other local batch size /
                    # shard): position by batch index instead — reset
                    # and skip through the checkpointed batch, which is
                    # membership-invariant when the global batch layout
                    # is fixed
                    checkpoint.restore_training_state(self, ckpt_state,
                                                      train_iter=None)
                    _skip_batches(train_data, ckpt_state["nbatch"] + 1)
                else:
                    checkpoint.restore_training_state(self, ckpt_state,
                                                      train_data)
                resume_nbatch = ckpt_state["nbatch"]

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        kv_obj = getattr(self, "_kvstore", None)
        self._fit_step_count = getattr(self, "_fit_step_count", 0)

        # live efficiency accounting (PR 12): every loop iteration
        # feeds the goodput tracker one wall decomposition sample —
        # io-wait vs step vs checkpoint-blocking — and the fused step
        # contributes its FLOPs (module.py) for the training.mfu
        # gauge.  The MXNET_METRICS_PORT ops endpoint (if configured)
        # makes all of it scrapeable DURING the fit.
        _prof.maybe_start_metrics_server()
        goodput = _prof.goodput_tracker()

        ################################################################
        # training loop (reference: base_module.py:404-449); a while
        # loop so an elastic rollback can REWIND epoch/nbatch to the
        # last committed checkpoint and keep going
        ################################################################
        epoch = begin_epoch
        while epoch < num_epoch:
            tic = time.time()
            eval_metric.reset()
            # manual iteration so the step timeline can split "waiting
            # on the input pipeline" (io.next) from the training step
            # itself (fit.step) — the two spans every per-step perf
            # question starts from
            train_iter = iter(train_data)
            nbatch = 0
            if resume_nbatch >= 0:
                # the restored iterator continues mid-epoch right after
                # the checkpointed batch; keep nbatch aligned with it
                nbatch = resume_nbatch + 1
                resume_nbatch = -1
            rolled_back = False
            while True:
                t_io0 = time.perf_counter()
                with _prof.scope("io.next", "io",
                                 args={"epoch": epoch, "step": nbatch}):
                    try:
                        data_batch = next(train_iter)
                    except StopIteration:
                        break
                io_s = time.perf_counter() - t_io0
                if monitor is not None:
                    monitor.tic()
                if checkpoint is not None:
                    checkpoint.step_begin()
                try:
                    chaos.on_step(self._fit_step_count,
                                  rank=getattr(kv_obj, "rank", None))
                    self._fit_step_count += 1
                    t_step0 = time.perf_counter()
                    with _prof.scope("fit.step", "step",
                                     args={"epoch": epoch, "step": nbatch}):
                        self.forward_backward(data_batch)
                        self.update()
                    step_s = time.perf_counter() - t_step0
                    self.update_metric(eval_metric, data_batch.label)
                    ckpt_s = 0.0
                    if checkpoint is not None:
                        t_ck0 = time.perf_counter()
                        checkpoint.step_end(self, epoch=epoch,
                                            nbatch=nbatch,
                                            train_iter=train_data)
                        ckpt_s = time.perf_counter() - t_ck0
                    goodput.step(step_s, io_s=io_s, ckpt_s=ckpt_s)
                    # once per BUILT program, attribute the fused
                    # program's OWN collectives to the comm fraction
                    # (in-program reduce-scatter/all-gather otherwise
                    # books as compute).  Walks the compiled program's
                    # HLO text once per program, so it waits for step 8 —
                    # short smoke fits never pay — unless the ops
                    # endpoint is live (an operator is watching; pay at
                    # step 1).  Called every step past the threshold:
                    # the module's per-program guard makes repeats free
                    # and re-accounts after a mid-fit rebuild/re-mesh
                    if (self._fit_step_count >= 8
                            or (self._fit_step_count == 1
                                and _prof.metrics_server_running())) \
                            and hasattr(self, "account_program_comm"):
                        self.account_program_comm()
                    if checkpoint is not None:
                        admitted = self._elastic_admit(
                            kv_obj, checkpoint, elastic_data, elastic)
                        if admitted is not None:
                            # membership grew: swap in this rank's
                            # re-sharded data mid-epoch, positioned at
                            # the batch we just finished
                            train_data = admitted
                            _skip_batches(train_data, nbatch + 1)
                            train_iter = iter(train_data)
                            checkpoint.attach(self, train_data)
                except DeadRankError as dead:
                    if checkpoint is not None:
                        checkpoint.step_abandoned()
                    train_data, epoch, resume_nbatch = \
                        self._elastic_recover(dead, kv_obj, checkpoint,
                                              elastic_data, train_data)
                    rolled_back = True
                    break
                if monitor is not None:
                    monitor.toc_print()
                if batch_end_callback is not None:
                    batch_end_params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                     eval_metric=eval_metric,
                                                     locals=locals())
                    for callback in _as_list(batch_end_callback):
                        callback(batch_end_params)
                nbatch += 1

            if rolled_back:
                continue  # re-enter the (possibly rewound) epoch

            # one epoch of training is finished
            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))

            arg_params_, aux_params_ = self.get_params()
            self.set_params(arg_params_, aux_params_)

            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params_, aux_params_)

            if eval_data:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)

            train_data.reset()
            epoch += 1
        if checkpoint is not None:
            # land queued async snapshots before the process can exit
            checkpoint.flush()

    # ------------------------------------------------------------------
    # Elastic fault tolerance (ISSUE 8): rollback-resume + re-admission
    # ------------------------------------------------------------------
    def _elastic_recover(self, dead, kv, checkpoint, elastic_data,
                         train_data):
        """Resume-in-place after a DeadRankError verdict.

        Survivors (1) agree on the shrunk membership epoch, (2)
        re-scatter the last committed checkpoint's weights onto the
        surviving parameter-server shards (``DistKVStore.remesh``), (3)
        roll their own params/optimizer/RNG back to that snapshot, (4)
        rebuild this rank's data shard for the new membership and
        position it at the checkpointed batch.  Returns ``(train_data,
        epoch, resume_nbatch)`` for fit to continue from.  Without a
        checkpoint there is nothing consistent to roll back to — the
        verdict propagates."""
        from .. import profiler as _prof_mod
        from ..base import MXNetError as _MXE

        _prof_mod.inc_counter("elastic.dead_rank_verdicts")
        # the verdict IS the post-mortem moment: capture what this
        # survivor was doing in the seconds before the death
        dead.dump_flight_record()
        if checkpoint is None:
            raise _MXE(
                "elastic recovery needs a CheckpointManager (pass "
                "checkpoint=/set MXNET_CKPT_DIR with a save cadence): "
                f"cannot roll back after {dead}") from dead
        self.logger.warning("[elastic] %s — re-meshing and rolling back "
                            "to the last committed checkpoint", dead)
        t0 = time.time()
        with checkpoint.rollback():
            membership = getattr(kv, "membership", None)
            rec = None
            if membership is not None:
                rec = membership.remesh(
                    dead.dead_ranks,
                    is_alive=lambda r: not kv.dead_ranks(ranks=[r]))
            state = checkpoint.load_latest()
            if state is None:
                raise _MXE(
                    "elastic recovery found no committed checkpoint to "
                    "roll back to (did the first save cadence fire?)"
                ) from dead
            if membership is not None:
                # kv keys are param indices (model._initialize_kvstore)
                names = getattr(self, "_param_names",
                                list(state["arg_params"]))
                restored = {i: np.asarray(state["arg_params"][n])
                            for i, n in enumerate(names)}
                kv.remesh(rec, restored_params=restored)
            # module-side rollback: params, optimizer state, RNG, step
            self.set_params(state["arg_params"], state["aux_params"])
            checkpoint.restore_training_state(self, state, train_iter=None)
            opt = getattr(self, "_optimizer", None)
            if opt is not None:
                # restore_training_state only ever RAISES num_update
                # (max with the live value, the forward-resume case);
                # a rollback must REWIND it or every lr_scheduler step
                # replays at post-death learning rates forever
                nu = (state.get("optimizer") or {}).get("num_update")
                if nu is not None:
                    opt.num_update = int(nu)
            if membership is not None:
                if getattr(self, "_update_on_kvstore", False) \
                        and opt is not None:
                    # the shard reset cleared the server-side updater;
                    # re-install AFTER the rollback so the shards get
                    # the rewound optimizer, not the pre-death one
                    kv.set_optimizer(opt)
                if getattr(self, "_auto_rescale", False) \
                        and opt is not None \
                        and "dist" in kv.type and "_sync" in kv.type:
                    # the 1/global-batch default must track the new
                    # world size (a user-pinned rescale is never
                    # touched); same dist_sync derivation as
                    # init_optimizer — mesh-plan runs (batch_scale)
                    # re-mesh through Module.remesh, not this path
                    local_batch = self._data_shapes[0][1][0]
                    opt.rescale_grad = 1.0 / (local_batch * kv.num_workers)
            # data: re-shard for the new membership, positioned at the
            # checkpointed batch (reset-and-skip keeps batch indices
            # membership-invariant)
            if elastic_data is not None and rec is not None:
                train_data = elastic_data(list(rec["active"]))
                checkpoint.attach(self, train_data)
            _skip_batches(train_data, state["nbatch"] + 1)
        _prof_mod.observe("elastic.recover_ms",
                          (time.time() - t0) * 1e3)
        # goodput accounting: the whole re-mesh + rollback window is
        # attributed LOST time (training.lost_s.remesh), so the
        # goodput gauge keeps telling the truth across elastic events
        _prof_mod.goodput_tracker().add_lost(time.time() - t0, "remesh")
        self.logger.warning(
            "[elastic] resumed at epoch %d batch %d (step %d) after "
            "%.2fs", state["epoch"], state["nbatch"] + 1, state["step"],
            time.time() - t0)
        return train_data, int(state["epoch"]), int(state["nbatch"])

    def _elastic_admit(self, kv, checkpoint, elastic_data, elastic):
        """Checkpoint-boundary re-admission (scale back up).

        Runs on EVERY active rank right after a cadence save so the
        epoch flip is collective: the lowest active rank scans join
        requests and commits the admitting epoch; an elastic barrier
        aligns everyone; then every rank reads the ledger and, if the
        epoch advanced, attaches to it (quorum grows, round clocks
        restart) and re-shards its data.  Returns the new DataIter for
        this rank (caller positions it), or None."""
        if not elastic or kv is None or checkpoint is None:
            return None
        membership = getattr(kv, "membership", None)
        if membership is None:
            return None
        every = checkpoint.every_n_steps
        if not every or checkpoint._step % every != 0:
            return None  # not a boundary — every rank agrees (cadence
            #               and step counters are deterministic)
        if kv.rank == min(kv.active_ranks):
            from ..elastic import dead_rank_timeout

            joins = membership.pending_joins(
                max_age=dead_rank_timeout())
            if joins:
                # only admit against a committed checkpoint of THIS
                # step: the joiner restores from it, and both sides
                # must resume from identical state
                checkpoint.flush()
                from ..checkpoint import list_checkpoints
                committed = [i for i in list_checkpoints(checkpoint.dir)
                             if i.committed]
                if committed and committed[-1].step == checkpoint._step:
                    try:
                        membership.admit(joins)
                    except MXNetError as exc:
                        # lost an epoch-commit race (e.g. a concurrent
                        # scale-down consensus) — the winner's record
                        # is attached below; re-admit next boundary
                        self.logger.warning("[elastic] %s", exc)
        kv._elastic_barrier()
        rec = membership.read()
        if rec is None or rec["epoch"] <= kv.epoch:
            return None
        kv.remesh(rec)  # scale-up: weights stay live on the shards
        self.logger.warning("[elastic] scaled up to active=%s at "
                            "membership epoch %d", rec["active"],
                            rec["epoch"])
        if elastic_data is not None:
            return elastic_data(list(rec["active"]))
        return None

    @contextmanager
    def _adopted_prologue(self, data_iter):
        """Adopt ``data_iter``'s device-side input prologue for one
        eval/predict pass, restoring whatever was installed before
        (fit's training prologue, possibly with a different raw
        pre-crop shape) when the pass ends — the next train epoch's
        fused step must see the training prologue again."""
        prev = getattr(self, "_input_prologue", None)
        self._install_data_prologue(data_iter)
        try:
            yield
        finally:
            if getattr(self, "_input_prologue", None) is not prev:
                self.set_input_prologue(prev)

    def _install_data_prologue(self, data_iter):
        """Adopt the data iterator's device-side input prologue (the
        fused crop/flip/normalize/mixup of device_augment mode).  A
        plain iterator installs None — explicitly clearing any prologue
        a previous fit left behind, so switching back to a host-format
        iterator never routes its batches through a stale raw-shape
        check."""
        prologue = getattr(data_iter, "device_prologue", None)
        if hasattr(self, "set_input_prologue"):
            self.set_input_prologue(prologue)
        elif prologue is not None:
            # silently dropping the prologue would feed raw uint8 NHWC
            # batches to an executor bound for the final NCHW shape and
            # die in an opaque broadcast error far from the cause
            raise MXNetError(
                f"{type(self).__name__} does not support device-side "
                "input augmentation; rebuild the iterator with "
                "device_augment=0 (host augmentation)")

    # ------------------------------------------------------------------
    # Symbol & params (reference: base_module.py:452-545)
    # ------------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False, force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        from ..checkpoint import atomic_save

        arg_params, aux_params = self.get_params()
        save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
        save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
        atomic_save(fname, lambda tmp: nd.save(tmp, save_dict))

    def load_params(self, fname):
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError(f"Invalid param file {fname}")
        self.set_params(arg_params, aux_params)

    # computation interface
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]


def _skip_batches(data_iter, n):
    """Position a fresh epoch of ``data_iter`` AFTER its first ``n``
    batches — the membership-invariant way to land on a checkpointed
    position when the local shard layout may differ from the saving
    run's (elastic re-shard): batch INDICES line up across any world
    size as long as the global batch layout is fixed, while a raw
    cursor snapshot would not."""
    data_iter.reset()
    if n <= 0:
        return
    it = iter(data_iter)
    for _ in range(n):
        try:
            next(it)
        except StopIteration:
            break
