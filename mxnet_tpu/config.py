"""Runtime configuration catalog.

The reference documents its ~20 ``MXNET_*`` env vars in
``docs/how_to/env_var.md`` read via ``dmlc::GetEnv`` (SURVEY §5.6).
This module is the equivalent declarative catalog: every environment
variable the framework reads, with type, default, and documentation —
queryable at runtime (``mx.config.list_env()``, ``describe()``) so
configuration is discoverable rather than folklore.
"""

from __future__ import annotations

import os
from collections import namedtuple
from typing import Any, Dict, List

from .base import get_env

__all__ = ["EnvVar", "register_env", "list_env", "describe", "current",
           "env_bool", "ensure_overlap_flags", "place_compile_cache",
           "refuse_shared_chip"]

EnvVar = namedtuple("EnvVar", ["name", "default", "dtype", "doc"])

_CATALOG: Dict[str, EnvVar] = {}


def register_env(name: str, default, dtype: type, doc: str) -> None:
    """Declare an environment variable the framework reads."""
    _CATALOG[name] = EnvVar(name, default, dtype, doc)


def list_env() -> List[EnvVar]:
    """All declared env vars, sorted by name."""
    return [_CATALOG[k] for k in sorted(_CATALOG)]


def describe(name: str) -> EnvVar:
    if name not in _CATALOG:
        raise KeyError(f"{name!r} is not a declared mxnet_tpu env var; "
                       f"known: {sorted(_CATALOG)}")
    return _CATALOG[name]


def current() -> Dict[str, Any]:
    """Effective value of every declared var (env override or default)."""
    return {v.name: get_env(v.name, v.default, v.dtype)
            for v in list_env()}


# ---------------------------------------------------------------------------
# The catalog (reference: docs/how_to/env_var.md)
# ---------------------------------------------------------------------------

register_env(
    "MXNET_FUSED_STEP", "1", str,
    "'1' (default): Module training runs as ONE donated XLA program "
    "(forward+backward+optimizer).  '0': separate forward/backward/"
    "update programs (debugging; the reference's per-phase execution).")
register_env(
    "MXNET_BACKWARD_DO_MIRROR", 0, int,
    "1: recompute activations in backward (jax.checkpoint over the "
    "forward) instead of storing them — memory down, ~30% more FLOPs.  "
    "The reference's gradient-mirroring flag "
    "(graph_executor.cc:199-212).")
register_env(
    "MXNET_ZERO", 1, int,
    "1 (default): when a device mesh with dp>1 is active, the fused "
    "training step runs the ZeRO-1 sharded-optimizer update — "
    "gradients reduce-scattered over 'dp', Adam/momentum slots stored "
    "and updated on the local 1/dp shard only, parameters all-gathered "
    "back in-program (Rajbhandari et al., 2020 stage 1).  Cuts "
    "per-device optimizer-state bytes and update FLOPs ~dp×; "
    "tests/test_zero.py holds both.  0: replicate the optimizer state and the "
    "update on every device (the pre-ZeRO behavior).  Checkpointed "
    "optimizer states are layout-independent either way.")
register_env(
    "MXNET_PP", 1, int,
    "Pipeline-parallel degree of the device mesh built by "
    "parallel.make_plan (the kvstore='tpu' idiom): the mesh becomes "
    "dp x pp x tp and the fused training step runs the mxnet_tpu.pp "
    "interleaved-1F1B microbatch pipeline over __pp_block__-annotated "
    "models (models/transformer.py).  The layer count must divide by "
    "pp.  Garbage ('banana'), zero or negative values raise at plan "
    "construction.")
register_env(
    "MXNET_MICROBATCHES", None, int,
    "Microbatch count of the pipeline schedule (= gradient-"
    "accumulation depth inside the ONE fused program).  Unset: 2*pp "
    "when pp > 1, else 1.  The global batch must divide by "
    "dp x microbatches (MeshPlan.check_batch).  More microbatches "
    "shrink the pipeline bubble — (pp-1)/(microbatches+pp-1) — at the "
    "cost of per-microbatch activation stash.  Garbage, zero or "
    "negative values raise at plan construction.")
register_env(
    "MXNET_PARTITION_RULES", None, str,
    "Logical-axis partition rules table as ';'-separated 'regex:axis' "
    "entries, first match wins, axis '-' = replicated (e.g. "
    "'batch:dp;vocab|qkv|heads|ffn:tp;embed|length:-').  Parameters "
    "and activations carry logical axis names "
    "(parallel.logical_axes); every placement — params, inputs, "
    "activations, ZeRO optimizer state ('zero' axis) — resolves "
    "through this ONE table.  A named axis no rule matches raises "
    "loudly.  Malformed entries raise at plan construction.")
register_env(
    "MXNET_ZERO_BUCKET_BYTES", 4 << 20, int,
    "Capacity in BYTES of one in-program gradient-collective bucket "
    "(default 4 MiB): the ZeRO-1 update segment packs same-dtype "
    "flat gradients into buckets EMITTED IN BACKWARD ORDER, one "
    "reduce-scatter + one updated-param all-gather per bucket, so the "
    "async-collective scheduler can run layer i's gradient collective "
    "under layer i-1's backward compute (see README 'Training "
    "raw-speed').  The pack layout is deterministic and per-lane "
    "(pack -> sum -> unpack == per-key sums bitwise, the PR-3 comm.py "
    "contract), so bucket size never changes numerics.  0: ONE "
    "monolithic bucket holding every gradient (the serialized "
    "baseline the overlap tests compare against).  A single gradient "
    "larger than the bound rides its own bucket.  Negative or garbage "
    "values raise when the fused step is built.")
register_env(
    "MXNET_PP_RESIDENT", 1, int,
    "1 (default): under pipeline parallelism (pp > 1) the stacked "
    "block parameters are stored STAGE-RESIDENT — per-slot (S, L/S, "
    "...) slabs sharded P('pp', ...) so each pipeline stage holds "
    "only its own layers' weights and optimizer state (~1/pp the "
    "bytes; Module.param_bytes_per_device() is the number).  Stage-boundary "
    "data movement runs through explicit shard_map ppermute/psum "
    "helpers, NOT the SPMD partitioner's handling of a 'pp'-sharded "
    "scan carry — the documented MXNET_PP_CONSTRAIN miscompile on "
    "this jaxlib never gets a chance to fire (equivalence-tested "
    "against the replicated path, tests/test_pp.py).  0: the "
    "replicated-weight path (stacked block weights rest replicated "
    "over pp; the pre-residency behavior).  Values other than 0/1 "
    "raise when the fused step is built.")
register_env(
    "MXNET_ASYNC_COLLECTIVES", 1, int,
    "1 (default): when the process targets the TPU, append libtpu's "
    "async-collective flags to LIBTPU_INIT_ARGS at import "
    "(xla_enable_async_all_gather / xla_enable_async_collective_"
    "permute / xla_tpu_enable_async_collective_fusion* / "
    "xla_tpu_overlap_compute_collective_tc) so the per-bucket "
    "gradient collectives emitted by the ZeRO update segment overlap "
    "backward/update compute — the in-program analogue of the PR-3 "
    "CommScheduler.  Flags the user already set there are never "
    "overridden; XLA_FLAGS is never touched (jaxlib aborts on flags "
    "it does not know, and these are libtpu's).  On CPU nothing is "
    "appended.  0: leave LIBTPU_INIT_ARGS untouched.  Values other "
    "than 0/1 raise at import.")
register_env(
    "MXNET_PP_CONSTRAIN", 0, int,
    "1: pin the pipeline's (stage, microbatch, ...) activation stash "
    "to its stage-resident P('pp', ...) placement with explicit "
    "sharding constraints.  0 (default): leave the stash layout to "
    "XLA's propagation — required on this jaxlib, whose SPMD "
    "partitioner miscompiles roll/select updates of a 'pp'-sharded "
    "scan carry at some shapes (silently wrong values; the "
    "pp-vs-single-process equivalence tests catch it).  Turn on with "
    "newer toolchains to guarantee stage placement.")
register_env(
    "MXNET_PP_SCHEDULE", "1f1b", str,
    "Pipeline microbatch schedule: '1f1b' (default, interleaved "
    "PipeDream-flush compute ordering) or 'gpipe' (all-forwards-then-"
    "all-backwards).  Both run in the optimal 2*(microbatches + pp - "
    "1) ticks, and in this implementation both keep the full (pp x "
    "microbatches) activation stash — 1f1b changes compute order "
    "(and bounds the LIVE window on stage-resident runs), it does "
    "not shrink the stash allocation today.  Unknown values raise "
    "when the fused step is built.")
register_env(
    "MXNET_CONV_LAYOUT", "NCHW", str,
    "Internal lowering layout for 2-D Convolution: 'NCHW' (default, "
    "direct) or 'NHWC' (channels-last dimension numbers with "
    "transposes at the conv edges).  Measured identical on the fused "
    "ResNet-50 step (XLA's layout assignment already relayouts); kept "
    "as an experiment knob — see PERF.md.")
register_env(
    "MXNET_PALLAS", None, str,
    "Force the hand-written Pallas kernels on ('1') or off ('0').  "
    "Unset (default): kernels run on TPU backends, lax fallbacks "
    "elsewhere.  Forcing on off-TPU uses the (slow) interpreter — "
    "useful for testing the kernel code path.")
register_env(
    "MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice", str,
    "Scheduling mode (reference: src/engine/engine.cc:13-39).  "
    "'NaiveEngine': synchronous debugging — every op blocks to "
    "completion so failures surface at the faulting call.  The two "
    "threaded names mean normal async XLA dispatch.")
register_env(
    "MXNET_PROFILER_AUTOSTART", 0, int,
    "1: start the Chrome-trace profiler at import "
    "(reference: env_var.md MXNET_PROFILER_AUTOSTART).")
register_env(
    "MXNET_PROFILER_NO_AUTOSTART", 0, int,
    "1: ignore MXNET_PROFILER_AUTOSTART — lets test suites and "
    "embedding apps import the package without an env var flipping "
    "global profiler state.")
register_env(
    "MXNET_WATCHDOG_DEADLINE", 60.0, float,
    "Seconds a kvstore barrier or a parameter-server sync round may "
    "stay open before the straggler watchdog logs which ranks have "
    "arrived and which are late (instead of hanging silently).  0 "
    "disables.  Naming ranks at a barrier needs the launcher's SHARED "
    "MXNET_KVSTORE_HEARTBEAT_DIR (arrival stamps); without it the "
    "timeout is still reported, anonymously.")
register_env(
    "MXNET_COORDINATOR", None, str,
    "host:port of the JAX coordination service for multi-process "
    "(dist_*) runs.  Set by tools/launch.py; requires "
    "MXNET_NUM_WORKERS and MXNET_WORKER_ID.")
register_env(
    "MXNET_NUM_WORKERS", 1, int,
    "Total process count of a dist_* run (launcher-set).")
register_env(
    "MXNET_WORKER_ID", 0, int,
    "This process's rank in a dist_* run (launcher-set).")
register_env(
    "MXNET_KVSTORE_HEARTBEAT_DIR", None, str,
    "Shared directory for worker heartbeat files (liveness /  "
    "get_num_dead_node) and, in elastic mode, the membership ledger.  "
    "Set by tools/launch.py.")
register_env(
    "MXNET_KVSTORE_HEARTBEAT_INTERVAL", 1.0, float,
    "DEPRECATED alias of MXNET_HEARTBEAT_INTERVAL (still honored when "
    "the new name is unset).")
register_env(
    "MXNET_HEARTBEAT_INTERVAL", 1.0, float,
    "Seconds between heartbeat-file touches — the single liveness-"
    "cadence knob read by the kvstore heartbeat writer and implied by "
    "every staleness scan.  Must be well under "
    "MXNET_DEAD_RANK_TIMEOUT.  Garbage or non-positive values raise at "
    "kvstore construction.")
register_env(
    "MXNET_DEAD_RANK_TIMEOUT", 60.0, float,
    "Heartbeat-staleness threshold in seconds: a worker whose "
    "heartbeat file is older than this counts as DEAD — the default "
    "timeout of kvstore.get_num_dead_node/dead_ranks, the elastic "
    "barrier's verdict deadline, and the bound on parameter-server "
    "sync-round waits in elastic mode.  Detection latency of the "
    "2->1 re-mesh is bounded by this value.  Size it ABOVE the "
    "worst-case scheduling stall of a healthy rank (an overloaded "
    "host that can't run the heartbeat thread for this long gets "
    "falsely convicted) and so that ~6x its value exceeds a "
    "re-admitted rank's restore+compile warm-up (the survivors' "
    "bounded retries cover that window).  Garbage or non-positive "
    "values raise at kvstore construction.")
register_env(
    "MXNET_ELASTIC", 0, int,
    "1: elastic fault-tolerant training.  dist kvstores run the "
    "survivable control plane — file-based barriers with a "
    "DeadRankError verdict instead of uninterruptible collectives, a "
    "membership-epoch ledger in MXNET_KVSTORE_HEARTBEAT_DIR, gradient "
    "traffic forced onto the reconnectable parameter-server transport, "
    "and epoch-fenced wire frames.  Module.fit then survives rank "
    "death: re-mesh to the survivors, roll back to the last committed "
    "checkpoint, resume, and re-admit returning ranks at checkpoint "
    "boundaries.  See README 'Elastic training'.  0 (default): the "
    "fixed-membership paths.")
register_env(
    "MXNET_ELASTIC_JOIN", 0, int,
    "1: this process is a RETURNING rank re-joining a live elastic run "
    "(set by tools/chaos_drill.py / the elastic launcher on respawn, "
    "never by hand): the kvstore skips jax.distributed and discovers "
    "the run from the membership ledger, files a join request once "
    "warm, and waits to be admitted at a checkpoint boundary.")
register_env(
    "MXNET_KVSTORE_RECONNECTS", 3, int,
    "Bounded reconnect budget of a parameter-server client connection: "
    "transient socket failures (ECONNRESET/EPIPE mid-frame) retry with "
    "exponential backoff + jitter up to this many times before the "
    "connection is declared dead (and the comm scheduler poisoned).  "
    "0 disables reconnecting.  Counted in the ps.reconnects profiler "
    "counter.")
register_env(
    "MXNET_CHAOS_KILL_STEP", None, int,
    "CHAOS fault injection (tools/chaos_drill.py): SIGKILL this "
    "process at the start of fit step N.  Honors MXNET_CHAOS_RANK.  "
    "NEVER set in production.")
register_env(
    "MXNET_CHAOS_DEAD_RANK_STEP", None, int,
    "CHAOS: raise DeadRankError (ranks from MXNET_CHAOS_DEAD_RANKS, "
    "default '1') at fit step N, once — the single-process "
    "rollback-resume smoke.  NEVER set in production.")
register_env(
    "MXNET_CHAOS_DEAD_RANKS", "1", str,
    "CHAOS: CSV of ranks MXNET_CHAOS_DEAD_RANK_STEP pretends died.")
register_env(
    "MXNET_CHAOS_HEARTBEAT_STALL", None, float,
    "CHAOS: the heartbeat writer goes silent for S seconds after its "
    "first beat (delayed-heartbeat fault).  NEVER set in production.")
register_env(
    "MXNET_CHAOS_TORN_SOCKET", None, int,
    "CHAOS: tear the N-th parameter-server wire frame mid-send (half "
    "the bytes, then the socket dies) — exercises the bounded "
    "reconnect.  NEVER set in production.")
register_env(
    "MXNET_CHAOS_MIGRATION_TEAR", None, int,
    "CHAOS: tear the N-th disaggregated KV page-migration frame "
    "mid-send (length header + half the body, then the socket dies) — "
    "the decode replica discards the torn frame and the router must "
    "resolve the stream exactly-once through re-prefill.  NEVER set "
    "in production.")
register_env(
    "MXNET_CHAOS_SLOW_RANK", None, float,
    "CHAOS: sleep S seconds at every fit step AND every serving "
    "decode step (straggler / slow-replica fault — the SLO engine's "
    "burn-rate drill: a slow replica still heartbeats, so only the "
    "fast-window alert catches it).  NEVER set in production.")
register_env(
    "MXNET_CHAOS_RANK", None, int,
    "CHAOS: apply the MXNET_CHAOS_* faults only on this rank "
    "(default: every rank).")
register_env(
    "MXNET_KVSTORE_BIGARRAY_BOUND", 1000 * 1000, int,
    "Element count above which a dist-kvstore array is split flat "
    "across ALL parameter-server shards instead of living whole on "
    "one hashed shard (reference: comm.h:65, kvstore_dist.h:286-296).")
register_env(
    "MXNET_KVSTORE_BUCKET_BYTES", 4 << 20, int,
    "Gradient-comm bucket capacity in BYTES (default 4 MiB): dist-"
    "kvstore pushes coalesce same-dtype gradients into flat buckets "
    "this large, so one collective / one wire frame moves many keys.  "
    "A single gradient larger than the bound rides its own bucket.  "
    "The pack layout is deterministic (submission order), so bucketing "
    "never changes the numerics — see mxnet_tpu/comm.py.")
register_env(
    "MXNET_KVSTORE_GRAD_DTYPE", "fp32", str,
    "Wire dtype for float32 gradient payloads on the dist kvstore: "
    "'fp32' (default, lossless), 'bf16' or 'fp16' halve the bytes on "
    "the wire; accumulation stays float32 on the receiving side.  "
    "bf16 keeps fp32's exponent range (safe for raw gradient "
    "magnitudes); fp16 has more mantissa but overflows past 65504 — "
    "prefer bf16 unless gradients are pre-scaled.  Latched per bucket "
    "at seal time on the pushing thread, so a runtime flip lands on "
    "the same bucket boundary on every rank (flip at the same point "
    "in the push sequence everywhere).")
register_env(
    "MXNET_KVSTORE_OVERLAP", 1, int,
    "1 (default): dist-kvstore pushes enqueue into the async bucketed "
    "comm scheduler (background thread, priority-ordered, overlaps "
    "the rest of the step; pulls wait only at the true dependency "
    "point).  0: the pre-scheduler blocking per-key push/pull path "
    "(debugging / apples-to-apples benchmarking).")
register_env(
    "MXNET_KVSTORE_INFLIGHT", 4, int,
    "Max gradient buckets in flight per parameter-server connection "
    "(the windowed send-now/collect-later pipeline); also bounds the "
    "comm scheduler's finisher queue.  1 = fully serialized "
    "round-trips.")
register_env(
    "MXNET_KVSTORE_SYNC_ON_SERVER", 0, int,
    "dist_sync architecture switch: 1 runs the optimizer ON the "
    "sharded parameter servers after NumWorkers pushes (workers "
    "stateless, pulls wait for the round — the reference's "
    "kvstore_dist_server.h:136-219 design); 0 (default) keeps the "
    "replicated-updater allgather-sum path.")
register_env(
    "MXNET_IO_WORKERS", 0, int,
    "Decode-pool size for ImageRecordIter(workers=None): N > 0 fans "
    "JPEG decode out to N forked worker processes writing a zero-copy "
    "shared-memory batch ring (mxnet_tpu/io_pool.py); 0 (default) "
    "keeps the single-process path.  ImageRecordIter(workers='auto') "
    "sizes the pool min(cpu_count, 8) when this is unset.  Garbage "
    "values raise at iterator construction.")
register_env(
    "MXNET_IO_RING_SLOTS", 0, int,
    "Shared-memory ring depth in BATCHES for the decode pool.  0 "
    "(default): auto — 2*workers + 2, each worker one batch ahead "
    "plus a double-buffer margin.  Explicit values must be >= 2 "
    "(one slot filling + one draining); anything else raises at "
    "construction.")
register_env(
    "MXNET_IO_DEVICE_AUGMENT", 0, int,
    "1: ImageRecordIter(device_augment=None) yields raw uint8 NHWC "
    "batches (4x fewer H2D bytes) and crop/flip/normalize/mixup run "
    "ON DEVICE as a fused jitted prologue of the training step, under "
    "the per-step PRNG key (checkpoint resume replays augmentation "
    "bit-exactly).  0 (default): host-side cv2 augmentation.  Values "
    "other than 0/1 raise at construction.")
register_env(
    "MXNET_CKPT_DIR", None, str,
    "Checkpoint root directory.  When set, Module.fit creates a "
    "CheckpointManager automatically (cadence from "
    "MXNET_CKPT_EVERY_N_STEPS); pass fit(resume='auto') to restore the "
    "newest committed checkpoint.  Shared across ranks of a dist run.")
register_env(
    "MXNET_CKPT_EVERY_N_STEPS", 0, int,
    "Checkpoint every N optimizer steps inside Module.fit (0 = only "
    "manual and SIGTERM-emergency saves).  Invalid values raise at "
    "CheckpointManager construction.")
register_env(
    "MXNET_CKPT_KEEP", 5, int,
    "Newest committed checkpoints retained; older ones (and torn .tmp "
    "attempts they supersede) are garbage-collected by rank 0 after "
    "each commit.")
register_env(
    "MXNET_CKPT_ASYNC", 1, int,
    "1 (default): checkpoint saves snapshot training state "
    "synchronously (device-side copies; cross-host shards gather) and "
    "serialize/checksum/write/commit on a background thread so "
    "fit.step keeps running.  0: block through the distributed commit, "
    "with the kvstore barrier gating rank 0's COMMIT marker.")
register_env(
    "MXNET_CKPT_COMMIT_TIMEOUT", 300.0, float,
    "Seconds rank 0's committer waits for every rank's shard-OK marker "
    "before abandoning the checkpoint as uncommitted (async mode's "
    "file-based barrier).  The torn .tmp directory is left for the "
    "next GC; training continues.")
register_env(
    "MXNET_CKPT_CRASH", None, str,
    "Fault-injection hook for the crash tests: 'mid_shard[:n]' dies "
    "halfway through writing this rank's shard of the n-th save; "
    "'before_commit[:n]' dies after the all-shards barrier, before "
    "rank 0's COMMIT.  Unknown values raise.  NEVER set in production.")
register_env(
    "MXNET_SERVING_KV_BLOCK", 16, int,
    "KV-cache page size in TOKENS for serving.DecodeEngine (default "
    "16).  Also the attention block size of the decode path: page "
    "boundaries ARE online-softmax block boundaries, which is what "
    "makes prefill + incremental decode bit-identical (lax path) to "
    "the full-sequence forward of transformer_lm(block_size=kv_block)."
    "  Garbage values raise at engine construction.")
register_env(
    "MXNET_SERVING_MAX_STREAMS", 64, int,
    "Concurrent-stream ceiling of the continuous-batching decode "
    "scheduler; tops the decode batch-bucket ladder.  Admission "
    "control may hold requests below it when free cache blocks run "
    "out.  Garbage values raise at engine construction.")
register_env(
    "MXNET_SERVING_DECODE_BUCKETS", None, str,
    "Decode batch-size ladder as a strictly increasing CSV (e.g. "
    "'1,2,4,8').  Unset: a doubling ladder up to "
    "MXNET_SERVING_MAX_STREAMS.  One decode executable is AOT-"
    "compiled per (batch bucket, cache-blocks bucket) pair, so ladder "
    "length bounds compile count.  Malformed ladders raise at engine "
    "construction.")
register_env(
    "MXNET_SERVING_CACHE_BUCKETS", None, str,
    "Cache-length ladder in BLOCKS (block-table width) as a strictly "
    "increasing CSV.  Unset: a doubling ladder up to "
    "ceil(max_len / kv_block).  Malformed ladders raise at engine "
    "construction.")
register_env(
    "MXNET_SERVING_PREFILL_BUCKETS", None, str,
    "Prefill prompt-length ladder in TOKENS (CSV, each a multiple of "
    "MXNET_SERVING_KV_BLOCK so one block-table width serves each "
    "bucket).  Unset: kv_block-sized doubling ladder up to max_len.  "
    "Malformed ladders raise at engine construction.")
register_env(
    "MXNET_SERVING_PREFIX_CACHE", 1, int,
    "1 (default): serving.DecodeEngine shares KV-cache pages between "
    "streams with common block-aligned prompt prefixes — a radix "
    "index maps cached prefixes to ref-counted page chains, admission "
    "attaches a new stream to existing pages (prefill runs only on "
    "the uncached suffix; a fully-cached prompt skips prefill "
    "entirely), writes to shared pages copy-on-write, and refcount-0 "
    "cached pages evict LRU under pressure (MXNET_SERVING_EVICT).  "
    "0: the exclusive-owner cache (decode output bit-identical to "
    "the pre-sharing engine).  Values other than 0/1 raise at engine "
    "construction.")
register_env(
    "MXNET_SERVING_KV_DTYPE", "fp32", str,
    "KV-cache page storage dtype for serving.DecodeEngine: 'fp32' "
    "(default, bit-exact), 'bf16' (plain narrow cast, 2x less cache "
    "HBM), 'int8' or 'fp8' (ml_dtypes float8_e4m3fn; ~4x less, "
    "quantize-on-write with per-page-slot-per-head float32 scales, "
    "dequantized inside the paged-decode kernel with fp32 softmax "
    "accumulation — the bf16-gradient-wire precedent: lossy storage, "
    "exact math).  Unknown names raise at engine construction; 'fp8' "
    "raises when the toolchain lacks float8_e4m3fn.")
register_env(
    "MXNET_SERVING_EVICT", "lru", str,
    "Eviction policy for refcount-0 prefix-cached KV pages: 'lru' "
    "(default) keeps them parked and reclaims leaf-first in "
    "least-recently-used order (deterministic logical clock) when "
    "the pool runs dry; 'off' frees pages the moment their last "
    "stream detaches (no retention — prefix hits then only come from "
    "still-running streams).  Unknown values raise at engine "
    "construction.")
register_env(
    "MXNET_SERVING_SPEC_TOKENS", 0, int,
    "Speculative-decoding draft depth k for serving.DecodeEngine: "
    "0 (default) decodes one token per stream per step; k >= 1 asks "
    "the proposer (MXNET_SERVING_PROPOSER) for up to k draft tokens "
    "per scheduling step and the target model scores pending + drafts "
    "in ONE multi-query verify step (QKVPagedVerifyAttend), "
    "committing the longest verified prefix plus one bonus token — "
    "up to k+1 tokens per step.  Greedy output is bit-identical to "
    "non-speculative decode; temperature sampling stays exactly the "
    "target distribution via rejection sampling keyed by the "
    "existing (seed, stream, position) sampler.  Negative or garbage "
    "values raise at engine construction.")
register_env(
    "MXNET_SERVING_PROPOSER", "ngram", str,
    "Draft proposer for speculative decoding (used when "
    "MXNET_SERVING_SPEC_TOKENS > 0): 'ngram' (default) is model-free "
    "prompt-lookup self-drafting — match the stream's trailing "
    "n-gram against its own prompt+output history and propose the "
    "continuation of the most recent earlier occurrence "
    "(deterministic, so fleet decode retries re-propose "
    "identically).  The interface (mxnet_tpu.speculative.Proposer-"
    "style propose(context, k)) is pluggable; 'draft_lm' runs a "
    "small trained LM as the drafter (weights from "
    "MXNET_SERVING_DRAFT_CKPT), greedy and deterministic so fleet "
    "decode retries re-propose identically.  Unknown names raise at "
    "engine construction.")
register_env(
    "MXNET_SERVING_PREFILL_CHUNK", 0, int,
    "Chunked-prefill slice size in TOKENS for serving.DecodeEngine "
    "(Sarathi-style): 0 (default) prefills each admitted prompt "
    "monolithically; N > 0 (a multiple of MXNET_SERVING_KV_BLOCK) "
    "splits prompts whose uncached suffix exceeds N into N-token "
    "suffix-prefill continuations interleaved with decode steps at "
    "iteration boundaries, so one long admission no longer stalls "
    "every active stream's token cadence (admission charges cache "
    "pages incrementally per chunk).  Chunked prefill is "
    "bit-identical (lax path, fp32 pools) to monolithic prefill.  "
    "Negative, garbage, or non-multiple-of-kv_block values raise at "
    "engine construction.")
register_env(
    "MXNET_SERVING_TP", 1, int,
    "Tensor-parallel width of serving.DecodeEngine: 1 (default) is "
    "the single-device engine; N > 1 AOT-compiles every prefill / "
    "suffix-prefill / verify / decode executable against an N-way "
    "'tp' mesh (shard_map) with attention heads, the fused QKV "
    "projection, ff1, and the vocab head/embedding split exactly as "
    "lm_partition_rules() declares, and KV pages + scale pages "
    "sharded over heads — per-device pool bytes drop ~1/N, so "
    "weights+pool bigger than one chip fit.  Decode output stays "
    "bit-identical (fp32/lax) to tp=1: only output dims shard, "
    "contractions are reconstructed with exact all-gathers, and "
    "sampling is psum'd off the mesh so the (engine seed, stream "
    "seed, position) contract survives.  Values < 1, garbage, or tp "
    "not dividing num_heads raise at engine construction.")
register_env(
    "MXNET_SERVING_PP", 1, int,
    "Pipeline-parallel depth of serving.DecodeEngine: 1 (default) "
    "keeps all layers on every tp shard; S > 1 stacks the residual "
    "blocks into S stage-resident slabs (dim-0 sharded over a 'pp' "
    "mesh axis, the PR-15 layout) and runs decode as S ppermute "
    "micro-hops inside one SPMD program, tokens psum'd off the last "
    "stage.  Composes with MXNET_SERVING_TP (mesh is pp x tp; "
    "tp*pp devices per engine).  Values < 1, garbage, or pp not "
    "dividing num_layers raise at engine construction.")
register_env(
    "MXNET_SERVING_DEVICES", None, str,
    "Comma-separated jax.devices() ordinals the DecodeEngine mesh "
    "uses (e.g. '0,1,2,3'), length tp*pp.  Unset: the first tp*pp "
    "devices.  fleet.spawn_replica(devices=...) exports this to each "
    "replica child so one host packs several tp-sharded replicas on "
    "disjoint device sets.  Out-of-range ordinals, duplicates, or a "
    "length not equal to tp*pp raise at engine construction.")
register_env(
    "MXNET_ADAPTER_ENABLE", 0, int,
    "1: serving.DecodeEngine builds its executables with the "
    "per-stream paged-LoRA adapter epilogue (mxnet_tpu.adapters) so "
    "one engine serves batches mixing tenants — each stream's "
    "low-rank (A, B) delta is gathered from the adapter pool by slot "
    "id inside the one fused program; slot 0 is an exact no-op, so "
    "streams without an adapter stay bit-identical to the "
    "pre-adapter engine.  0 (default): adapter-free executables, "
    "byte-identical graphs to before this subsystem existed.  "
    "Garbage or values other than 0/1 raise at engine construction "
    "naming this variable.")
register_env(
    "MXNET_ADAPTER_SLOTS", 8, int,
    "Resident adapter slots PER RANK BUCKET in the "
    "adapters.AdapterPool — the device slab holds slots+1 rows (row "
    "0 is the reserved null adapter).  Publishing beyond capacity "
    "LRU-evicts parked (refcount-0) adapters deterministically; a "
    "request for an evicted adapter re-publishes it from the host "
    "copy (a pool miss, visible in stats).  Must be >= 1; garbage "
    "or values < 1 raise at pool construction naming this variable.")
register_env(
    "MXNET_ADAPTER_RANK_BUCKETS", "8", str,
    "Comma-separated LoRA rank buckets (e.g. '4,16') the adapter "
    "pool allocates slabs for — an adapter of rank r is zero-padded "
    "into the smallest bucket >= r (numerically exact; padded lanes "
    "multiply zero rows), keeping the AOT executable matrix finite "
    "while serving mixed ranks.  Buckets must be positive, strictly "
    "increasing integers; garbage, non-positive, or unsorted lists "
    "raise at pool construction naming this variable.")
register_env(
    "MXNET_TENANT_QUOTA_TOKENS", 0, int,
    "Per-tenant token-bucket quota capacity for DecodeEngine "
    "admission: each submitted request charges prompt + max_new "
    "tokens against its tenant's bucket; an empty bucket sheds the "
    "request with a typed QuotaExceededError (reason tenant_quota, "
    "counted per tenant in stats()/statusz — fairness stays "
    "auditable).  0 (default): quotas off.  Negative or garbage "
    "values raise at engine construction naming this variable.")
register_env(
    "MXNET_TENANT_QUOTA_REFILL", 0.0, float,
    "Token-bucket refill rate in tokens/second for "
    "MXNET_TENANT_QUOTA_TOKENS (0, the default, makes the quota a "
    "hard per-lifetime cap — useful in tests; production wants a "
    "positive sustained rate).  Negative or garbage values raise at "
    "engine construction naming this variable.")
register_env(
    "MXNET_SERVING_DRAFT_CKPT", None, str,
    "Checkpoint directory holding the draft LM's weights for the "
    "'draft_lm' speculative proposer (MXNET_SERVING_PROPOSER) — the "
    "newest checkpoint under it loads at engine construction; its "
    "architecture (layers, d_model, vocab) is inferred from the "
    "parameter shapes, and head count comes from "
    "MXNET_SERVING_DRAFT_HEADS.  Unset while the proposer is "
    "'draft_lm' raises at engine construction naming this variable; "
    "a missing/empty directory raises too.")
register_env(
    "MXNET_SERVING_DRAFT_HEADS", 0, int,
    "Attention head count of the MXNET_SERVING_DRAFT_CKPT draft LM "
    "(head count is not recoverable from fused-QKV parameter "
    "shapes).  0 (default) only while the proposer is not "
    "'draft_lm'; otherwise must be >= 1 and divide the draft's "
    "d_model — violations raise at engine construction naming this "
    "variable.")
register_env(
    "MXNET_FLEET_REPLICAS", 2, int,
    "Replica-process count for fleet.launch_local_fleet / "
    "tools/bench_fleet.py when none is given explicitly.  Each replica "
    "wraps one serving engine (InferenceEngine or DecodeEngine) behind "
    "the fleet wire.  Values < 1 or garbage raise at construction.")
register_env(
    "MXNET_FLEET_SHED_DEADLINE_MS", 0.0, float,
    "Default per-request deadline budget (milliseconds) the fleet "
    "Router applies to requests that carry none: a request the learned "
    "per-bucket cost model says cannot finish inside its budget is "
    "rejected with a typed ShedError, and under overload the pending "
    "queue sheds oldest-deadline-first.  0 (default): no implicit "
    "deadline — only explicit per-request deadlines shed.  Negative or "
    "garbage values raise at Router construction.")
register_env(
    "MXNET_FLEET_RETRY_BUDGET", 2, int,
    "Re-dispatches one fleet request survives before its client sees "
    "the failure: a dead replica's in-flight requests are retried on "
    "survivors up to this many times (delivery stays exactly-once via "
    "the router's ticket latch; decode retries re-sample bit-"
    "identically from the router-stamped seed).  0 disables retries.  "
    "Negative or garbage values raise at Router construction.")
register_env(
    "MXNET_FLEET_SWAP_DRAIN_TIMEOUT", 60.0, float,
    "Seconds Router.swap_weights waits for a draining replica's "
    "in-flight requests to deliver before aborting the rolling weight "
    "swap (the replica resumes on its old weights; replicas already "
    "swapped stay swapped).  Must be >= 0.1; garbage raises at Router "
    "construction.")
register_env(
    "MXNET_FLEET_ROLES", "", str,
    "CSV of disaggregated replica roles (prefill|decode|mixed), one "
    "token per replica in rid order — e.g. 'prefill,decode,decode'.  "
    "Prefill-role replicas run admission + chunked/prefix-shared "
    "prefill only and export the stream's KV pages as a signed page "
    "frame; the Router forwards the frame to a decode-role replica "
    "where decode continues bit-identically.  Empty (default): roles "
    "off, every replica serves both phases.  Unknown tokens, a count "
    "mismatch, or a one-sided split (prefill without decode or vice "
    "versa) raise at Router construction.")
register_env(
    "MXNET_FLEET_AUTOSCALE", 0, int,
    "1: the Router re-balances the prefill/decode role split from its "
    "own telemetry (queue depth and in-flight work per role weighted "
    "by the learned cost EMAs, decode cache_util, interactive SLO "
    "burn-rates) — one drain->flip->warmup per evaluation, 2x "
    "hysteresis, never stripping the last replica of a role.  Only "
    "meaningful with MXNET_FLEET_ROLES set.  0 (default): the split "
    "is static (Router.set_role / autoscale_once remain callable).  "
    "Garbage raises at Router construction.")
register_env(
    "MXNET_FLEET_AUTOSCALE_INTERVAL", 5.0, float,
    "Seconds between autoscaler evaluations of the prefill/decode "
    "role split.  Must be > 0; garbage raises at Router construction.")
register_env(
    "MXNET_METRICS_PORT", 0, int,
    "Port of the per-process ops HTTP endpoint serving /metrics "
    "(Prometheus text), /statusz (JSON: gauges, goodput/MFU, serving "
    "and router stats, membership epoch) and /tracez (flight-recorder "
    "snapshot).  0/unset (default): disabled.  Serving engines, the "
    "fleet Router and Module.fit auto-start it when set; fleet replica "
    "processes always bind an EPHEMERAL port instead and publish it in "
    "<fleet_dir>/mz_<rid> (tools/fleet_top.py polls those).  Binds "
    "loopback only; garbage values raise at server start.")
register_env(
    "MXNET_FLIGHT_RECORDER", 1, int,
    "1 (default): every span/event/metric sample also lands in a "
    "bounded in-memory ring (the crash flight recorder) — dumped to a "
    "post-mortem JSON on DeadRankError, replica conviction, ShedError "
    "bursts, SIGTERM and engine/serving-loop crashes.  No file I/O in "
    "steady state.  0: off (spans revert to profiler-only).")
register_env(
    "MXNET_FLIGHT_RECORDER_SIZE", 4096, int,
    "Flight-recorder ring capacity in EVENTS (default 4096 ≈ the last "
    "few seconds of a busy serving loop).  Values < 16 or garbage "
    "raise at first record.")
register_env(
    "MXNET_FLIGHT_RECORDER_DIR", None, str,
    "Directory for flight-recorder artifacts.  When set, the ring "
    "ALSO write-throughs into a memory-mapped ring file "
    "(flight_rank<R>_pid<P>.ring) whose pages the OS flushes after "
    "process death — a kill -9'd process still leaves its last-N-"
    "seconds record (tools/trace_merge.py reads it).  Post-mortem "
    "JSON dumps (flightdump_*.json) land here too; unset: dumps go "
    "to <tmpdir>/mxnet_tpu_flight and no ring file is kept.")
register_env(
    "MXNET_TRACE_SAMPLE", 1.0, float,
    "Fraction of fleet requests that get a root distributed-trace "
    "context (W3C-traceparent-style ids propagated client → router → "
    "replica → engine; see README 'Observability').  1.0 (default): "
    "trace everything; 0: tracing off.  The per-request decision is "
    "deterministic in the ticket id, so retries keep their verdict.  "
    "Out-of-range or garbage values raise at first use.")
register_env(
    "MXNET_PEAK_TFLOPS", None, float,
    "Per-chip peak dense-matmul TFLOP/s for the training.mfu gauge "
    "denominator.  Unset: profiler.PEAK_BY_DEVICE_KIND, keyed on "
    "jax's device_kind (it holds the TPU v5e); on a CPU backend the "
    "gauge is withheld rather than guessed, and an accelerator the "
    "table does not know raises.  Non-positive or garbage values "
    "raise at first use.")
register_env(
    "MXNET_SLO_TTFT_MS", "interactive=250,batch=5000", str,
    "Per-class time-to-first-token SLO targets, as 'class=ms,...' "
    "over the declared classes (slo.SLO_CLASSES: interactive, "
    "batch).  A TTFT above its class target is one bad event for the "
    "burn-rate engine.  Unknown classes, garbage or non-positive "
    "values raise at SloConfig construction naming this var.")
register_env(
    "MXNET_SLO_TPT_MS", "interactive=50,batch=500", str,
    "Per-class time-per-token SLO targets ('class=ms,...'; see "
    "MXNET_SLO_TTFT_MS for the format and validation).  Each decoded "
    "token's step share is judged against its class target.")
register_env(
    "MXNET_SLO_OBJECTIVE", 0.99, float,
    "Fraction of events that must be GOOD for every (class, metric) "
    "objective — the error budget is 1 - objective, the denominator "
    "of every burn rate.  Must be in (0, 1): 1.0 leaves a zero "
    "budget.  Garbage or out-of-range values raise at SloConfig "
    "construction.")
register_env(
    "MXNET_SLO_FAST_WINDOW", 60.0, float,
    "Fast burn-rate window in seconds (SRE multi-window style; the "
    "paging signal).  Must be >= 1 and < MXNET_SLO_SLOW_WINDOW.  A "
    "sustained fast-window burn above MXNET_SLO_BURN_ALERT fires the "
    "typed SloAlert — designed to trip BEFORE a slow replica's "
    "MXNET_DEAD_RANK_TIMEOUT conviction window (which never fires "
    "for a replica that still heartbeats).")
register_env(
    "MXNET_SLO_SLOW_WINDOW", 600.0, float,
    "Slow burn-rate window in seconds — the budget_remaining gauge's "
    "horizon and the flap damper.  Must exceed "
    "MXNET_SLO_FAST_WINDOW.")
register_env(
    "MXNET_SLO_BURN_ALERT", 10.0, float,
    "Fast-window burn-rate alert threshold (1.0 = budget spent "
    "exactly on schedule).  Alerts re-arm after burn falls below "
    "half this (hysteresis).  Must be >= 1; garbage raises at "
    "SloConfig construction.")
register_env(
    "MXNET_SLO_MIN_EVENTS", 10, int,
    "Minimum events in the fast window before a burn-rate alert may "
    "fire (a 1-request window would alert on any single miss).  "
    "Must be >= 1.")
register_env(
    "MXNET_CANARY_INTERVAL", 0.0, float,
    "Seconds between synthetic canary probes (DecodeEngine and "
    "fleet.Router each run a prober when set).  0/unset (default): "
    "prober off.  Probes ride the full admission→prefill→decode→"
    "deliver path, are EXCLUDED from serving.requests / "
    "fleet.requests, and export slo.canary_* metrics feeding the "
    "availability objective.  Negative or garbage values raise at "
    "construction.")
register_env(
    "MXNET_CANARY_TOKENS", 4, int,
    "Decode length of one canary probe — with the fixed probe prompt "
    "this pins the probe's cost, so canary latency is comparable "
    "across time.  Must be >= 1.")
register_env(
    "MXNET_TEST_DEVICE", None, str,
    "Device the test utilities bind to (test_utils.default_context; "
    "the reference's MXNET_TEST_DEVICE).  Unset: the ambient current "
    "context.")


# ---------------------------------------------------------------------------
# Async-collective compiler flag wiring (MXNET_ASYNC_COLLECTIVES)
# ---------------------------------------------------------------------------

# The flags the overlap path needs.  They split each collective into
# <op>-start / <op>-done pairs and let the latency-hiding scheduler
# move real compute between them.  They are libtpu's, so they go where
# libtpu reads them: LIBTPU_INIT_ARGS.  jaxlib's own XLA_FLAGS parser
# does not know them and kills the process at the first backend
# ("Unknown flags in XLA_FLAGS") — on the chip machine too.  Each one
# below was accepted through LIBTPU_INIT_ARGS by libtpu 0.0.34 on a
# v5e (chip run, PR 21).
TPU_OVERLAP_FLAGS = (
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def env_bool(name: str) -> bool:
    """Strict 0/1 read of a registered boolean env var: unset falls to
    the catalog default, anything but '0'/'1' raises loudly (the
    MXNET_CKPT_* validation pattern).  The one parser behind
    MXNET_PP_RESIDENT and MXNET_ASYNC_COLLECTIVES."""
    raw = os.environ.get(name)
    if raw is None:
        return bool(_CATALOG[name].default)
    if raw in ("0", "1"):
        return raw == "1"
    from .base import MXNetError

    raise MXNetError(f"{name}={raw!r} must be 0 or 1")


def _wants_tpu(env=os.environ) -> bool:
    """True when a process with environment ``env`` may initialize a
    TPU backend — decided WITHOUT importing jax (libtpu reads
    LIBTPU_INIT_ARGS once, when the backend is created)."""
    plats = env.get("JAX_PLATFORMS", "")
    if plats:
        return "tpu" in plats.lower()
    import importlib.util

    try:
        return importlib.util.find_spec("libtpu") is not None
    except (ImportError, ValueError):
        return False


def ensure_overlap_flags() -> bool:
    """Append the async-collective flags to ``LIBTPU_INIT_ARGS`` when
    MXNET_ASYNC_COLLECTIVES=1 and the process targets the TPU.  Called
    at package import (before any jax backend exists); idempotent;
    never overrides a flag the user already set there.  Returns True
    when flags were appended."""
    if not env_bool("MXNET_ASYNC_COLLECTIVES") or not _wants_tpu():
        return False
    current = os.environ.get("LIBTPU_INIT_ARGS", "")
    have = {f.split("=")[0] for f in current.split() if f.startswith("--")}
    add = [f for f in TPU_OVERLAP_FLAGS if f.split("=")[0] not in have]
    if add:
        os.environ["LIBTPU_INIT_ARGS"] = (
            current + " " + " ".join(add)).strip()
    return bool(add)


def refuse_shared_chip(child_env, what: str) -> None:
    """One process for each chip.  Called by the launchers before they
    start a JAX child process: a child that would open the TPU while
    this process holds it, or that is not told which chip is its own
    (``TPU_VISIBLE_CHIPS``) and so would open every chip of the host
    like each of its siblings, fails or hangs at its first backend —
    refuse here, with a message, instead.  Children held to the CPU
    (``JAX_PLATFORMS=cpu``, what the tests and ``launch.py --cpu``
    start) pass."""
    if not _wants_tpu(child_env):
        return
    import sys

    from .base import MXNetError

    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is not None and "tpu" in getattr(bridge, "_backends", {}):
        raise MXNetError(
            f"{what}: this process has already opened the TPU, and a "
            f"chip belongs to one process at a time — the child would "
            f"fail or hang.  Start children from a parent that never "
            f"creates a jax backend, or hold them to JAX_PLATFORMS=cpu")
    if not child_env.get("TPU_VISIBLE_CHIPS"):
        raise MXNetError(
            f"{what}: the child targets the TPU (JAX_PLATFORMS="
            f"{child_env.get('JAX_PLATFORMS', '')!r}) but its environment "
            f"sets no TPU_VISIBLE_CHIPS, so it would open every chip of "
            f"this host, as would each sibling — one process for each "
            f"chip.  Give each child its own chip(s) through its env "
            f"(TPU_VISIBLE_CHIPS plus the process-bounds variables "
            f"libtpu documents), drive all chips from ONE process "
            f"(MeshPlan / DecodeEngine(tp=..., devices=...)), or hold "
            f"the children to JAX_PLATFORMS=cpu")


def place_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and say where it
    lives — the ONE place this repo's scripts decide that.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives there and no
    directory is set in code (jax reads the variable itself); where it
    is not, ``<checkout>/.jax_cache`` — a fixed path, never one made
    from tempfile, a pid or the time: a directory that moves never
    hits.  Returns the directory in use.

    Every compile is kept, however short: ``init_params`` alone runs a
    couple of hundred 0.2-0.4 s compiles on the chip (one per
    parameter shape), a minute of a cold run that jax's default
    threshold of 1 s would never cache."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


ensure_overlap_flags()
