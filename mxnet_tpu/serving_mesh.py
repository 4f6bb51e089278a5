"""Mesh-native serving executables: tensor(+pipeline)-parallel decode
through the SAME PartitionRules table that shards training.

``serving.DecodeEngine`` is a single-device engine until it is handed a
:class:`~mxnet_tpu.parallel.MeshPlan`; this module then provides the
per-phase step programs (prefill / suffix-prefill / verify / decode)
as explicit per-device SPMD bodies under ``shard_map``, AOT-compiled by
the engine per (batch-bucket, cache-bucket) exactly like the local
path, pools donated.  The forward calls the SAME registered op
computes (``LayerNorm``, flatten=False ``FullyConnected``, gelu
``Activation``, the paged attention family) that
``executor.build_graph_fn`` composes for the single-device symbols, so
there is no second model implementation to drift.

**What shards** — resolved from the decode symbols' logical axis names
(``models/transformer.py``) through ``plan.rules`` — is deliberately
only OUTPUT dims:

* the fused QKV projection's rows, PER HEAD (rows host-permuted so each
  device's contiguous chunk packs ``[q_local | k_local | v_local]`` for
  its ``num_heads/tp`` heads — the local FC output feeds the attention
  ops directly at ``num_heads=H/tp``);
* ff1 rows (when ``d_ff % tp == 0``);
* the vocab head + token-embedding rows (when ``vocab % tp == 0``; the
  sharded embedding lookup is a clip + masked local gather + ``psum``
  — exact, one shard contributes the row, the rest contribute zeros);
* the KV pools' and scale pools' head dim (``'heads'`` in the rules
  table; the value pools' is their lane dim H·D, heads being
  contiguous D-lane spans — ``kv_cache.value_pool_shape`` — so a tp
  shard holds H/tp whole heads), so per-device pool bytes drop by
  ~1/tp.

``proj_weight``/``ff2_weight`` — whose rules spec shards the
CONTRACTION dim ('heads'/'ffn' on dim 1) — stay REPLICATED on purpose:
a row-parallel matmul psums partial fp32 products, a different
reduction order than the single-device dot, and the engine's contract
is that a sharded engine decodes BIT-IDENTICAL (fp32/lax) to the
single-device one (fleet decode-retry bit-replay, speculation's
rejection sampler and COW semantics all lean on it).  Activations are
instead reconstructed with exact concatenating ``all_gather``s before
each replicated contraction.  Dims that do not divide ``tp`` fall back
to replicated (visible in :meth:`MeshPrograms.describe`).

**Pipeline leg**: ``pp = S`` stacks the KV pools into stage-resident
``(L, ...)`` slabs, dim 0 sharded over the ``'pp'`` mesh axis (the
stage-resident-slab layout of the training pipeline), so per-device
pool bytes drop by another 1/pp.  One decode step runs S micro-hops
inside one SPMD program: hop ``it`` computes layers ``[it*Ll,
(it+1)*Ll)`` — a STATIC python range, so every weight reaching a dot is
a direct program parameter — with a ``ppermute`` activation hand-off
between hops.  Stage ``it`` is the one holding the real activation on
hop ``it`` (and the pool slab rows those layers write), so each stage
keeps its pool writes only on its own hop (``jnp.where`` select) and
the sampled tokens are ``psum``'d off the last stage — integer psum, so
the (engine seed, stream seed, position) sampling contract survives
sharding bit-for-bit.  Dead-stage compute operates on the zero
activations ``ppermute`` leaves behind (LayerNorm(0) is finite) and is
discarded; at pp=S every stage runs S hops, so pp buys pool CAPACITY,
not step latency.

Block WEIGHTS stay per-layer leaves, tp-sharded on their output dims
and replicated across pp stages — NOT stacked and sliced in-program.
This is a bit-identity requirement, found empirically, not a style
choice: XLA:CPU emits a different dot kernel (different accumulation
order) when a matmul operand is any in-program derivation — even an
identity ``[0]``-slice of a leading-dim-1 array — instead of a direct
program parameter, which at decode shapes (seq len 1) drifts the
written KV values by ~1-2 ULP per step against the single-device
engine.  Pool slabs may be sliced freely: the paged attention ops
gather pages out of the pool before any contraction, and gathers /
scatters are exact data movement.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .base import MXNetError

__all__ = ["MeshPrograms"]

# per-layer parameter kinds of one residual block — kept as individual
# "layer{i}_<kind>" leaves (never stacked+sliced: dots must see direct
# program parameters to stay bitwise with the single-device engine)
_BLOCK_KINDS = ("ln1_gamma", "ln1_beta", "qkv_weight", "qkv_bias",
                "proj_weight", "proj_bias", "ln2_gamma", "ln2_beta",
                "ff1_weight", "ff1_bias", "ff2_weight", "ff2_bias")
_TRUNK_NAMES = ("tok_embed_weight", "pos_embed_weight", "ln_f_gamma",
                "ln_f_beta", "head_weight", "head_bias")
_FC_ATTRS = {"flatten": "False"}


def _np(v):
    return np.asarray(v.asnumpy() if hasattr(v, "asnumpy") else v)


def _ops(name, attrs, inputs):
    """Run one registered op compute (inference ctx) — the exact
    arithmetic ``build_graph_fn`` runs for the same node."""
    from .ops.registry import OpContext, get_op

    out = get_op(name).compute(OpContext(False, None), attrs, inputs, [])
    return out if isinstance(out, (list, tuple)) else [out]


def _op1(name, attrs, inputs):
    return _ops(name, attrs, inputs)[0]


class MeshPrograms:
    """The tp(+pp) serving programs for one transformer-LM family
    engine: parameter/pool sharding + the per-phase SPMD step bodies.

    Owned by ``serving.DecodeEngine`` when ``tp * pp > 1``; the engine
    keeps its bucket ladders, executable cache, donation policy and
    scheduler — only the step function and the placement of params,
    pools and feeds change.
    """

    def __init__(self, plan, spec, *, kv_block, kv_dtype="fp32",
                 pool_dtype=np.float32, seed=0):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .kv_cache import kv_quantized
        from .parallel import parse_logical

        if plan.dp != 1:
            raise MXNetError(
                f"serving MeshPlan must have dp=1 (got dp={plan.dp}) — "
                f"data parallelism in the serving tier is fleet "
                f"replicas, not a mesh axis")
        self.plan = plan
        self.mesh = plan.mesh
        self.tp = int(plan.tp)
        self.pp = int(plan.pp)
        self.L = spec.num_layers
        self.H = spec.num_heads
        self.V = spec.vocab_size
        self.dm = spec.d_model
        self.dff = int(spec.d_ff) if spec.d_ff else 4 * self.dm
        self.kvb = int(kv_block)
        if self.H % self.tp:
            raise MXNetError(
                f"tp={self.tp} does not divide num_heads={self.H} — "
                f"attention heads are the tp shard unit")
        if self.L % self.pp:
            raise MXNetError(
                f"pp={self.pp} does not divide num_layers={self.L} — "
                f"pipeline stages hold equal layer slabs")
        self.D = spec.head_dim
        self.Hl = self.H // self.tp
        self.Ll = self.L // self.pp
        self._quant = kv_quantized(kv_dtype)
        self._pool_dtype = np.dtype(pool_dtype)
        self._base_key = np.asarray(jax.random.PRNGKey(int(seed)))

        # logical axis names come off the DECODE symbol itself — the
        # annotations in models/transformer.py, resolved through the
        # plan's rules table (one table drives training AND serving)
        dec = spec.symbol("decode", kv_block=self.kvb, kv_dtype=kv_dtype)
        self._axes: Dict[str, tuple] = {}
        for name, attrs in dec.attr_dict().items():
            logical = attrs.get("__logical__")
            if logical:
                self._axes[name] = parse_logical(logical)

        # divisibility-gated shard flags (heads always divide — raised
        # above — vocab/ffn fall back to replicated when uneven)
        self._tp_vocab = (self.V % self.tp == 0)
        self._tp_ffn = (self.dff % self.tp == 0)
        self.Vl = self.V // self.tp if self._tp_vocab else self.V

        # the fused qkv weight packs rows [q_0..q_H | k_0..k_H |
        # v_0..v_H]; contiguous tp chunks must pack [q_loc|k_loc|v_loc]
        # per device, so permute rows head-wise before sharding
        # (inverse restores the checkpoint layout in unshard_params)
        chunks = []
        for t in range(self.tp):
            for c in range(3):
                base = c * self.dm + t * self.Hl * self.D
                chunks.append(np.arange(base, base + self.Hl * self.D))
        self._qkv_perm = np.concatenate(chunks)
        self._qkv_inv = np.argsort(self._qkv_perm)

        # KV/scale pool specs through the rules table: the pools'
        # 'heads' dim resolves to 'tp'; the stacked layer dim rides
        # 'pp' (stage-resident slabs)
        kv_axes = self._axes.get("layer0_kpool", (None, None, "heads"))
        sc_axes = self._axes.get("layer0_kscale", (None, None, "heads"))
        self._kv_spec = ("pp",) + tuple(
            plan.rules.spec(kv_axes, None, param="layer0_kpool"))
        self._sc_spec = ("pp",) + tuple(
            plan.rules.spec(sc_axes, None, param="layer0_kscale"))

        self.replicated = NamedSharding(self.mesh, P())
        self._specs: Dict[str, tuple] = {}
        self._host_shapes: Dict[str, tuple] = {}

    # ------------------------------------------------------------------
    # parameter / pool placement
    # ------------------------------------------------------------------
    def _param_spec(self, name, shape) -> tuple:
        """Mesh spec of one per-layer/trunk param: rules-resolved, then
        gated to output-dim shards only (dim 0) and even divisions —
        anything else replicates to preserve fp32 bit-identity."""
        axes = self._axes.get(name)
        if not axes:
            return (None,) * len(shape)
        raw = self.plan.rules.spec(axes, shape, param=name)
        spec = []
        for d, ax in enumerate(raw):
            if ax is None or ax == "dp":
                spec.append(None)
            elif d != 0:
                # proj/ff2: the rules map their INPUT rows ('heads' /
                # 'ffn' on dim 1) to 'tp' — a contraction-dim shard
                # whose matmul would psum partial fp32 products in a
                # different order than the single-device dot.  The
                # engine reconstructs the activation with an exact
                # all-gather instead and keeps these replicated.
                spec.append(None)
            elif shape[d] % self.tp:
                spec.append(None)  # uneven (e.g. vocab % tp) → replicate
            else:
                spec.append(ax)
        return tuple(spec)

    def _put(self, arr, spec):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    def shard_params(self, host_params) -> Dict[str, object]:
        """Place a per-layer-named host checkpoint onto the mesh:
        every param under its rules spec (output-dim tp shards,
        replicated across pp), the fused qkv rows head-permuted so
        contiguous tp chunks are per-device head groups.  Adds the
        replicated sampler ``base_key``."""
        out = {}
        names = list(_TRUNK_NAMES) + [
            f"layer{i}_{kind}"
            for i in range(self.L) for kind in _BLOCK_KINDS]
        for name in names:
            if name not in host_params:
                raise MXNetError(f"params missing {name!r} for the "
                                 f"mesh decode program")
            arr = _np(host_params[name])
            self._host_shapes[name] = tuple(arr.shape)
            spec = self._param_spec(name, arr.shape)
            if name.endswith(("qkv_weight", "qkv_bias")) \
                    and spec[0] == "tp":
                arr = arr[self._qkv_perm]
            self._specs[name] = spec
            out[name] = self._put(arr, spec)
        self._specs["base_key"] = ()
        out["base_key"] = self._put(self._base_key, ())
        return out

    def unshard_params(self, params) -> Dict[str, np.ndarray]:
        """Back to the host checkpoint layout (qkv rows restored to
        checkpoint order) — get_params / swap-rollback anchor."""
        import jax

        host = {}
        for name, spec in self._specs.items():
            if name == "base_key":
                continue
            arr = np.asarray(jax.device_get(params[name]))
            if name.endswith(("qkv_weight", "qkv_bias")) \
                    and spec[0] == "tp":
                arr = arr[self._qkv_inv]
            host[name] = arr
        return host

    def host_shape(self, name) -> Optional[tuple]:
        return self._host_shapes.get(name)

    def init_pools(self, cache_blocks: int) -> tuple:
        """Zeroed stacked pools: k/v (L, P, KVB, H·D) sharded
        ('pp', -, -, 'tp') + quantized f32 scale pools
        (L, P, KVB, H) sharded ('pp', -, -, 'tp')."""
        from .kv_cache import value_pool_shape

        shape = (self.L,) + value_pool_shape(cache_blocks, self.kvb,
                                             self.H, self.D)
        zero = np.zeros(shape, self._pool_dtype)
        pools = [self._put(zero, self._kv_spec),
                 self._put(zero, self._kv_spec)]
        if self._quant:
            one = np.ones(shape[:3] + (self.H,), np.float32)
            pools.append(self._put(one, self._sc_spec))
            pools.append(self._put(one, self._sc_spec))
        return tuple(pools)

    def pool_specs(self) -> tuple:
        specs = [self._kv_spec, self._kv_spec]
        if self._quant:
            specs += [self._sc_spec, self._sc_spec]
        return tuple(specs)

    def pool_bytes_per_device(self, pools) -> int:
        """Bytes of pool (values + scales) each device holds: the
        stacked dim shards over pp, the head dim over tp."""
        return sum(int(np.prod(np.shape(p)))
                   * np.dtype(p.dtype).itemsize
                   for p in pools) // (self.tp * self.pp)

    def describe(self) -> dict:
        """stats() / statusz mesh section — what actually sharded."""
        return {
            "tp": self.tp,
            "pp": self.pp,
            "devices": [str(d) for d in self.plan.devices],
            "sharded": {"heads": self.tp > 1,
                        "ffn": self._tp_ffn and self.tp > 1,
                        "vocab": self._tp_vocab and self.tp > 1,
                        "layers": self.pp > 1},
        }

    # ------------------------------------------------------------------
    # the per-device forward (runs INSIDE shard_map; all shapes local)
    # ------------------------------------------------------------------
    def _embed(self, p, data, positions):
        import jax.numpy as jnp
        from jax import lax

        w = p["tok_embed_weight"]
        if self._tp_vocab and self.tp > 1:
            # clip FIRST (jnp.take's out-of-range semantics under jit),
            # then localize: exactly one shard holds the row, the rest
            # contribute exact zeros — psum is bit-exact
            ids = jnp.clip(data.astype(jnp.int32), 0, self.V - 1)
            tp_i = lax.axis_index("tp")
            loc = ids - tp_i * self.Vl
            hit = (loc >= 0) & (loc < self.Vl)
            rows = jnp.take(w, jnp.clip(loc, 0, self.Vl - 1), axis=0)
            x = lax.psum(
                jnp.where(hit[..., None], rows, jnp.zeros_like(rows)),
                "tp")
        else:
            x = _op1("Embedding", {}, [data, w])
        return x + _op1("take", {}, [p["pos_embed_weight"], positions])

    def _block(self, p, gl, j, x, attend):
        """One residual block: ``gl`` is the STATIC global layer id
        (names the weight leaves), ``j`` the local pool-slab row the
        attention reads/writes (= gl % Ll; they coincide on the stage
        whose hop this is)."""
        from jax import lax

        def g(kind):
            return p[f"layer{gl}_{kind}"]

        h = _op1("LayerNorm", {}, [x, g("ln1_gamma"), g("ln1_beta")])
        qkv = _op1("FullyConnected", _FC_ATTRS,
                   [h, g("qkv_weight"), g("qkv_bias")])
        att, cache = attend(j, qkv)
        if self.tp > 1:
            # heads live in tp-index order → tiled gather concatenates
            # them back into the global (B, S, H*D) layout exactly
            att = lax.all_gather(att, "tp", axis=-1, tiled=True)
        att = _op1("FullyConnected", _FC_ATTRS,
                   [att, g("proj_weight"), g("proj_bias")])
        x = x + att
        h = _op1("LayerNorm", {}, [x, g("ln2_gamma"), g("ln2_beta")])
        h = _op1("FullyConnected", _FC_ATTRS,
                 [h, g("ff1_weight"), g("ff1_bias")])
        h = _op1("Activation", {"act_type": "gelu"}, [h])
        if self._tp_ffn and self.tp > 1:
            h = lax.all_gather(h, "tp", axis=-1, tiled=True)
        h = _op1("FullyConnected", _FC_ATTRS,
                 [h, g("ff2_weight"), g("ff2_bias")])
        return x + h, cache

    def _forward(self, p, pools, data, positions, attend):
        """Embedding → pp micro-hop slab loop → ln_f → full-vocab
        logits.  Returns (logits — valid on the LAST pp stage — and
        the updated stacked local pools)."""
        import jax.numpy as jnp
        from jax import lax

        x = self._embed(p, data, positions)

        def run_slab(x, pools, base):
            # static global layer ids base..base+Ll-1: weight leaves
            # reach every dot as direct program parameters
            outs: List[list] = [[] for _ in pools]
            for j in range(self.Ll):
                x, cache = self._block(p, base + j, j, x, attend)
                for i, c in enumerate(cache):
                    outs[i].append(c)
            return x, tuple(jnp.stack(o) for o in outs)

        S = self.pp
        if S == 1:
            x, new_pools = run_slab(x, pools, 0)
        else:
            pp_i = lax.axis_index("pp")
            hop = [(i, i + 1) for i in range(S - 1)]
            new_pools = pools
            y = x
            for it in range(S):
                y, cand = run_slab(x, pools, it * self.Ll)
                # hop `it` is real exactly on stage `it` — the stage
                # whose pool slab rows layers [it*Ll, (it+1)*Ll) live
                # in; every other stage ran the hop on hand-off (or
                # zero-fill) activations and is discarded here
                keep = (it == pp_i)
                new_pools = tuple(
                    jnp.where(keep, c, n)
                    for c, n in zip(cand, new_pools))
                if it < S - 1:
                    # stages without a source are zero-filled; their
                    # next hop is finite garbage, discarded above
                    x = lax.ppermute(y, "pp", hop)
            x = y
        x = _op1("LayerNorm", {}, [x, p["ln_f_gamma"], p["ln_f_beta"]])
        logits = _op1("FullyConnected", _FC_ATTRS,
                      [x, p["head_weight"], p["head_bias"]])
        if self._tp_vocab and self.tp > 1:
            logits = lax.all_gather(logits, "tp", axis=-1, tiled=True)
        return logits, new_pools

    def _pp_emit(self, toks):
        """Sampling psum'd off the last stage: earlier stages sampled
        finite garbage, masked to zero — integer psum, bit-exact, so
        the (engine seed, stream seed, position) contract holds."""
        import jax.numpy as jnp
        from jax import lax

        if self.pp == 1:
            return toks
        pp_i = lax.axis_index("pp")
        return lax.psum(
            jnp.where(pp_i == self.pp - 1, toks, jnp.zeros_like(toks)),
            "pp")

    def _pool_slices(self, pools, l):
        sl = [pools[0][l], pools[1][l]]
        if self._quant:
            sl += [pools[2][l], pools[3][l]]
        return sl

    def _wrap(self, body, n_feeds):
        """shard_map the step body: params dict + replicated feeds +
        sharded pools in, (replicated tokens, sharded pools) out."""
        from jax.sharding import PartitionSpec as P

        from .sequence import _shard_map

        if not self._specs:
            raise MXNetError("MeshPrograms.shard_params must run "
                             "before building step programs")
        pspecs = {n: P(*s) for n, s in self._specs.items()}
        pool_specs = tuple(P(*s) for s in self.pool_specs())
        in_specs = (pspecs,) + (P(),) * n_feeds + (pool_specs,)
        out_specs = (P(), pool_specs)
        # check=False: all_gather outputs are value-replicated but
        # vma-"varying", the same reason sequence.py's shim disables
        # the check for ring attention
        return _shard_map(body, self.mesh, in_specs, out_specs, False)

    # ------------------------------------------------------------------
    # phase step programs (engine-compatible signatures)
    # ------------------------------------------------------------------
    def decode_step(self):
        import jax.numpy as jnp

        from .serving import sample_tokens

        op = "QKVPagedAttentionDecodeQ" if self._quant \
            else "QKVPagedAttentionDecode"
        hl = {"num_heads": str(self.Hl)}

        def body(params, tokens, positions, lengths, table, temps,
                 seeds, steps, pools):
            def attend(l, qkv):
                outs = _ops(op, hl, [qkv] + self._pool_slices(pools, l)
                            + [table, lengths])
                return outs[0], outs[1:]

            logits, new_pools = self._forward(params, pools, tokens,
                                              positions, attend)
            toks = sample_tokens(params["base_key"], logits[:, 0, :],
                                 temps, seeds, steps)
            return self._pp_emit(toks), new_pools

        return self._wrap(body, 7)

    def verify_step(self):
        from .speculative import verify_sample

        op = "QKVPagedVerifyAttendQ" if self._quant \
            else "QKVPagedVerifyAttend"
        hl = {"num_heads": str(self.Hl)}

        def body(params, tokens, positions, start, lengths, table,
                 temps, seeds, steps0, pools):
            def attend(l, qkv):
                outs = _ops(op, hl, [qkv] + self._pool_slices(pools, l)
                            + [table, start, lengths])
                return outs[0], outs[1:]

            logits, new_pools = self._forward(params, pools, tokens,
                                              positions, attend)
            emit = verify_sample(params["base_key"], logits, tokens,
                                 lengths - start, temps, seeds, steps0)
            return self._pp_emit(emit), new_pools

        return self._wrap(body, 8)

    def prefill_step(self):
        import jax.numpy as jnp

        from .serving import sample_tokens

        wop = "PagedCacheWriteQ" if self._quant else "PagedCacheWrite"
        attrs = {"num_heads": str(self.Hl),
                 "block_size": str(self.kvb)}

        def body(params, tokens, positions, lengths, table, temps,
                 seeds, steps, pools):
            def attend(l, qkv):
                out, k, v = _ops("QKVSelfAttentionPrefill", attrs,
                                 [qkv])
                new = _ops(wop, {},
                           [k, v] + self._pool_slices(pools, l)
                           + [table, lengths])
                return out, new

            logits, new_pools = self._forward(params, pools, tokens,
                                              positions, attend)
            last = logits[jnp.arange(logits.shape[0]), lengths - 1]
            toks = sample_tokens(params["base_key"], last, temps,
                                 seeds, steps)
            return self._pp_emit(toks), new_pools

        return self._wrap(body, 7)

    def prefix_prefill_step(self):
        import jax.numpy as jnp

        from .serving import sample_tokens

        op = "QKVPagedPrefillAttendQ" if self._quant \
            else "QKVPagedPrefillAttend"
        hl = {"num_heads": str(self.Hl)}

        def body(params, tokens, positions, start, lengths, table,
                 temps, seeds, steps, pools):
            def attend(l, qkv):
                outs = _ops(op, hl, [qkv] + self._pool_slices(pools, l)
                            + [table, start, lengths])
                return outs[0], outs[1:]

            logits, new_pools = self._forward(params, pools, tokens,
                                              positions, attend)
            last = logits[jnp.arange(logits.shape[0]),
                          lengths - start - 1]
            toks = sample_tokens(params["base_key"], last, temps,
                                 seeds, steps)
            return self._pp_emit(toks), new_pools

        return self._wrap(body, 8)

    def cow_fn(self):
        """Copy-on-write page copy over the STACKED pools (page axis
        1): pure data movement, no collective — GSPMD keeps each
        shard's copy local."""

        def copy(pools, src, dst):
            return tuple(p.at[:, dst].set(p[:, src]) for p in pools)

        return copy
