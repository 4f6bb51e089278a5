"""Decoder-only transformer language model.

The reference (v0.9.1) predates transformers; this model family is the
framework's long-context flagship, built entirely from registered
symbol ops: ``DotProductAttention`` (the Pallas flash kernel on TPU,
``ops/attention.py``), ``LayerNorm``, GELU, and flatten=False
``FullyConnected``.  Pre-LN residual blocks (the trainable-at-depth
variant), learned positional embeddings, weight-tied-free output head,
``SoftmaxOutput(preserve_shape)`` loss over (B, T) token labels.

Sequence parallelism: the same attention primitive is distributed by
``mxnet_tpu.sequence`` (ring / Ulysses) over an 'sp' mesh axis — see
``__graft_entry__.dryrun_multichip`` and tests/test_sequence.py; this
symbol graph is the single-shard program those wrap.

3D parallelism: every weight carries LOGICAL axis names (``('vocab',
'embed')``, ``('qkv', 'embed')`` …) and every residual block carries a
``__pp_block__`` annotation — sharding comes from ONE rules table
(:func:`lm_partition_rules` or your own, via ``MeshPlan(rules=...)``)
and pipeline stages from ``MeshPlan(pp=...)``, with **zero** per-op
``__shard__`` attrs anywhere in this file.  See README "3D
parallelism".
"""

import functools

from .. import symbol as sym
from ..attribute import AttrScope
from ..base import MXNetError
from ..kv_cache import KV_DTYPES, kv_quantized
from ..parallel import logical_axes


def lm_partition_rules(sequence_parallel: bool = False):
    """The canonical rules table for this model family: first match
    wins, ``None`` = replicated.  Feed to ``MeshPlan(rules=...)`` (or
    set ``MXNET_PARTITION_RULES=batch:dp;vocab|qkv|heads|ffn:tp;...``).

    ``sequence_parallel=True`` additionally shards the 'length'
    activation axis over 'tp' between attention calls (the Megatron-SP
    layout; composes with the ring-attention 'sp' path)."""
    return (
        ("batch", "dp"),
        ("vocab", "tp"),
        ("qkv", "tp"),
        ("heads", "tp"),
        ("ffn", "tp"),
        ("length", "tp" if sequence_parallel else None),
        ("embed", None),
    )


def _block(x, d_model, d_ff, name, attend, dropout=0.0, lora=(), layer=0):
    """One pre-LN residual block, the attention sublayer given by
    ``attend(qkv) -> (att_out, cache_outs)``: ``QKVSelfAttention`` for
    the training symbol, an op over the K/V cache for a serving one.
    The fused QKV projection output feeds ``attend`` DIRECTLY — the
    packed-heads Pallas kernel slices heads by lane span, so no
    reshape/slice/transpose ops exist between the two matmuls (they
    measured ~20 ms/step at GPT-2-small scale in July; today
    `benchmark/run.py --trace 1`'s `scope_time` line shows such ops).

    ``lora``: rank buckets (ints).  Each bucket adds a per-stream
    LoRA epilogue on the fused QKV projection — the adapter slabs
    ``adapter_a_r{rb}``/``adapter_b_r{rb}`` (N, L, d, rb)/(N, L, rb,
    3d) are gathered by the ``adapter_slots_r{rb}`` (B,) id vector,
    slot 0 selecting the base bits exactly (``ops/adapter.py``).  An
    empty tuple builds the pre-adapter graph byte-identically."""
    h = sym.LayerNorm(x, name=f"{name}_ln1")
    qkv = sym.FullyConnected(
        h, num_hidden=3 * d_model, flatten=False, name=f"{name}_qkv",
        weight=sym.Variable(f"{name}_qkv_weight",
                            attr=logical_axes("qkv", "embed")),
        bias=sym.Variable(f"{name}_qkv_bias", attr=logical_axes("qkv")))
    for rb in (lora or ()):
        # a stream lives in at most one bucket (slot 0 elsewhere), so
        # chaining buckets is exact: slot-0 rows pass base bits through
        qkv = sym.LoraGatherDelta(
            qkv, h, sym.Variable(f"adapter_a_r{rb}"),
            sym.Variable(f"adapter_b_r{rb}"),
            sym.Variable(f"adapter_slots_r{rb}"),
            layer=layer, name=f"{name}_lora_r{rb}")
    att, cache_outs = attend(qkv)
    att = sym.FullyConnected(
        att, num_hidden=d_model, flatten=False, name=f"{name}_proj",
        weight=sym.Variable(f"{name}_proj_weight",
                            attr=logical_axes("embed", "heads")),
        bias=sym.Variable(f"{name}_proj_bias",
                          attr=logical_axes("embed")))
    if dropout > 0:
        att = sym.Dropout(att, p=dropout, name=f"{name}_attn_drop")
    x = x + att
    # feed-forward sublayer (pre-LN, GELU)
    h = sym.LayerNorm(x, name=f"{name}_ln2")
    h = sym.FullyConnected(
        h, num_hidden=d_ff, flatten=False, name=f"{name}_ff1",
        weight=sym.Variable(f"{name}_ff1_weight",
                            attr=logical_axes("ffn", "embed")),
        bias=sym.Variable(f"{name}_ff1_bias", attr=logical_axes("ffn")))
    h = sym.Activation(h, act_type="gelu", name=f"{name}_gelu")
    h = sym.FullyConnected(
        h, num_hidden=d_model, flatten=False, name=f"{name}_ff2",
        weight=sym.Variable(f"{name}_ff2_weight",
                            attr=logical_axes("embed", "ffn")),
        bias=sym.Variable(f"{name}_ff2_bias", attr=logical_axes("embed")))
    if dropout > 0:
        h = sym.Dropout(h, p=dropout, name=f"{name}_ff_drop")
    return x + h, cache_outs


def _embed(vocab_size, d_model):
    return sym.Embedding(sym.Variable("data"), input_dim=vocab_size,
                         output_dim=d_model, name="tok_embed",
                         weight=sym.Variable(
                             "tok_embed_weight",
                             attr=logical_axes("vocab", "embed")))


def _head(x, vocab_size):
    x = sym.LayerNorm(x, name="ln_f")
    return sym.FullyConnected(
        x, num_hidden=vocab_size, flatten=False, name="head",
        weight=sym.Variable("head_weight",
                            attr=logical_axes("vocab", "embed")),
        bias=sym.Variable("head_bias", attr=logical_axes("vocab")))


def transformer_lm(vocab_size, seq_len, num_layers=4, num_heads=4,
                   d_model=128, d_ff=None, causal=True, dropout=0.0,
                   block_size=0, dtype="float32", head="softmax"):
    """Token ids (B, T) -> SoftmaxOutput probabilities (B, T, vocab),
    or per-token CE loss (B, T) with ``head="ce"`` — the fused
    SoftmaxCELoss head never materializes the (B, T, V) probability or
    gradient tensors, the right head for 32k+ vocabularies (PERF.md).

    Labels are next-token ids (B, T); padding id 0 is ignored
    (ignore_label, like the LSTM LM example).

    ``dtype``: compute dtype of the network.  Token ids stay float32
    (bf16 cannot represent ids >= 256 exactly — an id rounding past
    ``vocab_size`` is an out-of-range gather); the cast sits after the
    embedding so dtype propagation types every downstream layer.  Use
    "bfloat16" on TPU — beyond the MXU benefit, this backend's f32
    softmax over 3-D logits lowers ~30x slower than bf16 (PERF.md)."""
    if d_model % num_heads:
        raise ValueError(f"d_model {d_model} % num_heads {num_heads} != 0")
    d_ff = d_ff or 4 * d_model
    label = sym.Variable("softmax_label")
    x = _embed(vocab_size, d_model)
    if dtype != "float32":
        x = sym.Cast(x, dtype=dtype, name="embed_cast")
    # learned positional embedding: a (T, d) parameter broadcast over
    # the batch (declared shape so inference doesn't depend on a
    # position-id input)
    pos = sym.Variable("pos_embed_weight", shape=(seq_len, d_model),
                       dtype=dtype, init="[\"zero\", {}]",
                       attr=logical_axes("length", "embed"))
    x = sym.broadcast_add(x, sym.expand_dims(pos, axis=0),
                          attr={"__logical__": "batch,length,embed"})
    for i in range(num_layers):
        # __pp_block__ marks the pipeline-splittable trunk: every op
        # (and auto-created weight) of block i carries the annotation,
        # so MeshPlan(pp=S) can cut the graph into S stages
        # (mxnet_tpu.pp.split_blocks)
        with AttrScope(__pp_block__=str(i)):
            x, _ = _block(
                x, d_model, d_ff, f"layer{i}",
                lambda qkv, i=i: (sym.QKVSelfAttention(
                    qkv, num_heads=num_heads, causal=causal,
                    block_size=block_size, name=f"layer{i}_attn"), []),
                dropout=dropout)
    logits = _head(x, vocab_size)
    if head == "ce":
        return sym.SoftmaxCELoss(logits, label, use_ignore=True,
                                 ignore_label=0, name="softmax")
    return sym.SoftmaxOutput(logits, label, preserve_shape=True,
                             ignore_label=0, use_ignore=True,
                             name="softmax")


def get_symbol(vocab_size=10000, seq_len=128, num_layers=4, num_heads=4,
               d_model=128, **kwargs):
    return transformer_lm(vocab_size, seq_len, num_layers=num_layers,
                          num_heads=num_heads, d_model=d_model, **kwargs)


# ---------------------------------------------------------------------------
# Decode mode — the serving-side symbols.  Parameter names line up
# exactly with transformer_lm, so the weights of a trained checkpoint
# (or a Predictor) bind without renaming.  Both symbols take a
# ``positions`` (B, S) int input instead of assuming rows 0..S-1, so
# ONE symbol serves every (batch, length) bucket the engine compiles.
#
# Like the training symbols, every decode weight (and KV pool) carries
# LOGICAL axis names, so the SAME :func:`lm_partition_rules` table that
# shards training drives the serving engine's MeshPlan
# (``serving_mesh.py``): 'qkv'/'ffn'/'vocab' weight rows and the pools'
# 'heads' dim resolve to 'tp', everything else replicates.
# ---------------------------------------------------------------------------


def _cache_var(name: str):
    """A K/V pool Variable: values (P, KVB, H·D) —
    ``kv_cache.value_pool_shape``: lane-dense, because D = 64 is half
    a lane tile and a (…, H, D) pool is re-laid-out whole by every
    program that touches it — or a quantized pool's (P, KVB, H) float32
    scales.  The last dim is 'heads', the pool's tensor-parallel shard
    axis: heads are contiguous D-lane spans, so the rules table's 'tp'
    split still hands each device H/tp whole heads, exactly like the
    attention, and the scales shard alongside the values they scale."""
    return sym.Variable(name, attr=logical_axes(None, None, "heads"))


def _kv_quant(kv_dtype):
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype {kv_dtype!r} not in {KV_DTYPES}")
    return kv_quantized(kv_dtype)


# phase -> (the op that meets the paged cache, its quantize-on-write
# twin, the feeds it takes after the block table).  ``prefill`` attends
# the prompt alone (``QKVSelfAttentionPrefill``) and its op WRITES the
# K/V that returned; the others attend THROUGH the cache.
PHASES = {
    "prefill": ("PagedCacheWrite", "PagedCacheWriteQ", ("lengths",)),
    "decode": ("QKVPagedAttentionDecode", "QKVPagedAttentionDecodeQ",
               ("lengths",)),
    "prefix_prefill": ("QKVPagedPrefillAttend", "QKVPagedPrefillAttendQ",
                       ("start", "lengths")),
    "verify": ("QKVPagedVerifyAttend", "QKVPagedVerifyAttendQ",
               ("start", "lengths")),
}


def _serving_lm(phase, vocab_size, num_layers, num_heads, d_model, d_ff,
                kv_block, kv_dtype, lora, paged=True):
    """Embedding -> blocks -> ln_f -> head logits for one serving phase:
    ``[logits] + [updated caches ...]``, a layer's caches [k, v] or,
    quantized, [k, v, k_scale, v_scale]."""
    if phase not in PHASES:
        raise MXNetError(f"the transformer_lm family builds no {phase!r} "
                         f"symbol (it builds {tuple(PHASES)})")
    quant = _kv_quant(kv_dtype)
    op = getattr(sym, PHASES[phase][1 if quant else 0])
    feeds = {n: sym.Variable(n) for n in PHASES[phase][2]}
    n_cache = 4 if quant else 2

    def cache(name):
        pools = ("kpool", "vpool", "kscale", "vscale")[:n_cache]
        return [_cache_var(f"{name}_{p}") for p in pools] \
            + [sym.Variable("block_table")] + list(feeds.values())

    def attend(name, qkv):
        if phase == "prefill":
            att = sym.QKVSelfAttentionPrefill(
                qkv, num_heads=num_heads, block_size=kv_block,
                name=f"{name}_attn")
            if not paged:
                return att[0], [att[1], att[2]]
            pools = op(att[1], att[2], *cache(name),
                       name=f"{name}_cache_write")
            return att[0], [pools[j] for j in range(n_cache)]
        if not paged:  # decode against a contiguous (B, C, H, D) cache
            att = sym.QKVSelfAttentionDecode(
                qkv, sym.Variable(f"{name}_kcache"),
                sym.Variable(f"{name}_vcache"), feeds["lengths"],
                num_heads=num_heads, block_size=kv_block,
                name=f"{name}_attn")
            return att[0], [att[1], att[2]]
        att = op(qkv, *cache(name), num_heads=num_heads,
                 name=f"{name}_attn")
        return att[0], [att[1 + j] for j in range(n_cache)]

    d_ff = d_ff or 4 * d_model
    x = _embed(vocab_size, d_model)  # data: (B, S) token ids
    pos = sym.Variable("pos_embed_weight",
                       attr=logical_axes("length", "embed"))
    # positions: (B, S) absolute, so one symbol serves every bucket
    x = x + sym.take(pos, sym.Variable("positions"), name="pos_lookup")
    caches = []
    for i in range(num_layers):
        x, cache_outs = _block(x, d_model, d_ff, f"layer{i}",
                               functools.partial(attend, f"layer{i}"),
                               lora=lora, layer=i)
        caches.extend(cache_outs)
    return sym.Group([_head(x, vocab_size)] + caches)


def transformer_lm_prefill(vocab_size, num_layers=4, num_heads=4,
                           d_model=128, d_ff=None, kv_block=16,
                           paged=True, kv_dtype="fp32", lora=None):
    """Prefill symbol: the full causal forward over a (padded) prompt
    that ALSO writes each layer's K/V state into the cache.

    Inputs: ``data``/``positions`` (B, T), ``lengths`` (B,) int32
    prompt lengths, plus — paged — ``block_table`` (B, MB) and
    per-layer ``layer{i}_kpool``/``layer{i}_vpool`` pools.  Outputs:
    ``[logits (B, T, vocab)] + [updated caches ...]``.  Attention runs
    at ``block_size=kv_block`` so the logits are bit-identical to
    ``transformer_lm(..., block_size=kv_block)`` rows (lax path).

    ``kv_dtype``: K/V pool storage — 'fp32'/'bf16' write through the
    plain ops (a bf16 pool is just a narrow cast); 'int8'/'fp8' route
    through the quantize-on-write ops and add per-layer
    ``layer{i}_kscale``/``layer{i}_vscale`` (P, KVB, H) float32 scale
    pools, making each layer contribute FOUR cache outputs.
    """
    return _serving_lm("prefill", vocab_size, num_layers, num_heads,
                       d_model, d_ff, kv_block, kv_dtype, lora, paged)


def transformer_lm_prefix_prefill(vocab_size, num_layers=4, num_heads=4,
                                  d_model=128, d_ff=None, kv_block=16,
                                  kv_dtype="fp32", lora=None):
    """Suffix-prefill symbol for a prefix-cache hit: the forward runs
    ONLY over the uncached suffix of the prompt, attending the shared
    prefix through the paged cache.

    Inputs: ``data``/``positions`` (B, Ts) — the suffix tokens at
    absolute positions ``start[b] + i``; ``start`` (B,) int32 cached
    (block-aligned) token counts; ``lengths`` (B,) int32 TOTAL tokens
    (start + real suffix); ``block_table`` (B, MB) covering prefix AND
    suffix pages; per-layer pools (+ scale pools when quantized).
    Outputs: ``[suffix logits (B, Ts, vocab)] + [updated caches]``.
    Bit-identical (lax path, fp32 pools) to the matching rows of the
    full causal forward — see ``ops.attention.prefix_suffix_attention``.
    """
    return _serving_lm("prefix_prefill", vocab_size, num_layers,
                       num_heads, d_model, d_ff, kv_block, kv_dtype, lora)


def transformer_lm_verify(vocab_size, num_layers=4, num_heads=4,
                          d_model=128, d_ff=None, kv_block=16,
                          kv_dtype="fp32", lora=None):
    """Speculative-verify symbol: W = 1 + k tokens per stream per step
    against the paged KV cache — the multi-query decode step that
    scores the pending token plus k draft tokens in ONE program.

    Inputs: ``data``/``positions`` (B, W) — the pending token and the
    drafts at absolute positions ``start[b] + i``; ``start`` (B,)
    int32 tokens already cached; ``lengths`` (B,) int32 ``start`` +
    live window rows (rows past it are padding and write to the
    scratch page); ``block_table`` (B, MB); per-layer pools (+ scale
    pools when quantized).  Outputs: ``[logits (B, W, vocab)] +
    [updated caches]``.  Row ``i`` of the logits is bit-identical
    (lax path) to the single-token decode step at length
    ``start + 1 + i`` over the same cache bytes — see
    ``ops.attention.QKVPagedVerifyAttend``."""
    return _serving_lm("verify", vocab_size, num_layers, num_heads,
                       d_model, d_ff, kv_block, kv_dtype, lora)


def transformer_lm_decode(vocab_size, num_layers=4, num_heads=4,
                          d_model=128, d_ff=None, kv_block=16,
                          paged=True, kv_dtype="fp32", lora=None):
    """Decode-mode symbol: ONE token per stream per step against the
    KV cache.

    Inputs: ``data``/``positions`` (B, 1), ``lengths`` (B,) int32
    counting the current token, plus — paged — ``block_table`` (B, MB)
    and per-layer pools, or — contiguous — per-layer
    ``layer{i}_kcache``/``layer{i}_vcache`` (B, C, H, D).  Outputs:
    ``[logits (B, 1, vocab)] + [updated caches ...]``; feed the
    updated caches back in (donate them under jit) for the next step.
    Prefill + N decode steps is bit-identical (lax path) to the
    full-sequence forward — the page size is the attention block size.
    """
    return _serving_lm("decode", vocab_size, num_layers, num_heads,
                       d_model, d_ff, kv_block, kv_dtype, lora, paged)


class DenseSpec:
    """This family as ``DecodeEngine`` sees a model (the protocol is in
    its docstring): what ``DecodeEngine(params, vocab_size=...,
    num_layers=..., num_heads=..., d_model=...)`` builds for itself; a
    family with other layers brings its own (``models/hybrid_lm.py``
    ``HybridSpec``)."""

    name = "the transformer_lm family"
    feeds = ("data", "positions", "lengths", "block_table", "start")
    phases = tuple(PHASES)
    kv_dtypes = KV_DTYPES
    positions = "pos_embed_weight"
    partition_rules = staticmethod(lm_partition_rules)

    def __init__(self, vocab_size, num_layers, num_heads, d_model,
                 d_ff=None):
        if d_model % num_heads:
            raise MXNetError(f"d_model {d_model} % num_heads "
                             f"{num_heads} != 0")
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.num_heads = self.kv_heads = int(num_heads)
        self.d_model = int(d_model)
        self.d_ff = d_ff
        self.head_dim = self.d_model // self.num_heads
        self.lora_width = 3 * self.d_model  # the fused QKV projection

    def pool_kinds(self, kv_dtype="fp32"):
        layer = ("pages", "pages") + (("scales", "scales")
                                      if _kv_quant(kv_dtype) else ())
        return layer * self.num_layers

    def pools(self, cache_blocks, kv_block, slots, dtype,
              kv_dtype="fp32"):
        """Per layer [k, v] or, quantized, [k, v, k_scale, v_scale]:
        ``(name, shape, dtype, fill)`` in the symbols' output order."""
        from ..kv_cache import value_pool_shape

        shape = value_pool_shape(cache_blocks, kv_block, self.num_heads,
                                 self.head_dim)
        out = []
        for i in range(self.num_layers):
            out += [(f"layer{i}_kpool", shape, dtype, 0),
                    (f"layer{i}_vpool", shape, dtype, 0)]
            if _kv_quant(kv_dtype):
                scale = shape[:2] + (self.num_heads,)
                out += [(f"layer{i}_kscale", scale, "float32", 1),
                        (f"layer{i}_vscale", scale, "float32", 1)]
        return out

    def symbol(self, which, kv_block=16, kv_dtype="fp32", lora=None):
        return _serving_lm(which, self.vocab_size, self.num_layers,
                           self.num_heads, self.d_model, self.d_ff,
                           kv_block, kv_dtype, lora)
