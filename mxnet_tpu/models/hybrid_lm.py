"""Hybrid decoder-only language models, built from a LAYER LIST.

A model of this family is a stack of pre-norm residual blocks (RMSNorm,
no biases, no learned positions) in which every layer names its own
MIXER and its own FFN:

* mixer ``attention``: softmax attention with grouped queries
  (``heads`` query heads over ``kv_heads`` KV heads of ``head_dim``;
  ``scale``: the scores' multiplier where it is not ``head_dim^-1/2``),
  an optional element-wise sigmoid output gate before the output
  projection (``gate``; it reads the block's normalised input);
  ``qk_norm``: an RMSNorm over each head's ``head_dim`` lanes of q and
  of k — one gain of ``head_dim`` each a layer, shared by the heads
  (nodes ``layer{i}_q_norm`` / ``_k_norm``) — BEFORE the rotation, so K
  goes into the pages normalised and rotated; ``rope_theta``: q and k
  rotated by their positions
  (rotate-half over the whole head; absent = no positions at all);
  ``window``: a query sees its own key and the ``window - 1`` before it
  (absent = every key).  Its per-stream state is K/V PAGES; a windowed
  layer's are pages of a SECOND pool (kind ``window_pages``, addressed
  through the ``window_table`` feed), in which a stream holds only the
  pages its window still reaches;
* mixer ``mla``: multi-head latent attention — ``heads`` heads whose
  queries come through a rank-``q_rank`` bottleneck (with its own norm)
  and whose keys and values are up-projections of ONE compressed row a
  token, ``kv_rank`` values (normalised) beside a positional key of
  ``rope_dim`` shared by every head; a head's query and key are
  ``nope_dim`` lanes without positions beside ``rope_dim`` rotated ones
  (rotate-half, base ``rope_theta``, frequencies rescaled by
  ``rope_scaling`` — the published dict, ``type`` yarn — where given),
  its value ``v_dim``; ``scale``: the scores' multiplier (derived from
  the widths and ``rope_scaling`` where absent).  Its per-stream state
  is PAGES of that row (``kv_cache.latent_pool_shape``: ONE pool a
  layer); a prefill up-projects and attends, a decode step absorbs the
  up-projection into the query and the output and attends over the rows
  as they lie (``ops/hybrid.py``);
* mixer ``kda``: gated-delta-rule linear attention with a per-channel
  decay (``heads`` heads of ``head_dim``, a depthwise short convolution
  of ``conv`` taps on q, k and v, low-rank decay and output-gate maps of
  rank ``head_dim``, ``neg_eigval``: beta in (0, 2)); its per-stream
  state is a SLOT: the (heads, head_dim, head_dim) float32 state and the
  convolution's last ``conv - 1`` inputs;
* mixer ``mamba2``: a Mamba-2 state-space layer, ``heads`` heads of
  ``head_dim`` over a state of ``d_state`` (one fused input map to gate
  | x, B, C | dt; a depthwise short convolution of ``conv`` taps, with
  a bias where ``conv_bias``, over x, B and C; a scalar decay a head; B
  and C shared by all heads — ONE group; the gate BEFORE the output
  norm); its slot: the (heads, head_dim, d_state)
  float32 state and the convolution's last inputs;
* mixer ``retention``: power retention — gated linear attention of
  ``degree`` 2 (the one built) with grouped queries, ``heads`` query
  heads over ``kv_heads`` KV heads of ``head_dim``: a query weighs a key
  by ``(q.k / sqrt(head_dim))^2`` times the gates between them (one
  log-sigmoid gate a token and KV head, from the block's normalised
  input, with a float32 bias, parameter ``layer{i}_g_bias``) and
  divides by the sum of its weights; ``qk_norm`` as an attention
  mixer's, ``rope_theta`` required (it always rotates).  It keeps NO page: its slot is, a KV head, the
  float32 symmetric square of the keys against the values — packed by
  lane rolls and held transposed, ``(head_dim / 2 + 1) * head_dim`` rows
  of ``head_dim`` lanes (``ops/hybrid.py retention_phi``: 8,320 rows at
  128 where the Kronecker square would take 16,384), read by all of the
  KV head's query heads — and, its auxiliary array, the normaliser
  ``Z = sum decay k k^T`` (``head_dim`` x ``head_dim``): no convolution,
  no tail;
* mixer ``cca``: compressed convolutional attention — grouped-query
  attention INSIDE a latent of ``heads`` query heads over ``kv_heads``
  KV heads of ``head_dim`` (q, k and v projected straight from the
  block's input at those widths; the output map leads back to the
  model's), whose q | k rows are mixed along the sequence before the
  scores: two causal convolutions of ``conv`` = [2, 2] taps (depthwise,
  then grouped by head) plus the q-k mean of the rows before them, each
  head then L2-normalised to length sqrt(head_dim), the keys times a
  learned temperature a KV head (``layer{i}_qk_norm_temperature``,
  float32); ``rope_theta`` required, ``rotary_dim``: the first that many
  lanes of a head rotate (absent: the whole head); the second half of
  the value lanes is projected from the PREVIOUS token.  Its per-stream
  state is BOTH kinds: K/V pages (mixed, normalised, rotated keys) AND a
  slot's auxiliary array, the tail — the previous token's rows before
  and after the depthwise convolution and its shifted value half
  (``ops/hybrid.py`` CCAMix);
* ffn ``dense``: a gated (SiLU) feed-forward of ``width``;
* ffn ``moe``: ``experts`` routed experts of ``width``, ``top_k`` a
  token (``score``: ``sigmoid`` — normalised sigmoid scores, the
  default — ``softmax_topk`` — a softmax over the chosen logits — or
  ``softmax`` — a softmax over ALL experts, the chosen probabilities
  un-normalised (a top-1 weight is not 1), ``select_bias`` moving the
  choice alone; ``router``: absent, one matrix; ``{"kind": "mlp",
  "hidden": n, "carry": bool}`` — a network of its own, all float32: a
  map down to ``hidden``, under ``carry`` joined by the hidden row the
  router of the layer BEFORE left (times a learned gain a channel; the
  first such layer carries nothing), an RMSNorm, three maps with GELU
  between to the experts' logits; ``top_k`` 1 is the one built;
  ``act``: the experts' gate, ``silu`` — the default — or ``relu``;
  ``router_input``: ``ffn`` — the router scores the rows the experts
  get, the default — or ``block`` — the block's un-normalised input,
  before attention; under ``sigmoid``, ``select_bias``: a learned bias
  (parameter ``router_bias``) moves the CHOICE and not the weights,
  ``groups`` / ``top_groups``: the choice is made inside the best
  ``top_groups`` of ``groups`` groups of consecutive experts,
  ``routed_scale``: the normalised weights' multiplier),
  plus ``shared`` always-on experts (of ``shared_width`` together;
  default ``width`` each); this program HOLDS ``experts_held`` of the routed experts,
  from ``first_expert`` on — the share of one chip of an expert-parallel
  deployment — and adds only their part (``ops/hybrid.py`` MoEFFN).

:class:`HybridSpec` is what ``mx.DecodeEngine(params, model=spec)``
takes: from the layer list it derives the feeds, the pools (pages for
attention, mla and cca layers, slots for kda, mamba2 and retention
layers, a slot's tail beside the pages for cca layers)
and the
prefill and decode symbols (a prefill's logits are those of each
prompt's LAST row alone, (B, 1, vocab): the engine samples one token).
Data of the spec too: ``embed_scale`` (token rows), ``residual_scale``
(a block's output before the add), ``logits_scale`` (the last norm's
output), ``tied_head``, ``post_norm`` (a branch's OUTPUT through an
RMSNorm of its own, ``layer{i}_post_norm1`` / ``_post_norm2``, before the
scale and the add), ``learned_residual`` (every add is ``(a_r x + b_r) +
(a_o out + b_o)`` under four learned vectors, ``layer{i}_res1_scales`` /
``_res2_scales`` (4, d_model)).  An absent key builds the symbol it built before the
key existed; a key no kind knows is refused by name.  NODE NAMES (a traced
run's ``scope_time`` line groups device time by them), after ``layer{i}_``:
``norm1 norm2``; attention ``q k v q_norm k_norm attn gate o``; mla ``q_down
q_norm q_up kv_down kv_norm``, ``kv_up`` (prefill) or ``absorb_k absorb_v``
(decode), ``attn o``; kda ``qkv conv a_down a_up beta kda g_down g_up onorm
o``; mamba2 ``in conv mamba2 onorm out``; retention ``q k v q_norm
k_norm g retention o``; cca ``q k v1 v2 mix qk_norm v attn o``; FFNs
``ffn_* moe shared_*``, an mlp router's ``router_down router_carry
router_norm router_1 router_2 router_3``; ``res1 res2`` (learned residual
scales); and ``tok_embed final_norm last_row head``.  Equations: ``benchmark/reference/``.
"""

from .. import symbol as sym
from ..base import MXNetError
from ..ops.hybrid import EXPERT_ACTS, mla_scale

# every key a mixer or an FFN dict may hold, by kind: another is a typo
# that would silently build a model without the mechanism it names
MIXERS = {
    "attention": ("kind", "heads", "kv_heads", "head_dim", "scale", "gate",
                  "rope_theta", "window", "qk_norm"),
    "mla": ("kind", "heads", "q_rank", "kv_rank", "nope_dim", "rope_dim",
            "v_dim", "rope_theta", "rope_scaling", "scale"),
    "kda": ("kind", "heads", "head_dim", "conv", "neg_eigval"),
    "mamba2": ("kind", "heads", "head_dim", "d_state", "groups", "conv",
               "conv_bias"),
    "retention": ("kind", "heads", "kv_heads", "head_dim", "degree",
                  "rope_theta", "qk_norm"),
    "cca": ("kind", "heads", "kv_heads", "head_dim", "conv", "rope_theta",
            "rotary_dim"),
}
FFNS = {
    "dense": ("kind", "width"),
    "moe": ("kind", "experts", "top_k", "width", "score", "shared",
            "shared_width", "experts_held", "first_expert", "act",
            "router_input", "groups", "top_groups", "routed_scale",
            "select_bias", "router"),
}
ROUTER_INPUTS = ("ffn", "block")
ROUTER_KEYS = ("kind", "hidden", "carry")     # of an ``mlp`` router
COUNTERS = "moe_counters"


def _fc(x, width, name):
    return sym.FullyConnected(x, num_hidden=width, flatten=False,
                              no_bias=True, name=name,
                              weight=sym.Variable(f"{name}_weight"))


def _norm(x, name, eps, **attrs):
    return sym.RMSNorm(x, sym.Variable(f"{name}_gamma"), eps=eps,
                       name=name, **attrs)


def _gated_ffn(h, width, d_model, name):
    g = sym.Activation(_fc(h, width, f"{name}_gate"), act_type="silu")
    return _fc(g * _fc(h, width, f"{name}_up"), d_model, f"{name}_down")


def mixer_state(m):
    """What a recurrent mixer keeps a stream, both float32: ((heads,
    rows, lanes) of its state, the shape a slot of its AUXILIARY array —
    its short convolution's tail, ``kv_cache.conv_tail_shape``, or, for
    a retention mixer, which has no convolution, the normaliser's
    (KV heads, head_dim, head_dim)); None for a mixer whose state is
    pages alone.  A cca mixer keeps pages AND a tail: (None, the shape
    of a slot of it — the previous token's u | c | shifted value half,
    ``_cca_widths``)."""
    from ..kv_cache import conv_tail_shape

    if m["kind"] == "cca":
        return None, _cca_tail(m)[1]
    if m["kind"] == "retention":
        from ..ops.hybrid import retention_rows

        Hkv, D = int(m.get("kv_heads", m["heads"])), int(m["head_dim"])
        return (Hkv, retention_rows(D), D), (Hkv, D, D)
    if m["kind"] == "kda":
        H, D = int(m["heads"]), int(m["head_dim"])
        return (H, D, D), conv_tail_shape(1, int(m["conv"]), 3 * H * D)[1:]
    if m["kind"] == "mamba2":
        H, P, N = int(m["heads"]), int(m["head_dim"]), int(m["d_state"])
        return (H, P, N), conv_tail_shape(1, int(m["conv"]),
                                          _mamba2_channels(m))[1:]
    return None


def _mamba2_channels(m):
    """What a mamba2 mixer's convolution carries: x, B and C."""
    return int(m["heads"]) * int(m["head_dim"]) + 2 * int(m["d_state"])


def _cca_widths(m):
    """(channels of a cca mixer's latent q | k, lanes of the value half
    it takes from the previous token)."""
    H, Hkv, D = int(m["heads"]), int(m["kv_heads"]), int(m["head_dim"])
    return (H + Hkv) * D, Hkv * D // 2


def _cca_tail(m):
    """(the float32 numbers a cca mixer carries a stream — the previous
    token's u | c | shifted value half —, the shape of the slot that
    holds them in whole tiles)."""
    from ..kv_cache import conv_tail_shape

    C, wv = _cca_widths(m)
    return 2 * C + wv, conv_tail_shape(1, 2, 2 * C + wv)[1:]


# the auxiliary pool's name after ``layer{i}_``, by mixer kind
_AUX_POOL = {"kda": "tail", "mamba2": "tail", "retention": "zsum",
             "cca": "tail"}


class HybridSpec:
    """The model ``DecodeEngine`` is given: sizes and the layer list.

    ``layers``: one dict a layer, ``{"mixer": {"kind": ...}, "ffn":
    {"kind": ...}}`` with the keys the module doc names (an attention
    mixer's ``qk_norm`` among them); ``post_norm``: every branch's
    output is normalised before it is added.  Plain data: a spec
    round-trips through JSON (:meth:`to_dict`)."""

    def __init__(self, vocab_size, d_model, layers, norm_eps=1e-5,
                 embed_scale=1.0, residual_scale=1.0, logits_scale=1.0,
                 tied_head=False, post_norm=False, learned_residual=False):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.norm_eps = float(norm_eps)
        self.embed_scale = float(embed_scale)
        self.residual_scale = float(residual_scale)
        self.logits_scale = float(logits_scale)
        self.tied_head = bool(tied_head)
        self.post_norm = bool(post_norm)
        self.learned_residual = bool(learned_residual)
        self.layers = [dict(mixer=dict(ly["mixer"]), ffn=dict(ly["ffn"]))
                       for ly in layers]
        for i, ly in enumerate(self.layers):
            m, f = ly["mixer"], ly["ffn"]
            if m.get("kind") not in MIXERS or f.get("kind") not in FFNS:
                raise MXNetError(
                    f"layer {i}: mixer kind {m.get('kind')!r} must be one "
                    f"of {tuple(MIXERS)} and ffn kind {f.get('kind')!r} "
                    f"one of {tuple(FFNS)}")
            for what, d, known in (("mixer", m, MIXERS[m["kind"]]),
                                   ("ffn", f, FFNS[f["kind"]])):
                unknown = sorted(set(d) - set(known))
                if unknown:
                    raise MXNetError(
                        f"layer {i}: {d['kind']} {what} has no key "
                        f"{unknown} (it takes {known})")
            if m["kind"] == "attention" and (
                    float(m.get("rope_theta") or 0) < 0
                    or int(m.get("window") or 0) < 0):
                raise MXNetError(
                    f"layer {i}: rope_theta {m.get('rope_theta')!r} and "
                    f"window {m.get('window')!r} must be positive where "
                    f"given")
            if f["kind"] == "moe" and (
                    f.get("act", "silu") not in EXPERT_ACTS
                    or f.get("router_input", "ffn") not in ROUTER_INPUTS):
                raise MXNetError(
                    f"layer {i}: moe act {f.get('act')!r} must be one of "
                    f"{tuple(EXPERT_ACTS)} and router_input "
                    f"{f.get('router_input')!r} one of {ROUTER_INPUTS}")
            if m["kind"] == "retention" and (
                    int(m.get("degree", 2)) != 2
                    or int(m["head_dim"]) % 2
                    or not float(m.get("rope_theta") or 0) > 0):
                raise MXNetError(
                    f"layer {i}: power retention of degree "
                    f"{m.get('degree')!r} over heads of {m['head_dim']} "
                    f"lanes, rope_theta {m.get('rope_theta')!r}: degree "
                    f"2 over an even head_dim is built (the state of "
                    f"degree p grows as head_dim^p / p!), and it rotates: "
                    f"rope_theta is required, positive")
            if m["kind"] == "cca":
                D = int(m["head_dim"])
                span = int(m.get("rotary_dim", D))
                if [int(t) for t in m.get("conv", ())] != [2, 2] \
                        or not float(m.get("rope_theta") or 0) > 0 \
                        or span % 2 or not 0 < span <= D \
                        or int(m["kv_heads"]) * D % 2:
                    raise MXNetError(
                        f"layer {i}: a cca mixer of conv {m.get('conv')!r}, "
                        f"rope_theta {m.get('rope_theta')!r}, rotary_dim "
                        f"{m.get('rotary_dim')!r} over heads of {D}: two "
                        f"convolutions of two taps each are built (conv "
                        f"[2, 2]: the tail is ONE token), it rotates "
                        f"(rope_theta required, positive) an even "
                        f"rotary_dim of at most head_dim, and half of its "
                        f"value lanes come from the previous token")
            if m["kind"] in ("attention", "retention", "cca") and \
                    int(m["heads"]) % int(m.get("kv_heads", m["heads"])):
                raise MXNetError(
                    f"layer {i}: {m['kv_heads']} KV heads do not divide "
                    f"{m['heads']} query heads")
            if m["kind"] == "mla":
                rs = m.get("rope_scaling")
                if rs is not None and (
                        not isinstance(rs, dict)
                        or rs.get("type", "yarn") != "yarn"
                        or (float(rs.get("factor", 1.0)) > 1.0 and not
                            rs.get("original_max_position_embeddings"))):
                    raise MXNetError(
                        f"layer {i}: rope_scaling {rs!r} is no dict of "
                        f"type 'yarn' (the one rescaling built) with its "
                        f"original_max_position_embeddings")
                if int(m["rope_dim"]) % 2:
                    raise MXNetError(
                        f"layer {i}: rope_dim {m['rope_dim']} is rotated "
                        f"in pairs")
                m.setdefault("scale", mla_scale(
                    int(m["nope_dim"]), int(m["rope_dim"]), rs))
            if m["kind"] == "mamba2" and int(m.get("groups", 1)) != 1:
                raise MXNetError(
                    f"layer {i}: a mamba2 mixer of {m['groups']} groups "
                    f"of B and C; one group, shared by all heads, is "
                    f"built")
            if f["kind"] == "moe" and f.get("router") is not None:
                r = f["router"]
                if not isinstance(r, dict) or r.get("kind") != "mlp" \
                        or set(r) - set(ROUTER_KEYS) \
                        or int(r.get("hidden", 0)) < 1 \
                        or int(f["top_k"]) != 1 \
                        or f.get("router_input", "ffn") != "ffn":
                    raise MXNetError(
                        f"layer {i}: moe router {r!r} with top_k "
                        f"{f['top_k']}: a router is one matrix (no key) "
                        f"or a dict of {ROUTER_KEYS} with kind 'mlp' and "
                        f"a positive hidden width, reading the FFN's "
                        f"rows; under it top_k 1 is built (what weighs "
                        f"a second expert of an MLP router is not "
                        f"published)")
            if f["kind"] == "moe":
                held = int(f.get("experts_held", f["experts"]))
                first = int(f.get("first_expert", 0))
                if held < 1 or first < 0 or first + held > int(f["experts"]):
                    raise MXNetError(
                        f"layer {i}: experts {first}..{first + held - 1} "
                        f"are not among the {f['experts']} routed")
        pages = {(int(ly["mixer"].get("kv_heads", ly["mixer"]["heads"])),
                  int(ly["mixer"]["head_dim"]))
                 for ly in self.layers
                 if ly["mixer"]["kind"] in ("attention", "cca")}
        if len(pages) > 1:
            raise MXNetError(
                f"attention layers of different K/V widths {sorted(pages)} "
                f"would need a page pool each; one width is built")
        # the K/V page geometry (what the engine sizes its pools by)
        self.kv_heads, self.head_dim = pages.pop() if pages else (0, 0)
        latents = {(int(ly["mixer"]["kv_rank"]), int(ly["mixer"]["rope_dim"]))
                   for ly in self.layers if ly["mixer"]["kind"] == "mla"}
        if len(latents) > 1 or (latents and self.kv_heads):
            raise MXNetError(
                f"mla layers of latent rows {sorted(latents)} beside "
                f"attention layers of K/V width "
                f"{(self.kv_heads, self.head_dim)}: one page geometry a "
                f"spec is built (a second would need a page pool and its "
                f"frames each)")
        # a spec of mla layers caches ONE row a token and layer, shared
        # by all heads: (the values it needs, the lanes its pool spends)
        self.latent_row = None
        if latents:
            from ..kv_cache import latent_pool_shape

            rank, rope = latents.pop()
            lanes = latent_pool_shape(1, 1, rank, rope)[2]
            self.latent_row = (rank + rope, lanes)
            self.kv_heads, self.head_dim = 1, lanes
        # a spec of cca layers keeps a TAIL a stream beside its pages:
        # (the float32 numbers it needs, the numbers its slots spend —
        # whole tiles), summed over the layers
        tails = [_cca_tail(ly["mixer"]) for ly in self.layers
                 if ly["mixer"]["kind"] == "cca"]
        self.tail_row = (sum(n for n, _ in tails),
                         sum(r * w for _, (r, w) in tails)) if tails \
            else None
        windows = {int(ly["mixer"]["window"]) for ly in self.layers
                   if ly["mixer"].get("window")}
        if len(windows) > 1:
            raise MXNetError(
                f"attention layers of different windows {sorted(windows)} "
                f"would need a windowed pool (and its allocator) each; "
                f"one window is built")
        # the keys a windowed layer's pools keep a stream (0: no such
        # layer): what the engine sizes the second page pool by
        self.window = windows.pop() if windows else 0
        rotary = any(ly["mixer"].get("rope_theta")
                     or ly["mixer"]["kind"] == "mla" for ly in self.layers)
        self.feeds = ("data", "lengths", "block_table", "slots") \
            + (("positions",) if rotary else ()) \
            + (("window_table",) if self.window else ())

    # -- what the engine asks (the protocol: DecodeEngine's docstring) ---
    phases = ("prefill", "decode")    # no suffix-prefill, no verify symbol
    positions = None                  # no learned positions: max_len given
    partition_rules = None            # no tp/pp placement
    lora_width = 0                    # no LoRA epilogue

    @property
    def kv_dtypes(self):
        """No quantized pages; and a retention layer's degree-2 state
        has no bfloat16 form (its running sums are float32 or wrong)."""
        return ("fp32",) if "retention" in self.mixer_kinds() \
            else ("fp32", "bf16")

    @property
    def num_layers(self):
        return len(self.layers)

    def mixer_kinds(self):
        return tuple(ly["mixer"]["kind"] for ly in self.layers)

    @property
    def name(self):
        return (f"a model spec with "
                f"{'/'.join(sorted(set(self.mixer_kinds())))} layers"
                + (" and windowed pools" if self.window else ""))

    def cache_kinds(self):
        """Per layer, the kind of its per-stream state: ``pages`` (K/V,
        through the block table), ``window_pages`` (K/V of a windowed
        layer, through the window table), ``slots`` (one row a
        stream) or ``pages+slots`` (a cca layer: K/V pages AND a slot's
        tail)."""
        return tuple("pages" if ly["mixer"]["kind"] == "mla" else
                     "pages+slots" if ly["mixer"]["kind"] == "cca" else
                     "slots" if ly["mixer"]["kind"] != "attention" else
                     "window_pages" if ly["mixer"].get("window") else "pages"
                     for ly in self.layers)

    def prompt_attention(self):
        """``(window, latent)`` a layer whose prefill attends the whole
        prompt by a kernel that takes its length (``window`` 0: global;
        ``latent``: an mla layer, whose ``mla_flash`` walks the same
        band schedule without a window in tiles of its own choice,
        ``pallas_kernels._mla_tiles``): what
        ``pallas_kernels.prompt_tile_visits`` / ``prompt_tile_work``
        count a prefill's tiles and scores by."""
        return tuple((int(ly["mixer"].get("window") or 0),
                      ly["mixer"]["kind"] == "mla")
                     for ly in self.layers
                     if ly["mixer"]["kind"] in ("attention", "mla", "cca"))

    def has_moe(self):
        return any(ly["ffn"]["kind"] == "moe" for ly in self.layers)

    def pool_kinds(self, kv_dtype="fp32"):
        """The kind of each of :meth:`pools`' rows: a recurrent layer's
        state is what ``return_state`` reads — a retention layer's
        normaliser with it, which is the other half of its sums; a
        convolution's tail rides in the same slot, read by the programs
        alone — a cca layer's too, beside its two page pools."""
        out = []
        for k, ly in zip(self.cache_kinds(), self.layers):
            aux = "slots" if ly["mixer"]["kind"] == "retention" \
                else "slots_aux"
            out += ["slots", aux] if k == "slots" else \
                ["pages", "pages", aux] if k == "pages+slots" else \
                [k] if ly["mixer"]["kind"] == "mla" else [k, k]
        return tuple(out) + (("counters",) if self.has_moe() else ())

    def pools(self, cache_blocks, kv_block, slots, dtype, kv_dtype="fp32",
              window_blocks=0):
        """The per-stream state arrays a program carries, in the order
        its symbols return them: ``(name, shape, dtype, fill)``.  Pages
        take ``dtype`` (no quantized pools here: the engine refuses
        them for this family); a windowed layer's pools hold
        ``window_blocks`` pages, a page-id space of their own; slot
        state is float32 whatever the model's."""
        from ..kv_cache import (latent_pool_shape, state_pool_shape,
                                value_pool_shape)

        out = []
        for i, ly in enumerate(self.layers):
            m = ly["mixer"]
            if m["kind"] == "mla":
                out.append((f"layer{i}_latent_pool", latent_pool_shape(
                    cache_blocks, kv_block, m["kv_rank"], m["rope_dim"]),
                    dtype, 0))
            elif m["kind"] in ("attention", "cca"):
                shape = value_pool_shape(
                    window_blocks if m.get("window") else cache_blocks,
                    kv_block, self.kv_heads, self.head_dim)
                out += [(f"layer{i}_kpool", shape, dtype, 0),
                        (f"layer{i}_vpool", shape, dtype, 0)]
                if m["kind"] == "cca":
                    out.append((f"layer{i}_tail", (int(slots),)
                                + mixer_state(m)[1], "float32", 0))
            else:
                head_state, aux = mixer_state(m)
                out += [(f"layer{i}_state",
                         state_pool_shape(slots, head_state), "float32", 0),
                        (f"layer{i}_{_AUX_POOL[m['kind']]}",
                         (int(slots),) + aux, "float32", 0)]
        if self.has_moe():
            out.append((COUNTERS, (4,), "int32", 0))
        return out

    def symbol(self, which, kv_block=16, **unused):
        if which not in self.phases:
            raise MXNetError(f"{self.name} builds no {which!r} symbol (it "
                             f"builds {self.phases})")
        return _trunk(self, step=(which == "decode"))

    _SCALARS = ("norm_eps", "embed_scale", "residual_scale",
                "logits_scale", "tied_head", "post_norm",
                "learned_residual")

    def to_dict(self):
        return {"vocab_size": self.vocab_size, "d_model": self.d_model,
                "layers": self.layers,
                **{k: getattr(self, k) for k in self._SCALARS}}

    @classmethod
    def from_dict(cls, d):
        return cls(d["vocab_size"], d["d_model"], d["layers"],
                   **{k: d[k] for k in cls._SCALARS if k in d})


def _grouped_qkv(spec, h, name, m):
    """(q, k, v, heads, KV heads) of a mixer with grouped queries: the
    three projections and, under ``qk_norm``, the per-head norms."""
    H, Hkv, D = int(m["heads"]), int(m.get("kv_heads", m["heads"])), \
        int(m["head_dim"])
    q = _fc(h, H * D, f"{name}_q")
    k = _fc(h, Hkv * D, f"{name}_k")
    v = _fc(h, Hkv * D, f"{name}_v")
    if m.get("qk_norm"):
        # each head's lanes, one gain for all heads; the op rotates
        # what it is given, so the norm comes first
        q = _norm(q, f"{name}_q_norm", spec.norm_eps, num_groups=H)
        k = _norm(k, f"{name}_k_norm", spec.norm_eps, num_groups=Hkv)
    return q, k, v, H, Hkv


def _attention(spec, h, i, m, step, feeds):
    name = f"layer{i}"
    q, k, v, H, Hkv = _grouped_qkv(spec, h, name, m)
    D = int(m["head_dim"])
    op = sym.GQAPagedDecode if step else sym.GQAPrefillAttention
    attrs = {"scale": float(m["scale"])} if m.get("scale") else {}
    args = [q, k, v, sym.Variable(f"{name}_kpool"),
            sym.Variable(f"{name}_vpool"),
            feeds["window_table" if m.get("window") else "block_table"],
            feeds["lengths"]]
    if m.get("window"):
        attrs["window"] = int(m["window"])
    if m.get("rope_theta"):
        attrs["rope_theta"] = float(m["rope_theta"])
        args.append(feeds["positions"])
    att = op(*args, num_heads=H, kv_heads=Hkv, name=f"{name}_attn", **attrs)
    out = att[0]
    if m.get("gate"):
        out = out * sym.Activation(_fc(h, H * D, f"{name}_gate"),
                                   act_type="sigmoid")
    return _fc(out, spec.d_model, f"{name}_o"), [att[1], att[2]]


def _mla(spec, h, i, m, step, feeds):
    H, R = int(m["heads"]), int(m["kv_rank"])
    n, r, dv = int(m["nope_dim"]), int(m["rope_dim"]), int(m["v_dim"])
    name = f"layer{i}"
    rs = m.get("rope_scaling") or {}
    attrs = dict(num_heads=H, nope_dim=n, rope_dim=r, v_dim=dv, kv_rank=R)
    rot = dict(scale=float(m["scale"]),
               rope_theta=float(m.get("rope_theta", 10000.0)))
    if float(rs.get("factor", 1.0)) > 1.0:
        rot.update(
            rope_factor=float(rs["factor"]),
            rope_orig_len=float(rs["original_max_position_embeddings"]),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)))
    # rows: [every head's q_n | every head's q_r]
    q = _fc(_norm(_fc(h, int(m["q_rank"]), f"{name}_q_down"),
                  f"{name}_q_norm", spec.norm_eps),
            H * (n + r), f"{name}_q_up")
    kv = _fc(h, R + r, f"{name}_kv_down")
    c = _norm(sym.slice_axis(kv, axis=-1, begin=0, end=R),
              f"{name}_kv_norm", spec.norm_eps)
    k_r = sym.slice_axis(kv, axis=-1, begin=R, end=R + r)
    tail = [sym.Variable(f"{name}_latent_pool"), feeds["block_table"],
            feeds["lengths"], feeds["positions"]]
    # rows: [every head's W_uk | every head's W_uv]; a prefill applies
    # it to the latents, a decode step to the query and the output
    w_up = sym.Variable(f"{name}_kv_up_weight")
    if step:
        qa = sym.MLAAbsorb(
            sym.slice_axis(q, axis=-1, begin=0, end=H * n), w_up,
            name=f"{name}_absorb_k", **attrs)
        att = sym.MLAPagedDecode(
            qa, sym.slice_axis(q, axis=-1, begin=H * n, end=H * (n + r)),
            c, k_r, *tail, name=f"{name}_attn", **attrs, **rot)
        out = sym.MLAAbsorb(att[0], w_up, value=True,
                            name=f"{name}_absorb_v", **attrs)
    else:
        up = sym.FullyConnected(c, num_hidden=H * (n + dv), flatten=False,
                                no_bias=True, name=f"{name}_kv_up",
                                weight=w_up)
        att = sym.MLAPrefillAttention(q, up, c, k_r, *tail,
                                      name=f"{name}_attn", **attrs, **rot)
        out = att[0]
    return _fc(out, spec.d_model, f"{name}_o"), [att[1]]


def _kda(spec, h, i, m, step, feeds):
    H, D = int(m["heads"]), int(m["head_dim"])
    name = f"layer{i}"
    conv = sym.ShortConv(
        _fc(h, 3 * H * D, f"{name}_qkv"),
        sym.Variable(f"{name}_conv_weight"), sym.Variable(f"{name}_tail"),
        feeds["slots"], feeds["lengths"], step=step, name=f"{name}_conv")
    decay = _fc(_fc(h, D, f"{name}_a_down"), H * D, f"{name}_a_up")
    op = sym.KDAStep if step else sym.KDAChunk
    rec = op(conv[0], decay, _fc(h, H, f"{name}_beta"),
             sym.Variable(f"{name}_a_log"), sym.Variable(f"{name}_dt_bias"),
             sym.Variable(f"{name}_state"), feeds["slots"],
             feeds["lengths"], num_heads=H,
             neg_eigval=bool(m.get("neg_eigval", False)),
             name=f"{name}_kda")
    gate = _fc(_fc(h, D, f"{name}_g_down"), H * D, f"{name}_g_up")
    out = sym.GatedRMSNorm(rec[0], gate,
                           sym.Variable(f"{name}_onorm_gamma"),
                           eps=spec.norm_eps, num_groups=H,
                           name=f"{name}_onorm")
    return _fc(out, spec.d_model, f"{name}_o"), [rec[1], conv[1]]


def _mamba2(spec, h, i, m, step, feeds):
    (H, P, N), channels = mixer_state(m)[0], _mamba2_channels(m)
    name = f"layer{i}"
    # one fused input map: gate | x, B, C | dt
    widths = (H * P, channels, H)
    proj = _fc(h, sum(widths), f"{name}_in")
    at = (0, widths[0], widths[0] + channels, sum(widths))
    z, xbc, dt = (sym.slice_axis(proj, axis=-1, begin=at[j], end=at[j + 1])
                  for j in range(3))
    conv_args = [xbc, sym.Variable(f"{name}_conv_weight"),
                 sym.Variable(f"{name}_tail"), feeds["slots"],
                 feeds["lengths"]]
    bias = {}
    if m.get("conv_bias"):
        conv_args.append(sym.Variable(f"{name}_conv_bias"))
        bias = {"bias": True}
    conv = sym.ShortConv(*conv_args, step=step, name=f"{name}_conv", **bias)
    op = sym.Mamba2Step if step else sym.Mamba2Chunk
    rec = op(conv[0], dt, sym.Variable(f"{name}_a_log"),
             sym.Variable(f"{name}_dt_bias"), sym.Variable(f"{name}_d_skip"),
             sym.Variable(f"{name}_state"), feeds["slots"],
             feeds["lengths"], num_heads=H, d_state=N,
             name=f"{name}_mamba2")
    out = sym.GatedRMSNorm(rec[0], z, sym.Variable(f"{name}_onorm_gamma"),
                           eps=spec.norm_eps, gate="silu_first",
                           name=f"{name}_onorm")
    return _fc(out, spec.d_model, f"{name}_out"), [rec[1], conv[1]]


def _retention(spec, h, i, m, step, feeds):
    name = f"layer{i}"
    q, k, v, H, Hkv = _grouped_qkv(spec, h, name, m)
    # the log-gate's raw projection, one a token and KV head; its bias
    # is added in the op, in float32
    op = sym.RetentionStep if step else sym.RetentionChunk
    rec = op(q, k, v, _fc(h, Hkv, f"{name}_g"),
             sym.Variable(f"{name}_g_bias"), sym.Variable(f"{name}_state"),
             sym.Variable(f"{name}_zsum"), feeds["slots"], feeds["lengths"],
             feeds["positions"], num_heads=H, kv_heads=Hkv,
             rope_theta=float(m["rope_theta"]), name=f"{name}_retention")
    return _fc(rec[0], spec.d_model, f"{name}_o"), [rec[1], rec[2]]


def _cca(spec, h, i, m, step, feeds):
    H, Hkv, D = int(m["heads"]), int(m["kv_heads"]), int(m["head_dim"])
    name = f"layer{i}"
    half = _cca_widths(m)[1]
    # the latent: q | k straight from the block's input, mixed along
    # the sequence; the value's second half is the PREVIOUS token's
    mix = sym.CCAMix(
        _fc(h, H * D, f"{name}_q"), _fc(h, Hkv * D, f"{name}_k"),
        _fc(h, half, f"{name}_v2"),
        sym.Variable(f"{name}_mix_conv0_weight"),
        sym.Variable(f"{name}_mix_conv1_weight"),
        sym.Variable(f"{name}_tail"), feeds["slots"], feeds["lengths"],
        num_heads=H, kv_heads=Hkv, step=step, name=f"{name}_mix")
    qk = sym.QKL2Norm(mix[0], mix[1],
                      sym.Variable(f"{name}_qk_norm_temperature"),
                      num_heads=H, kv_heads=Hkv, name=f"{name}_qk_norm")
    v = sym.Concat(_fc(h, Hkv * D - half, f"{name}_v1"), mix[2], dim=2,
                   num_args=2, name=f"{name}_v")
    op = sym.GQAPagedDecode if step else sym.GQAPrefillAttention
    attrs = {"rotary_dim": int(m["rotary_dim"])} \
        if int(m.get("rotary_dim", D)) != D else {}
    att = op(qk[0], qk[1], v, sym.Variable(f"{name}_kpool"),
             sym.Variable(f"{name}_vpool"), feeds["block_table"],
             feeds["lengths"], feeds["positions"], num_heads=H,
             kv_heads=Hkv, rope_theta=float(m["rope_theta"]),
             name=f"{name}_attn", **attrs)
    return _fc(att[0], spec.d_model, f"{name}_o"), [att[1], att[2], mix[3]]


_MIXER_BUILDERS = {"attention": _attention, "mla": _mla, "kda": _kda,
                   "mamba2": _mamba2, "retention": _retention, "cca": _cca}


def _router_mlp(h, name, r, carried, eps):
    """(the experts' logits of an ``mlp`` router, float32; its hidden
    row, what the next layer's router is handed).  ``carried``: the row
    the layer before left, or None (the first: nothing is carried).  The
    maps' widths are their weights' (``hidden``, the experts)."""
    def lin(x, node, **attrs):
        return sym.RouterLinear(x, sym.Variable(f"{name}_{node}_weight"),
                                name=f"{name}_{node}", **attrs)

    row = lin(h, "router_down")
    if r.get("carry") and carried is not None:
        row = sym.RouterCarry(row, carried,
                              sym.Variable(f"{name}_router_carry_gamma"),
                              name=f"{name}_router_carry")
    x = _norm(row, f"{name}_router_norm", eps)
    x = lin(lin(x, "router_1", act="gelu"), "router_2", act="gelu")
    return lin(x, "router_3"), (row if r.get("carry") else None)


def _ffn(spec, h, x_in, i, f, step, feeds, counters, carried=None):
    """``h``: the rows the FFN takes; ``x_in``: the block's input, which
    a router with ``router_input: block`` scores in their place;
    ``carried``: the hidden row the last ``mlp`` router with ``carry``
    left.  -> (output, counters, the row this layer's router leaves or
    ``carried`` as it came)."""
    name = f"layer{i}"
    if f["kind"] == "dense":
        return _gated_ffn(h, int(f["width"]), spec.d_model,
                          f"{name}_ffn"), counters, carried
    attrs = {k: f[k] for k in ("score", "act") if f.get(k)}
    router = sym.Variable(f"{name}_router_weight")
    if f.get("router"):
        router, carried = _router_mlp(h, name, f["router"], carried,
                                      spec.norm_eps)
        attrs["router_logits"] = True
    args = [h, router,
            sym.Variable(f"{name}_experts_gate_weight"),
            sym.Variable(f"{name}_experts_up_weight"),
            sym.Variable(f"{name}_experts_down_weight"), feeds["lengths"],
            counters]
    if f.get("router_input", "ffn") == "block":
        attrs["router_data"] = True
        args.append(x_in)
    if f.get("select_bias"):
        attrs["select_bias"] = True
        args.append(sym.Variable(f"{name}_router_bias"))
    for k, cast in (("groups", int), ("top_groups", int),
                    ("routed_scale", float)):
        if f.get(k):
            attrs[k] = cast(f[k])
    routed = sym.MoEFFN(
        *args, top_k=int(f["top_k"]),
        first_expert=int(f.get("first_expert", 0)), step=step,
        count=step, name=f"{name}_moe", **attrs)
    out = routed[0]
    if int(f.get("shared", 0)):
        width = int(f.get("shared_width",
                          int(f["width"]) * int(f["shared"])))
        out = out + _gated_ffn(h, width, spec.d_model, f"{name}_shared")
    # decode steps count; a prefill hands the counters on as they are
    return out, (routed[1] if step else counters), carried


def _trunk(spec, step):
    """Token ids (B, S) -> ``[logits] + spec.pools()``'s arrays,
    updated.  ``step``: one token a stream against its state (decode),
    logits (B, 1, vocab); else a whole (padded) prompt from nothing
    (prefill), logits (B, 1, vocab) of row ``lengths[b] - 1`` alone:
    the head over every position of a long prompt is more work and
    memory than the layers, and one row of it is read."""
    feeds = {k: sym.Variable(k) for k in spec.feeds}
    def scaled(t, by):       # a multiplier of 1 adds no node
        return t if by == 1.0 else t * by

    def branch(out, name):   # what a block adds to the residual stream
        if spec.post_norm:
            out = _norm(out, name, spec.norm_eps)
        return scaled(out, spec.residual_scale)

    def add(x, out, name):   # the stream after a branch
        if not spec.learned_residual:
            return x + out
        return sym.ScaledResidual(x, out, sym.Variable(f"{name}_scales"),
                                  name=name)

    table = sym.Variable("tok_embed_weight")
    x = scaled(sym.Embedding(feeds["data"], input_dim=spec.vocab_size,
                             output_dim=spec.d_model, name="tok_embed",
                             weight=table), spec.embed_scale)
    counters = sym.Variable(COUNTERS) if spec.has_moe() else None
    state = []
    carried = None           # an mlp router's hidden row, layer to layer
    for i, ly in enumerate(spec.layers):
        x_in = x
        h = _norm(x, f"layer{i}_norm1", spec.norm_eps)
        out, st = _MIXER_BUILDERS[ly["mixer"]["kind"]](
            spec, h, i, ly["mixer"], step, feeds)
        state += st
        x = add(x, branch(out, f"layer{i}_post_norm1"), f"layer{i}_res1")
        h = _norm(x, f"layer{i}_norm2", spec.norm_eps)
        out, counters, carried = _ffn(spec, h, x_in, i, ly["ffn"], step,
                                      feeds, counters, carried)
        x = add(x, branch(out, f"layer{i}_post_norm2"), f"layer{i}_res2")
    if not step:
        x = sym.expand_dims(sym.SequenceLast(
            sym.SwapAxis(x, dim1=0, dim2=1), feeds["lengths"],
            use_sequence_length=True, name="last_row"), axis=1)
    x = scaled(_norm(x, "final_norm", spec.norm_eps), spec.logits_scale)
    if spec.tied_head:
        logits = sym.FullyConnected(x, num_hidden=spec.vocab_size,
                                    flatten=False, no_bias=True,
                                    name="head", weight=table)
    else:
        logits = _fc(x, spec.vocab_size, "head")
    if counters is not None:
        state.append(counters)
    return sym.Group([logits] + state)
