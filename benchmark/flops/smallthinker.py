"""Operations and bytes the ``smallthinker`` family's kernels need, from
their shapes.  Counted: what the algorithm requires of the kernel as it
is called — operands read once, results written once, in the model's
type (the down product leaves in float32); the window's masks, the
softmax and ReLU are left out (they err the count low, which a share of
a roofline may do and a share over 100% may not).

``layer_counts`` and ``moe_gmm`` are what ``reducers/
spec_kernel_roofline.py`` asks of a family (its ``paged_attention`` is
the GLOBAL layers' kernel, sized by the whole context); ``need`` is what
``reducers/family_kernel_roofline.py`` asks, for the two windowed
kernels this family brought, sized by what the window lets them see.
"""

from benchmark.flops import paged_attention

F32 = 4


def layer_counts(cfg):
    """(global attention layers, windowed attention layers, expert
    layers) of the cut."""
    L = int(cfg["num_hidden_layers"])
    windowed = sum(1 for v in cfg["sliding_window_layout"][:L] if v)
    return L - windowed, windowed, L


def moe_gmm(experts_hit, pairs, cfg, itemsize=2):
    """(operations, bytes) of the grouped matmuls — gate, up and down —
    over ``pairs`` token-expert rows that touch ``experts_hit`` experts:
    the three matrices of every expert hit, each row's input and hidden
    activation in and out (the down product leaves in float32)."""
    d, w = int(cfg["hidden_size"]), int(cfg["moe_ffn_hidden_size"])
    ops = 2.0 * 3 * d * w * pairs
    nbytes = experts_hit * 3.0 * d * w * itemsize \
        + pairs * (d * itemsize + 2 * w * itemsize + d * F32)
    return ops, nbytes


def flash_window(tokens, pairs, cfg, itemsize=2):
    """(operations, bytes) of one layer's windowed prefill kernel over a
    prompt of ``tokens`` positions whose band holds ``pairs`` query-key
    pairs (row i sees min(i + 1, window) keys): q.k and p.v a pair, a
    multiply and an add a lane of the head, in every query head; q in
    and o out (query heads), k and v in (KV heads)."""
    H, Hkv, D = (int(cfg[k]) for k in ("num_attention_heads",
                                       "num_key_value_heads", "head_dim"))
    return 4.0 * pairs * D * H, \
        tokens * 2.0 * (H + Hkv) * D * itemsize


def need(kernel, stats, cfg, itemsize):
    """(operations, bytes) per execution of the program ``kernel`` runs
    in, from the engine's counters over the window, or ``None``:
    ``paged_window`` — the context a decode step's windows hold
    (``window_context_tokens / steps``: a row's context or the window,
    whichever is less) at the K/V width; ``flash_fwd_window`` — a
    prefill's band (``window_prefill_pairs / prefills``) over its
    positions (``prefill_tokens / prefills``); each in every windowed
    layer."""
    _, windowed, _ = layer_counts(cfg)
    if kernel == "paged_window":
        steps = stats.get("steps")
        if not steps or not stats.get("window_context_tokens"):
            return None
        return paged_attention.decode_step(
            context_tokens=stats["window_context_tokens"] / steps,
            rows=stats["stream_steps"] / steps, layers=windowed,
            width=int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
            itemsize=itemsize)
    if kernel == "flash_fwd_window":
        n = stats.get("prefills")
        if not n or not stats.get("window_prefill_pairs"):
            return None
        ops, nbytes = flash_window(stats["prefill_tokens"] / n,
                                   stats["window_prefill_pairs"] / n, cfg,
                                   itemsize)
        return windowed * ops, windowed * nbytes
    raise ValueError(f"no count for kernel {kernel!r}")
