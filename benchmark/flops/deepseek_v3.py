"""Operations and bytes the ``deepseek_v3`` family's kernels need, from
their shapes.  Counted: what the algorithm requires of the kernel as it
is called — operands read once, results written once, in the model's
type; a cached row at the 576 values it NEEDS, not the 640 lanes the
pool spends on it; padding, masks, the softmax and the absorption's
matmuls (they are XLA's, outside the kernel) are left out.  The count
may err low, which a share of a roofline may do and a share over 100%
may not.

``layer_counts`` and ``moe_gmm`` are what ``reducers/
spec_kernel_roofline.py`` asks of a family; ``need`` is what
``reducers/family_kernel_roofline.py`` asks, for the two kernels this
family brought.
"""

F32 = 4


def layer_counts(cfg):
    """(attention layers, recurrent layers, expert layers) of the cut:
    every layer attends; the leading dense ones hold no experts."""
    L = int(cfg["num_hidden_layers"])
    dense = min(L, int(cfg.get("dense_layers_held",
                               cfg["first_k_dense_replace"])))
    return L, 0, L - dense


def moe_gmm(experts_hit, pairs, cfg, itemsize=2):
    """(operations, bytes) of the grouped matmuls — gate, up and down —
    over ``pairs`` token-expert rows that touch ``experts_hit`` experts:
    the three matrices of every expert hit, each row's input and hidden
    activation in and out (the down product leaves in float32)."""
    d, w = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    ops = 2.0 * 3 * d * w * pairs
    nbytes = experts_hit * 3.0 * d * w * itemsize \
        + pairs * (d * itemsize + 2 * w * itemsize + d * F32)
    return ops, nbytes


def _widths(cfg):
    return (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
            int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]))


def mla_paged_decode(context_tokens, rows, cfg, itemsize=2):
    """(operations, bytes) of one layer's decode kernel over
    ``context_tokens`` cached rows in all, ``rows`` streams: a head's
    score over the row's rank + rope values and its value over the
    row's rank, a multiply and an add each; the rows read once a
    stream (every head shares them), the queries in and the attended
    latents out."""
    H, R, _, r, _ = _widths(cfg)
    return 2.0 * H * ((R + r) + R) * context_tokens, \
        (context_tokens * (R + r) + rows * H * ((R + r) + R)) * itemsize


def mla_flash_fwd(tokens, pairs, cfg, itemsize=2):
    """(operations, bytes) of one layer's prefill kernel over a prompt
    of ``tokens`` positions that holds ``pairs`` causal query-key pairs:
    q.k over nope + rope lanes and p.v over v lanes a pair and head; q
    (both spans), k_n, the ONE rotary key, v in and o out."""
    H, _, n, r, dv = _widths(cfg)
    return 2.0 * H * ((n + r) + dv) * pairs, \
        tokens * (H * (n + r) + H * n + r + H * dv + H * dv) * itemsize


def need(kernel, stats, cfg, itemsize):
    """(operations, bytes) per execution of the program ``kernel`` runs
    in, from the engine's counters over the window, or ``None``:
    ``mla_paged_decode`` — the context a decode step attends over
    (``context_tokens / steps``) and its rows (``stream_steps /
    steps``); ``mla_flash_fwd`` — a prefill's causal pairs
    (``prefill_pairs / prefills``) over its positions (``prefill_tokens
    / prefills``); each in every layer."""
    layers, _, _ = layer_counts(cfg)
    if kernel == "mla_paged_decode":
        steps = stats.get("steps")
        if not steps or not stats.get("context_tokens"):
            return None
        ops, nbytes = mla_paged_decode(stats["context_tokens"] / steps,
                                       stats["stream_steps"] / steps, cfg,
                                       itemsize)
    elif kernel == "mla_flash_fwd":
        n = stats.get("prefills")
        if not n or not stats.get("prefill_pairs"):
            return None
        ops, nbytes = mla_flash_fwd(stats["prefill_tokens"] / n,
                                    stats["prefill_pairs"] / n, cfg,
                                    itemsize)
    else:
        raise ValueError(f"no count for kernel {kernel!r}")
    return layers * ops, layers * nbytes
