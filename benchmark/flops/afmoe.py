"""Operations and bytes the ``afmoe`` family's kernels need, from their
shapes.  Counted: what the algorithm requires of the kernel as it is
called — operands read once, results written once, in the model's type
(the down product leaves in float32); REAL rows, not the bucket's:
padding, masks, the softmax, the q / k norms and the rotation (XLA's,
outside the kernels) are left out.  The count may err low, which a
share of a roofline may do and a share over 100% may not.

``layer_counts`` and ``moe_gmm`` are what ``reducers/
spec_kernel_roofline.py`` asks of a family (its ``paged_attention`` is
the GLOBAL layers' kernel, sized by the whole context); ``need`` is what
``reducers/family_kernel_roofline.py`` asks: the two windowed kernels,
sized by what the window lets them see, and the global layers' prefill
kernel, each over its own layers.
"""

from benchmark.flops import paged_attention
# the grouped matmuls' count is the one every family of sigmoid-scored
# experts of ``moe_intermediate_size`` shares
from benchmark.flops.deepseek_v3 import moe_gmm  # noqa: F401


def _sliding(cfg):
    """Per held layer: is it a sliding-window layer?"""
    L = int(cfg["num_hidden_layers"])
    held = cfg.get("layers_held", range(L))
    return [cfg["layer_types"][int(i)] == "sliding_attention" for i in held]


def layer_counts(cfg):
    """(global attention layers, windowed attention layers, expert
    layers) of the cut."""
    sliding = _sliding(cfg)
    dense = min(int(cfg["num_dense_layers"]), len(sliding))
    return len(sliding) - sum(sliding), sum(sliding), len(sliding) - dense


def flash(tokens, pairs, cfg, itemsize=2):
    """(operations, bytes) of one layer's prefill attention kernel over
    a prompt of ``tokens`` positions that holds ``pairs`` query-key
    pairs (row i sees i + 1 keys, or min(i + 1, window) in a sliding
    layer): q.k and p.v a pair, a multiply and an add a lane of the
    head, in every query head; q in and o out (query heads), k and v in
    ONCE at the KV heads — a repeat to the query heads would be the
    program's cost, not the kernel's need."""
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
    prefill's band (``window_prefill_pairs / prefills``) and
    ``flash_fwd_mha`` — all its causal pairs (``prefill_pairs /
    prefills``), each over its positions (``prefill_tokens /
    prefills``); each in its own layers."""
    full, windowed, _ = layer_counts(cfg)
    if kernel == "paged_window":
        steps = stats.get("steps")
        if not steps or not stats.get("window_context_tokens"):
            return None
        return paged_attention.decode_step(
            context_tokens=stats["window_context_tokens"] / steps,
            rows=stats["stream_steps"] / steps, layers=windowed,
            width=int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
            itemsize=itemsize)
    pairs_key, layers = {
        "flash_fwd_window": ("window_prefill_pairs", windowed),
        "flash_fwd_mha": ("prefill_pairs", full)}.get(kernel, (None, 0))
    if pairs_key is None:
        raise ValueError(f"no count for kernel {kernel!r}")
    n = stats.get("prefills")
    if not n or not stats.get(pairs_key):
        return None
    ops, nbytes = flash(stats["prefill_tokens"] / n, stats[pairs_key] / n,
                        cfg, itemsize)
    return layers * ops, layers * nbytes
