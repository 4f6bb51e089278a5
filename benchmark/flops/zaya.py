"""Operations and bytes the ``zaya`` family's kernels need, from their
shapes.  Counted: what the algorithm requires of the kernel as it is
called — operands read once, results written once, in the model's type
(the down product leaves in float32); REAL rows, not the bucket's:
padding, masks, the softmax, and the latent's mixing, norm and rotation
(XLA's, outside the kernels) are left out.  The count may err low, which
a share of a roofline may do and a share over 100% may not.

The attention runs INSIDE the latent: the kernels see 8 query heads over
2 KV heads of 128, so a token leaves 2 x 256 values a layer in the pages
— 1,024 B in bfloat16 — and that is what ``paged_attention`` reads of
it.  ``layer_counts`` and ``moe_gmm`` are what ``reducers/
spec_kernel_roofline.py`` asks of a family (ONE expert a token, all 16
held: a decode step of 128 rows hits every expert with 8 rows on
average); ``need`` is what ``reducers/family_kernel_roofline.py`` asks:
the prompt kernel, ``flash_fwd_mha``, over all its causal pairs.
"""

# the grouped matmuls' count is the one every family of gated experts
# of ``moe_intermediate_size`` shares
from benchmark.flops.deepseek_v3 import moe_gmm  # noqa: F401
# a prompt's attention at the heads the kernel sees
from benchmark.flops.afmoe import flash


def layer_counts(cfg):
    """(attention layers, kda layers, expert layers) of the cut: every
    layer holds both halves."""
    L = int(cfg["num_hidden_layers"])
    return L, 0, L


def need(kernel, stats, cfg, itemsize):
    """(operations, bytes) per execution of the program ``kernel`` runs
    in, from the engine's counters over the window, or ``None``:
    ``flash_fwd_mha`` — a prefill's causal pairs (``prefill_pairs /
    prefills``) over its positions (``prefill_tokens / prefills``) at 8
    query heads and 2 KV heads, in every layer."""
    if kernel != "flash_fwd_mha":
        raise ValueError(f"no count for kernel {kernel!r}")
    n = stats.get("prefills")
    if not n or not stats.get("prefill_pairs"):
        return None
    ops, nbytes = flash(stats["prefill_tokens"] / n,
                        stats["prefill_pairs"] / n, cfg, itemsize)
    layers = layer_counts(cfg)[0]
    return layers * ops, layers * nbytes
