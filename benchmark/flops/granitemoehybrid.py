"""Operations and bytes the ``granitemoehybrid`` family's kernels need,
from their shapes.  Counted: what the algorithm requires of the kernel
as it is called — operands read once, results written once; float32
where the program holds float32 (the Mamba-2 state, the decode step's
rows, the scan's outputs), the model's type elsewhere.  SiLU, softplus,
norms, exponentials and masks are left out, and the chunked scan is
counted as the token-by-token recurrence it regroups (they err the
count low, which a share of a roofline may do and a share over 100% may
not).  The paged attention kernel is counted by
``flops/paged_attention.decode_step`` at the K/V width and one layer.

``layer_counts`` and ``moe_gmm`` are what ``reducers/
spec_kernel_roofline.py`` asks of a family; ``need`` is what
``reducers/family_kernel_roofline.py`` asks, for the kernels this family
brought.
"""

F32 = 4


def layer_counts(cfg):
    """(attention layers, mamba layers, expert layers) of the cut."""
    L = int(cfg["num_hidden_layers"])
    att = sum(1 for t in cfg["layer_types"][:L] if t == "attention")
    return att, L - att, L


def moe_gmm(experts_hit, pairs, cfg, itemsize=2):
    """(operations, bytes) of the grouped matmuls — gate, up and down —
    over ``pairs`` token-expert rows that touch ``experts_hit`` experts:
    the three matrices of every expert hit, each row's input and hidden
    activation in and out (the down product leaves in float32)."""
    d, w = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    ops = 2.0 * 3 * d * w * pairs
    nbytes = experts_hit * 3.0 * d * w * itemsize \
        + pairs * (d * itemsize + 2 * w * itemsize + d * F32)
    return ops, nbytes


def _mamba2_sizes(cfg):
    H, P, N = (int(cfg[k]) for k in ("mamba_n_heads", "mamba_d_head",
                                     "mamba_d_state"))
    # per head and token: the decay P N, dx B^T P N multiplies and P N
    # adds, S C 2 P N
    return H, P, N, 5.0 * H * P * N


def mamba2_step(rows, cfg):
    """(operations, bytes) of one layer's ``mamba2_step`` over ``rows``
    live streams: the state read and written; dx in and y out (a row of
    P a head), the decay (a number a head), B and C (rows of N)."""
    H, P, N, ops = _mamba2_sizes(cfg)
    nbytes = rows * (2.0 * H * P * N + 2 * H * P + H + 2 * N) * F32
    return rows * ops, nbytes


def mamba2_chunk(tokens, prompts, cfg, itemsize=2):
    """(operations, bytes) of one layer's ``mamba2_chunk`` over
    ``tokens`` prompt positions of ``prompts`` prompts: per position dx,
    B and C in (the model's type), the running log-decay (a float32 a
    head), y out (float32); per prompt the last state out."""
    H, P, N, ops = _mamba2_sizes(cfg)
    nbytes = tokens * ((H * P + 2 * N) * itemsize + H * F32 + H * P * F32) \
        + prompts * H * P * N * F32
    return tokens * ops, nbytes


def need(kernel, stats, cfg, itemsize):
    """(operations, bytes) per execution of the program ``kernel`` runs
    in, from the engine's counters over the window, or ``None``:
    ``mamba2_step`` — the live rows of a decode step (``stream_steps /
    steps``); ``mamba2_chunk`` — the prompt positions of a prefill
    (``prefill_tokens / prefills``); each in every mamba layer."""
    _, mamba, _ = layer_counts(cfg)
    if kernel == "mamba2_step":
        if not stats.get("steps") or not stats.get("stream_steps"):
            return None
        ops, nbytes = mamba2_step(stats["stream_steps"] / stats["steps"],
                                  cfg)
    elif kernel == "mamba2_chunk":
        if not stats.get("prefills") or not stats.get("prefill_tokens"):
            return None
        ops, nbytes = mamba2_chunk(
            stats["prefill_tokens"] / stats["prefills"], 1, cfg, itemsize)
    else:
        raise ValueError(f"no count for kernel {kernel!r}")
    return mamba * ops, mamba * nbytes
