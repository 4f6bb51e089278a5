"""Operations and bytes the ``solar_open2`` family's kernels need, from
their shapes.  Counted: what the algorithm requires of the kernel as it
is called — operands read once, results written once; float32 where the
program holds float32 (the KDA state and its rows), the model's type
elsewhere.  Softmax, SiLU, norms and masks are left out (they err the
count low, which a share of a roofline may do and a share over 100% may
not).  The paged attention kernel is counted by
``flops/paged_attention.decode_step`` at the K/V width and one layer.
"""

F32 = 4


def layer_counts(cfg):
    """(attention layers, kda layers, expert layers) of the cut."""
    L = int(cfg["num_hidden_layers"])
    att = sum(1 for i in range(L) if i in cfg["gqa_layers"])
    return att, L - att, L


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


def _kda_token_ops(cfg):
    lin = cfg["linear_attn_config"]
    H, D = int(lin["num_heads"]), int(lin["head_dim"])
    # per head: decay D^2, k^T S 2 D^2, the rank-1 update 2 D^2, S^T q
    # 2 D^2
    return H, D, 7.0 * H * D * D


def kda_step(rows, cfg):
    """(operations, bytes) of one layer's ``kda_step`` over ``rows``
    live streams: the state read and written, q, k, v and the decay in
    (a row of D each, a head), beta (a number a head), o out."""
    H, D, ops = _kda_token_ops(cfg)
    nbytes = rows * (2.0 * H * D * D + 5 * H * D + H) * F32
    return rows * ops, nbytes


def kda_chunk(tokens, prompts, cfg):
    """(operations, bytes) of one layer's ``kda_chunk`` over ``tokens``
    prompt positions of ``prompts`` prompts: per position q, k, v and
    the decay in, beta, o out; per prompt the last state out."""
    H, D, ops = _kda_token_ops(cfg)
    nbytes = (tokens * (5.0 * H * D + H) + prompts * H * D * D) * F32
    return tokens * ops, nbytes
