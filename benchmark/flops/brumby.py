"""Operations and bytes the ``brumby`` family's kernels need, from their
shapes.  Counted: what the ALGORITHM requires, whatever implements it —
operands read once, results written once; float32 where the program
holds float32 (the state, the normaliser, the decode step's rows), the
model's type elsewhere; the state at the NEEDED head_dim (head_dim + 1)
/ 2 rows a KV head (8,256 at 128), not at the rows a slot spends on its
layout.  Norms, rotations, exponentials, masks and the division are left
out, and a prompt's outputs are counted in the cheaper of the two forms
the mathematics allows (they err the count low, which a share of a
roofline may do and a share over 100% may not).

``need`` is what ``reducers/family_kernel_roofline.py`` asks.
"""

F32 = 4


def _sizes(cfg):
    H, J, D = (int(cfg[k]) for k in ("num_attention_heads",
                                     "num_key_value_heads", "head_dim"))
    return H, J, D, D * (D + 1) // 2


def retention_step(rows, cfg):
    """(operations, bytes) of one layer's ``retention_step`` over
    ``rows`` live streams.  Bytes: the state and the normaliser z read
    and written at the needed rows; q, k, v and the gate in, y out
    (float32).  Operations, a KV head and state entry: the decay, the
    rank-one update (a multiply and an add) and a multiply and an add a
    query head for ``phi(q)^T S`` — (3 + 2 G) P D — and the same over z's
    P numbers."""
    H, J, D, P = _sizes(cfg)
    G = H // J
    ops = rows * J * (3.0 + 2 * G) * P * (D + 1)
    nbytes = rows * (2.0 * J * P * (D + 1) + 2 * H * D + 2 * J * D + J) * F32
    return ops, nbytes


def retention_chunk(tokens, prompts, cfg, itemsize=2):
    """(operations, bytes) of one layer's ``retention_chunk`` over
    ``tokens`` prompt positions of ``prompts`` prompts.  Operations: the
    state and normaliser built, 2 P (D + 1) a token and KV head; the
    outputs in the cheaper form at the prompts' mean length n — the
    attention form's n (n + 1) / 2 weights a query head, 4 D + 3 each
    (q.k, the square, the decay, the weight times v and its sum), or the
    recurrent form's 2 P (D + 1) a token and query head.  Bytes: q, k, v
    in and y out in the model's type, the log-gate (float32); per prompt
    the last state and normaliser out."""
    H, J, D, P = _sizes(cfg)
    n = tokens / max(prompts, 1)
    read = min((4.0 * D + 3) * (n + 1) / 2, 2.0 * P * (D + 1))
    ops = tokens * (J * 2.0 * P * (D + 1) + H * read)
    nbytes = tokens * ((2 * H * D + 2 * J * D) * itemsize + J * F32) \
        + prompts * J * P * (D + 1) * F32
    return ops, nbytes


def need(kernel, stats, cfg, itemsize):
    """(operations, bytes) per execution of the program ``kernel`` runs
    in, from the engine's counters over the window, or ``None``:
    ``retention_step`` — the live rows of a decode step (``stream_steps /
    steps``); ``retention_chunk`` — the prompt positions of a prefill
    (``prefill_tokens / prefills``); each in every layer."""
    L = int(cfg["num_hidden_layers"])
    if kernel == "retention_step":
        if not stats.get("steps") or not stats.get("stream_steps"):
            return None
        ops, nbytes = retention_step(stats["stream_steps"] / stats["steps"],
                                     cfg)
    elif kernel == "retention_chunk":
        if not stats.get("prefills") or not stats.get("prefill_tokens"):
            return None
        ops, nbytes = retention_chunk(
            stats["prefill_tokens"] / stats["prefills"], 1, cfg, itemsize)
    else:
        raise ValueError(f"no count for kernel {kernel!r}")
    return L * ops, L * nbytes
