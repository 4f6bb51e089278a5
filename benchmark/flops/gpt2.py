"""Operations and bytes the ``gpt2`` family needs, from its shapes.

Counted: what the algorithm requires.  Lookup tables (token and
position embeddings) are gathers and count no operations; biases,
LayerNorm, GELU and softmax are left out (they err the count low, which
a share of a peak may do and a share over 100% may not).  Attention is
counted over the causal half.  Nothing recomputed is counted: the flash
backward's second pass over Q K^T is the kernel's cost, not the
model's need.
"""


def _sizes(cfg):
    d = int(cfg["n_embd"])
    return (int(cfg["n_layer"]), d, int(cfg["n_head"]),
            int(cfg.get("n_inner") or 4 * d), int(cfg["vocab_size"]))


def matmul_params(cfg):
    """Weights that are multiplied: QKV, projection, both FFN matrices
    per layer, and the output head.  No table, no bias, no gain."""
    L, d, _, dff, V = _sizes(cfg)
    return L * (3 * d * d + d * d + 2 * d * dff) + V * d


def train_flops_per_token(cfg, seq_len):
    """Forward + backward: 6 per multiplied weight, and per layer the
    causal half of Q K^T and P V (2 T d forward, three times that with
    the backward) = 6 T d."""
    L, d, _, _, _ = _sizes(cfg)
    return 6 * matmul_params(cfg) + 6 * L * seq_len * d


def flash_forward(batch, seq_len, heads, head_size, causal=True,
                  itemsize=2):
    """(operations, bytes) of one attention forward over whole
    sequences: Q K^T and P V; reads q, k, v, writes o."""
    ops = 4 * batch * heads * seq_len * seq_len * head_size
    if causal:
        ops //= 2
    return ops, 4 * batch * seq_len * heads * head_size * itemsize


def flash_backward(batch, seq_len, heads, head_size, causal=True,
                   itemsize=2):
    """(operations, bytes) of its backward: dV, dP, dQ, dK (four
    products, twice the forward); reads q, k, v, o, do, writes dq, dk,
    dv."""
    ops, _ = flash_forward(batch, seq_len, heads, head_size, causal,
                           itemsize)
    return 2 * ops, 8 * batch * seq_len * heads * head_size * itemsize
