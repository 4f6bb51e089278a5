"""``flops/gpt2.py`` against a count made by hand at one shape."""

from benchmark.flops import gpt2


def test_train_flops_by_hand():
    cfg = {"n_layer": 24, "n_embd": 1024, "n_head": 16,
           "vocab_size": 50257, "n_inner": None}
    # per layer: qkv 3 d^2, proj d^2, ffn 2 * 4 d^2 = 12 d^2
    per_layer = 12 * 1024 * 1024
    head = 50257 * 1024
    assert gpt2.matmul_params(cfg) == 24 * per_layer + head == 353453056
    # lookup tables are left out: 50257 x 1024 + 1024 x 1024 more would
    # be 405,964,800 "parameters", the count the old script used
    attention = 6 * 24 * 1024 * 1024  # causal half, forward + backward
    assert gpt2.train_flops_per_token(cfg, 1024) == \
        6 * 353453056 + attention == 2271713280


def test_flash_counts_by_hand():
    # one head of 64, 128 positions, causal: Q K^T and P V, half of
    # 2 * 2 * 128 * 128 * 64
    ops, nbytes = gpt2.flash_forward(1, 128, 1, 64, causal=True)
    assert ops == 2 * 128 * 128 * 64
    assert nbytes == 4 * 128 * 64 * 2
    bops, bbytes = gpt2.flash_backward(1, 128, 1, 64)
    assert bops == 2 * ops and bbytes == 2 * nbytes
