"""The generator reproduces from a seed, and gives every seed the same
work: only the token ids differ."""

import json
import os

import numpy as np

from benchmark.traffic import generate

HERE = os.path.dirname(os.path.abspath(__file__))
MIX = os.path.join(HERE, "..", "traffic")


def load(name):
    with open(os.path.join(MIX, name + ".json")) as f:
        return json.load(f)


def test_a_seed_changes_only_the_ids():
    mix = load("serve-doc-closed")
    a = generate.requests(mix, 1, 30, 50257)
    b = generate.requests(mix, 2**31 + 1, 30, 50257)
    assert a["max_new"] == b["max_new"]
    assert [len(p) for p in a["prompts"]] == [len(p) for p in b["prompts"]]
    assert not np.array_equal(a["prompts"][0], b["prompts"][0])


def test_open_loop_reproduces():
    mix = {"mix_seed": 23, "arrivals": {"process": "poisson", "rate": 1.5},
           "prompt_tokens": {"dist": "lognormal", "median": 192,
                             "sigma": 0.8, "min": 16, "max": 768},
           "output_tokens": {"dist": "lognormal", "median": 96,
                             "sigma": 0.7, "min": 8, "max": 256}}
    a = generate.requests(mix, 2**31 + 5, 30, 50257)
    b = generate.requests(mix, 2**31 + 5, 30, 50257)
    c = generate.requests(mix, 7, 30, 50257)
    assert len(a["prompts"]) == round(mix["arrivals"]["rate"] * 30)
    assert all(np.array_equal(x, y)
               for x, y in zip(a["prompts"], b["prompts"]))
    # another seed: the same sizes and gaps in the same order, other ids
    assert np.array_equal(a["due"], c["due"]) and a["max_new"] == c["max_new"]
    assert not np.array_equal(a["prompts"][0], c["prompts"][0])
    assert 0 < a["due"][0] and a["due"][-1] < 30
    lens = np.array([len(p) for p in a["prompts"]])
    assert lens.min() >= 16 and lens.max() <= 768
    assert min(a["max_new"]) >= 8 and max(a["max_new"]) <= 256
    assert all(p.min() >= 1 and p.max() < 50257 for p in a["prompts"])


def test_closed_loop_pool():
    mix = load("serve-doc-closed")
    a = generate.requests(mix, 3, 30, 50257)
    assert a["clients"] == 48 and len(a["prompts"]) == 4096
    lens = np.array([len(p) for p in a["prompts"]])
    assert lens.min() >= 512 and lens.max() <= 960
    assert all(p + n <= 1024 for p, n in zip(lens, a["max_new"]))


def test_token_batches_reproduce():
    mix = {"batches": 4, "batch": 2, "seq_len": 16}
    a = np.asarray(generate.token_batches(mix, 2**31 + 9, 211))
    b = np.asarray(generate.token_batches(mix, 2**31 + 9, 211))
    c = np.asarray(generate.token_batches(mix, 1, 211))
    assert a.shape == (4, 2, 17) and np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() >= 1 and a.max() < 211
    assert len({row.tobytes() for row in a.reshape(-1, 17)}) == 8
