"""The one general generator of traffic.  A mix is a data file of
parameters (``traffic/<name>.json``); this code reads any of them.

Every seed gets the SAME sizes and gaps in the same order: they are
drawn once from the mix's own ``mix_seed``, and the run's ``--seed``
draws the token ids (and the weights).  So two seeds offer the same
work, and their difference is the system's noise and not the traffic's:
with some dozens of long requests to a window even their order moves a
tail by several per cent (PERF.md, Findings).
"""

import numpy as np


def _draw(spec, n, rng):
    """n whole numbers from a length distribution."""
    kind = spec["dist"]
    if kind == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    elif kind == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _gaps(spec, n, seconds, rng):
    """n gaps between the arrivals of a Poisson process, scaled so that
    every request falls inside the window."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    g = rng.exponential(1.0, n)
    return g * (seconds / (g.sum() + g.mean()))


def requests(mix, seed, seconds, vocab):
    """The requests of one run.

    Open loop (``arrivals.process`` other than ``closed``):
    ``round(rate * seconds)`` requests with their due times.  Closed
    loop: ``clients`` callers and a list of ``pool`` requests they take
    in turn.  Returns a dict with ``prompts`` (int32 arrays, ids
    1..vocab-1), ``max_new`` and, open loop, ``due`` (seconds from the
    start of the window, ascending)."""
    arr = mix["arrivals"]
    closed = arr["process"] == "closed"
    n = int(arr["pool"]) if closed else int(round(arr["rate"] * seconds))
    fixed = np.random.default_rng(int(mix.get("mix_seed", 0)))
    p_len = _draw(mix["prompt_tokens"], n, fixed)
    o_len = _draw(mix["output_tokens"], n, fixed)
    gaps = None if closed else _gaps(arr, n, seconds, fixed)
    rng = np.random.default_rng(int(seed))
    out = {"prompts": [rng.integers(1, vocab, int(k)).astype(np.int32)
                       for k in p_len],
           "max_new": [int(k) for k in o_len]}
    if closed:
        out["clients"] = int(arr["clients"])
    else:
        out["due"] = np.cumsum(gaps)
    return out


def token_batches(mix, seed, vocab):
    """(batches, batch, seq_len + 1) int32 token ids 1..vocab-1, made on
    the device from the seed: every row differs."""
    import jax

    from benchmark.reference.gpt2 import seed_key

    shape = (int(mix["batches"]), int(mix["batch"]),
             int(mix["seq_len"]) + 1)
    return jax.jit(lambda k: jax.random.randint(
        k, shape, 1, vocab, dtype="int32"))(
            jax.random.fold_in(seed_key(seed), 0x7AFF1C))
