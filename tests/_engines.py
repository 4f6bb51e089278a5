"""What the test files share instead of each defining its own: an op run
by name, the tiny dense LM and its engine, and the engines of the model
families — one helper that takes the reference module and the tiny
configuration, so that a new family's file adds no copy.

The rule (``test_docs_name_what_exists.py`` holds it): a test file builds
a ``DecodeEngine`` through ``build`` below — ``dense_engine`` and
``Family.engine`` do — and tests that want the same arguments share ONE
engine a file through conftest's ``engines``: tracing and compiling an
engine's programs is seconds, serving a test's requests through them is
tenths of one.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.ops.registry import OpContext, get_op

# how long a test waits for a served request: about five times what a
# sound engine case takes under six workers (5-25 s; the slowest, an engine
# of interpreted kernels compiling its programs, 43 s), so that a hung
# engine fails its own test inside the suite's clock
WAIT = 120


def run_op(name, inputs, **attrs):
    """A registered op's compute on arrays, attributes as the symbol
    carries them (strings)."""
    attrs = {k: str(v) for k, v in attrs.items()}
    return get_op(name).compute(OpContext(is_train=False, rng=None), attrs,
                                [jnp.asarray(x) for x in inputs], [])


# -- engines ---------------------------------------------------------------

UNCLOSED = []


def build(params, **kw):
    """``mx.DecodeEngine``, noted so that conftest closes it when its
    test (or, for a shared one, its file) ends, whatever the test did."""
    eng = mx.DecodeEngine(params, **kw)
    UNCLOSED.append(eng)
    return eng


class Engines:
    """One engine for each distinct argument set a file asks for
    (conftest's module-scoped ``engines``): built the first time, handed
    out again with its counters zeroed, closed when the file ends.  A test
    whose engine must be its own (a pool sized for preemption, counters
    since construction, an engine it closes or breaks) calls the maker
    itself and says why."""

    def __init__(self):
        self._held = {}     # arguments -> (what the maker gave, its engine)

    def __call__(self, make, *args, **kw):
        key = (make, tuple(map(id, args)), repr(sorted(kw.items())))
        if key not in self._held:
            before = len(UNCLOSED)
            got = make(*args, **kw)     # an engine, or (engine, weights)
            del UNCLOSED[before:]       # the file's now, not the test's
            # (the positional arguments are kept so that their ids stay
            # theirs)
            self._held[key] = (got, got[0] if isinstance(got, tuple)
                               else got, args)
        got, eng, _ = self._held[key]
        assert eng.inflight() == 0, "the test before left work behind"
        eng.reset_stats()
        return got

    def close(self):
        for _, eng, _ in self._held.values():
            eng.close()
        self._held.clear()


# -- the tiny dense LM -----------------------------------------------------

V, KVB, L, H, DM, MAXLEN = 61, 4, 2, 2, 32, 32


@functools.lru_cache(maxsize=None)
def tiny_lm_params(max_len=MAXLEN, seed=None):
    """The tiny trained-shape transformer's parameters, drawn through the
    TRAINING symbol's module (``seed``: ``mx.random.seed`` first, for a
    test that needs one draw and not whatever the stream holds)."""
    sym = models.transformer_lm(V, max_len, num_layers=L, num_heads=H,
                                d_model=DM, block_size=KVB)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, max_len))],
             label_shapes=[("softmax_label", (2, max_len))],
             for_training=False)
    if seed is not None:
        mx.random.seed(seed)
    mod.init_params(mx.initializer.Xavier(factor_type="in",
                                          magnitude=2.0))
    arg, aux = mod.get_params()
    return {**arg, **aux}


@functools.lru_cache(maxsize=None)
def _tiny_lm_forward():
    """The unpaged prefill symbol as (parameters, tokens (T,), length)
    -> logits (T, V): as it is, and jitted."""
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.models.transformer import transformer_lm_prefill

    gfn = build_graph_fn(transformer_lm_prefill(
        V, num_layers=L, num_heads=H, d_model=DM, kv_block=KVB, paged=False))
    key = jax.random.PRNGKey(0)

    def forward(base, data, length):
        a = dict(base, data=data[None],
                 positions=jnp.arange(data.shape[0], dtype=jnp.int32)[None],
                 lengths=jnp.reshape(length, (1,)).astype(jnp.int32))
        return gfn(a, {}, key, False)[0][0][0]

    return forward, jax.jit(forward)


def tiny_lm_reference(params, max_len=MAXLEN):
    """-> (full_logits, naive_generate) of the tiny LM with ``params``
    (possibly merged) through the unpaged prefill symbol:
    ``full_logits(seq)`` (T, V) at the natural length (what the bitwise
    tests hold a prefill row against), and ``naive_generate(prompt, n)``,
    the greedy chain — through ONE program at ``max_len`` rows (a causal
    forward told the length: a row does not see the padding behind it),
    not one a length."""
    forward, padded = _tiny_lm_forward()
    base = {n: jnp.asarray(v.asnumpy() if hasattr(v, "asnumpy") else v)
            for n, v in params.items()}

    def full_logits(seq):
        seq = jnp.asarray(np.asarray(seq, np.int32))
        return np.asarray(forward(base, seq, jnp.asarray(len(seq))))

    def naive_generate(prompt, n):
        seq = np.zeros(max_len, np.int32)
        first = len(prompt)
        seq[:first] = prompt
        for t in range(first, first + n):
            seq[t] = int(np.argmax(np.asarray(padded(base, seq, t))[t - 1]))
        return seq[first:first + n].copy()

    return full_logits, naive_generate


def dense_engine(params, **kw):
    """The tiny LM's engine: four streams, greedy."""
    args = dict(vocab_size=V, num_layers=L, num_heads=H, d_model=DM,
                max_len=MAXLEN, kv_block=KVB, max_streams=4,
                decode_buckets=[1, 2, 4], temperature=0.0)
    args.update(kw)
    return build(params, **args)


# -- a family: its reference module and its tiny configuration -------------

class Family:
    """``ref`` (a module of ``benchmark/reference``) at ``cfg``, float32:
    the engine over the drawn weights and the reference's full forward
    to hold its served tokens against.  ``engine_kw``: the file's default
    engine arguments; ``pad``: the one length the reference is traced at
    (the models are causal, so a sequence's rows do not see the padding
    behind them — each file holds that in one case)."""

    def __init__(self, ref, cfg, pad, **engine_kw):
        self.ref, self.cfg, self.pad, self.engine_kw = ref, cfg, pad, \
            engine_kw
        self._drawn = {}
        self._forward = jax.jit(
            lambda w, tokens, precision: ref.forward(cfg, w, tokens,
                                                     precision),
            static_argnames="precision")

    def draw(self, seed=7):
        if seed not in self._drawn:
            self._drawn[seed] = self.ref.draw(
                self.cfg, seed, embed_dtype="float32", dtype="float32")
        return self._drawn[seed]

    def engine(self, drawn=None, **kw):
        """-> (the engine, the drawn weights it serves)."""
        drawn = drawn or self.draw()
        args = dict(model=self.ref.spec(self.cfg), ctx=mx.cpu(),
                    dtype="float32", **self.engine_kw)
        args.update(kw)
        return build(self.ref.program_names(drawn), **args), drawn

    def prompts(self, rng, sizes):
        return [rng.integers(1, self.cfg["vocab_size"], n).astype(np.int32)
                for n in sizes]

    def logits(self, drawn, seq, precision="float32", pad=True):
        """The reference's logits (len(seq), V) of one sequence, traced
        once at ``pad`` rows (``pad=False``: at the sequence's own) for
        each ``precision`` (or mechanism left out) a test names."""
        seq = np.asarray(seq, np.int32)
        rows = max(self.pad, len(seq)) if pad else len(seq)
        fed = np.zeros(rows, np.int32)
        fed[:len(seq)] = seq
        return np.asarray(self._forward(drawn, fed, precision))[:len(seq)]

    def served_gap(self, drawn, prompt, out):
        """How far below the reference's best logit the served tokens
        lie, teacher-forced through the reference's full forward."""
        z = self.logits(drawn, np.concatenate([prompt, out]))
        rows = z[len(prompt) - 1:-1]
        return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())

    def padding_is_not_seen(self, n=29, seed=4):
        """The case each file holds: the padded call's live rows are the
        unpadded call's."""
        seq = self.prompts(np.random.default_rng(seed), (n,))[0]
        padded, alone = self.logits(self.draw(), seq), \
            self.logits(self.draw(), seq, pad=False)
        assert padded.shape == alone.shape == (n, self.cfg["vocab_size"])
        assert np.abs(alone).max() > 1e-2
        np.testing.assert_allclose(padded, alone, atol=1e-5)


@contextlib.contextmanager
def watch_slots(eng):
    """While the block runs, record every slot's owners, and fail the
    moment one is handed out while held: -> (``held``: slot -> owner now,
    ``history``: (slot, owner) in order)."""
    alloc = eng._slot_alloc
    held, history = {}, []
    real_alloc, real_free = alloc.alloc, alloc.free

    def a(owner=None):
        slot = real_alloc(owner=owner)
        assert slot not in held, f"slot {slot} given to two streams"
        held[slot] = owner
        history.append((slot, owner))
        return slot

    def f(slot):
        del held[slot]
        real_free(slot)

    alloc.alloc, alloc.free = a, f
    try:
        yield held, history
    finally:
        alloc.alloc, alloc.free = real_alloc, real_free
