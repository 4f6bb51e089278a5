"""The decode loop runs one program ahead of what it has read: a step is
dispatched from the last step's tokens while they are still on the
device, a prefill's first token is not waited for, and the tokens
served are the synchronous loop's, request for request.

The synchronous loop is the same engine with ``_hold_back`` patched to
hold every batch back: it drains before each program and fetches each
program's tokens at once.  Tiny engines on the CPU: the dense block,
the hybrid family with slots, the family's windowed spec.  Tests that
want the same arguments share one engine (``engines``, counters from
``reset_stats()`` on); one that counts what is built, closes or breaks
its engine, or sizes its pool builds its own and says so.
"""
import functools
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from mxnet_tpu.serving import EngineClosedError  # noqa: E402

from _engines import WAIT, V, dense_engine, tiny_lm_params  # noqa: E402

MAXLEN = 64
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def lm_params():
    return tiny_lm_params(MAXLEN, seed=0)


dense = functools.partial(dense_engine, max_len=MAXLEN, seed=3,
                          prefix_cache=0)


@pytest.fixture
def synchronous(monkeypatch):
    """-> the same engine, never ahead of a token it has not read (until
    the test ends), its counters from here on."""
    def hold(eng):
        monkeypatch.setattr(eng, "_hold_back", lambda streams: "off")
        eng.reset_stats()
        return eng

    return hold


def prompts(seed, sizes, vocab=V):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in sizes]


def wait_for_steps(eng, n):
    """Until the loop has dispatched ``n`` more decode steps: a stream
    is decoding, with a step in flight."""
    before = eng.stats()["steps"]
    while eng.stats()["steps"] < before + n:
        time.sleep(0.005)


def serve(eng, ps, news, **kw):
    """-> (the served result of each request, the engine's stats)."""
    futs = [eng.submit(p, max_new_tokens=m, seed=11 + i, **kw)
            for i, (p, m) in enumerate(zip(ps, news))]
    outs = [f.result(timeout=WAIT) for f in futs]
    return outs, eng.stats()


def hybrid_engine(**kw):
    import test_hybrid_lm as th

    return th.FAMILY.engine(**kw)[0]


def windowed_engine(**kw):
    import test_smallthinker as ts

    return ts.FAMILY.engine(**kw)[0]


SIZES, NEWS = (9, 20, 13, 27, 6, 11), (24, 21, 30, 23, 27, 5)


# -- served tokens: the synchronous loop's, request for request ----------

@pytest.mark.parametrize("family, kw", [
    ("dense", {}),
    ("dense", {"temperature": 0.9}),
    ("slots", {}),
    ("slots", {"temperature": 0.7}),
    ("windowed", {}),
])
def test_served_tokens_equal_the_synchronous_loops(
        engines, synchronous, lm_params, family, kw):
    eng = {"dense": lambda: engines(dense, lm_params),
           "slots": lambda: engines(hybrid_engine),
           "windowed": lambda: engines(windowed_engine)}[family]()
    ps = prompts(8, SIZES, vocab=V if family == "dense" else 96)
    ahead, st = serve(eng, ps, NEWS, **kw)
    plain, st0 = serve(synchronous(eng), ps, NEWS, **kw)
    for a, b in zip(ahead, plain):
        np.testing.assert_array_equal(a, b)
    assert [len(a) for a in ahead] == list(NEWS)
    # the loop really ran ahead, and the patched one never did
    assert st["steps_run_ahead"] > 0.7 * st["steps"]
    assert st["prefill_first_deferred"] == len(ps) == st["prefills"]
    assert st["d2h_syncs_saved"] >= st["steps_run_ahead"]
    assert (st0["steps_run_ahead"], st0["prefill_first_deferred"],
            st0["d2h_syncs_saved"], st0["run_ahead_drains"]) == (0, 0, 0, 0)
    # every token is fetched once either way: one fetch a program
    assert st["d2h_syncs"] == st["steps"] + st["prefills"]
    assert st["tokens"] == st0["tokens"] == sum(NEWS)
    assert st["overshoot_row_steps"] == 0


def test_a_stream_ending_by_count_is_not_in_the_step_in_flight(
        engines, monkeypatch, lm_params):
    eng = engines(dense, lm_params)
    booked = []
    real = eng._book_step

    def spy(rec, toks, t_done):
        booked.extend((s.sid, len(s.generated), s.max_new)
                      for s in rec.streams)
        return real(rec, toks, t_done)

    monkeypatch.setattr(eng, "_book_step", spy)
    _, st = serve(eng, prompts(2, SIZES), NEWS)
    # no row was run for a stream that already had all its tokens: a
    # request of n tokens rides n - 1 steps (its prefill samples one)
    assert all(have < want for _, have, want in booked)
    assert st["stream_steps"] == len(booked) == sum(n - 1 for n in NEWS)
    assert st["overshoot_row_steps"] == 0
    assert st["steps_run_ahead"] > 0


# -- eos: a stream rides ahead of its check, once ------------------------

def first_seen_at(tokens, lo=2):
    """Index >= lo of a token that occurs nowhere before it."""
    for k in range(lo, len(tokens) - 3):
        if tokens[k] not in tokens[:k]:
            return k
    raise AssertionError(f"no fresh token in {tokens}")


def test_an_eos_stream_overshoots_once_and_the_token_is_dropped(
        engines, synchronous, lm_params):
    ps, n = prompts(5, (7, 12, 9)), 20
    kw = dict(temperature=1.0)
    eng = engines(dense, lm_params)
    free, _ = serve(eng, ps, (n,) * 3, **kw)
    cut = [first_seen_at(list(o)) for o in free]
    eng.reset_stats()
    futs = [eng.submit(p, max_new_tokens=n, seed=11 + i,
                       eos_id=int(o[k]), **kw)
            for i, (p, o, k) in enumerate(zip(ps, free, cut))]
    outs = [f.result(timeout=WAIT) for f in futs]
    time.sleep(0.05)
    st = eng.stats()
    # the overshoot's pages went back with the rest
    assert eng._alloc.used_blocks == 0
    for o, ref, k in zip(outs, free, cut):
        np.testing.assert_array_equal(o, ref[:k + 1])  # eos included
    # each stream read its eos with ONE later row already dispatched
    assert st["overshoot_row_steps"] == len(ps)
    assert st["tokens"] == sum(k + 1 for k in cut)
    assert st["stream_steps"] == sum(cut) + len(ps)
    same, st0 = serve(synchronous(eng), ps, (n,) * 3,
                      eos_id=int(free[0][cut[0]]), **kw)
    np.testing.assert_array_equal(same[0], outs[0])
    assert st0["overshoot_row_steps"] == 0


def test_return_state_with_eos_is_never_run_ahead_of_its_check(
        engines, synchronous):
    ps = prompts(11, (9, 14), vocab=96)
    eng = engines(hybrid_engine)
    free, _ = serve(eng, ps[:1], (18,), temperature=0.8)
    k = first_seen_at(list(free[0]))
    kw = dict(temperature=0.8, eos_id=int(free[0][k]), return_state=True)

    def run(eng):
        eng.reset_stats()
        other = eng.submit(ps[1], max_new_tokens=70, seed=5)
        wait_for_steps(eng, 2)  # it is running ahead, alone
        out = eng.submit(ps[0], max_new_tokens=18, seed=11,
                         **kw).result(timeout=WAIT)
        other.result(timeout=WAIT)
        return out, eng.stats()

    out, st = run(eng)
    want, _ = run(synchronous(eng))
    np.testing.assert_array_equal(out["tokens"], free[0][:k + 1])
    np.testing.assert_array_equal(out["tokens"], want["tokens"])
    assert st["overshoot_row_steps"] == 0
    # the loop drained what the other stream had in flight, by name
    assert st["run_ahead_drain_reasons"].get("state_eos", 0) >= 1
    assert st["steps_run_ahead"] > 0  # ... and ran ahead around it
    for name, state in out["state"].items():
        np.testing.assert_allclose(state, want["state"][name], atol=1e-6)
        assert np.abs(state).max() > 1e-3


# -- drains: what needs the delivery state whole, counted by reason ------

def test_a_drain_comes_before_a_preemption(synchronous, lm_params):
    ps = [np.arange(1, 6, dtype=np.int32), np.arange(7, 12, dtype=np.int32),
          np.arange(13, 18, dtype=np.int32)]
    # an engine of its own: the pool is sized for the preemption
    eng = dense(lm_params, max_streams=3, cache_blocks=10)
    outs, st = serve(eng, ps, (14,) * 3)
    want, st0 = serve(synchronous(eng), ps, (14,) * 3)
    assert st["preempted"] > 0 and st0["preempted"] > 0
    assert st["run_ahead_drain_reasons"]["preempt"] >= 1
    for a, b in zip(outs, want):
        np.testing.assert_array_equal(a, b)


def test_a_drain_comes_before_a_verify_window(engines, lm_params):
    rng = np.random.RandomState(0)
    motif = rng.randint(1, V, size=5).astype(np.int32)
    prompt = np.tile(motif, 4)[:18]
    (want,), _ = serve(engines(dense, lm_params), [prompt], (12,))
    # (the only engine with a verify program)
    (got,), st = serve(dense(lm_params, spec_tokens=3), [prompt], (12,))
    np.testing.assert_array_equal(got, want)
    assert st["spec_steps"] > 0
    assert st["run_ahead_drain_reasons"]["verify"] >= 1


def test_a_drain_comes_before_a_chunk(engines, synchronous, lm_params):
    ps = prompts(4, (6, 30))
    # (the only engine that chunks its prompts)
    with dense(lm_params, prefill_chunk=8) as eng:
        first = eng.submit(ps[0], max_new_tokens=50, seed=1)
        wait_for_steps(eng, 2)  # a step in flight when the long one comes
        second = eng.submit(ps[1], max_new_tokens=6, seed=2)
        got = [first.result(timeout=WAIT), second.result(timeout=WAIT)]
        st = eng.stats()
    assert st["prefill_chunks"] == 4
    assert st["run_ahead_drain_reasons"]["chunk"] >= 1
    eng = synchronous(engines(dense, lm_params))
    want = [eng.generate(ps[0], 50, seed=1),
            eng.generate(ps[1], 6, seed=2)]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_a_drain_comes_before_swap_params_reset_stats_and_close(
        engines, lm_params):
    p = prompts(6, (5,))[0]
    want = engines(dense, lm_params).generate(p, 50, seed=9)
    eng = dense(lm_params)      # its own: swapped into, and closed
    fut = eng.submit(p, max_new_tokens=50, seed=9)
    wait_for_steps(eng, 2)
    eng.swap_params(lm_params)  # the same weights: the tokens stay
    reasons = eng.stats()["run_ahead_drain_reasons"]
    assert reasons.get("swap_params") == 1
    eng.reset_stats()
    st = eng.stats()
    assert st["run_ahead_drains"] == 0 and st["steps_run_ahead"] == 0 \
        and st["run_ahead_drain_reasons"] == {}
    np.testing.assert_array_equal(fut.result(timeout=WAIT), want)
    # close: what is in flight is booked (and counted) before the rest
    # of the stream fails
    fut = eng.submit(p, max_new_tokens=50, seed=9)
    wait_for_steps(eng, 5)
    eng.close()
    with pytest.raises(EngineClosedError):
        fut.result(timeout=60)
    assert eng.stats()["run_ahead_drain_reasons"].get("close") == 1


def test_a_device_error_at_the_fetch_fails_every_stream_in_flight(lm_params):
    eng = dense(lm_params)      # its own: the error kills it
    real = eng._read

    def read(toks):
        # fail the fetch of a step while the short request's last
        # tokens are in flight: it has left the batch by count, and is
        # in no list but its programs' records when the error surfaces
        rec = eng._inflight[0]
        if rec.prefill is None and any(
                s.max_new == 4 and s not in eng._active
                for s in rec.streams):
            raise RuntimeError("injected device error")
        return real(toks)

    eng._read = read
    ps = prompts(7, (5, 8, 6, 9))
    futs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(ps, (4, 30, 30, 30))]
    for f in futs:
        with pytest.raises(EngineClosedError, match="injected"):
            f.result(timeout=60)
    assert eng.inflight() == 0 and eng._alloc.used_blocks == 0
    with pytest.raises(EngineClosedError):
        eng.submit(ps[0], max_new_tokens=2)
    eng.close()


# -- the feed program -----------------------------------------------------

def test_warmup_builds_the_feed_program_and_nothing_is_built_after(
        lm_params):
    built = []

    def on_duration(event, duration, **kw):
        if event == COMPILE_EVENT:
            built.append(event)

    eng = dense(lm_params)      # its own: what it builds is counted
    eng.warmup()
    assert {("feed", bb) for bb in (1, 2, 4)} <= set(eng.compiles)
    # one turn of everything (pools written, tokens read), then listen
    serve_open = [eng.submit(p, max_new_tokens=6)
                  for p in prompts(1, (5, 9))]
    [f.result(timeout=WAIT) for f in serve_open]
    before = dict(eng.compiles)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        outs, st = serve(eng, prompts(8, SIZES), NEWS)
    finally:
        jax.monitoring.unregister_event_duration_listener(
            on_duration)
    assert dict(eng.compiles) == before and not built
    assert st["steps_run_ahead"] > 0 and len(outs) == len(NEWS)


def test_a_steady_batch_runs_ahead_nearly_always(lm_params):
    ps = prompts(9, (6, 9, 7, 8))
    eng = dense(lm_params, decode_buckets=[4])      # the one bucket
    eng.warmup()
    outs, st = serve(eng, ps, (50,) * 4)
    assert st["run_ahead_share"] >= 0.9
    assert st["run_ahead_share"] == pytest.approx(
        st["steps_run_ahead"] / st["steps"], abs=1e-3)
    # the tail alone was fetched with nothing queued behind it
    assert st["run_ahead_drain_reasons"] == {"idle": 1}
    assert st["decode_buckets"] == [4] and all(len(o) == 50 for o in outs)


def test_slots_held_by_streams_in_flight_hold_admission(engines):
    """Five streams through three slots, requests queued: a stream
    whose last token is in flight keeps its slot until it is fetched,
    and the next one waits for it (no slot is handed out twice)."""
    import test_hybrid_lm as th

    eng, drawn = engines(hybrid_engine), th.FAMILY.draw()
    ps = prompts(8, (9, 20, 13, 27, 6), vocab=96)
    with th.watch_slots(eng) as (held, history):
        outs, st = serve(eng, ps, (12, 9, 14, 8, 11))
    assert len(history) == 5 and not held
    assert st["state_slots_live"] == 0 and st["steps_run_ahead"] > 0
    for p, o in zip(ps, outs):
        assert th.served_gap(drawn, p, o) < 1e-4
