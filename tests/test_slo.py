"""SLO engine tests: burn-rate math against hand-computed windows,
the alert-before-conviction contract under injected latency
(``MXNET_CHAOS_SLOW_RANK``), canary exclusion from the request
counters, EXACT per-request cost-record conservation against the
engine counters across a mixed prefix-hit/speculative/chunked run.
"""

import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import chaos, profiler, slo
from mxnet_tpu.elastic import dead_rank_timeout

from _engines import dense_engine as _engine, tiny_lm_params


def _cfg(**kw):
    """Explicit SloConfig (no env): budget = 1 - 0.8 = 0.2."""
    args = dict(ttft_ms={"interactive": 100.0, "batch": 1000.0},
                tpt_ms={"interactive": 10.0, "batch": 100.0},
                objective=0.8, fast_window_s=60.0,
                slow_window_s=600.0, burn_alert=4.0, min_events=5)
    args.update(kw)
    return slo.SloConfig(**args)


@pytest.fixture(autouse=True)
def _fresh_slo_state():
    """Every test gets a fresh process-wide tracker + metrics slate
    (the tracker is built from the env at first use)."""
    profiler.reset_metrics()
    slo.reset_tracker()
    chaos.reset_chaos()
    yield
    profiler.reset_metrics()
    slo.reset_tracker()
    chaos.reset_chaos()


# ---------------------------------------------------------------------------
# burn-rate math vs hand-computed windows
# ---------------------------------------------------------------------------


def test_burn_rate_hand_computed_windows():
    """10 TTFT events, 1 bad, budget 0.2: fast burn = (1/10)/0.2 =
    0.5 and slow-window budget_remaining = 1 - 0.5 = 0.5 — checked
    with explicit timestamps, no wall clock involved."""
    tr = slo.SloTracker(_cfg(), source="test")
    t0 = 1000.0
    for i in range(10):
        ms = 150.0 if i == 0 else 50.0  # 1 bad of 10 vs 100ms target
        tr.observe_ttft("interactive", ms, now=t0 + i * 0.01)
    now = t0 + 1.0
    assert tr.burn_rate("interactive", "ttft", "fast",
                        now=now) == pytest.approx(0.5)
    assert tr.burn_rate("interactive", "ttft", "slow",
                        now=now) == pytest.approx(0.5)
    assert tr.budget_remaining("interactive", "ttft",
                               now=now) == pytest.approx(0.5)
    # untouched objective: zero burn, full budget
    assert tr.burn_rate("batch", "ttft", now=now) == 0.0
    assert tr.budget_remaining("batch", "ttft", now=now) == 1.0

    # the fast window forgets first: at t0+61 every event has left
    # the 60s fast window but all still sit in the 600s slow window
    late = t0 + 61.0
    assert tr.burn_rate("interactive", "ttft", "fast", now=late) == 0.0
    assert tr.burn_rate("interactive", "ttft", "slow",
                        now=late) == pytest.approx(0.5)
    # ... and at t0+601 the slow window is empty too: full budget
    assert tr.budget_remaining("interactive", "ttft",
                               now=t0 + 601.0) == 1.0


def test_burn_rate_availability_objective():
    """Availability rides the same windows: 2 failed deliveries of 8
    → bad fraction 0.25, burn 1.25 against the 0.2 budget."""
    tr = slo.SloTracker(_cfg(), source="test")
    t0 = 5000.0
    for i in range(8):
        tr.observe_avail("interactive", ok=i >= 2, now=t0 + i * 0.01)
    assert tr.burn_rate("interactive", "avail",
                        now=t0 + 1) == pytest.approx(1.25)


def test_alert_fires_once_with_hysteresis_and_rearms():
    """5 bad TTFTs (burn 5.0 >= alert 4.0, min_events met) fire ONE
    typed alert; it clears only under half the threshold and re-arms
    after the window forgets."""
    tr = slo.SloTracker(_cfg(), source="test")
    # anchored at the real clock: stats() prunes with perf_counter()
    t0 = time.perf_counter()
    for i in range(5):
        tr.observe_ttft("interactive", 500.0, now=t0 + i * 0.01)
    fired = tr.check(now=t0 + 1.0)
    assert len(fired) == 1
    a = fired[0]
    assert (a.slo_class, a.metric, a.window) == ("interactive",
                                                 "ttft", "fast")
    assert a.burn_rate == pytest.approx(5.0)  # bad_frac 1.0 / 0.2
    assert a.threshold == 4.0
    assert "interactive/ttft" in a.message
    assert tr.alert_active()
    # no flap: a second check does not re-fire
    assert tr.check(now=t0 + 1.1) == []
    # exported judgment surface: gauges + counter + statusz section
    summ = profiler.metrics_summary()
    assert summ["counters"]["slo.alerts"] == 1
    assert summ["gauges"]["slo.alerts_active"] == 1
    st = tr.stats()
    assert st["worst"]["class"] == "interactive"
    assert st["worst"]["metric"] == "ttft"
    assert st["alerts_active"] and st["alerts_recent"]
    assert st["classes"]["interactive"]["ttft"]["fast_burn"] \
        == pytest.approx(5.0)
    # hysteresis: 10 good events → burn 5/15/0.2 ≈ 1.67 < 4/2 → clear
    for i in range(10):
        tr.observe_ttft("interactive", 10.0, now=t0 + 2 + i * 0.01)
    tr.check(now=t0 + 3.0)
    assert not tr.alert_active()
    # re-arm: after the fast window forgets, a fresh burst re-fires
    t1 = t0 + 120.0
    for i in range(5):
        tr.observe_ttft("interactive", 500.0, now=t1 + i * 0.01)
    assert len(tr.check(now=t1 + 1.0)) == 1
    assert len(tr.alerts) == 2


def test_alert_min_events_gate():
    """4 bad events with min_events=5: burn 5.0 but NO alert — a
    tiny sample must not page anyone."""
    tr = slo.SloTracker(_cfg(), source="test")
    t0 = 3000.0
    for i in range(4):
        tr.observe_ttft("interactive", 500.0, now=t0 + i * 0.01)
    assert tr.check(now=t0 + 1.0) == []
    assert not tr.alert_active()


# ---------------------------------------------------------------------------
# configuration: loud validation + env round-trip
# ---------------------------------------------------------------------------


def test_config_validation_is_loud():
    with pytest.raises(mx.MXNetError, match="unknown SLO class"):
        slo.check_class("premium")
    with pytest.raises(mx.MXNetError, match="missing SLO class"):
        slo._parse_class_map("X", "interactive=5", minimum=0.0)
    with pytest.raises(mx.MXNetError, match="unknown SLO class"):
        slo._parse_class_map("X", "interactive=5,gold=1", minimum=0.0)
    with pytest.raises(mx.MXNetError, match="not a number"):
        slo._parse_class_map("X", "interactive=fast,batch=1",
                             minimum=0.0)
    with pytest.raises(mx.MXNetError, match="zero error budget"):
        _cfg(objective=1.0)
    with pytest.raises(mx.MXNetError, match="must exceed"):
        _cfg(fast_window_s=600.0, slow_window_s=60.0)


def test_config_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_SLO_TTFT_MS", "interactive=123,batch=456")
    monkeypatch.setenv("MXNET_SLO_TPT_MS", "interactive=7,batch=77")
    monkeypatch.setenv("MXNET_SLO_OBJECTIVE", "0.95")
    monkeypatch.setenv("MXNET_SLO_FAST_WINDOW", "30")
    monkeypatch.setenv("MXNET_SLO_SLOW_WINDOW", "300")
    monkeypatch.setenv("MXNET_SLO_BURN_ALERT", "7")
    monkeypatch.setenv("MXNET_SLO_MIN_EVENTS", "3")
    cfg = slo.SloConfig.from_env()
    assert cfg.ttft_ms == {"interactive": 123.0, "batch": 456.0}
    assert cfg.tpt_ms == {"interactive": 7.0, "batch": 77.0}
    assert cfg.budget == pytest.approx(0.05)
    assert (cfg.fast_window_s, cfg.slow_window_s) == (30.0, 300.0)
    assert (cfg.burn_alert, cfg.min_events) == (7.0, 3)
    # garbage raises naming the variable (the MXNET_CKPT_* pattern)
    monkeypatch.setenv("MXNET_SLO_OBJECTIVE", "1.5")
    with pytest.raises(mx.MXNetError, match="MXNET_SLO_OBJECTIVE"):
        slo.SloConfig.from_env()
    monkeypatch.setenv("MXNET_SLO_OBJECTIVE", "0.99")
    monkeypatch.setenv("MXNET_SLO_TPT_MS", "interactive=-1,batch=5")
    with pytest.raises(mx.MXNetError, match="MXNET_SLO_TPT_MS"):
        slo.SloConfig.from_env()


# ---------------------------------------------------------------------------
# canary prober (unit: fake probe)
# ---------------------------------------------------------------------------


def test_canary_prober_books_metrics_and_failures():
    tr = slo.SloTracker(_cfg(), source="test")
    seen = []

    def probe(trace):
        seen.append(trace)
        if len(seen) == 2:
            raise RuntimeError("boom")  # a failed probe is a data point

    p = slo.CanaryProber(probe, 0.02, tracker=tr, name="test")
    deadline = time.time() + 10.0
    while len(seen) < 3 and time.time() < deadline:
        time.sleep(0.02)
    p.stop()
    assert len(seen) >= 3
    assert all(t is not None for t in seen)  # trace-stamped probes
    summ = profiler.metrics_summary()
    assert summ["counters"]["slo.canary_probes"] >= 3
    assert summ["counters"]["slo.canary_failures"] >= 1
    assert summ["histograms"]["slo.canary_ms"]["count"] >= 3
    # outcomes fed the availability objective (1 bad in the window)
    assert tr.burn_rate("interactive", "avail") > 0.0
    # statusz canary section reads the same counters
    st = tr.stats()
    assert st["canary"]["probes"] >= 3
    assert st["canary"]["failures"] >= 1
    assert st["canary"]["p50_ms"] is not None


def test_canary_prober_rejects_zero_interval():
    with pytest.raises(mx.MXNetError, match="canary interval"):
        slo.CanaryProber(lambda trace: None, 0.0)


# ---------------------------------------------------------------------------
# engine integration (real decode path)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    return tiny_lm_params()


def test_cost_records_conserve_engine_counters(lm):
    """The tentpole reconciliation contract: across a mixed
    prefix-hit + speculative + chunked-prefill run, the per-stream
    cost records sum EXACTLY (==, not approx) to the engine counters
    for tokens / prefill_tokens / cow_copies — both sides increment
    at the same program points, so any drift is a wiring bug."""
    shared = np.arange(1, 9, dtype=np.int32)        # 2 full blocks
    pa = np.concatenate([shared, [11, 12, 13]]).astype(np.int32)
    pb = np.concatenate([shared, [21, 22]]).astype(np.int32)
    with _engine(lm, cache_blocks=12, prefix_cache=1, spec_tokens=2,
                 prefill_chunk=4) as eng:
        eng.generate(pa, 4)                         # miss (chunked)
        eng.generate(pb, 4, slo_class="batch")      # suffix-only hit
        eng.generate(shared, 4)                     # full hit → COW
        recs = eng.cost_records()
        st = eng.stats()
    assert len(recs) == 3
    assert sum(r["tokens"] for r in recs) == st["tokens"]
    assert sum(r["prefill_tokens"] for r in recs) \
        == st["prefill_tokens"]
    assert sum(r["cow_copies"] for r in recs) == st["cow_copies"]
    assert st["cow_copies"] >= 1                    # the run COWed
    assert sum(r["spec_accepted"] for r in recs) == st["spec_accepted"]
    # d2h: records attribute one sync per DELIVERED step per stream;
    # with sequential single-stream traffic that equals the engine's
    # per-program count (a batch of riders shares one fetch)
    assert sum(r["d2h_syncs"] for r in recs) == st["d2h_syncs"]
    # per-record shape: prompt accounting + live resource integrals
    assert [r["prompt_tokens"] for r in recs] == [11, 10, 8]
    assert [r["slo_class"] for r in recs] == ["interactive", "batch",
                                              "interactive"]
    for r in recs:
        assert r["tokens"] >= 4 and r["decode_steps"] >= 1
        assert r["page_s"] > 0.0 and r["wall_s"] > 0.0
        assert not r["canary"]
    # the by-class aggregation in stats() carries the same sums
    by_cls = st["cost_by_class"]
    assert by_cls["interactive"]["requests"] == 2
    assert by_cls["batch"]["requests"] == 1
    assert by_cls["interactive"]["tokens"] \
        + by_cls["batch"]["tokens"] == st["tokens"]
    # ... and the Reporter-visible slo.cost.* counters agree
    c = profiler.metrics_summary()["counters"]
    assert c["slo.cost.interactive.tokens"] \
        + c["slo.cost.batch.tokens"] == st["tokens"]


def test_engine_rejects_unknown_slo_class(lm):
    with _engine(lm) as eng:
        with pytest.raises(mx.MXNetError, match="unknown SLO class"):
            eng.generate(np.arange(1, 5, dtype=np.int32), 2,
                         slo_class="gold")


def test_engine_canary_excluded_from_request_counters(lm, monkeypatch):
    """With MXNET_CANARY_INTERVAL set the engine probes itself
    through the full submit path, yet ``requests`` counts ONLY the 2
    real generations while ``slo.canary_*`` proves probes ran."""
    monkeypatch.setenv("MXNET_CANARY_INTERVAL", "0.05")
    monkeypatch.setenv("MXNET_CANARY_TOKENS", "2")
    with _engine(lm) as eng:
        eng.generate(np.arange(1, 6, dtype=np.int32), 3)
        eng.generate(np.arange(2, 7, dtype=np.int32), 3)
        deadline = time.time() + 15.0
        while time.time() < deadline:
            summ = profiler.metrics_summary()
            if summ["counters"].get("slo.canary_probes", 0) >= 1:
                break
            time.sleep(0.05)
        st = eng.stats()
        recs = eng.cost_records()
    assert summ["counters"]["slo.canary_probes"] >= 1
    assert st["requests"] == 2            # canaries excluded
    assert st["generations"] >= 3         # ... but they DID decode
    # canary cost records are flagged (quota layers can drop them)
    assert any(r["canary"] for r in recs)


def test_slow_rank_alert_fires_before_conviction(lm, monkeypatch,
                                                 tmp_path):
    """THE timing contract: an injected per-step latency fault
    (MXNET_CHAOS_SLOW_RANK) trips the fast-window burn alert in
    seconds — long before MXNET_DEAD_RANK_TIMEOUT could convict the
    replica, which never stops heartbeating.  The alert lands in the
    tracker, /statusz and a flight-recorder dump."""
    monkeypatch.setenv("MXNET_CHAOS_SLOW_RANK", "0.12")
    monkeypatch.setenv("MXNET_SLO_TPT_MS", "interactive=5,batch=50")
    monkeypatch.setenv("MXNET_SLO_MIN_EVENTS", "4")
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    slo.reset_tracker()
    chaos.reset_chaos()
    t_fault = time.perf_counter()
    with _engine(lm) as eng:
        eng.generate(np.arange(1, 6, dtype=np.int32), 8)
        tracker = slo.get_tracker()
        tracker.check()
        assert tracker.alert_active()
        alert = list(tracker.alerts)[-1]
    t_alert = alert.monotonic_s
    assert alert.metric == "tpt"
    assert alert.burn_rate >= tracker.config.burn_alert
    # the whole point: alert latency << the conviction window
    assert t_alert - t_fault < dead_rank_timeout()
    assert t_alert - t_fault < 30.0
    # surfaced in the statusz section ...
    st = tracker.stats()
    assert st["alerts_recent"]
    assert st["alerts_recent"][-1]["metric"] == "tpt"
    # ... and in a flight-recorder dump tagged with the alert
    dumps = list(tmp_path.iterdir())
    assert dumps, "slo_alert flight-recorder dump missing"
    assert any("slo_alert" in d.name for d in dumps)
