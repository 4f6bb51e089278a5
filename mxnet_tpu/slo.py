"""SLO engine: objectives, multi-window burn rates, canaries, cost.

The fleet is traceable per-request (PR 12) and accountable per-FLOP,
but nothing *judges* it.  This module adds the judgment layer the
role-aware admission and tenant-quota roadmap items presuppose:

* **Objectives + burn-rate tracking** (:class:`SloTracker`) —
  declared targets for TTFT, time-per-token and availability per SLO
  class (``interactive``/``batch``), each tracked over a fast
  (~1 min) and a slow (~10 min) rolling window, SRE-style.  A window's
  *burn rate* is its bad-event fraction divided by the error budget
  (``1 - objective``): burn 1.0 spends the budget exactly on
  schedule, burn 10 spends it 10x too fast.  A sustained fast-window
  burn above ``MXNET_SLO_BURN_ALERT`` raises a typed
  :class:`SloAlert` — surfaced in /statusz, the ``slo.*`` gauges,
  fleet_top, and a rate-limited flight-recorder dump — designed to
  fire *minutes before* the heartbeat conviction window
  (``MXNET_DEAD_RANK_TIMEOUT``) would: a slow replica still
  heartbeats, so conviction alone never catches it.

* **Synthetic canary probes** (:class:`CanaryProber`) — a low-rate
  background client sending known-cost, trace-stamped probes through
  the full admission→prefill→decode→deliver path, so availability and
  latency stay observable at zero traffic.  Canary results are
  EXCLUDED from the request counters (``serving.requests`` /
  ``fleet.requests``) but exported as ``slo.canary_*`` metrics and
  fed to the availability objective.

* **Per-request cost attribution** (:class:`CostRecord`) — every
  retired ``DecodeEngine`` stream emits one record (prompt/prefill
  tokens, uncached-suffix tokens, decode steps, accepted speculative
  tokens, COW copies, page-seconds held, D2H syncs, estimated FLOPs
  from the executable's own XLA cost analysis — the PR-12 surface
  ``training.mfu`` uses), aggregated by SLO class in the engine's
  ``stats()`` and exported through the Reporter via ``slo.cost.*``
  counters.  Records mirror the engine counters at the SAME
  increment sites, so ``sum(records) == engine counters`` holds
  exactly for tokens / prefill_tokens / cow_copies.

All ``MXNET_SLO_*`` / ``MXNET_CANARY_*`` knobs resolve through the
config catalog with loud at-construction validation (the
MXNET_CKPT_* pattern): garbage, negative values, or an unknown SLO
class raise naming the variable.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from .base import MXNetError

__all__ = ["SLO_CLASSES", "SloConfig", "SloAlert", "SloTracker",
           "CanaryProber", "CostRecord", "get_tracker", "reset_tracker"]

_log = logging.getLogger("mxnet_tpu.slo")

#: The declared SLO classes.  ``interactive`` is the default for any
#: request that does not name one; ``batch`` trades latency for
#: throughput.  A request naming anything else raises loudly.
SLO_CLASSES = ("interactive", "batch")

#: Latency metrics an objective can target (availability rides along
#: as the third objective, fed by canary/delivery outcomes).
_LATENCY_METRICS = ("ttft", "tpt")


def check_class(slo_class: str) -> str:
    """Validate a request's SLO class (loudly, naming the choices)."""
    if slo_class not in SLO_CLASSES:
        raise MXNetError(
            f"unknown SLO class {slo_class!r}: expected one of "
            f"{SLO_CLASSES}")
    return slo_class


# ---------------------------------------------------------------------------
# configuration (env-driven, loudly validated)
# ---------------------------------------------------------------------------


def _env(name: str, minimum=None, maximum=None):
    """The shared validated reader (elastic's MXNET_CKPT_* pattern)."""
    from .elastic import _validated_env

    return _validated_env(name, minimum=minimum, maximum=maximum)


def _parse_class_map(name: str, raw, minimum: float) -> Dict[str, float]:
    """Parse ``interactive=250,batch=5000`` into a per-class map.

    Every declared class must appear; unknown classes, garbage or
    sub-``minimum`` values raise naming the variable."""
    out: Dict[str, float] = {}
    for part in str(raw).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise MXNetError(
                f"invalid {name}={raw!r}: expected "
                f"'class=value,...' (e.g. 'interactive=250,batch=5000')")
        cls, _, val = part.partition("=")
        cls = cls.strip()
        if cls not in SLO_CLASSES:
            raise MXNetError(
                f"invalid {name}={raw!r}: unknown SLO class {cls!r} "
                f"(expected one of {SLO_CLASSES})")
        try:
            v = float(val)
        except ValueError:
            raise MXNetError(
                f"invalid {name}={raw!r}: {val!r} is not a number")
        if v < minimum:
            raise MXNetError(
                f"invalid {name}={raw!r}: {cls}={v} must be >= "
                f"{minimum}")
        out[cls] = v
    missing = [c for c in SLO_CLASSES if c not in out]
    if missing:
        raise MXNetError(
            f"invalid {name}={raw!r}: missing SLO class(es) {missing}")
    return out


class SloConfig:
    """Validated objective set for one process.

    Parameters mirror the env knobs; passing them explicitly (tests,
    embedded engines) skips the env entirely.  ``ttft_ms``/``tpt_ms``
    are per-class latency targets; ``objective`` is the fraction of
    events that must be good (one value for every class/metric —
    per-class objectives can split later without changing callers)."""

    def __init__(self, ttft_ms: Dict[str, float],
                 tpt_ms: Dict[str, float], objective: float,
                 fast_window_s: float, slow_window_s: float,
                 burn_alert: float, min_events: int = 10):
        if not 0.0 < objective < 1.0:
            raise MXNetError(
                f"SLO objective {objective} must be in (0, 1) — 1.0 "
                "leaves a zero error budget (burn rate undefined)")
        if slow_window_s <= fast_window_s:
            raise MXNetError(
                f"slow window {slow_window_s}s must exceed the fast "
                f"window {fast_window_s}s (multi-window burn rates)")
        self.ttft_ms = {c: float(ttft_ms[c]) for c in SLO_CLASSES}
        self.tpt_ms = {c: float(tpt_ms[c]) for c in SLO_CLASSES}
        self.objective = float(objective)
        self.budget = 1.0 - self.objective
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = float(slow_window_s)
        self.burn_alert = float(burn_alert)
        self.min_events = int(min_events)

    def target_ms(self, slo_class: str, metric: str) -> Optional[float]:
        if metric == "ttft":
            return self.ttft_ms[slo_class]
        if metric == "tpt":
            return self.tpt_ms[slo_class]
        return None  # availability has no latency target

    @classmethod
    def from_env(cls) -> "SloConfig":
        return cls(
            ttft_ms=_parse_class_map(
                "MXNET_SLO_TTFT_MS", _env("MXNET_SLO_TTFT_MS"),
                minimum=0.001),
            tpt_ms=_parse_class_map(
                "MXNET_SLO_TPT_MS", _env("MXNET_SLO_TPT_MS"),
                minimum=0.001),
            objective=_env("MXNET_SLO_OBJECTIVE", minimum=0.0,
                           maximum=0.9999),
            fast_window_s=_env("MXNET_SLO_FAST_WINDOW", minimum=1.0),
            slow_window_s=_env("MXNET_SLO_SLOW_WINDOW", minimum=2.0),
            burn_alert=_env("MXNET_SLO_BURN_ALERT", minimum=1.0),
            min_events=_env("MXNET_SLO_MIN_EVENTS", minimum=1))


# ---------------------------------------------------------------------------
# rolling windows + burn rates
# ---------------------------------------------------------------------------


class _Window:
    """Rolling (timestamp, good) event window; O(1) amortized."""

    __slots__ = ("span_s", "events", "bad")

    def __init__(self, span_s: float):
        self.span_s = float(span_s)
        self.events: Deque[Tuple[float, bool]] = collections.deque()
        self.bad = 0

    def add(self, t: float, good: bool, n: int = 1):
        self.events.extend(((t, good),) * n)
        if not good:
            self.bad += n
        self.prune(t)

    def prune(self, now: float):
        cutoff = now - self.span_s
        ev = self.events
        while ev and ev[0][0] < cutoff:
            _, good = ev.popleft()
            if not good:
                self.bad -= 1

    @property
    def total(self) -> int:
        return len(self.events)

    def bad_fraction(self) -> float:
        n = len(self.events)
        return self.bad / n if n else 0.0


class SloAlert:
    """One typed burn-rate alert: which objective, how fast the budget
    is burning, and over which window.  ``as_dict()`` is what lands in
    /statusz and the flight-recorder dump."""

    __slots__ = ("slo_class", "metric", "window", "burn_rate",
                 "threshold", "budget_remaining", "wall_time_s",
                 "monotonic_s", "message")

    def __init__(self, slo_class: str, metric: str, window: str,
                 burn_rate: float, threshold: float,
                 budget_remaining: float):
        self.slo_class = slo_class
        self.metric = metric
        self.window = window
        self.burn_rate = burn_rate
        self.threshold = threshold
        self.budget_remaining = budget_remaining
        self.wall_time_s = time.time()
        self.monotonic_s = time.perf_counter()
        self.message = (
            f"SLO burn: {slo_class}/{metric} burning "
            f"{burn_rate:.1f}x budget over the {window} window "
            f"(alert threshold {threshold:g}; "
            f"{budget_remaining:.0%} of budget remaining)")

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


class SloTracker:
    """Multi-window burn-rate engine for one process.

    Feed it latency observations (:meth:`observe_ttft` /
    :meth:`observe_tpt`) and availability outcomes
    (:meth:`observe_avail`); read burn rates, budget gauges and typed
    alerts back.  Every observation prunes its windows and a
    throttled alert check runs inline (cheap: deque arithmetic), so
    there is no poller thread to leak.

    Alert semantics: when a (class, metric) fast window holds at
    least ``min_events`` events and its burn rate crosses
    ``burn_alert``, ONE :class:`SloAlert` fires — gauge flip, log
    line, rate-limited flight-recorder dump — and the pair re-arms
    only after burn falls below half the threshold (hysteresis, no
    flap storm)."""

    def __init__(self, config: Optional[SloConfig] = None,
                 source: str = "engine"):
        self.config = config if config is not None \
            else SloConfig.from_env()
        self.source = source
        self._lock = threading.Lock()
        self._windows: Dict[Tuple[str, str, str], _Window] = {}
        for cls in SLO_CLASSES:
            for metric in _LATENCY_METRICS + ("avail",):
                self._windows[(cls, metric, "fast")] = _Window(
                    self.config.fast_window_s)
                self._windows[(cls, metric, "slow")] = _Window(
                    self.config.slow_window_s)
        self._alerting: Dict[Tuple[str, str], SloAlert] = {}
        self.alerts: Deque[SloAlert] = collections.deque(maxlen=64)
        self._last_check = 0.0

    # -- observation ----------------------------------------------------
    def observe_ttft(self, slo_class: str, ms: float, now=None):
        self._observe(slo_class, "ttft", ms, now)

    def observe_tpt(self, slo_class: str, ms: float, now=None, n=1):
        """``n`` tokens of one cadence (a decode step's rows of one
        class) are booked at once."""
        self._observe(slo_class, "tpt", ms, now, n)

    def observe_avail(self, slo_class: str, ok: bool, now=None):
        """One delivery outcome (real request or canary probe)."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            for w in ("fast", "slow"):
                self._windows[(slo_class, "avail", w)].add(now, bool(ok))
        self._maybe_check(now)

    def _observe(self, slo_class: str, metric: str, ms: float, now,
                 n: int = 1):
        target = self.config.target_ms(slo_class, metric)
        good = ms <= target
        now = time.perf_counter() if now is None else now
        with self._lock:
            for w in ("fast", "slow"):
                self._windows[(slo_class, metric, w)].add(now, good, n)
        self._maybe_check(now)

    # -- readout --------------------------------------------------------
    def burn_rate(self, slo_class: str, metric: str,
                  window: str = "fast", now=None) -> float:
        """Bad-event fraction over the window / the error budget."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            win = self._windows[(slo_class, metric, window)]
            win.prune(now)
            return win.bad_fraction() / self.config.budget

    def budget_remaining(self, slo_class: str, metric: str,
                         now=None) -> float:
        """1.0 = untouched budget, 0.0 = spent (slow window's view);
        clamped at 0 — the gauge reports exhaustion, not debt."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            win = self._windows[(slo_class, metric, "slow")]
            win.prune(now)
            if not win.total:
                return 1.0
            return max(0.0, 1.0 - win.bad_fraction()
                       / self.config.budget)

    # -- alerting -------------------------------------------------------
    def _maybe_check(self, now: float):
        # throttle the full scan; observations are per-token hot
        if now - self._last_check < 0.2:
            return
        self._last_check = now
        self.check(now)

    def check(self, now=None) -> List[SloAlert]:
        """Scan every (class, metric) pair; fire/clear alerts.  Returns
        the alerts that FIRED on this call."""
        from . import profiler

        now = time.perf_counter() if now is None else now
        fired: List[SloAlert] = []
        cleared: List[Tuple[str, str]] = []
        with self._lock:
            for cls in SLO_CLASSES:
                for metric in _LATENCY_METRICS + ("avail",):
                    fast = self._windows[(cls, metric, "fast")]
                    fast.prune(now)
                    burn = fast.bad_fraction() / self.config.budget
                    profiler.set_gauge(
                        f"slo.burn_rate.{cls}.{metric}", round(burn, 4))
                    slow = self._windows[(cls, metric, "slow")]
                    slow.prune(now)
                    remaining = 1.0 if not slow.total else max(
                        0.0, 1.0 - slow.bad_fraction()
                        / self.config.budget)
                    profiler.set_gauge(
                        f"slo.budget_remaining.{cls}.{metric}",
                        round(remaining, 4))
                    key = (cls, metric)
                    active = self._alerting.get(key)
                    if active is None:
                        if (fast.total >= self.config.min_events
                                and burn >= self.config.burn_alert):
                            alert = SloAlert(cls, metric, "fast", burn,
                                             self.config.burn_alert,
                                             remaining)
                            self._alerting[key] = alert
                            self.alerts.append(alert)
                            fired.append(alert)
                    elif burn < self.config.burn_alert / 2.0:
                        cleared.append(key)
                        del self._alerting[key]
            profiler.set_gauge("slo.alerts_active", len(self._alerting))
        # side effects OUTSIDE the lock (the dump serializes the ring)
        for alert in fired:
            _log.warning("[slo] %s", alert.message)
            profiler.inc_counter("slo.alerts")
            profiler.dump_flight_record(
                "slo_alert", extra=alert.as_dict())
        for cls, metric in cleared:
            _log.info("[slo] %s/%s burn back under %.1f: alert cleared",
                      cls, metric, self.config.burn_alert / 2.0)
        return fired

    def alert_active(self) -> bool:
        with self._lock:
            return bool(self._alerting)

    # -- statusz --------------------------------------------------------
    def stats(self) -> dict:
        """The /statusz ``slo`` section (fleet_top reads this)."""
        from . import profiler

        now = time.perf_counter()
        classes: Dict[str, dict] = {}
        worst = None
        with self._lock:
            for cls in SLO_CLASSES:
                sec: Dict[str, dict] = {}
                for metric in _LATENCY_METRICS + ("avail",):
                    fast = self._windows[(cls, metric, "fast")]
                    fast.prune(now)
                    slow = self._windows[(cls, metric, "slow")]
                    slow.prune(now)
                    burn = fast.bad_fraction() / self.config.budget
                    remaining = 1.0 if not slow.total else max(
                        0.0, 1.0 - slow.bad_fraction()
                        / self.config.budget)
                    sec[metric] = {
                        "target_ms": self.config.target_ms(cls, metric),
                        "objective": self.config.objective,
                        "fast_burn": round(burn, 4),
                        "slow_burn": round(
                            slow.bad_fraction() / self.config.budget,
                            4),
                        "budget_remaining": round(remaining, 4),
                        "events_fast": fast.total,
                    }
                    if fast.total and (worst is None
                                       or burn > worst["fast_burn"]):
                        worst = {"class": cls, "metric": metric,
                                 "fast_burn": round(burn, 4),
                                 "budget_remaining": round(remaining,
                                                           4)}
                classes[cls] = sec
            active = [a.as_dict() for a in self._alerting.values()]
            recent = [a.as_dict() for a in list(self.alerts)[-8:]]
        out = {
            "source": self.source,
            "objective": self.config.objective,
            "fast_window_s": self.config.fast_window_s,
            "slow_window_s": self.config.slow_window_s,
            "burn_alert": self.config.burn_alert,
            "classes": classes,
            "worst": worst,
            "alerts_active": active,
            "alerts_recent": recent,
        }
        # canary summary (fleet_top's CANP50 column): the probe
        # histogram lives in the GLOBAL registry so the Reporter and
        # /metrics export it with everything else
        summ = profiler.metrics_summary()
        h = summ["histograms"].get("slo.canary_ms")
        out["canary"] = {
            "probes": int(summ["counters"].get("slo.canary_probes", 0)),
            "failures": int(summ["counters"].get(
                "slo.canary_failures", 0)),
            "p50_ms": h["p50"] if h else None,
        }
        return out


# ---------------------------------------------------------------------------
# process-wide tracker (engine + router share one judgment surface)
# ---------------------------------------------------------------------------

_TRACKER: Optional[SloTracker] = None
_TRACKER_LOCK = threading.Lock()


def get_tracker() -> SloTracker:
    """The process-wide tracker, built from the env on first use and
    registered as the ``slo`` /statusz section.  Engine and Router in
    one process share it — one process, one judgment surface."""
    global _TRACKER
    with _TRACKER_LOCK:
        if _TRACKER is None:
            from . import profiler

            _TRACKER = SloTracker(SloConfig.from_env())
            profiler.register_statusz("slo", _TRACKER.stats)
        return _TRACKER


def reset_tracker() -> None:
    """Drop the cached tracker (tests re-read the env)."""
    global _TRACKER
    with _TRACKER_LOCK:
        _TRACKER = None


# ---------------------------------------------------------------------------
# synthetic canary prober
# ---------------------------------------------------------------------------


class CanaryProber:
    """Low-rate background client: one known-cost, trace-stamped probe
    every ``interval_s`` through the caller-supplied ``probe``
    callable (the full admission→prefill→decode→deliver path of an
    engine or a Router).

    ``probe(trace)`` performs ONE probe synchronously and returns
    nothing; an exception marks the probe failed.  Results are
    excluded from the request counters by the submitting tier (the
    ``canary=True`` flag) and exported here as ``slo.canary_probes`` /
    ``slo.canary_failures`` counters plus the ``slo.canary_ms``
    latency histogram; each outcome also feeds the tracker's
    availability objective and its latency is booked as a TTFT-class
    observation (a probe IS a request — that is the point)."""

    def __init__(self, probe: Callable, interval_s: float,
                 tracker: Optional[SloTracker] = None,
                 slo_class: str = "interactive",
                 name: str = "canary", book_latency: bool = True):
        #: ``book_latency=False`` for tiers whose serving path already
        #: feeds the tracker per-probe (the engine books real TTFT/TPT
        #: for canary streams; booking the probe wall again would
        #: double-count) — the Router's prober keeps the default.
        if interval_s <= 0:
            raise MXNetError(
                f"canary interval {interval_s} must be > 0 (0/unset "
                "disables the prober at the call site instead)")
        self._probe = probe
        self._interval = float(interval_s)
        self._tracker = tracker
        self._book_latency = bool(book_latency)
        self._class = check_class(slo_class)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"mxnet_tpu-slo-{name}")
        self._thread.start()

    def _loop(self):
        from . import profiler

        n = 0
        while not self._stop.wait(self._interval):
            n += 1
            trace = profiler.make_trace(key=-n)  # stamped, negative
            t0 = time.perf_counter()             # keyspace: no tid clash
            ok = True
            try:
                self._probe(trace)
            except Exception as exc:  # noqa: BLE001 — a failed probe
                ok = False            # is a DATA POINT, not a crash
                _log.warning("[slo] canary probe failed: %r", exc)
            ms = (time.perf_counter() - t0) * 1e3
            profiler.inc_counter("slo.canary_probes")
            if not ok:
                profiler.inc_counter("slo.canary_failures")
            profiler.observe("slo.canary_ms", ms)
            if self._tracker is not None:
                self._tracker.observe_avail(self._class, ok)
                if ok and self._book_latency:
                    self._tracker.observe_ttft(self._class, ms)

    def stop(self, timeout: float = 5.0):
        self._stop.set()
        self._thread.join(timeout=timeout)


def canary_interval_s() -> float:
    """``MXNET_CANARY_INTERVAL`` (seconds; 0/unset = prober off)."""
    return float(_env("MXNET_CANARY_INTERVAL", minimum=0.0))


def canary_tokens() -> int:
    """``MXNET_CANARY_TOKENS``: probe decode length (known cost)."""
    return int(_env("MXNET_CANARY_TOKENS", minimum=1))


def canary_prompt(vocab_size: int, n: int = 4) -> np.ndarray:
    """The fixed probe prompt: deterministic, in-vocab, tiny."""
    return (np.arange(n, dtype=np.int32) % max(int(vocab_size), 1))


# ---------------------------------------------------------------------------
# per-request cost attribution
# ---------------------------------------------------------------------------

#: Additive cost fields — every key sums across records and (for the
#: starred ones) reconciles EXACTLY with the engine counters because
#: both sides increment at the same program points:
#: tokens*, prefill_tokens*, cow_copies*, migration_bytes*,
#: migration_ms*.
COST_FIELDS = ("prompt_tokens", "prefill_tokens", "tokens",
               "decode_steps", "spec_accepted", "cow_copies",
               "d2h_syncs", "page_s", "flops_est",
               "migration_bytes", "migration_ms")


class CostRecord:
    """Mutable per-stream cost accumulator → one retired record.

    The engine books into it at the SAME sites it books its own
    counters (prefill completion, step absorption, COW probe), so the
    conservation property is structural, not statistical."""

    __slots__ = ("sid", "slo_class", "canary", "tenant", "adapter_id",
                 "t_submit", "t_retired", "pg_t") + COST_FIELDS

    def __init__(self, sid: int, slo_class: str, canary: bool,
                 tenant: Optional[str] = None,
                 adapter_id: Optional[str] = None):
        self.sid = sid
        self.slo_class = slo_class
        self.canary = canary
        # tenancy identity (PR 20): stamped at submit, mirrored into
        # every retired record at the SAME sites as the class fields,
        # so per-tenant sums conserve exactly like per-class sums do
        self.tenant = tenant
        self.adapter_id = adapter_id
        self.t_submit = time.perf_counter()
        self.t_retired = 0.0
        self.pg_t = self.t_submit  # last page-count booking time
        for f in COST_FIELDS:
            setattr(self, f, 0.0 if f in ("page_s", "flops_est",
                                          "migration_ms")
                    else 0)

    def book_pages(self, n_pages: int, now: Optional[float] = None):
        """Integrate page-seconds: ``n_pages`` held since the last
        booking.  Call BEFORE every block-table mutation."""
        now = time.perf_counter() if now is None else now
        if n_pages > 0:
            self.page_s += n_pages * (now - self.pg_t)
        self.pg_t = now

    def as_dict(self) -> dict:
        d = {f: getattr(self, f) for f in COST_FIELDS}
        d["page_s"] = round(d["page_s"], 6)
        d["migration_ms"] = round(d["migration_ms"], 6)
        d.update(sid=self.sid, slo_class=self.slo_class,
                 canary=self.canary, tenant=self.tenant,
                 adapter_id=self.adapter_id,
                 wall_s=round(self.t_retired - self.t_submit, 6))
        return d


class CostAggregator:
    """Per-class running sums of retired records + a bounded tail of
    raw records (tests and debugging read it).  Also exports the sums
    as global ``slo.cost.<class>.<field>`` counters so the Reporter's
    JSONL and /metrics carry them without extra plumbing."""

    def __init__(self, keep: int = 1024):
        self._lock = threading.Lock()
        self._by_class: Dict[str, Dict[str, float]] = {}
        self._by_tenant: Dict[str, Dict[str, float]] = {}
        self.records: Deque[dict] = collections.deque(maxlen=keep)

    def add(self, rec: CostRecord):
        from . import profiler

        rec.t_retired = time.perf_counter()
        d = rec.as_dict()
        with self._lock:
            agg = self._by_class.setdefault(
                rec.slo_class, {f: 0.0 for f in COST_FIELDS})
            for f in COST_FIELDS:
                agg[f] += d[f]
            agg["requests"] = agg.get("requests", 0) + 1
            if rec.tenant is not None:
                # same increment site as the class sums: per-tenant
                # conservation is structural too
                tag = self._by_tenant.setdefault(
                    rec.tenant, {f: 0.0 for f in COST_FIELDS})
                for f in COST_FIELDS:
                    tag[f] += d[f]
                tag["requests"] = tag.get("requests", 0) + 1
            self.records.append(d)
        for f in ("tokens", "prefill_tokens", "flops_est", "page_s"):
            if d[f]:
                profiler.inc_counter(
                    f"slo.cost.{rec.slo_class}.{f}", d[f])

    def by_class(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {c: {k: (round(v, 6) if isinstance(v, float) else v)
                        for k, v in agg.items()}
                    for c, agg in self._by_class.items()}

    def by_tenant(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant sums of retired records (only streams submitted
        with a tenant appear; same fields as :meth:`by_class`)."""
        with self._lock:
            return {t: {k: (round(v, 6) if isinstance(v, float) else v)
                        for k, v in agg.items()}
                    for t, agg in self._by_tenant.items()}

    def reset(self):
        with self._lock:
            self._by_class.clear()
            self._by_tenant.clear()
            self.records.clear()


def executable_flops(exe) -> float:
    """Estimated FLOPs of one compiled executable via its own XLA
    cost analysis (the PR-12 path ``training.mfu`` uses).  0.0 when
    the toolchain has no cost model — attribution degrades to the
    token counts, never breaks serving."""
    try:
        cost = exe.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        return float((cost or {}).get("flops", 0.0))
    except Exception:  # noqa: BLE001 — accounting never breaks serving
        return 0.0
