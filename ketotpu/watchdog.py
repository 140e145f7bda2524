"""Regression watchdog: push-on-regression over the pull-only diagnostics.

PR 6 (compile observatory, wave ledger, profiler) and PR 11 (trace
store, shadow plane) built deep diagnostic surfaces — but every one is
pull-only: an operator has to already suspect trouble to curl them.
This thread closes the loop.  Every ``observability.watchdog.interval_s``
it evaluates four rules over those surfaces:

* ``after_warm_compile`` — the compile observatory counted a backend
  compile after the engine declared itself warm;
* ``device_ms_drift`` — the wave ledger's device-ms p50 drifted more
  than ``drift_pct`` above a rolling baseline learned over the first
  ``baseline_waves`` waves (and re-learned after each incident);
* ``shadow_divergence`` — the shadow plane filed new divergence records
  since the last tick;
* ``burn_alarm`` — the SLO engine's fast-window burn rate crossed
  ``burn_threshold`` (error budget burning faster than N× sustainable).

A firing rule files a bounded incident record (``GET /debug/incidents``),
bumps ``keto_incidents_total{rule}``, and force-promotes the implicated
traces through the PR-11 :meth:`TraceStore.force_promote` hook — the
divergence's own trace ids when the shadow ledger names them, else the
slowest traceparents of the most recent waves — so the anatomy of the
regressing requests is preserved before the recent ring evicts them.
Level-triggered rules (drift, burn) are edge-filtered: one incident on
entering violation, re-armed only after the condition clears.

Config-gated (``auto_profile``), an incident also arms ONE automatic
profiler capture per ``profile_cooldown_s`` on a side thread —
``ProfilerDisabled``/``ProfilerBusy`` are swallowed; the watchdog never
throws, never blocks the serving path, and every rule evaluation is
wrapped so a diagnostics failure cannot kill the thread.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ketotpu.observability import parse_traceparent

INCIDENTS_METRIC = "keto_incidents_total"

RULES = (
    "after_warm_compile",
    "device_ms_drift",
    "shadow_divergence",
    "burn_alarm",
    "overload",
)

#: how many recent waves to mine for implicated traceparents when the
#: firing rule does not name trace ids itself
_IMPLICATE_WAVES = 4


class Watchdog:
    """Background rule evaluator + bounded incident log."""

    def __init__(
        self,
        registry,
        *,
        interval_s: float = 5.0,
        baseline_waves: int = 32,
        drift_pct: float = 75.0,
        incident_cap: int = 64,
        burn_threshold: float = 2.0,
        auto_profile: bool = False,
        profile_cooldown_s: float = 600.0,
        profile_seconds: float = 2.0,
    ):
        self._r = registry
        self.interval_s = max(0.25, float(interval_s))
        self.baseline_waves = max(1, int(baseline_waves))
        self.drift_pct = float(drift_pct)
        self.burn_threshold = float(burn_threshold)
        self.auto_profile = bool(auto_profile)
        self.profile_cooldown_s = float(profile_cooldown_s)
        self.profile_seconds = float(profile_seconds)
        self._lock = threading.Lock()
        self._incidents: deque = deque(maxlen=max(1, int(incident_cap)))
        self._next_id = 0
        self.ticks = 0
        # rule state
        self._primed = False
        self._seen_after_warm = 0
        self._seen_divergences = 0
        self._baseline_device_ms: Optional[float] = None
        self._baseline_samples = 0
        self._active: set = set()  # level-triggered rules currently firing
        self._last_profile: Optional[float] = None  # None = never captured
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        metrics = registry.metrics()
        if metrics is not None:
            # pre-register the vocabulary so `== 0` is provable on scrape
            for rule in RULES:
                metrics.counter(
                    INCIDENTS_METRIC, 0,
                    help="watchdog incidents filed by rule", rule=rule,
                )

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> "Watchdog":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="keto-watchdog", daemon=True
            )
            self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - diagnostics never crash
                pass

    # -- rule evaluation ------------------------------------------------------

    def tick(self, now: Optional[float] = None) -> List[Dict]:
        """Evaluate every rule once; returns the incidents filed (tests
        drive this directly for determinism)."""
        t = time.time() if now is None else float(now)
        with self._lock:
            self.ticks += 1
            first = not self._primed
            self._primed = True
        if first:
            # adopt the current counter floors: the compile observatory is
            # process-global and the shadow ledger may predate this
            # watchdog — what happened before arming is history, not a
            # regression
            self._prime()
            return []
        filed: List[Dict] = []
        for rule in (
            self._rule_after_warm_compile,
            self._rule_device_ms_drift,
            self._rule_shadow_divergence,
            self._rule_burn_alarm,
            self._rule_overload,
        ):
            try:
                inc = rule(t)
            except Exception:  # noqa: BLE001 - one broken surface must
                inc = None     # not mute the other rules
            if inc is not None:
                filed.append(inc)
        return filed

    def _prime(self) -> None:
        try:
            self._seen_after_warm = int(
                self._r.compile_watch().snapshot().get(
                    "compiles_after_warm", 0
                )
            )
        except Exception:  # noqa: BLE001
            pass
        try:
            shadow = self._r.shadow()
            if shadow is not None:
                self._seen_divergences = int(
                    getattr(shadow, "divergences", 0)
                )
        except Exception:  # noqa: BLE001
            pass
        try:
            slo = self._r.slo()
            if slo is not None:
                slo.sample()
        except Exception:  # noqa: BLE001
            pass

    def _rule_after_warm_compile(self, now: float) -> Optional[Dict]:
        watch = self._r.compile_watch()
        snap = watch.snapshot()
        n = int(snap.get("compiles_after_warm", 0))
        if n <= self._seen_after_warm:
            return None
        fresh = [
            {k: e.get(k) for k in ("fn", "signature", "duration_ms", "ts")}
            for e in snap.get("log", []) if e.get("after_warm")
        ][-(n - self._seen_after_warm):]
        self._seen_after_warm = n
        return self._file(
            "after_warm_compile", now,
            detail={"compiles_after_warm": n, "compiles": fresh},
            trace_ids=self._recent_wave_traces(),
        )

    def _rule_device_ms_drift(self, now: float) -> Optional[Dict]:
        stats = self._r.wave_ledger().stats()
        if int(stats.get("waves_in_ring", 0)) < 1:
            return None
        p50 = float(stats.get("device_ms_p50", 0.0))
        if (self._baseline_device_ms is None
                or self._baseline_samples < self.baseline_waves):
            # still learning: fold the observation into the baseline
            b = self._baseline_device_ms
            self._baseline_device_ms = (
                p50 if b is None else 0.9 * b + 0.1 * p50
            )
            self._baseline_samples += int(stats.get("waves_in_ring", 0))
            return None
        baseline = self._baseline_device_ms
        threshold = baseline * (1.0 + self.drift_pct / 100.0)
        if p50 <= threshold or baseline <= 0.0:
            self._active.discard("device_ms_drift")
            # healthy: keep tracking the slow trend
            self._baseline_device_ms = 0.95 * baseline + 0.05 * p50
            return None
        if "device_ms_drift" in self._active:
            return None
        self._active.add("device_ms_drift")
        return self._file(
            "device_ms_drift", now,
            detail={
                "device_ms_p50": p50,
                "baseline_ms": round(baseline, 3),
                "drift_pct_observed": round(
                    (p50 / baseline - 1.0) * 100.0, 1
                ),
                "drift_pct_threshold": self.drift_pct,
            },
            trace_ids=self._recent_wave_traces(),
        )

    def _rule_shadow_divergence(self, now: float) -> Optional[Dict]:
        shadow = self._r.shadow()
        if shadow is None:
            return None
        n = int(getattr(shadow, "divergences", 0))
        if n <= self._seen_divergences:
            return None
        fresh = shadow.ledger()[-(n - self._seen_divergences):]
        self._seen_divergences = n
        tids = [r.get("trace_id") for r in fresh if r.get("trace_id")]
        return self._file(
            "shadow_divergence", now,
            detail={
                "divergences_total": n,
                "records": [
                    {k: r.get(k) for k in (
                        "tuple", "served", "oracle", "tier", "wave",
                        "trace_id",
                    )} for r in fresh
                ],
            },
            trace_ids=tids or self._recent_wave_traces(),
        )

    def _rule_burn_alarm(self, now: float) -> Optional[Dict]:
        slo = self._r.slo()
        if slo is None:
            return None
        slo.sample()
        burn = slo.max_burn("fast")
        if burn < self.burn_threshold:
            self._active.discard("burn_alarm")
            return None
        if "burn_alarm" in self._active:
            return None
        self._active.add("burn_alarm")
        return self._file(
            "burn_alarm", now,
            detail={
                "fast_burn": round(burn, 4),
                "threshold": self.burn_threshold,
                "fast": slo.window_report(slo.fast_window_s),
            },
            trace_ids=self._recent_wave_traces(),
        )

    def _rule_overload(self, now: float) -> Optional[Dict]:
        """Edge-triggered on the overload plane leaving stage 0: one
        incident per brownout episode, cleared when the ladder returns
        to normal."""
        ov = self._r.overload()
        if ov is None or ov.stage < 1:
            self._active.discard("overload")
            return None
        if "overload" in self._active:
            return None
        self._active.add("overload")
        snap = {}
        try:
            snap = ov.snapshot()
        except Exception:  # noqa: BLE001
            pass
        return self._file(
            "overload", now,
            detail={
                "stage": ov.stage,
                "stage_name": snap.get("stage_name", ""),
                "admission": snap.get("admission", {}),
                "signals": snap.get("signals", {}),
            },
            trace_ids=self._recent_wave_traces(),
        )

    # -- incident plumbing ----------------------------------------------------

    def _recent_wave_traces(self) -> List[str]:
        """Trace ids of the slowest members of the most recent waves —
        the implicated anatomy when a rule has no trace ids of its own."""
        tids: List[str] = []
        try:
            waves = self._r.wave_ledger().snapshot(_IMPLICATE_WAVES)
        except Exception:  # noqa: BLE001
            return tids
        for w in waves:
            for s in w.get("slowest") or []:
                parsed = parse_traceparent(s.get("traceparent"))
                if parsed and parsed[0] not in tids:
                    tids.append(parsed[0])
        return tids

    def _file(self, rule: str, now: float, *, detail: Dict,
              trace_ids: List[str]) -> Dict:
        promoted: List[str] = []
        try:
            store = self._r.trace_store()
        except Exception:  # noqa: BLE001
            store = None
        if store is not None:
            for tid in trace_ids:
                try:
                    if store.force_promote(tid, f"incident:{rule}"):
                        promoted.append(tid)
                except Exception:  # noqa: BLE001
                    pass
        with self._lock:
            self._next_id += 1
            incident = {
                "id": self._next_id,
                "rule": rule,
                "ts": round(now, 3),
                "detail": detail,
                "trace_ids": trace_ids,
                "promoted": promoted,
            }
            self._incidents.append(incident)
        metrics = self._r.metrics()
        if metrics is not None:
            metrics.counter(
                INCIDENTS_METRIC, 1,
                help="watchdog incidents filed by rule", rule=rule,
            )
        logger = None
        log = getattr(self._r, "logger", None)
        if callable(log):
            try:
                logger = log()
            except Exception:  # noqa: BLE001
                logger = None
        if logger is not None:
            logger.warning(
                "watchdog incident #%d rule=%s traces=%s detail=%s",
                incident["id"], rule, trace_ids, detail,
            )
        incident["profile"] = self._maybe_profile(now)
        return incident

    def _maybe_profile(self, now: float) -> str:
        if not self.auto_profile:
            return "disabled"
        with self._lock:
            if (self._last_profile is not None
                    and now - self._last_profile < self.profile_cooldown_s):
                return "cooldown"
            self._last_profile = now

        def _capture():
            from ketotpu.profiler import ProfilerBusy, ProfilerDisabled

            try:
                self._r.profiler().capture(self.profile_seconds)
            except (ProfilerDisabled, ProfilerBusy):
                pass
            except Exception:  # noqa: BLE001 - best-effort evidence only
                pass

        threading.Thread(
            target=_capture, name="keto-watchdog-profile", daemon=True
        ).start()
        return "armed"

    # -- read side ------------------------------------------------------------

    def incidents(self, n: int = 0) -> List[Dict]:
        """Newest-first incident records (``GET /debug/incidents``)."""
        with self._lock:
            out = [dict(i) for i in reversed(self._incidents)]
        return out[:n] if n > 0 else out

    def stats(self) -> Dict:
        with self._lock:
            return {
                "ticks": self.ticks,
                "incidents_filed": self._next_id,
                "incidents_held": len(self._incidents),
                "interval_s": self.interval_s,
                "burn_threshold": self.burn_threshold,
                "drift_pct": self.drift_pct,
                "baseline_device_ms": (
                    round(self._baseline_device_ms, 3)
                    if self._baseline_device_ms is not None else None
                ),
                "auto_profile": self.auto_profile,
                "active_rules": sorted(self._active),
            }
