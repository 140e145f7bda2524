"""Wave ledger: a lock-cheap ring of the last N dispatched waves.

The flight recorder (flightrec.py) answers "where did THIS request's
milliseconds go"; the ledger answers the dual question — "what did the
WAVE this request rode look like": how many slots, how long the window
held them, how long the device took, how many were answered by the
cache/singleflight/Leopard short-circuits instead of the BFS, and which
requests dragged the tail.  The coalescer records one entry per wave
(`CoalescingEngine._serve`), the device engines supply the counter and
phase deltas, and the two views cross-link both directions: flight
recorder entries already carry ``wave=``, and each ledger entry carries
the traceparents of its slowest member requests.

Served at ``GET /debug/waves`` on the metrics port and by
``keto-tpu status --debug``.  Recording happens on the single coalescer
worker thread, so the ring needs a lock only to keep ``snapshot`` (a
scrape-path read) consistent — the hot path takes it once per WAVE, not
per request.
"""

from __future__ import annotations

import math
import threading
from collections import deque
from typing import Dict, List, Optional


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank (ceiling) percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = math.ceil(q * (len(sorted_vals) - 1))
    return sorted_vals[min(len(sorted_vals) - 1, max(0, idx))]


class WaveLedger:
    """Ring of per-wave dispatch records + monotonic wave-id source."""

    def __init__(self, capacity: int = 256):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._next_id = 0
        self.recorded = 0  # total waves ever recorded (ring evicts)

    def next_wave_id(self) -> int:
        """Monotonic wave id — the same id flight-recorder entries carry
        as ``wave=``, so the two debug views join on it."""
        with self._lock:
            self._next_id += 1
            return self._next_id

    def record(self, entry: Dict) -> None:
        """File one wave's record (called once per wave, coalescer thread)."""
        with self._lock:
            self._ring.append(dict(entry))
            self.recorded += 1

    def snapshot(self, n: Optional[int] = None,
                 wave: Optional[int] = None) -> List[Dict]:
        """Newest-first wave records; ``wave`` filters to one id."""
        with self._lock:
            out = [dict(e) for e in reversed(self._ring)]
        if wave is not None:
            out = [e for e in out if e.get("wave") == wave]
        if n is not None:
            out = out[: max(0, int(n))]
        return out

    def stats(self) -> Dict:
        """Occupancy/wait aggregates over the current ring — the serving
        bench's before/after baseline for the batching-efficiency work."""
        with self._lock:
            entries = list(self._ring)
            recorded = self.recorded
        sizes = sorted(float(e.get("size", 0)) for e in entries)
        waits = sorted(
            float(e.get("window_wait_ms_p50", 0.0)) for e in entries
        )
        devs = sorted(float(e.get("device_ms", 0.0)) for e in entries)
        n = len(entries)
        # fused tiered dispatch (engine/fused.py): ring-wide sums of the
        # per-wave deltas; `fused_waves == fused_d2h_fetches` IS the
        # single-fetch-per-wave invariant the serving bench asserts
        fused_waves = fused_d2h = fused_gen_rows = fused_gen_lanes = 0
        fused_tiers: Dict[str, int] = {}
        # multi-host mesh: ring-wide sums of each wave's per-peer
        # shipped-row deltas — how much of the recent window crossed DCN
        peer_rows: Dict[str, int] = {}
        for e in entries:
            f = e.get("fused") or {}
            fused_waves += int(f.get("waves", 0))
            fused_d2h += int(f.get("d2h_fetches", 0))
            fused_gen_rows += int(f.get("general_rows", 0))
            fused_gen_lanes += int(f.get("general_lanes", 0))
            for t, d in (f.get("tiers") or {}).items():
                fused_tiers[t] = fused_tiers.get(t, 0) + int(d)
            for h, d in (e.get("peers") or {}).items():
                peer_rows[h] = peer_rows.get(h, 0) + int(d)
        return {
            "waves_recorded": recorded,
            "waves_in_ring": n,
            "wave_size_mean": round(sum(sizes) / n, 3) if n else 0.0,
            "wave_size_p50": _percentile(sizes, 0.50),
            "wave_size_p95": _percentile(sizes, 0.95),
            "window_wait_ms_p50": round(_percentile(waits, 0.50), 3),
            "window_wait_ms_p95": round(_percentile(waits, 0.95), 3),
            "device_ms_p50": round(_percentile(devs, 0.50), 3),
            "device_ms_p95": round(_percentile(devs, 0.95), 3),
            "fused_waves": fused_waves,
            "fused_d2h_fetches": fused_d2h,
            "fused_general_rows": fused_gen_rows,
            "fused_general_lanes": fused_gen_lanes,
            "fused_tier_rows": fused_tiers,
            "peer_rows": peer_rows,
        }
