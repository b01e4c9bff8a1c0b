"""Per-epoch latency budget ledger: gapless churn-to-ack attribution.

Every convergence epoch (KvStore receive -> FIB ack) is decomposed into a
fixed, exhaustive taxonomy of components.  The ledger enforces a
*conservation invariant*: the attributed components plus the residual
``budget.unattributed_ms`` always sum to the measured end-to-end wall time
of the epoch.  A growing residual means the taxonomy rotted (a new stage
appeared that nobody stamps) and pages via its own drift SLO before the
per-component numbers start to mislead.

Mechanics
---------
An :class:`EpochBudget` is a cursor walking the epoch's wall clock: each
``advance(component)`` call attributes the segment ``[cursor, now]`` to
that component and moves the cursor.  ``advance_split`` carves a segment
into sub-components using externally measured durations (e.g. the solver's
``last_timing`` exec/materialize split), clipping so no split can claim
more wall time than the segment actually spans — over-claims fall back to
the primary component, never double-count.

Budgets are keyed by the convergence trace that rides the epoch through
the queues (see ``runtime/tracing.py``), so the decision and FIB actors
can stamp the same epoch without passing a handle around.  Closing a
budget records ``budget.<component>_ms`` stats (windowed p50/p95/p99 via
the counter fabric, exported through OpenMetrics automatically),
``budget.e2e_ms`` and ``budget.unattributed_ms``, and appends the row to
a bounded ring for ``breeze decision budget`` / flight-recorder annexes.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

from openr_tpu.runtime.counters import counters

#: Canonical, exhaustive taxonomy.  ``tools/lint/metric_names.py`` expands
#: ``budget.<component>_ms`` against this list; adding a component here is
#: the single place the schema changes.
BUDGET_COMPONENTS: Tuple[str, ...] = (
    "ingest_wait",     # KvStore recv -> dispatch-fiber pickup
    "coalesce_hold",   # deliberate coalescing sleep + merge window
    "host_sync",       # LSDB delta read + host->device upload (dispatch)
    "dispatch_gap",    # solve enqueued -> device work actually starts
    "device_exec",     # device kernel execution
    "collect_block",   # host blocked collecting device results
    "payload_apply",   # changed rows -> RouteDatabase/RouteColumnBatch + fib diff
    "program",         # netlink / dataplane programming
    "ack_rtt",         # programming done -> ack observed/published
)

#: Conservation tolerance.  Components are cursor-derived so the sum is
#: exact up to float noise; anything above this is real unattributed time.
CONSERVATION_EPSILON_MS = 0.05

_MAX_ACTIVE = 256
_RING_LEN = 128


class EpochBudget:
    """One epoch's budget: a monotonic cursor over wall time."""

    __slots__ = ("key", "start", "cursor", "components", "meta", "closed")

    def __init__(self, key: Any, start: float, meta: Optional[dict] = None):
        self.key = key
        self.start = float(start)
        self.cursor = float(start)
        self.components: Dict[str, float] = {}
        self.meta = dict(meta or {})
        self.closed = False

    def advance(self, component: str, now: Optional[float] = None) -> float:
        """Attribute ``[cursor, now]`` to *component*; move the cursor.

        Returns the milliseconds attributed.  Clamped non-negative: a
        stale ``now`` (earlier than the cursor) attributes nothing rather
        than going negative and breaking conservation.
        """
        if now is None:
            now = time.monotonic()
        if now < self.cursor:
            now = self.cursor
        dt_ms = (now - self.cursor) * 1e3
        self.cursor = now
        if dt_ms > 0.0:
            self.components[component] = (
                self.components.get(component, 0.0) + dt_ms
            )
        return dt_ms

    def advance_split(
        self,
        splits: Dict[str, Optional[float]],
        primary: str,
        now: Optional[float] = None,
    ) -> float:
        """Carve the segment ``[cursor, now]`` into *splits* (ms values
        measured externally, e.g. solver ``last_timing``), attributing any
        remainder — and any over-claim — to *primary*.

        Each split is clipped to what is left of the segment, in dict
        order, so the sum of attributed parts equals the segment exactly:
        conservation survives noisy external measurements.
        """
        if now is None:
            now = time.monotonic()
        if now < self.cursor:
            now = self.cursor
        seg_ms = (now - self.cursor) * 1e3
        self.cursor = now
        remaining = seg_ms
        for comp, val in splits.items():
            take = min(max(float(val or 0.0), 0.0), remaining)
            if take > 0.0:
                self.components[comp] = self.components.get(comp, 0.0) + take
                remaining -= take
        if remaining > 0.0:
            self.components[primary] = (
                self.components.get(primary, 0.0) + remaining
            )
        return seg_ms

    def top_component(self) -> Tuple[str, float]:
        if not self.components:
            return "", 0.0
        comp = max(self.components, key=self.components.get)
        return comp, self.components[comp]


class LatencyBudgetLedger:
    """Process-global registry of in-flight and recently closed budgets."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active: Dict[Any, EpochBudget] = {}
        self._closed: deque = deque(maxlen=_RING_LEN)
        self.enabled = True

    # -- lifecycle ----------------------------------------------------

    def begin(
        self, key: Any, start: Optional[float] = None, **meta
    ) -> Optional[EpochBudget]:
        if not self.enabled or key is None:
            return None
        if start is None:
            start = time.monotonic()
        bud = EpochBudget(key, start, meta)
        with self._lock:
            existing = self._active.get(key)
            if existing is not None:
                return existing
            while len(self._active) >= _MAX_ACTIVE:
                # Evict the oldest in-flight budget (leaked epoch): its
                # trace died without closing.  Count it — silent eviction
                # would read as perfect conservation.
                oldest = next(iter(self._active))
                del self._active[oldest]
                counters.increment("budget.evicted")
            self._active[key] = bud
        return bud

    def begin_for_trace(self, ctx, **meta) -> Optional[EpochBudget]:
        """Begin a budget keyed by a convergence trace context, anchored
        at the trace's monotonic start so ``ingest_wait`` is real."""
        if ctx is None or not self.enabled:
            return None
        from openr_tpu.runtime.tracing import tracer

        started = tracer.trace_start(ctx)
        return self.begin(("trace", ctx.trace_id), start=started, **meta)

    def of(self, key: Any) -> Optional[EpochBudget]:
        if key is None:
            return None
        with self._lock:
            return self._active.get(key)

    def of_trace(self, ctx) -> Optional[EpochBudget]:
        if ctx is None:
            return None
        return self.of(("trace", ctx.trace_id))

    def discard(self, key: Any) -> None:
        """Drop a budget without recording stats (epoch did not complete
        as a churn-to-ack interval: no-change, not-in-lsdb, coalesced)."""
        if key is None:
            return
        with self._lock:
            if self._active.pop(key, None) is not None:
                counters.increment("budget.discarded")

    def discard_trace(self, ctx) -> None:
        if ctx is not None:
            self.discard(("trace", ctx.trace_id))

    def close(
        self,
        budget: Optional[EpochBudget],
        status: str = "ok",
        final_component: Optional[str] = None,
        now: Optional[float] = None,
    ) -> Optional[dict]:
        """Close a budget: enforce conservation, record stats, ring it.

        ``final_component`` absorbs the tail ``[cursor, now]`` (normally
        ``ack_rtt``).  Returns the closed row (also appended to the ring)
        or None if the budget was absent/already closed.
        """
        if budget is None or budget.closed:
            return None
        budget.closed = True
        with self._lock:
            self._active.pop(budget.key, None)
        if now is None:
            now = time.monotonic()
        if now < budget.cursor:
            now = budget.cursor
        if final_component:
            budget.advance(final_component, now)
        e2e_ms = (now - budget.start) * 1e3
        attributed = sum(budget.components.values())
        unattributed = e2e_ms - attributed
        if unattributed < CONSERVATION_EPSILON_MS:
            unattributed = max(unattributed, 0.0)
        for comp in BUDGET_COMPONENTS:
            counters.add_stat_value(
                f"budget.{comp}_ms", budget.components.get(comp, 0.0)
            )
        counters.add_stat_value("budget.e2e_ms", e2e_ms)
        counters.add_stat_value("budget.unattributed_ms", unattributed)
        if e2e_ms > 0.0:
            counters.set_counter(
                "budget.unattributed_pct",
                int(round(100.0 * unattributed / e2e_ms)),
            )
        counters.increment("budget.epochs")
        if status == "requeued":
            counters.increment("budget.requeued_epochs")
        top_comp, top_ms = budget.top_component()
        row = {
            "key": str(budget.key),
            "status": status,
            "e2e_ms": round(e2e_ms, 3),
            "unattributed_ms": round(unattributed, 3),
            "components": {
                k: round(v, 3) for k, v in budget.components.items()
            },
            "top_component": top_comp,
            "top_ms": round(top_ms, 3),
            "ts_ms": int(time.time() * 1e3),
        }
        if budget.meta:
            row["meta"] = dict(budget.meta)
        with self._lock:
            self._closed.append(row)
        return row

    def close_trace(
        self,
        ctx,
        status: str = "ok",
        final_component: Optional[str] = None,
    ) -> Optional[dict]:
        if ctx is None:
            return None
        return self.close(
            self.of_trace(ctx), status=status, final_component=final_component
        )

    # -- reporting ----------------------------------------------------

    def last_epochs(self, n: int = 16) -> list:
        with self._lock:
            rows = list(self._closed)
        return rows[-n:]

    def report(self) -> dict:
        """Full budget report for ``ctrl.decision.budget``."""
        stats = counters.get_statistics("budget.")
        comps = {}
        for comp in BUDGET_COMPONENTS:
            win = stats.get(f"budget.{comp}_ms")
            if win:
                comps[comp] = win
        rows = self.last_epochs(_RING_LEN)
        ok_rows = [r for r in rows if r["status"] == "ok"] or rows
        per_comp = {c: [] for c in BUDGET_COMPONENTS}
        e2e_samples = []
        for r in ok_rows:
            e2e_samples.append(r["e2e_ms"])
            for c in BUDGET_COMPONENTS:
                per_comp[c].append(r["components"].get(c, 0.0))
        rep = {
            "taxonomy": list(BUDGET_COMPONENTS),
            "components": comps,
            "e2e": stats.get("budget.e2e_ms") or {},
            "unattributed": stats.get("budget.unattributed_ms") or {},
            "conservation": {
                "epsilon_ms": CONSERVATION_EPSILON_MS,
                "epochs": counters.get_counter("budget.epochs"),
                "requeued": counters.get_counter("budget.requeued_epochs"),
                "discarded": counters.get_counter("budget.discarded"),
                "evicted": counters.get_counter("budget.evicted"),
                "unattributed_pct": counters.get_counter(
                    "budget.unattributed_pct"
                ),
            },
            "tail": tail_attribution(per_comp, e2e_samples),
            "last_epochs": rows[-8:],
        }
        return rep

    def snapshot(self) -> dict:
        """Compact annex for flight-recorder bundles."""
        stats = counters.get_statistics("budget.")

        def _q(name):
            win = stats.get(name) or {}
            agg = win.get("600") or (
                next(iter(win.values())) if win else {}
            )
            return {
                k: agg.get(k)
                for k in ("p50", "p95", "p99", "count")
                if agg.get(k) is not None
            }

        return {
            "components": {
                comp: _q(f"budget.{comp}_ms") for comp in BUDGET_COMPONENTS
            },
            "e2e": _q("budget.e2e_ms"),
            "unattributed": _q("budget.unattributed_ms"),
            "epochs": counters.get_counter("budget.epochs"),
            "requeued": counters.get_counter("budget.requeued_epochs"),
            "last_epochs": self.last_epochs(8),
        }

    def clear(self) -> None:
        with self._lock:
            self._active.clear()
            self._closed.clear()


def _pctl(samples: list, q: float) -> float:
    if not samples:
        return 0.0
    s = sorted(samples)
    idx = min(len(s) - 1, int(round(q * (len(s) - 1))))
    return s[idx]


def tail_attribution(
    component_samples: Dict[str, list], e2e_samples: list
) -> dict:
    """Attribute the p50 -> p99 gap of e2e to components.

    For each component, compute its own p99 - p50 delta; rank descending.
    Reports the top components and the fraction of the e2e gap the top-2
    cover (ISSUE 17 acceptance: >= 0.8 under flapstorm).
    """
    e2e_gap = max(_pctl(e2e_samples, 0.99) - _pctl(e2e_samples, 0.50), 0.0)
    deltas = []
    for comp, samples in component_samples.items():
        d = max(_pctl(samples, 0.99) - _pctl(samples, 0.50), 0.0)
        if d > 0.0:
            deltas.append((comp, d))
    deltas.sort(key=lambda kv: kv[1], reverse=True)
    top2 = sum(d for _, d in deltas[:2])
    return {
        "e2e_gap_ms": round(e2e_gap, 3),
        "ranked": [
            {"component": c, "gap_ms": round(d, 3)} for c, d in deltas[:5]
        ],
        "top2_coverage": (
            round(min(top2 / e2e_gap, 1.0), 3) if e2e_gap > 0.0 else None
        ),
    }


#: Process-global ledger, mirroring ``tracing.tracer`` / counter fabric.
latency_budget = LatencyBudgetLedger()
