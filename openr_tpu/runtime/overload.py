"""Process-wide overload control: admission, flap damping, brownout.

The platform survives *faults* (supervised restart, CPU failover,
flight recorder, deterministic replay) but faults are discrete;
*overload* is sustained. A pathological flapping adjacency or a churn
storm past the solve pipeline's capacity grows the dispatch queue
without bound, monopolizes solves, and burns the ack-p99 SLO with no
mechanism to shed, damp, or degrade. This module is that mechanism —
one controller per node, three cooperating pieces:

- **state ladder** — an explicit, observable overload state
  ``ok -> backpressure -> brownout -> shedding`` driven by the
  pending-solve queue depth, HBM pressure (device_stats gauges),
  host RSS, and active SLO burn. Upshifts are immediate (pressure is
  now); downshifts step one rung at a time and only after a dwell
  period with every signal below its *clear* watermark — hysteresis,
  so a borderline load can't strobe the ladder. Every transition runs
  the registered callback (Decision emits an ``OVERLOAD_STATE_CHANGE``
  LogSample; the Monitor's trigger table freezes a flight-recorder
  bundle) and restamps the closed ``overload.*`` gauge family.

- **admission control** — ``admit(cls)`` schedules work by priority
  class: live convergence always runs; TE/what-if is rejected from
  brownout up (the generalization of the ad-hoc what-if deferral);
  background probes (kvstore flood probes, digest anti-entropy) are
  deferred from backpressure up. ``coalesce_ms()`` widens the dispatch
  fiber's coalescing window with queue depth and ladder level — deeper
  queue, bigger batches, bounded by ``overload_coalesce_max_ms``.
  ``shed()`` answers whether a new solve request should fold into the
  held overflow batch instead of growing the queue past the watermark.

- **flap damping** — :class:`FlapDamper`, RFC 2439 transplanted from
  BGP route flap damping onto LSDB keys: each ingest *change* of an
  (area, key) adds a fixed penalty to that key's figure of merit, the
  figure decays exponentially with a half-life, and a key whose figure
  crosses the suppress threshold stops perturbing the LSDB — its
  latest value is *held*, not dropped — until decay brings it under
  the reuse threshold, at which point the held value re-ingests
  through the normal path (no stale-route window: the LSDB converges
  to the key's final state the moment it calms down). One flapping
  adjacency is contained while the rest of the LSDB converges at full
  speed.

Decay is computed lazily from the last-touch monotonic timestamp —
no timer per key — and the clock is injectable (tests drive virtual
time). A clock that reads *backwards* (paused process, test reuse)
decays nothing rather than inflating penalties: monotonicity is
enforced, not assumed.

The rung beyond admission control is enacted by the owner of the
machinery: Decision consults ``multichip_allowed()`` to pin the solver
to the single-chip tier (decision/tpu_solver.py honors
``force_single_chip``). A rung is a query, not a command, so it
reverses the instant the ladder does.

One controller per node, looked up by node name (``get_controller``)
— same per-node registry idiom as the replay recorder: in-process
multi-node emulations keep their controllers separate, production
daemons have exactly one.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from openr_tpu.runtime.counters import counters

# the ladder, in escalation order; list index == numeric level
OVERLOAD_STATES = ("ok", "backpressure", "brownout", "shedding")

OK, BACKPRESSURE, BROWNOUT, SHEDDING = range(4)

# closed vocabulary of the overload.* counter family — restamped via
# set_counter(f"overload.{field}", ...) on every evaluation;
# tools/lint/metric_names.py expands this list for collision checking
# (keep the two in sync by importing, never copying)
OVERLOAD_COUNTER_FIELDS = (
    "state",             # numeric ladder level (0..3)
    "brownout",          # 1 while level >= brownout (gauge_duration SLO source)
    "transitions",       # ladder transitions since start
    "queue_depth",       # last observed pending-solve queue depth
    "damped_keys",       # keys currently suppressed
    "suppressed_events", # ingest events withheld by damping
    "released_keys",     # suppressions lifted after decay
    "shed_epochs",       # solve requests folded into the overflow batch
    "rejected_whatif",   # what-if admissions rejected by the ladder
    "deferred_probes",   # background probes deferred by the ladder
)

# admission priority classes, strongest first
PRIORITY_CLASSES = ("live", "whatif", "probe")


class FlapDamper:
    """RFC 2439-style per-key exponential flap damping (see module
    docstring). Keys are (area, key) pairs; time is whatever the
    injected clock says, in seconds."""

    def __init__(
        self,
        half_life_s: float = 60.0,
        penalty: float = 1.0,
        suppress_threshold: float = 3.0,
        reuse_threshold: float = 1.0,
        max_penalty: float = 12.0,
        clock: Optional[Callable[[], float]] = None,
    ):
        if half_life_s <= 0:
            raise ValueError("half_life_s must be positive")
        if not 0 < reuse_threshold < suppress_threshold <= max_penalty:
            raise ValueError(
                "thresholds must satisfy 0 < reuse < suppress <= max"
            )
        self.half_life_s = float(half_life_s)
        self.penalty = float(penalty)
        self.suppress_threshold = float(suppress_threshold)
        self.reuse_threshold = float(reuse_threshold)
        self.max_penalty = float(max_penalty)
        self._clock = clock or time.monotonic
        # (area, key) -> [figure, last_t, suppressed, held_event]
        self._keys: dict[tuple, list] = {}
        self.suppressed_events = 0
        self.released_keys = 0

    def _decayed(self, rec: list, now: float) -> float:
        """Figure of merit decayed to `now`. A backwards clock decays
        nothing (monotonicity enforced, never negative exponents)."""
        dt = now - rec[1]
        if dt <= 0.0:
            return rec[0]
        return rec[0] * (0.5 ** (dt / self.half_life_s))

    def record_change(self, area: str, key: str) -> bool:
        """One ingest change of (area, key): decay, add the penalty,
        maybe cross into suppression. Returns True when the key is
        suppressed AFTER this event (the caller withholds the event
        from the LSDB and parks it via `hold`)."""
        now = self._clock()
        rec = self._keys.get((area, key))
        if rec is None:
            rec = [0.0, now, False, None]
            self._keys[(area, key)] = rec
        figure = min(self._decayed(rec, now) + self.penalty,
                     self.max_penalty)
        rec[0] = figure
        rec[1] = max(rec[1], now)
        if not rec[2] and figure >= self.suppress_threshold:
            rec[2] = True
            counters.increment("overload.damper.suppressions")
        if rec[2]:
            self.suppressed_events += 1
        return rec[2]

    def is_suppressed(self, area: str, key: str) -> bool:
        rec = self._keys.get((area, key))
        return bool(rec and rec[2])

    def hold(self, area: str, key: str, event) -> None:
        """Park the LATEST withheld event for a suppressed key (latest
        wins) so release can re-ingest the key's final state."""
        rec = self._keys.get((area, key))
        if rec is not None and rec[2]:
            rec[3] = event

    def releasable(self) -> list[tuple]:
        """Suppressed keys whose figure has decayed below the reuse
        threshold: [(area, key, held_event)]. Clears the suppression —
        the caller MUST re-ingest each held event (or the key's state
        stays at its last pre-suppression value until the next change)."""
        now = self._clock()
        out = []
        for (area, key), rec in list(self._keys.items()):
            figure = self._decayed(rec, now)
            if rec[2] and figure <= self.reuse_threshold:
                out.append((area, key, rec[3]))
                self.released_keys += 1
                del self._keys[(area, key)]
            elif not rec[2] and figure < self.penalty * 0.01:
                del self._keys[(area, key)]  # fully calmed: forget
        return out

    def tracked_count(self) -> int:
        """Records in the table: what one releasable() walks."""
        return len(self._keys)

    def damped_count(self) -> int:
        return sum(1 for rec in self._keys.values() if rec[2])

    def figure_of_merit(self, area: str, key: str) -> float:
        rec = self._keys.get((area, key))
        return 0.0 if rec is None else self._decayed(rec, self._clock())

    def report(self) -> dict:
        now = self._clock()
        suppressed = {
            f"{area}/{key}": round(self._decayed(rec, now), 3)
            for (area, key), rec in self._keys.items()
            if rec[2]
        }
        return {
            "half_life_s": self.half_life_s,
            "suppress_threshold": self.suppress_threshold,
            "reuse_threshold": self.reuse_threshold,
            "tracked_keys": len(self._keys),
            "damped_keys": len(suppressed),
            "suppressed": suppressed,
            "suppressed_events": self.suppressed_events,
            "released_keys": self.released_keys,
        }


class OverloadController:
    """Per-node overload state ladder + admission control (see module
    docstring)."""

    def __init__(
        self,
        node_name: str,
        queue_watermark: int = 8,
        coalesce_max_ms: int = 250,
        hbm_high_frac: float = 0.9,
        hbm_clear_frac: float = 0.75,
        rss_high_mb: float = 0.0,
        rss_clear_mb: float = 0.0,
        dwell_s: float = 5.0,
        damper: Optional[FlapDamper] = None,
        clock: Optional[Callable[[], float]] = None,
        on_transition: Optional[Callable] = None,
    ):
        if queue_watermark < 1:
            raise ValueError("queue_watermark must be >= 1")
        self.node_name = node_name
        self.queue_watermark = int(queue_watermark)
        self.coalesce_max_ms = int(coalesce_max_ms)
        self.hbm_high_frac = float(hbm_high_frac)
        self.hbm_clear_frac = float(hbm_clear_frac)
        self.rss_high_mb = float(rss_high_mb)
        self.rss_clear_mb = float(rss_clear_mb)
        self.dwell_s = float(dwell_s)
        self.damper = damper if damper is not None else FlapDamper()
        self._clock = clock or time.monotonic
        self.on_transition = on_transition
        self.level = OK
        self._since = self._clock()
        self.transitions = 0
        # cached signals (partial observers each feed what they see)
        self._depth = 0
        self._hbm_frac: Optional[float] = None
        self._rss_mb: Optional[float] = None
        self._slo_burning = False
        self.shed_epochs = 0
        self.rejected_whatif = 0
        self.deferred_probes = 0
        self._history: list[dict] = []

    # -- signals ------------------------------------------------------

    def observe(
        self,
        queue_depth: Optional[int] = None,
        hbm_frac: Optional[float] = None,
        rss_mb: Optional[float] = None,
        slo_burning: Optional[bool] = None,
    ) -> int:
        """Feed whichever signals this observer sees (Decision's
        dispatch fiber feeds depth; the Monitor tick feeds memory and
        SLO burn — same event loop, so no locking), then re-evaluate
        the ladder. Returns the post-evaluation level."""
        if queue_depth is not None:
            self._depth = int(queue_depth)
        if hbm_frac is not None:
            self._hbm_frac = float(hbm_frac)
        if rss_mb is not None:
            self._rss_mb = float(rss_mb)
        if slo_burning is not None:
            self._slo_burning = bool(slo_burning)
        return self.evaluate()

    def _mem_high(self) -> bool:
        if self._hbm_frac is not None and self._hbm_frac >= self.hbm_high_frac:
            return True
        return bool(
            self.rss_high_mb > 0
            and self._rss_mb is not None
            and self._rss_mb >= self.rss_high_mb
        )

    def _mem_clear(self) -> bool:
        """Memory below the CLEAR watermarks (hysteresis band)."""
        if self._hbm_frac is not None and self._hbm_frac > self.hbm_clear_frac:
            return False
        if (
            self.rss_high_mb > 0
            and self._rss_mb is not None
            and self._rss_mb > (self.rss_clear_mb or self.rss_high_mb)
        ):
            return False
        return True

    def _target(self) -> int:
        """Escalation target from the current signals (the watermark
        side of the hysteresis band — upshifts key off this)."""
        wm = self.queue_watermark
        mem_high = self._mem_high()
        if self._depth >= 2 * wm or (mem_high and self._depth >= wm):
            return SHEDDING
        if self._depth >= wm or mem_high:
            return BROWNOUT
        if self._depth >= max(1, wm // 2) or self._slo_burning:
            return BACKPRESSURE
        return OK

    def _clear_target(self) -> int:
        """De-escalation target: every signal must sit below its clear
        watermark before a rung releases (the other side of the band)."""
        wm = self.queue_watermark
        if not self._mem_clear() or self._depth >= wm:
            return max(BROWNOUT, min(self._target(), self.level))
        if self._depth >= max(1, wm // 4) or self._slo_burning:
            return BACKPRESSURE
        return OK

    def evaluate(self) -> int:
        """One ladder step: upshift immediately to the escalation
        target; downshift one rung only after `dwell_s` at the current
        level with the clear target below it."""
        now = self._clock()
        target = self._target()
        if target > self.level:
            self._transition(target, now)
        elif (
            self.level > OK
            and (now - self._since) >= self.dwell_s
            and self._clear_target() < self.level
        ):
            self._transition(self.level - 1, now)
        self._export()
        return self.level

    def _transition(self, new_level: int, now: float) -> None:
        old = self.level
        self.level = new_level
        self._since = now
        self.transitions += 1
        entry = {
            "t": now,
            "from": OVERLOAD_STATES[old],
            "to": OVERLOAD_STATES[new_level],
            "queue_depth": self._depth,
            "hbm_frac": self._hbm_frac,
            "rss_mb": self._rss_mb,
            "slo_burning": self._slo_burning,
        }
        self._history.append(entry)
        del self._history[:-32]
        if self.on_transition is not None:
            try:
                self.on_transition(entry)
            # lint: allow(broad-except) observer failure must not wedge
            # the ladder — control beats telemetry under overload
            except Exception:
                counters.increment("overload.transition_hook_errors")

    # -- queries the pipeline consults --------------------------------

    @property
    def state(self) -> str:
        return OVERLOAD_STATES[self.level]

    def admit(self, priority: str) -> bool:
        """Admission by priority class: live convergence always runs;
        what-if from brownout up and probes from backpressure up are
        turned away (counted — rejection is an answer, not a drop)."""
        if priority == "live" or self.level == OK:
            return True
        if priority == "whatif":
            if self.level >= BROWNOUT:
                self.rejected_whatif += 1
                self._export()
                return False
            return True
        if priority == "probe":
            self.deferred_probes += 1
            self._export()
            return False
        return True

    def coalesce_ms(self, base_ms: int) -> float:
        """Adaptive coalescing window for the dispatch fiber: the
        configured base in steady state, widened with ladder level and
        queue depth under pressure, capped at coalesce_max_ms. A zero
        base widens from a 1 ms seed so backpressure can engage even
        where coalescing was configured off."""
        if self.level == OK:
            return float(base_ms)
        seed = float(base_ms) if base_ms > 0 else 1.0
        scale = 1.0 + self.level + self._depth / float(self.queue_watermark)
        return min(seed * scale, float(self.coalesce_max_ms))

    def shed(self, queue_depth: int) -> bool:
        """Should a new solve request fold into the held overflow batch
        instead of growing the queue? Only in shedding, and only while
        the queue sits at/over the watermark — depth stays bounded."""
        if self.still_shedding(queue_depth):
            self.shed_epochs += 1
            self._export()
            return True
        return False

    def still_shedding(self, queue_depth: int) -> bool:
        """Passive form of `shed` (no counting): is the held overflow
        batch still better off waiting? The dispatch fiber flushes the
        batch back onto the queue the moment this goes False."""
        return (
            self.level >= SHEDDING and queue_depth >= self.queue_watermark
        )

    def multichip_allowed(self) -> bool:
        """Deepest rung before shedding-only: pin the solver to the
        single-chip tier, releasing the mesh's HBM."""
        return self.level < SHEDDING

    # -- export -------------------------------------------------------

    def _export(self) -> None:
        for field, value in (
            ("state", self.level),
            ("brownout", 1 if self.level >= BROWNOUT else 0),
            ("transitions", self.transitions),
            ("queue_depth", self._depth),
            ("damped_keys", self.damper.damped_count()),
            ("suppressed_events", self.damper.suppressed_events),
            ("released_keys", self.damper.released_keys),
            ("shed_epochs", self.shed_epochs),
            ("rejected_whatif", self.rejected_whatif),
            ("deferred_probes", self.deferred_probes),
        ):
            counters.set_counter(f"overload.{field}", value)

    def report(self) -> dict:
        """`breeze decision overload` / ctrl payload."""
        now = self._clock()
        return {
            "node": self.node_name,
            "state": self.state,
            "level": self.level,
            "since_s": round(now - self._since, 3),
            "queue_watermark": self.queue_watermark,
            "queue_depth": self._depth,
            "hbm_frac": self._hbm_frac,
            "rss_mb": self._rss_mb,
            "slo_burning": self._slo_burning,
            "transitions": self.transitions,
            "shed_epochs": self.shed_epochs,
            "rejected_whatif": self.rejected_whatif,
            "deferred_probes": self.deferred_probes,
            "coalesce_max_ms": self.coalesce_max_ms,
            "dwell_s": self.dwell_s,
            "multichip_allowed": self.multichip_allowed(),
            "damper": self.damper.report(),
            "history": [
                {**h, "t": round(h["t"], 3)} for h in self._history[-10:]
            ],
        }


# -- per-node registry (Monitor/kvstore/ctrl lookup path) ---------------

_registry: dict[str, OverloadController] = {}


def register(controller: OverloadController) -> OverloadController:
    """Install `controller` as its node's controller (latest wins —
    test harnesses rebuild Decisions under one node name)."""
    _registry[controller.node_name] = controller
    return controller


def get_controller(node_name: str) -> Optional[OverloadController]:
    return _registry.get(node_name)


def unregister(node_name: str) -> None:
    _registry.pop(node_name, None)
