"""Boot-to-first-RIB lifecycle tracer (ISSUE 14).

ROADMAP item 1 gates the cold-start work on "cold-process-to-first-RIB
under 2 s" — but convergence tracing (runtime/tracing.py) only opens a
trace at KvStore ingest, so everything a restarting daemon pays BEFORE
its first LSDB event (config load, jax/device init, persistent-jit-cache
attach, prewarm attribution, the initial full sync, the first
compile-heavy solve) was invisible. This module records that one-shot
timeline:

  config_load -> device_init -> jit_cache_attach -> aot_load
    -> prewarm -> kvstore_initial_sync -> first_solve
    -> first_rib_delta -> first_fib_program

``aot_load`` (ISSUE 20) is the persistent executable-cache preload:
deserializing previously compiled kernels from disk so the prewarm
phase that follows installs them instead of invoking XLA.

``main.run_daemon`` calls ``boot_tracer.begin(node)`` before any actor
spins up; phases are stamped from wherever they actually complete
(main.py for the explicit setup steps, KvStore/Decision/Fib for the
pipeline milestones). The tracer keeps a contiguous cursor, so a
retroactive ``phase_mark`` covers everything since the previous phase
ended — the phases tile the boot wall-clock with no gaps.

Three outputs per boot:

  - gauges: ``boot.phase.<name>_ms`` per phase and the headline
    ``boot.first_rib_ms`` (plus ``boot.complete``), scraped like any
    other counter and recorded as a bench headline (bench.py boot lane)
  - a span tree: one ``boot`` trace whose root carries the node name,
    so ``export_chrome`` lanes it next to the node's convergence
    traces; closed with status="boot" (the whatif pattern) so it never
    pollutes the convergence_ms stat
  - a report: ``ctrl.monitor.boot`` / ``breeze monitor boot`` render
    the phase ledger with per-phase attributes (the first solve's
    compile/device/mat split, the jit-cache dir, prewarm attribution)

Process-global singleton (the ``tracer``/``counters`` pattern): actors
stamp phases without plumbing, and pass their node name so that in
multi-node test processes only the node that ``begin``-ed records.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Optional

from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.tracing import tracer

# Canonical phase order — documentation + the lint expansion for the
# dynamic boot.phase.<name>_ms gauge family (tools/lint/metric_names.py).
BOOT_PHASES = (
    "config_load",
    "device_init",
    "jit_cache_attach",
    "aot_load",
    "prewarm",
    "kvstore_initial_sync",
    "first_solve",
    "first_rib_delta",
    "first_fib_program",
)


class BootTracer:
    """One cold start's phase ledger + span tree. Reusable via reset()
    (tests, bench boot lane); a daemon runs exactly one boot."""

    def __init__(self):
        self._lock = threading.Lock()
        self._node: Optional[str] = None
        self._ctx = None
        self._t0: Optional[float] = None
        self._started_wall_ms = 0
        self._cursor: Optional[float] = None
        self._phases: list[dict] = []
        self._complete = False
        self._first_rib_ms: Optional[float] = None

    # -- lifecycle ---------------------------------------------------------

    def begin(self, node: str, start: Optional[float] = None) -> None:
        """Open the boot timeline. `start` (time.monotonic()) backdates
        the root over work already done (e.g. config load) when the
        caller could only learn the node name from the config."""
        with self._lock:
            if self._node is not None and not self._complete:
                return  # one boot per process; ignore re-entry
            t0 = start if start is not None else time.monotonic()
            self._node = node
            self._t0 = t0
            self._cursor = t0
            self._started_wall_ms = int(
                time.time() * 1000 - (time.monotonic() - t0) * 1000
            )
            self._phases = []
            self._complete = False
            self._first_rib_ms = None
            self._ctx = tracer.start_trace("boot", start=t0, node=node)

    def active(self, node: Optional[str] = None) -> bool:
        """True while a boot is being recorded (begun, not complete) —
        and, when `node` is given, recording THAT node. The cheap guard
        actors use before stamping."""
        if self._node is None or self._complete:
            return False
        return node is None or node == self._node

    def phase_mark(
        self, name: str, node: Optional[str] = None, **attrs
    ) -> None:
        """Record a phase retroactively: it spans from the end of the
        previous phase to now, keeping the boot timeline gapless."""
        now = time.monotonic()
        with self._lock:
            if not self.active(node):
                return
            self._record(name, self._cursor, now, attrs)

    @contextlib.contextmanager
    def phase(self, name: str, node: Optional[str] = None, **attrs):
        """Explicitly timed phase; yields a dict merged into the phase
        attributes at exit (for values only known inside the block)."""
        extra: dict = {}
        start = time.monotonic()
        try:
            yield extra
        finally:
            now = time.monotonic()
            with self._lock:
                if self.active(node):
                    self._record(name, start, now, {**attrs, **extra})

    def complete(self, node: Optional[str] = None, **attrs) -> None:
        """Boot done: the first RIB is programmed. Stamps the headline
        gauge and closes the span tree (status="boot" so the trace
        never lands in the convergence_ms stat)."""
        with self._lock:
            if not self.active(node):
                return
            now = time.monotonic()
            self._complete = True
            self._first_rib_ms = (now - self._t0) * 1e3
            counters.set_counter(
                "boot.first_rib_ms", round(self._first_rib_ms, 3)
            )
            counters.set_counter("boot.complete", 1)
            ctx, self._ctx = self._ctx, None
        if ctx is not None:
            tracer.end_trace(
                ctx,
                status="boot",
                first_rib_ms=round(self._first_rib_ms, 3),
                **attrs,
            )

    def reset(self) -> None:
        """Drop state (tests / bench boot lane). Abandons an unclosed
        trace with an explicit status rather than leaking it active."""
        with self._lock:
            ctx, self._ctx = self._ctx, None
            self._node = None
            self._t0 = None
            self._cursor = None
            self._phases = []
            self._complete = False
            self._first_rib_ms = None
        if ctx is not None:
            tracer.end_trace(ctx, status="boot_abandoned")

    # -- internals ---------------------------------------------------------

    def _record(
        self, name: str, start: float, end: float, attrs: dict
    ) -> None:
        """Caller holds the lock and has already passed the node gate."""
        dur_ms = max(0.0, (end - start) * 1e3)
        self._phases.append(
            {
                "name": name,
                "start_ms": round((start - self._t0) * 1e3, 3),
                "duration_ms": round(dur_ms, 3),
                "attrs": {k: v for k, v in attrs.items() if v is not None},
            }
        )
        self._cursor = max(self._cursor, end)
        counters.set_counter(f"boot.phase.{name}_ms", round(dur_ms, 3))
        tracer.record_span(
            self._ctx, f"boot.{name}", start, end, node=self._node, **attrs
        )

    # -- report ------------------------------------------------------------

    def report(self) -> dict:
        """`ctrl.monitor.boot` / `breeze monitor boot` payload."""
        with self._lock:
            if self._node is None:
                return {"enabled": False, "phases": []}
            return {
                "enabled": True,
                "node": self._node,
                "started_at_ms": self._started_wall_ms,
                "complete": self._complete,
                "first_rib_ms": (
                    round(self._first_rib_ms, 3)
                    if self._first_rib_ms is not None
                    else None
                ),
                "elapsed_ms": round(
                    (time.monotonic() - self._t0) * 1e3, 3
                ),
                "phases": [dict(p) for p in self._phases],
            }


boot_tracer = BootTracer()


_heap_frozen = False


def freeze_boot_heap() -> None:
    """Once a process: take what the boot has built, the LSDB, the first
    table and Fib's copy of it, out of the cyclic collector's sight
    (`gc.freeze`, after one full collection so that no garbage is kept).
    Those objects live as long as the process, or die by reference count
    when an update replaces them; none needs the collector. Left in its
    oldest generation they are walked whole by every collection of that
    generation, and an event that builds a table's worth of routes sets
    one off (PERF.md section 6, PR 47: at 200,000 prefix entries and
    50,000 routes, 0.34-0.44 s in every second event). After the freeze
    such a collection walks what events have made since and no more.

    The freeze takes the collector's brake with it, so the oldest
    generation's threshold is raised in the same call: CPython runs a
    full collection only once a quarter as many objects have come of age
    as survived the last one, frozen objects do not count as survivors,
    and without the brake the oldest generation runs every tenth time the
    middle one does. Measured with the freeze alone (PERF.md section 4,
    call 2, PR 47): about one collection an event, short where a table is
    rebuilt whole, but 135-293 ms in nearly every plane drain of
    fabric10k.plane, whose median then carries them (+5 and +31 % in two
    pairs). A tenth as often, the median event carries none in the three
    cells measured (call 3).

    The first Fib of a process to program its first table calls it, the
    last step of the initialization sequence wherever the stack was built
    (`main.run_daemon`, a harness that wires the actors itself); later
    ones (a test process runs many stacks) leave the heap alone."""
    global _heap_frozen
    if _heap_frozen:
        return
    _heap_frozen = True
    gc.collect()
    gc.freeze()
    young, middle, oldest = gc.get_threshold()
    gc.set_threshold(young, middle, oldest * 10)
    counters.set_counter("runtime.gc.frozen_objects", gc.get_freeze_count())
