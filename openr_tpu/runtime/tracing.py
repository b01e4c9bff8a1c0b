"""Convergence tracing fabric — spans + trace-context propagation.

Role of the perf-event breadcrumbs the reference threads through
thrift::PerfEvents (Decision.cpp addPerfEvent, Fib.cpp logPerfEvents),
generalised into a proper span tree: one topology event entering
KvStore carries a single trace_id through decision → tpu_solver →
columnar RIB materialization → fib → platform programming ack, and the
closed trace exports as Chrome trace-event JSON (chrome://tracing /
Perfetto).

Design constraints:
- Process-wide singleton (like runtime.counters.counters) because the
  pipeline crosses actor and thread boundaries (the TPU solver's
  "rib-mat" worker thread records materialization spans).
- The queue items (Publication, DecisionRouteUpdate) are mutable
  dataclasses with eq=True — unhashable — so the context rides in a
  side-table keyed by id(item), cleaned up by weakref.finalize. Items
  that are not weakref-able simply don't carry context.
- Opt-out cheap: with tracing disabled start_trace returns None and
  every other entry point takes the None fast path (one attribute
  check); context_of is one dict lookup.

The background track: work that belongs to no event — the interpreter's
collector, KvStore's digest beacon, the flap damper's sweep — holds the
one event loop all the same. `hold` / `record_hold` record such a
stretch as a span that belongs to no trace, kept in a ring of its own
(`get_holds`, a lane of `export_chrome`), and copy it into every trace
that was active while it ran, so the one trace an operator pulls says
what held it. `watch_gc` names the collector's pauses, `note_loop_lag`
(asked by the actors' heartbeat) the stretches nobody named.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import math
import os
import threading
import time
import weakref
from typing import Any, Optional

from openr_tpu.runtime.counters import counters

# ring of closed traces kept for monitor.traces / export
MAX_CLOSED_TRACES = 256
# safety valve: a trace that never closes (e.g. FIB never acks because
# the platform is down) must not leak — oldest active is force-closed
# with status "evicted" once this many are in flight
MAX_ACTIVE_TRACES = 256
# side-table cap: a stuck consumer (queue reader crashed between push
# and pop) strands contexts whose items never get collected — past this
# many, orphans (contexts of no-longer-active traces) are evicted
# first, then the oldest entries
MAX_TRACE_CONTEXTS = 1024
# ring of holds: one run's, from process start to the end of a
# benchmark window, with room (a 190 s run at lsdb100k leaves a few
# hundred: a sweep a second, a beacon every 15 s, the collector's long
# pauses); oldest dropped, counted in tracing.holds_dropped
MAX_HOLDS = 16384
# a trace that never closes (the safety valve above) must not grow by a
# span a sweep for as long as it lingers: past this many copies a trace
# takes no more (the ring still has every hold)
MAX_HOLD_COPIES = 256
# a collection shorter than this is counted and leaves no span: the
# young generations run thousands of times a minute, tens of µs each
GC_HOLD_MIN_S = 0.001
# an uncovered stretch of a late heartbeat shorter than this is the
# loop's ordinary turn-taking, not a hold
UNNAMED_HOLD_MIN_S = 0.005


class Span:
    """One timed stage. start/end are time.monotonic() seconds; the
    tracer's wall-clock anchor maps them to epoch µs at export time."""

    __slots__ = (
        "span_id", "trace_id", "parent_id", "name",
        "start", "end", "attributes", "thread",
    )

    def __init__(
        self,
        span_id: int,
        trace_id: int,
        name: str,
        start: float,
        parent_id: Optional[int] = None,
        attributes: Optional[dict] = None,
        thread: str = "",
    ):
        self.span_id = span_id
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.attributes: dict = attributes or {}
        self.thread = thread

    @property
    def duration_ms(self) -> Optional[float]:
        if self.end is None:
            return None
        return (self.end - self.start) * 1000.0

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "trace_id": self.trace_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "duration_ms": self.duration_ms,
            "attributes": dict(self.attributes),
            "thread": self.thread,
        }


class TraceContext:
    """Lightweight handle that rides through the queues. Only identity
    lives here; span storage is in the tracer so any thread can add."""

    __slots__ = ("trace_id", "root_span_id")

    def __init__(self, trace_id: int, root_span_id: int):
        self.trace_id = trace_id
        self.root_span_id = root_span_id

    def __repr__(self) -> str:  # breeze-friendly
        return f"TraceContext(trace_id={self.trace_id})"


class _Trace:
    __slots__ = (
        "trace_id", "name", "spans", "status", "started", "ended",
        "hold_copies", "touched",
    )

    def __init__(self, trace_id: int, name: str, started: float):
        self.trace_id = trace_id
        self.name = name
        self.spans: list[Span] = []
        self.hold_copies = 0
        # the latest time a span of this trace began or ended: a trace
        # that lingers is passed over by the lag probe at a glance
        self.touched = started
        self.status = "active"
        self.started = started
        self.ended: Optional[float] = None


class _NullSpan:
    """No-op context manager handed out when tracing is off or the
    context is None — hot paths need no branches beyond `with`."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _LiveSpan:
    """Context manager wrapping an open Span; closes it on exit."""

    __slots__ = ("_tracer", "span")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self.span = span

    def __enter__(self) -> "_LiveSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.span.attributes["error"] = repr(exc)
        self._tracer.end_span(self.span)
        return False

    def set(self, **attrs) -> None:
        self.span.attributes.update(attrs)


class _LiveHold(_LiveSpan):
    """An open hold: closes like a span, then is filed on the background
    track (Tracer._file_hold)."""

    __slots__ = ()

    def __exit__(self, exc_type, exc, tb) -> bool:
        super().__exit__(exc_type, exc, tb)
        self._tracer._file_hold(self.span)
        return False


class Tracer:
    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = True
        self._trace_seq = itertools.count(1)
        self._span_seq = itertools.count(1)
        self._active: dict[int, _Trace] = {}
        self._closed: "list[_Trace]" = []
        # side-table: id(item) -> TraceContext, scrubbed by finalizers
        self._ctx_by_id: dict[int, TraceContext] = {}
        # anchor for monotonic -> wall-clock µs mapping in exports
        self._wall_anchor = time.time()
        self._mono_anchor = time.monotonic()
        # the background track: (when filed, closed hold), oldest first.
        # A hold is filed at or after its end, so the filing times bound
        # a search from the newest end back (note_loop_lag)
        self._holds: collections.deque[tuple[float, Span]] = (
            collections.deque(maxlen=MAX_HOLDS)
        )
        # what the lag probe has looked at: with many actors on one loop
        # every heartbeat is late by the same hold, and one look is enough
        self._lag_seen = (0.0, 0.0)
        self.holds_dropped = 0  # by this ring; the counter is the process's
        # the collector's hook takes no lock (see _on_gc): it keeps
        # running totals by generation and queues its long pauses;
        # drain_gc turns both into counters and holds
        self._gc_t0: Optional[float] = None  # no collection seen to start
        self._gc_runs = [0, 0, 0]
        self._gc_seconds = [0.0, 0.0, 0.0]
        self._gc_counted = ([0, 0, 0], [0.0, 0.0, 0.0])
        self._gc_long: collections.deque[tuple] = collections.deque()

    # -- config -----------------------------------------------------------

    def configure(self, enabled: bool) -> None:
        self.enabled = enabled

    # -- context propagation (messaging/queue.py) -------------------------

    def attach(self, item: Any, ctx: Optional[TraceContext]) -> bool:
        """Associate ctx with a queue item. Returns False when the item
        cannot carry context (not weakref-able) or ctx is None."""
        if ctx is None:
            return False
        key = id(item)
        try:
            weakref.finalize(item, self._ctx_by_id.pop, key, None)
        except TypeError:
            return False
        self._ctx_by_id[key] = ctx
        if len(self._ctx_by_id) > MAX_TRACE_CONTEXTS:
            self._evict_contexts()
        return True

    def _evict_contexts(self) -> None:
        """Side-table hygiene: drop contexts whose trace already closed
        (the span tree is finished — the entry can only go stale), then
        oldest-first down to the cap. Keeps a wedged consumer from
        growing the table unbounded."""
        evicted = 0
        with self._lock:
            if len(self._ctx_by_id) > MAX_TRACE_CONTEXTS:
                orphans = [
                    k for k, c in self._ctx_by_id.items()
                    if c.trace_id not in self._active
                ]
                for k in orphans:
                    self._ctx_by_id.pop(k, None)
                evicted += len(orphans)
            excess = len(self._ctx_by_id) - MAX_TRACE_CONTEXTS
            if excess > 0:
                for k in list(itertools.islice(self._ctx_by_id, excess)):
                    self._ctx_by_id.pop(k, None)
                evicted += excess
        if evicted:
            counters.increment("tracing.contexts_evicted", evicted)

    def context_of(self, item: Any) -> Optional[TraceContext]:
        """One dict lookup; safe on any object."""
        return self._ctx_by_id.get(id(item))

    def active_context_count(self) -> int:
        return len(self._ctx_by_id)

    # -- span lifecycle ---------------------------------------------------

    def start_trace(
        self, name: str, start: Optional[float] = None, **attributes
    ) -> Optional[TraceContext]:
        """Open a new trace; returns None when tracing is disabled so
        producers can pass the context straight through push(trace=...).
        `start` (time.monotonic()) backdates the root to cover work
        already done when the producer decides the event is traceworthy."""
        if not self.enabled:
            return None
        now = start if start is not None else time.monotonic()
        with self._lock:
            trace_id = next(self._trace_seq)
            span_id = next(self._span_seq)
            root = Span(
                span_id, trace_id, name, now,
                attributes=dict(attributes),
                thread=threading.current_thread().name,
            )
            tr = _Trace(trace_id, name, now)
            tr.spans.append(root)
            self._active[trace_id] = tr
            evicted = None
            if len(self._active) > MAX_ACTIVE_TRACES:
                oldest_id = min(
                    self._active, key=lambda t: self._active[t].started
                )
                evicted = self._active.pop(oldest_id)
        if evicted is not None:
            self._finish(evicted, now, status="evicted")
        return TraceContext(trace_id, span_id)

    def start_span(
        self,
        ctx: Optional[TraceContext],
        name: str,
        parent_id: Optional[int] = None,
        **attributes,
    ) -> Optional[Span]:
        if ctx is None or not self.enabled:
            return None
        now = time.monotonic()
        with self._lock:
            tr = self._active.get(ctx.trace_id)
            if tr is None:
                return None
            span = Span(
                next(self._span_seq), ctx.trace_id, name, now,
                parent_id=parent_id or ctx.root_span_id,
                attributes=dict(attributes),
                thread=threading.current_thread().name,
            )
            tr.spans.append(span)
            tr.touched = now
            return span

    def end_span(self, span: Optional[Span], **attributes) -> None:
        if span is None:
            return
        span.end = time.monotonic()
        if attributes:
            span.attributes.update(attributes)
        tr = self._active.get(span.trace_id)
        if tr is not None:
            tr.touched = span.end

    def span(
        self,
        ctx: Optional[TraceContext],
        name: str,
        parent_id: Optional[int] = None,
        **attributes,
    ):
        """`with tracer.span(ctx, "decision.spf"): ...` — no-op when ctx
        is None / tracing off."""
        sp = self.start_span(ctx, name, parent_id, **attributes)
        if sp is None:
            return _NULL_SPAN
        return _LiveSpan(self, sp)

    def record_span(
        self,
        ctx: Optional[TraceContext],
        name: str,
        start: float,
        end: float,
        parent_id: Optional[int] = None,
        **attributes,
    ) -> Optional[Span]:
        """Retroactively add an already-timed stage (e.g. folding the
        TPU solver's last_timing sync/exec/mat breakdown). start/end are
        time.monotonic() seconds."""
        if ctx is None or not self.enabled:
            return None
        with self._lock:
            tr = self._active.get(ctx.trace_id)
            if tr is None:
                return None
            span = Span(
                next(self._span_seq), ctx.trace_id, name, start,
                parent_id=parent_id or ctx.root_span_id,
                attributes=dict(attributes),
                thread=threading.current_thread().name,
            )
            span.end = end
            tr.spans.append(span)
            tr.touched = max(tr.touched, end)
            return span

    def root_attributes(self, ctx: Optional[TraceContext]) -> dict:
        """Copy of an ACTIVE trace's root-span attributes — how Fib reads
        the origin stamp the KvStore ingress threaded onto the trace.
        Empty dict for None/closed/unknown contexts."""
        if ctx is None or not self.enabled:
            return {}
        with self._lock:
            tr = self._active.get(ctx.trace_id)
            if tr is None:
                return {}
            return dict(tr.spans[0].attributes)

    def trace_start(self, ctx: Optional[TraceContext]) -> Optional[float]:
        """Monotonic start of an ACTIVE trace, or None — anchors the
        latency-budget ledger's ``ingest_wait`` at the KvStore receive
        stamp the ingress passed to start_trace(start=...)."""
        if ctx is None or not self.enabled:
            return None
        with self._lock:
            tr = self._active.get(ctx.trace_id)
            return tr.started if tr is not None else None

    def annotate(self, ctx: Optional[TraceContext], **attributes) -> None:
        """Stamp attributes onto an active trace's root span without
        closing it — e.g. degraded=True when the solver failed over
        mid-flight, so the trace closes carrying the marker."""
        if ctx is None or not self.enabled or not attributes:
            return
        with self._lock:
            tr = self._active.get(ctx.trace_id)
            if tr is None:
                return
            tr.spans[0].attributes.update(attributes)

    def end_trace(
        self, ctx: Optional[TraceContext], status: str = "ok", **attributes
    ) -> None:
        """Close the root span, move the trace to the closed ring, and
        stamp the end-to-end convergence_ms stat (status "ok" only —
        coalesced/no_change closures are not convergence events)."""
        if ctx is None:
            return
        # a pause of the collector inside this trace is copied into it
        # while it is still active
        self.drain_gc()
        now = time.monotonic()
        with self._lock:
            tr = self._active.pop(ctx.trace_id, None)
        if tr is None:
            return
        if attributes:
            tr.spans[0].attributes.update(attributes)
        self._finish(tr, now, status=status)

    def _finish(self, tr: _Trace, now: float, status: str) -> None:
        root = tr.spans[0]
        if root.end is None:
            root.end = now
        tr.ended = now
        tr.status = status
        root.attributes.setdefault("status", status)
        with self._lock:
            self._closed.append(tr)
            if len(self._closed) > MAX_CLOSED_TRACES:
                del self._closed[: len(self._closed) - MAX_CLOSED_TRACES]
        if status == "ok":
            counters.add_stat_value(
                "convergence_ms", (now - tr.started) * 1000.0
            )
            counters.increment("tracing.traces_closed")
        else:
            counters.increment(f"tracing.traces_{status}")

    # -- the background track: holds of the event loop ---------------------

    def hold(self, name: str, **attributes):
        """`with tracer.hold("kvstore.digest", areas=1) as h: ...` — a
        span that belongs to no trace: work on the event loop that no
        event asked for. Filed in the hold ring when it closes, and
        copied into every trace active then (see _file_hold). No-op
        when tracing is off."""
        if not self.enabled:
            return _NULL_SPAN
        return _LiveHold(self, Span(
            next(self._span_seq), 0, name, time.monotonic(),
            attributes=attributes,
            thread=threading.current_thread().name,
        ))

    def record_hold(
        self,
        name: str,
        start: float,
        end: float,
        thread: Optional[str] = None,
        **attributes,
    ) -> Optional[Span]:
        """File an already-timed hold (time.monotonic() seconds).
        `thread` names the thread it ran on where that is not the
        caller's (a collection is filed by whoever drains the hook)."""
        if not self.enabled:
            return None
        if thread is not None:
            attributes["thread"] = thread
        span = Span(
            next(self._span_seq), 0, name, start,
            attributes=attributes,
            thread=thread or threading.current_thread().name,
        )
        span.end = end
        self._file_hold(span)
        return span

    def _file_hold(self, span: Span) -> None:
        """Into the ring, and into every trace the hold overlapped as a
        child of the root, clipped to the trace, attribute hold=True: a
        trace that took 900 ms then says "kvstore.digest 850". The
        traces: the active ones (root start < hold end), and those that
        closed after the hold began — a hold found after the fact (the
        lag probe's) is found just after the trace it delayed has
        closed. A trace takes MAX_HOLD_COPIES at most."""
        with self._lock:
            dropped = len(self._holds) == self._holds.maxlen
            self.holds_dropped += dropped
            self._holds.append((time.monotonic(), span))
            overlapped = list(self._active.values())
            for tr in reversed(self._closed):  # in the order they closed
                if tr.ended <= span.start:
                    break
                overlapped.append(tr)
            for tr in overlapped:
                start = max(span.start, tr.started)
                end = span.end if tr.ended is None else min(
                    span.end, tr.ended
                )
                if start >= end or tr.hold_copies >= MAX_HOLD_COPIES:
                    continue
                tr.hold_copies += 1
                copy = Span(
                    next(self._span_seq), tr.trace_id, span.name, start,
                    parent_id=tr.spans[0].span_id,
                    attributes={**span.attributes, "hold": True},
                    thread=span.thread,
                )
                copy.end = end
                tr.spans.append(copy)  # not a touch: the ring covers it
        if dropped:
            counters.increment("tracing.holds_dropped")

    def get_holds(
        self, since: Optional[float] = None, until: Optional[float] = None
    ) -> list[dict]:
        """The ring's holds that overlap [since, until] (monotonic
        seconds; None = unbounded), oldest filed first, as to_dict()
        gives them. The ring keeps the newest MAX_HOLDS: where
        `holds_dropped` has moved, whatever ended before the first
        entry's end may be missing."""
        self.drain_gc()
        with self._lock:
            holds = list(self._holds)
        return [
            h.to_dict() for _, h in holds
            if (since is None or h.end > since)
            and (until is None or h.start < until)
        ]

    def watch_gc(self) -> None:
        """Hang the collector's hook on gc.callbacks, once however
        often it is asked (every Actor.start asks)."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        """gc.callbacks hook. A collection starts wherever the
        interpreter happens to be — inside this tracer's lock or the
        counter registry's, neither re-entrant — so the hook takes no
        lock and calls nothing that does: two clock reads and two adds,
        and a queue entry for a pause long enough to be a hold. The
        interpreter runs one collection at a time, whichever thread set
        it off, so the fields have one writer."""
        if phase == "start":
            self._gc_t0 = time.monotonic()
            return
        start, end = self._gc_t0, time.monotonic()
        if start is None:  # hung on gc.callbacks in mid-collection
            return
        gen = info["generation"]
        self._gc_runs[gen] += 1
        self._gc_seconds[gen] += end - start
        if end - start >= GC_HOLD_MIN_S:
            self._gc_long.append((
                gen, start, end, info["collected"],
                threading.current_thread().name,
            ))

    def drain_gc(self) -> None:
        """What the hook left, from a place where locks may be taken
        (a heartbeat, a trace's end, a reader): the counters
        runtime.gc.collections / .pause_ms and their .gen2 twins, and a
        runtime.gc hold for each long pause."""
        if self._gc_runs == self._gc_counted[0] and not self._gc_long:
            return
        with self._lock:
            runs, seconds = list(self._gc_runs), list(self._gc_seconds)
            (was_runs, was_seconds) = self._gc_counted
            self._gc_counted = (runs, seconds)
        n = sum(runs) - sum(was_runs)
        if n > 0:
            counters.increment("runtime.gc.collections", n)
            counters.increment(
                "runtime.gc.pause_ms",
                (sum(seconds) - sum(was_seconds)) * 1e3,
            )
        if runs[2] > was_runs[2]:
            counters.increment(
                "runtime.gc.collections.gen2", runs[2] - was_runs[2]
            )
            counters.increment(
                "runtime.gc.pause_ms.gen2",
                (seconds[2] - was_seconds[2]) * 1e3,
            )
        while self._gc_long:
            try:
                gen, start, end, collected, thread = self._gc_long.popleft()
            except IndexError:  # another drainer took the last
                break
            self.record_hold(
                "runtime.gc", start, end, thread=thread,
                generation=gen, collected=collected,
            )

    def note_loop_lag(self, actor: str, due: float, now: float) -> None:
        """An actor's heartbeat was due at `due` and ran at `now`: the
        loop was not free in between. Whatever of [due, now] nothing
        accounts for becomes a runtime.unnamed_hold. What accounts for
        time: the holds of any thread (a collection stops the
        interpreter whichever thread set it off), and the closed spans
        that active and just-closed traces recorded on this, the loop's,
        thread — a 160 ms decision.rib_diff is work, not a hold. A span
        still open cannot be what held the loop (its coroutine is
        suspended, or this callback would not run), and a span marked
        wait=True (decision.debounce) timed a wait with the loop free.
        A lower bound: a hold is seen from the first beat due inside it,
        so up to one heartbeat interval of its start is not. Of the
        many actors of one loop, all late by the same hold, only those
        whose beat reaches further back than what was looked at look
        again."""
        if not self.enabled:
            return
        lo, hi = self._lag_seen
        if lo - UNNAMED_HOLD_MIN_S <= due and now <= hi + UNNAMED_HOLD_MIN_S:
            return  # another actor's beat has looked at this stretch
        self._lag_seen = (min(lo, due) if due <= hi else due, now)
        self.drain_gc()
        me = threading.current_thread().name
        with self._lock:
            cover = []
            for filed, h in reversed(self._holds):
                if filed < due:
                    break  # it ended by then, and so did all before it
                if h.end > due and h.start < now:
                    cover.append((h.start, h.end))
            traces = [
                tr for tr in self._active.values() if tr.touched > due
            ]
            for tr in reversed(self._closed):
                if tr.ended is None or tr.ended < due:
                    break
                traces.append(tr)
            for tr in traces:
                cover.extend(
                    (sp.start, sp.end) for sp in tr.spans[1:]
                    if sp.end is not None and sp.thread == me
                    and sp.end > due and sp.start < now
                    and not sp.attributes.get("wait")
                )
        cover.sort()
        at = due
        for start, end in cover + [(now, now)]:
            if start - at >= UNNAMED_HOLD_MIN_S:
                self.record_hold(
                    "runtime.unnamed_hold", at, start,
                    actor=actor, lag_ms=(now - due) * 1e3,
                )
            at = max(at, end)

    # -- introspection (ctrl server / breeze) -----------------------------

    def get_traces(
        self,
        limit: int = 20,
        trace_id: Optional[int] = None,
        include_active: bool = False,
    ) -> list[dict]:
        with self._lock:
            picked: list[_Trace] = list(self._closed)
            if include_active:
                picked += list(self._active.values())
        if trace_id is not None:
            picked = [t for t in picked if t.trace_id == trace_id]
        picked = picked[-max(1, limit):]
        return [
            {
                "trace_id": t.trace_id,
                "name": t.name,
                "status": t.status,
                "duration_ms": (
                    (t.ended - t.started) * 1000.0
                    if t.ended is not None else None
                ),
                "num_spans": len(t.spans),
                "spans": [s.to_dict() for s in t.spans],
            }
            for t in picked
        ]

    def export_chrome(
        self, trace_id: Optional[int] = None, limit: int = 20
    ) -> dict:
        """Chrome trace-event JSON (the `{"traceEvents": [...]}` object
        form): one "X" complete event per closed span with ts/dur in
        wall-clock µs, plus "M" thread_name metadata rows. Load in
        chrome://tracing or ui.perfetto.dev. The holds of the event loop
        that overlap the exported traces (all of the ring where no trace
        is exported) go in a process lane of their own, "loop holds"."""
        self.drain_gc()
        with self._lock:
            picked = [
                t for t in self._closed
                if trace_id is None or t.trace_id == trace_id
            ][-max(1, limit):]
            wall0, mono0 = self._wall_anchor, self._mono_anchor
            lo = min((t.started for t in picked), default=-math.inf)
            hi = max((t.ended for t in picked), default=math.inf)
            holds = [
                h for _, h in self._holds if h.end >= lo and h.start <= hi
            ]
        # one process lane per NODE (the root span's `node` attribute):
        # a stitched fleet trace renders each node's kvstore→decision→fib
        # tree in its own lane; traces without a node attr (e.g.
        # supervisor-restart one-spanners) share a process-named lane
        fallback = f"pid:{os.getpid()}"
        pids: dict[str, int] = {}
        tids: dict[tuple[int, str], int] = {}
        events: list[dict] = []
        lanes = [
            (str(t.spans[0].attributes.get("node") or fallback), t.name,
             t.spans) for t in picked
        ]
        if holds:
            lanes.append(("loop holds", "hold", holds))
        for node, cat, spans in lanes:
            pid = pids.setdefault(node, len(pids) + 1)
            for s in spans:
                if s.end is None:
                    continue
                tid = tids.setdefault(
                    (pid, s.thread or "main"), len(tids) + 1
                )
                ts_us = (wall0 + (s.start - mono0)) * 1e6
                events.append({
                    "name": s.name,
                    "cat": cat,
                    "ph": "X",
                    "ts": ts_us,
                    "dur": max(0.0, (s.end - s.start) * 1e6),
                    "pid": pid,
                    "tid": tid,
                    "args": {
                        "trace_id": s.trace_id,
                        "span_id": s.span_id,
                        "parent_id": s.parent_id,
                        **{
                            k: v for k, v in s.attributes.items()
                            if isinstance(v, (str, int, float, bool))
                            or v is None
                        },
                    },
                })
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": node},
            }
            for node, pid in sorted(pids.items(), key=lambda kv: kv[1])
        ] + [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": thread},
            }
            for (pid, thread), tid in sorted(
                tids.items(), key=lambda kv: kv[1]
            )
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def export_chrome_json(
        self, trace_id: Optional[int] = None, limit: int = 20
    ) -> str:
        return json.dumps(self.export_chrome(trace_id, limit))

    def convergence_summary(self) -> dict:
        """p50/p95/p99/max over the closed-trace ring (status ok) —
        the per-event incremental-convergence view DeltaPath measures."""
        with self._lock:
            raw = [
                (t.ended - t.started) * 1000.0
                for t in self._closed
                if t.status == "ok" and t.ended is not None
            ]
        durs = sorted(raw)
        n = len(durs)

        def pct(q: float) -> float:
            if not n:
                return 0.0
            idx = (q / 100.0) * (n - 1)
            lo, hi = math.floor(idx), math.ceil(idx)
            if lo == hi:
                return float(durs[lo])
            frac = idx - lo
            return durs[lo] * (1.0 - frac) + durs[hi] * frac

        return {
            "count": n,
            "p50_ms": pct(50.0),
            "p95_ms": pct(95.0),
            "p99_ms": pct(99.0),
            "max_ms": durs[-1] if n else 0.0,
            "last_ms": raw[-1] if n else 0.0,
        }

    def clear(self) -> None:
        with self._lock:
            self._active.clear()
            self._closed.clear()
            self._ctx_by_id.clear()
            self._holds.clear()
            self.holds_dropped = 0
            self._lag_seen = (0.0, 0.0)
            self._gc_long.clear()


# the process-wide instance (pattern of runtime.counters.counters)
tracer = Tracer()
