"""Fib actor — route programming agent client.

Role of the reference's openr/fib/Fib.{h,cpp}:

  - RouteState snapshot of desired routes + dirtyPrefixes/dirtyLabels retry
    sets (ref Fib.h:224-247) and FSM AWAITING -> SYNCING -> SYNCED
    (ref Fib.h:262-270)
  - first FULL_SYNC from Decision triggers a full syncFib; later updates
    program incrementally (ref processDecisionRouteUpdate, updateRoutes vs
    syncRoutes)
  - programming failures mark routes dirty; a retry fiber reprograms them
    with exponential backoff (ref retryRoutesSignal, Fib.cpp:118,345-430)
  - optional delayed deletes (route_delete_delay_ms)
  - publishes the PROGRAMMED delta on fibRouteUpdatesQueue — the FIB-ACK
    feature PrefixManager redistribution depends on (ref Main.cpp:381-400)
  - keepAlive: poll agent aliveSince; a restart forces full re-sync
    (ref Fib::keepAlive)
  - perf-event convergence log ring (ref PerfDatabase, Types.thrift:598)
"""

from __future__ import annotations

import asyncio
import collections
import enum
import logging
import time
from typing import Optional

from openr_tpu.config import FibConfig
from openr_tpu.decision.columnar_rib import (
    LazyUnicastRoutes,
    _lookup as _lazy_lookup,
)
from openr_tpu.decision.rib import (
    DecisionRouteUpdate,
    RibMplsEntry,
    RibUnicastEntry,
    RouteUpdateType,
)
from openr_tpu.fib.fib_service import FibServiceBase, FibUpdateError
from openr_tpu.messaging import RQueue, ReplicateQueue
from openr_tpu.runtime.actor import Actor
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.faults import maybe_fail
from openr_tpu.runtime.latency_budget import latency_budget
from openr_tpu.runtime.lifecycle import boot_tracer, freeze_boot_heap
from openr_tpu.runtime.throttle import ExponentialBackoff
from openr_tpu.runtime.tracing import TraceContext, tracer
from openr_tpu.types import (
    InitializationEvent,
    PerfEvents,
    add_perf_event,
    total_perf_duration_ms,
)

log = logging.getLogger(__name__)

CLIENT_ID_OPENR = 786  # ref Platform.thrift FibClient::OPENR


class FibState(enum.IntEnum):
    """ref Fib.h:262-270."""

    AWAITING_UPDATE = 0
    SYNCING = 1
    SYNCED = 2


class RouteState:
    """Desired routes + dirty tracking (ref Fib.h RouteState :224-247)."""

    def __init__(self) -> None:
        self.unicast_routes: dict[str, RibUnicastEntry] = {}
        self.mpls_routes: dict[int, RibMplsEntry] = {}
        self.dirty_prefixes: dict[str, float] = {}  # prefix -> ready-at ts
        self.dirty_labels: dict[int, float] = {}
        self.state = FibState.AWAITING_UPDATE

    def update(self, upd: DecisionRouteUpdate) -> None:
        cols = upd.columns
        if cols is not None and cols.new_mapping is not None:
            # columnar spine: Decision is the sole producer on this
            # queue and delivers in order, so our desired state equals
            # its previous table — swap in the new table's detached
            # lazy snapshot instead of re-keying O(routes) dict slots
            # (and, on the legacy path, forcing the lazy update map)
            self.unicast_routes = cols.new_mapping
        else:
            for prefix, entry in upd.unicast_routes_to_update.items():
                self.unicast_routes[prefix] = entry
            for prefix in upd.unicast_routes_to_delete:
                self.unicast_routes.pop(prefix, None)
        for label, entry in upd.mpls_routes_to_update.items():
            self.mpls_routes[label] = entry
        for label in upd.mpls_routes_to_delete:
            self.mpls_routes.pop(label, None)

    def unicast_route_of(self, prefix: str):
        """Single-route read WITHOUT bulk-forcing a columnar table (the
        dirty-programming path touches O(changed) routes; a plain
        [] would materialize every row of the backing column store)."""
        ur = self.unicast_routes
        if isinstance(ur, LazyUnicastRoutes):
            return _lazy_lookup(ur, prefix)
        return ur.get(prefix)

    def unicast_routes_of(self, prefixes: list) -> list:
        """`unicast_route_of` for many (None where the prefix is no
        route): a columnar table answers from its row index and builds
        what it lacks of them in one call, not a row at a time."""
        ur = self.unicast_routes
        if isinstance(ur, LazyUnicastRoutes):
            return ur.lookup_many(prefixes)
        return [ur.get(p) for p in prefixes]

    def unicast_snapshot(self):
        """Publishable snapshot of the desired unicast table: O(1) for
        a columnar table (detached lazy clone), dict copy otherwise."""
        ur = self.unicast_routes
        if isinstance(ur, LazyUnicastRoutes):
            return ur.snapshot()
        return dict(ur)


class Fib(Actor):
    """ref Fib.h:35."""

    def __init__(
        self,
        node_name: str,
        config: FibConfig,
        fib_service: FibServiceBase,
        route_updates_queue: RQueue,
        fib_route_updates_queue: ReplicateQueue,
        log_sample_queue: Optional[ReplicateQueue] = None,
        retry_initial_backoff_s: float = 0.05,
        retry_max_backoff_s: float = 2.0,
    ):
        super().__init__(f"fib:{node_name}")
        self.node_name = node_name
        self.cfg = config
        self.service = fib_service
        self._route_updates = route_updates_queue
        self._fib_updates_q = fib_route_updates_queue
        self._log_sample_q = log_sample_queue
        self.route_state = RouteState()
        self._retry_backoff = ExponentialBackoff(
            retry_initial_backoff_s, retry_max_backoff_s
        )
        self._retry_signal = None  # asyncio.Event, created on start
        self._agent_alive_since: Optional[float] = None
        self._synced_signalled = False
        self._partial_sync_published = False
        self._pending_perf: Optional[PerfEvents] = None
        # convergence trace awaiting the pass that actually programs
        # (first wins; later ones close as "coalesced", like pending
        # publications do in Decision)
        self._pending_trace: Optional[TraceContext] = None
        # newest Decision solve epoch folded into the pending dirty set
        # (epoch fence attribution: the pass that programs publishes it)
        self._pending_epoch: Optional[int] = None
        # convergence perf-event ring (ref PerfDatabase)
        self.perf_db: collections.deque[PerfEvents] = collections.deque(
            maxlen=32
        )
        # fleet-convergence ack backchannel: set via attach_kvstore so
        # FIB acks for origin-stamped events flood back as TTL'd
        # monitor:conv-ack:<node> keys (None = backchannel off)
        self._kvstore = None

    def attach_kvstore(self, kvstore) -> None:
        self._kvstore = kvstore

    async def on_start(self) -> None:
        self._retry_signal = asyncio.Event()
        # baseline the agent's aliveSince NOW — recording it lazily on the
        # first poll would miss a restart that happens before that poll
        try:
            self._agent_alive_since = await self.service.alive_since()
        # lint: allow(broad-except) agent not up yet is the normal cold
        except Exception:
            pass  # keepalive loop will establish it
        self.add_supervised_task(
            self._route_updates_loop, name=f"{self.name}.updates"
        )
        self.add_supervised_task(self._retry_loop, name=f"{self.name}.retry")
        self.add_supervised_task(
            self._keepalive_loop, name=f"{self.name}.keepalive"
        )

    async def on_fiber_restart(self, task_name: str) -> None:
        """A fiber crash mid-programming leaves the agent's table state
        unknown — force a full re-sync (same recovery as an agent
        restart in the keepalive loop)."""
        if self.route_state.state != FibState.AWAITING_UPDATE:
            self.route_state.state = FibState.SYNCING
        if self._retry_signal is not None:
            self._retry_signal.set()

    # -- main update path (ref processDecisionRouteUpdate) -----------------

    async def _route_updates_loop(self) -> None:
        while True:
            item = await self._route_updates.get()
            if isinstance(item, InitializationEvent):
                continue
            await self.process_decision_route_update(item)

    async def process_decision_route_update(
        self, upd: DecisionRouteUpdate
    ) -> None:
        rs = self.route_state
        ctx = tracer.context_of(upd)
        sp = tracer.start_span(ctx, "fib.diff", node=self.node_name)
        rs.update(upd)
        if upd.solve_epoch is not None:
            self._pending_epoch = upd.solve_epoch
        if upd.perf_events is not None:
            add_perf_event(upd.perf_events, self.node_name, "FIB_RECEIVED")

        if rs.state == FibState.AWAITING_UPDATE:
            tracer.end_span(sp)
            if upd.type != RouteUpdateType.FULL_SYNC:
                # folded into Decision's initial snapshot; not a
                # convergence event of its own
                tracer.end_trace(ctx, status="pre_sync")
                latency_budget.discard_trace(ctx)
                return  # wait for Decision's initial snapshot
            bud = latency_budget.of_trace(ctx)
            if bud is not None:
                bud.advance("payload_apply")
            rs.state = FibState.SYNCING
            await self._sync_routes(upd.perf_events, trace=ctx)
            return

        # SYNCED (or SYNCING retry pending): program incrementally
        now = time.monotonic()
        delete_delay = self.cfg.route_delete_delay_ms / 1e3
        for prefix in upd.unicast_routes_to_update:
            rs.dirty_prefixes[prefix] = now
        for prefix in upd.unicast_routes_to_delete:
            rs.dirty_prefixes[prefix] = now + delete_delay
        for label in upd.mpls_routes_to_update:
            rs.dirty_labels[label] = now
        for label in upd.mpls_routes_to_delete:
            rs.dirty_labels[label] = now + delete_delay
        tracer.end_span(sp)
        bud = latency_budget.of_trace(ctx)
        if bud is not None:
            # queue hop from Decision plus the fib diff / dirty-marking
            bud.advance("payload_apply")
        self._pending_perf = upd.perf_events
        if ctx is not None:
            if self._pending_trace is None:
                self._pending_trace = ctx
            else:
                tracer.end_trace(ctx, status="coalesced")
                latency_budget.discard_trace(ctx)
        self._retry_signal.set()

    # -- full sync (ref syncRoutes) ----------------------------------------

    async def _sync_routes(
        self,
        perf: Optional[PerfEvents] = None,
        trace: Optional[TraceContext] = None,
    ) -> None:
        rs = self.route_state
        if trace is None:
            # retry path: adopt the pending trace so the sync that
            # finally lands closes the right convergence event
            trace, self._pending_trace = self._pending_trace, None
        sp = tracer.start_span(
            trace, "platform.program", node=self.node_name, mode="full_sync"
        )
        t_prog = time.monotonic()
        prog0 = self._service_program_ms()
        # what is handed to the service is built under .build, the
        # awaited calls run under .write; _end_program closes whichever
        # is open when a failure leaves early
        parent = sp.span_id if sp is not None else None
        stages = [tracer.start_span(
            trace, "platform.program.build", parent_id=parent
        )]
        # both tables are always attempted — a partial unicast failure must
        # not leave pending MPLS routes unprogrammed (ref syncRoutes covers
        # both with retry)
        failed_p: set = set()
        failed_l: set = set()
        try:
            # chaos seam: a programming failure here must land in the
            # existing retry-with-backoff machinery below
            maybe_fail("fib.program", span=sp)
            batch = None
            if getattr(self.service, "supports_columns", False):
                from openr_tpu.decision.column_delta import (
                    build_column_batch,
                )

                batch = build_column_batch(rs.unicast_routes)
            unicast = (
                list(rs.unicast_routes.values()) if batch is None else batch
            )
            mpls = list(rs.mpls_routes.values())
            tracer.end_span(stages[0], routes=len(unicast) + len(mpls))
            stages.append(tracer.start_span(
                trace, "platform.program.write", parent_id=parent
            ))
            if batch is not None:
                # columnar spine: the desired table ships as packed
                # arrays — no per-route objects between here and the
                # dataplane's bulk transaction
                counters.increment("fib.column_syncs")
                await self.service.sync_fib_columns(CLIENT_ID_OPENR, batch)
            else:
                await self.service.sync_fib(CLIENT_ID_OPENR, unicast)
        except FibUpdateError as e:
            failed_p.update(e.failed_prefixes)
            failed_l.update(e.failed_labels)
        except Exception as e:
            log.warning("%s: syncFib failed: %s", self.name, e)
            counters.increment("fib.sync_fib_failure")
            self._end_program(
                sp, t_prog, ok=False, trace=trace, prog0=prog0,
                stages=stages,
            )
            self._park_trace(trace)
            self._schedule_retry()
            return
        try:
            await self.service.sync_mpls_fib(CLIENT_ID_OPENR, mpls)
        except FibUpdateError as e:
            failed_p.update(e.failed_prefixes)
            failed_l.update(e.failed_labels)
        except Exception as e:
            log.warning("%s: syncMplsFib failed: %s", self.name, e)
            counters.increment("fib.sync_fib_failure")
            self._end_program(
                sp, t_prog, ok=False, trace=trace, prog0=prog0,
                stages=stages,
            )
            self._park_trace(trace)
            # the unicast sync already ran: publish the unicast routes that
            # DID land as an INCREMENTAL delta (additive — it must not
            # claim snapshot completeness while the MPLS table state is
            # unknown), once per failure episode so persistent failures
            # don't re-flood subscribers every backoff tick. State stays
            # SYNCING, so the retry re-runs the full sync including MPLS;
            # no dirty-marking needed (SYNCING retries never take the
            # dirty-route path).
            if not self._partial_sync_published:
                self._partial_sync_published = True
                self._publish_programmed(
                    DecisionRouteUpdate(
                        type=RouteUpdateType.INCREMENTAL,
                        unicast_routes_to_update={
                            p: r
                            for p, r in rs.unicast_routes.items()
                            if p not in failed_p
                        },
                    ),
                    perf,
                )
            self._schedule_retry()
            return
        if failed_p or failed_l:
            # partial: only the failed subset stays dirty; publish ONLY what
            # actually landed (FIB-ACK must never claim unprogrammed routes)
            self._end_program(
                sp, t_prog, ok=False, trace=trace, prog0=prog0,
                stages=stages,
            )
            now = time.monotonic()
            for p in failed_p:
                rs.dirty_prefixes[p] = now
            for label in failed_l:
                rs.dirty_labels[label] = now
            self._finish_sync(
                perf,
                unicast={
                    p: r
                    for p, r in rs.unicast_routes.items()
                    if p not in failed_p
                },
                mpls={
                    label: r
                    for label, r in rs.mpls_routes.items()
                    if label not in failed_l
                },
                trace=trace,
            )
            self._schedule_retry()
            return
        self._end_program(
            sp, t_prog, ok=True, trace=trace, prog0=prog0,
            stages=stages,
        )
        rs.dirty_prefixes.clear()
        rs.dirty_labels.clear()
        self._retry_backoff.report_success()
        self._finish_sync(
            perf,
            unicast=rs.unicast_snapshot(),
            mpls=dict(rs.mpls_routes),
            trace=trace,
        )

    def _end_program(
        self,
        sp,
        t_prog: float,
        ok: bool,
        trace: Optional[TraceContext] = None,
        prog0: Optional[float] = None,
        stages=(),
    ) -> None:
        for stage in stages:
            if stage is not None and stage.end is None:
                tracer.end_span(stage)
        tracer.end_span(sp, ok=ok)
        counters.add_stat_value(
            "fib.program_ms", (time.monotonic() - t_prog) * 1000.0
        )
        bud = latency_budget.of_trace(trace)
        if bud is None:
            return
        # budget: when the dataplane handlers self-report their write
        # time (RemoteFibService.program_ms_total), split the segment
        # into the netlink write proper vs RPC/ack overhead; otherwise
        # the whole segment is programming
        dp_ms = None
        if prog0 is not None:
            total = getattr(self.service, "program_ms_total", None)
            if total is not None:
                dp_ms = max(0.0, float(total) - prog0)
        if dp_ms is not None:
            bud.advance_split({"program": dp_ms}, primary="ack_rtt")
        else:
            bud.advance("program")

    def _service_program_ms(self) -> Optional[float]:
        total = getattr(self.service, "program_ms_total", None)
        return float(total) if total is not None else None

    def _park_trace(self, trace: Optional[TraceContext]) -> None:
        """Hold the trace for the retry that eventually programs."""
        if trace is None:
            return
        if self._pending_trace is None:
            self._pending_trace = trace
        else:
            tracer.end_trace(trace, status="coalesced")
            latency_budget.discard_trace(trace)

    def _finish_sync(
        self,
        perf: Optional[PerfEvents],
        unicast,  # dict or LazyUnicastRoutes snapshot (columnar spine)
        mpls: dict[int, RibMplsEntry],
        trace: Optional[TraceContext] = None,
    ) -> None:
        rs = self.route_state
        rs.state = FibState.SYNCED
        self._partial_sync_published = False
        counters.increment("fib.full_sync")
        self._publish_programmed(
            DecisionRouteUpdate(
                type=RouteUpdateType.FULL_SYNC,
                unicast_routes_to_update=unicast,
                mpls_routes_to_update=mpls,
                solve_epoch=self._pending_epoch,
            ),
            perf,
            trace=trace,
        )
        if not self._synced_signalled:
            self._synced_signalled = True
            # boot lifecycle: the first programmed RIB closes the boot
            # span tree and stamps boot.first_rib_ms
            boot_tracer.phase_mark(
                "first_fib_program",
                node=self.node_name,
                routes=(
                    len(unicast) if hasattr(unicast, "__len__") else None
                ),
            )
            boot_tracer.complete(node=self.node_name)
            freeze_boot_heap()
            self._fib_updates_q.push(InitializationEvent.FIB_SYNCED)

    # -- dirty-route retry (ref retryRoutes Fib.cpp:345-430) ---------------

    def _schedule_retry(self) -> None:
        self._retry_backoff.report_error()
        counters.increment("fib.route_programming_failure")
        self._retry_signal.set()

    async def _retry_loop(self) -> None:
        while True:
            await self._retry_signal.wait()
            self._retry_signal.clear()
            rs = self.route_state
            # honor backoff after failures
            delay = self._retry_backoff.time_until_retry_s()
            if delay > 0:
                await asyncio.sleep(delay)
            if rs.state == FibState.SYNCING:
                await self._sync_routes()
                continue
            if not rs.dirty_prefixes and not rs.dirty_labels:
                continue
            # wait for the earliest delayed delete to come due
            now = time.monotonic()
            due_in = [
                ts - now
                for ts in list(rs.dirty_prefixes.values())
                + list(rs.dirty_labels.values())
                if ts > now
            ]
            await self._program_dirty_routes()
            if due_in:
                await asyncio.sleep(max(0.01, min(due_in)))
                self._retry_signal.set()

    async def _program_dirty_routes(self) -> None:
        """Program everything due in the dirty sets; failures stay dirty
        (ref updateRoutes + createUpdate from dirty state)."""
        rs = self.route_state
        now = time.monotonic()
        perf = self._pending_perf
        self._pending_perf = None
        ctx = self._pending_trace
        self._pending_trace = None
        sp = tracer.start_span(
            ctx, "platform.program", node=self.node_name, mode="incremental"
        )
        t_prog = now
        prog0 = self._service_program_ms()
        parent = sp.span_id if sp is not None else None
        stages = [tracer.start_span(
            ctx, "platform.program.build", parent_id=parent
        )]

        # one read of the desired table per due prefix: its route, or
        # None where it is to go (a full result dirties thousands)
        due = [p for p, ts in rs.dirty_prefixes.items() if ts <= now]
        due_routes = rs.unicast_routes_of(due)
        add_prefixes = [p for p, e in zip(due, due_routes) if e is not None]
        del_prefixes = [p for p, e in zip(due, due_routes) if e is None]
        add_unicast = [e for e in due_routes if e is not None]
        add_labels = [
            l
            for l, ts in rs.dirty_labels.items()
            if ts <= now and l in rs.mpls_routes
        ]
        del_labels = [
            l
            for l, ts in rs.dirty_labels.items()
            if ts <= now and l not in rs.mpls_routes
        ]
        add_mpls = [rs.mpls_routes[l] for l in add_labels]
        programmed = DecisionRouteUpdate(
            type=RouteUpdateType.INCREMENTAL,
            solve_epoch=self._pending_epoch,
        )
        tracer.end_span(
            stages[0],
            routes=len(add_prefixes) + len(del_prefixes)
            + len(add_labels) + len(del_labels),
        )
        stages.append(tracer.start_span(
            ctx, "platform.program.write", parent_id=parent
        ))
        ok = True
        try:
            # chaos seam: everything due stays dirty and retries
            maybe_fail("fib.program", span=sp)
            if add_prefixes:
                await self.service.add_unicast_routes(
                    CLIENT_ID_OPENR, add_unicast
                )
            for p in add_prefixes:
                rs.dirty_prefixes.pop(p, None)
            # read again: the table may have moved while the write waited
            programmed.unicast_routes_to_update.update(
                zip(add_prefixes, rs.unicast_routes_of(add_prefixes))
            )
        except FibUpdateError as e:
            ok = False
            for p in add_prefixes:
                if p not in e.failed_prefixes:
                    rs.dirty_prefixes.pop(p, None)
                    programmed.unicast_routes_to_update[p] = (
                        rs.unicast_route_of(p)
                    )
        except Exception as e:
            counters.increment("fib.program_error")
            log.warning("%s: add_unicast failed: %s", self.name, e)
            ok = False

        try:
            if del_prefixes:
                await self.service.delete_unicast_routes(
                    CLIENT_ID_OPENR, del_prefixes
                )
            for p in del_prefixes:
                rs.dirty_prefixes.pop(p, None)
                programmed.unicast_routes_to_delete.append(p)
        except FibUpdateError as e:
            # partial failure: successfully-deleted prefixes leave the
            # dirty set and publish their FIB-ACK now; only the failed
            # ones stay dirty for retry (mirrors the add path above)
            ok = False
            for p in del_prefixes:
                if p not in e.failed_prefixes:
                    rs.dirty_prefixes.pop(p, None)
                    programmed.unicast_routes_to_delete.append(p)
        except Exception as e:
            counters.increment("fib.program_error")
            log.warning("%s: delete_unicast failed: %s", self.name, e)
            ok = False

        try:
            if add_labels:
                await self.service.add_mpls_routes(CLIENT_ID_OPENR, add_mpls)
            for l in add_labels:
                rs.dirty_labels.pop(l, None)
                programmed.mpls_routes_to_update[l] = rs.mpls_routes[l]
        except FibUpdateError as e:
            ok = False
            for l in add_labels:
                if l not in e.failed_labels:
                    rs.dirty_labels.pop(l, None)
                    programmed.mpls_routes_to_update[l] = rs.mpls_routes[l]
        except Exception as e:
            counters.increment("fib.program_error")
            log.warning("%s: add_mpls failed: %s", self.name, e)
            ok = False

        try:
            if del_labels:
                await self.service.delete_mpls_routes(CLIENT_ID_OPENR, del_labels)
            for l in del_labels:
                rs.dirty_labels.pop(l, None)
                programmed.mpls_routes_to_delete.append(l)
        except FibUpdateError as e:
            ok = False
            for l in del_labels:
                if l not in e.failed_labels:
                    rs.dirty_labels.pop(l, None)
                    programmed.mpls_routes_to_delete.append(l)
        except Exception as e:
            counters.increment("fib.program_error")
            log.warning("%s: delete_mpls failed: %s", self.name, e)
            ok = False

        self._end_program(
            sp, t_prog, ok=ok, trace=ctx, prog0=prog0, stages=stages
        )
        if not programmed.empty():
            self._publish_programmed(programmed, perf, trace=ctx)
        else:
            # nothing landed this pass (backoff / delayed deletes not
            # due): hold the trace for the pass that actually programs
            self._park_trace(ctx)
        if ok:
            self._retry_backoff.report_success()
        else:
            self._schedule_retry()

    # -- programmed-delta publication (FIB-ACK) ----------------------------

    def _publish_programmed(
        self,
        programmed: DecisionRouteUpdate,
        perf: Optional[PerfEvents],
        trace: Optional[TraceContext] = None,
    ) -> None:
        # the programmed update's bookkeeping, the push of the ack and
        # the conv-ack write; ends before the trace does
        pub_sp = tracer.start_span(trace, "fib.publish", node=self.node_name)
        if perf is not None:
            add_perf_event(perf, self.node_name, "FIB_PROGRAMMED")
            programmed.perf_events = perf
            self.perf_db.append(perf)
            duration_ms = total_perf_duration_ms(perf)
            counters.add_stat_value("fib.convergence_time_ms", duration_ms)
            if self._log_sample_q is not None:
                from openr_tpu.runtime.monitor import LogSample

                self._log_sample_q.push(
                    LogSample(
                        event="ROUTE_CONVERGENCE",
                        node_name=self.node_name,
                        values={
                            "duration_ms": duration_ms,
                            "unicast_routes": len(
                                programmed.unicast_routes_to_update
                            ),
                        },
                    )
                )
        counters.increment("fib.routes_programmed")
        if programmed.solve_epoch is not None:
            # the ack attributes to the NEWEST epoch this pass folded
            # in; the gauge makes programmed-epoch monotonicity (the
            # fence property: a stale batch is never programmed)
            # observable from tests and the chaos drill
            counters.set_counter("fib.solve_epoch", programmed.solve_epoch)
            self._pending_epoch = None
        self._fib_updates_q.push(programmed, trace=trace)
        # latency budget: the ack is out — close the epoch's ledger with
        # the tail attributed to ack_rtt, enforcing the conservation
        # invariant; the dominant component rides the conv-ack and the
        # trace so the fleet join can name the straggler STAGE
        budget_row = latency_budget.close_trace(
            trace, status="ok", final_component="ack_rtt"
        )
        top_comp, top_ms = "", 0.0
        if budget_row is not None:
            top_comp = budget_row["top_component"]
            top_ms = budget_row["top_ms"]
        # fleet-convergence ack: a trace stitched to an origin event
        # reports (origin_event_id, this node, origin->ack latency) back
        # through the kvstore backchannel BEFORE the trace closes (the
        # stamp lives on the active trace's root attributes)
        attrs = tracer.root_attributes(trace)
        event_id = attrs.get("origin_event_id")
        if event_id is not None and self._kvstore is not None:
            origin_ts = attrs.get("origin_ts_ms")
            fleet_ms = (
                max(0.0, time.time() * 1000.0 - float(origin_ts))
                if origin_ts is not None
                else 0.0
            )
            counters.add_stat_value("fleet_convergence_ms", fleet_ms)
            try:
                self._kvstore.record_convergence_ack(
                    area=str(attrs.get("area") or "0"),
                    origin_node=str(attrs.get("origin_node") or ""),
                    origin_event_id=str(event_id),
                    fleet_convergence_ms=fleet_ms,
                    component=top_comp,
                    component_ms=top_ms,
                )
            # lint: allow(broad-except) the ack is telemetry — it must
            # never take down route programming
            except Exception:
                counters.increment("fib.conv_ack_failures")
        # programming ack published: the topology event has converged
        end_attrs = {}
        if programmed.solve_epoch is not None:
            end_attrs["solve_epoch"] = programmed.solve_epoch
        if top_comp:
            end_attrs["budget_top"] = top_comp
            end_attrs["budget_top_ms"] = round(top_ms, 3)
        tracer.end_span(pub_sp)
        tracer.end_trace(
            trace,
            status="ok",
            routes=len(programmed.unicast_routes_to_update)
            + len(programmed.unicast_routes_to_delete),
            **end_attrs,
        )

    # -- agent liveness (ref Fib::keepAlive) -------------------------------

    async def _keepalive_loop(self) -> None:
        while True:
            await asyncio.sleep(0.2)
            try:
                alive = await self.service.alive_since()
            except Exception:
                # an unreachable agent is a normal transient here; the
                # counter (not a log line every 200 ms) is the signal
                counters.increment("fib.keepalive_failure")
                continue
            if self._agent_alive_since is None:
                self._agent_alive_since = alive
            elif alive != self._agent_alive_since:
                # agent restarted: wipe assumptions, full re-sync
                log.warning("%s: fib agent restarted; re-syncing", self.name)
                self._agent_alive_since = alive
                if self.route_state.state != FibState.AWAITING_UPDATE:
                    self.route_state.state = FibState.SYNCING
                    self._retry_signal.set()

    # -- module API (ref Fib.h:140-180) ------------------------------------

    async def get_route_db(self) -> dict[str, RibUnicastEntry]:
        return dict(self.route_state.unicast_routes)

    async def get_mpls_route_db(self) -> dict[int, RibMplsEntry]:
        return dict(self.route_state.mpls_routes)

    async def get_perf_db(self) -> list[PerfEvents]:
        return list(self.perf_db)

    async def get_route_detail(self, prefix: str) -> dict:
        """Programmed-state view of one prefix — joined into
        ctrl.decision.explain so provenance answers both "which event
        produced this route" and "did it actually land in the agent"."""
        rs = self.route_state
        return {
            "desired": prefix in rs.unicast_routes,
            "dirty": prefix in rs.dirty_prefixes,
            "fib_state": rs.state.name,
            "synced": self.synced,
        }

    @property
    def synced(self) -> bool:
        return (
            self.route_state.state == FibState.SYNCED
            and not self.route_state.dirty_prefixes
            and not self.route_state.dirty_labels
        )
