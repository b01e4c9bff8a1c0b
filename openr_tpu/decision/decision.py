"""Decision actor — route computation orchestration.

Role of the reference's openr/decision/Decision.{h,cpp} (:130):

  - consumes KvStore publications (kvStoreUpdatesQueue), parses "adj:" /
    "prefix:" keys into per-area LinkState + global PrefixState
    (ref Decision.cpp:731,743,767 updateKeyInLsdb/processPublication)
  - applies the ordered cold-boot adjacency filter: an adjacency marked
    adj_only_used_by_other_node is visible only to that other node
    (ref Decision.cpp:567-644)
  - batches via DecisionPendingUpdates + AsyncDebounce (debounce_min..max)
    (ref Decision.h:40-108,328)
  - full rebuild vs per-prefix incremental (ref rebuildRoutes :919-996)
  - initialization gating: first route build waits for KVSTORE_SYNCED
    (ref unblockInitialRoutesBuild :998-1016)
  - applies RibPolicy, emits DecisionRouteUpdate FULL_SYNC/INCREMENTAL to
    routeUpdatesQueue; consumes static routes from PrefixManager
    (staticRouteUpdatesQueue, ref processStaticRoutesUpdate :873)
  - runtime-selectable solver backend: "cpu" (SpfSolver oracle) or "tpu"
    (batched JAX pipeline) behind the same build_route_db interface — the
    DecisionTpuPlugin boundary (ref openr/plugin/Plugin.h:19-44).
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import logging
import time
from dataclasses import dataclass, field
from typing import Optional

from openr_tpu.config import DecisionConfig
from openr_tpu.decision.columnar_rib import LazyUnicastRoutes
from openr_tpu.decision.link_state import LinkState, LinkStateChange
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.rib import (
    DecisionRouteDb,
    DecisionRouteUpdate,
    ProvenanceLedger,
    RouteProvenance,
    RouteUpdateType,
)
from openr_tpu.decision.rib_digest import (
    GENESIS,
    as_counter_value,
    delta_digest,
    roll,
)
from openr_tpu.decision.rib_policy import RibPolicy
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.messaging import RQueue, ReplicateQueue
from openr_tpu.runtime.actor import Actor
from openr_tpu.runtime.faults import maybe_fail
from openr_tpu.runtime.lifecycle import boot_tracer
from openr_tpu.serde import from_plain, to_plain
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.latency_budget import latency_budget
from openr_tpu.runtime.overload import FlapDamper, OverloadController
from openr_tpu.runtime.overload import register as overload_register
from openr_tpu.runtime.overload import unregister as overload_unregister
from openr_tpu.runtime.replay_log import ReplayRecorder
from openr_tpu.runtime.replay_log import register as replay_register
from openr_tpu.runtime.throttle import AsyncDebounce, ExponentialBackoff
from openr_tpu.runtime.tracing import TraceContext, tracer
from openr_tpu.serde import deserialize, serialize
from openr_tpu.types import (
    Adjacency,
    AdjacencyDatabase,
    InitializationEvent,
    PerfEvents,
    PrefixDatabase,
    PrefixEntry,
    Publication,
    add_perf_event,
    adj_key,
    parse_adj_key,
    parse_prefix_key,
    prefix_key,
    replace,
)

log = logging.getLogger(__name__)


@dataclass
class PendingUpdates:
    """Batched dirty state between debounced rebuilds
    (ref DecisionPendingUpdates, Decision.h:40-108)."""

    needs_full_rebuild: bool = False
    updated_prefixes: set[str] = field(default_factory=set)
    count: int = 0
    perf_events: Optional[PerfEvents] = None
    # at most ONE trace context survives debounce coalescing (first
    # wins); later publications' contexts are closed as "coalesced" so
    # a burst doesn't multiply spans across one rebuild
    trace: Optional[TraceContext] = None
    # provenance: per-prefix (kv_key, originator, area) tags and the
    # last topology event ingested into THIS batch — they ride the
    # snapshot through async dispatch so a coalesced solve still stamps
    # routes with the event that actually changed them
    provenance_tags: dict[str, tuple] = field(default_factory=dict)
    topo_tag: Optional[tuple] = None
    # replay recorder (runtime/replay_log.py): the event-ring cursor at
    # this batch's solve-read and, when a snapshot anchor came due, the
    # pending anchor — both captured in _begin_rebuild and committed in
    # _finish_rebuild, riding the batch so an epoch whose solve leaves
    # the loop (async_dispatch) keeps its own boundaries
    replay_cursor: int = 0
    replay_snapshot: Optional[dict] = None
    # time.monotonic() of the first rebuild trigger this batch raised
    # (the decision.debounce span's start); None until one fires
    first_trigger: Optional[float] = None

    def apply_link_state_change(
        self, change: LinkStateChange, node_name: str
    ) -> None:
        self.count += 1
        if change.topology_changed or change.link_attributes_changed:
            self.needs_full_rebuild = True

    def apply_prefix_changes(self, changed: set[str]) -> None:
        if changed:
            self.count += 1
            self.updated_prefixes |= changed

    def reset(self) -> None:
        self.needs_full_rebuild = False
        self.updated_prefixes = set()
        self.count = 0
        self.perf_events = None
        self.trace = None
        self.provenance_tags = {}
        self.topo_tag = None
        self.replay_cursor = 0
        self.replay_snapshot = None


# TpuSpfSolver-only constructor arguments the Decision actor fills in
# from DecisionConfig; the CPU oracle takes none of them
_DEVICE_SOLVER_KWARGS = (
    "xla_cache_dir", "enable_numerical_sentinels", "fuse_n_cap",
    "incremental_spf", "incremental_cone_frac",
    "multichip_n_cap_threshold", "multichip_batch", "spf_kernel",
    "transfer_guard", "aot_cache_dir", "aot_speculate",
)


def make_solver(
    node_name: str, backend: str, small_graph_nodes: int = 0, **kwargs
):
    """The solver-backend hook (role of the plugin boundary). "tpu" and
    "auto" are both the device solver; "auto" additionally routes
    graphs below small_graph_nodes to the CPU oracle (a device launch +
    result pull has a fixed cost that dwarfs small solves). A device
    backend that cannot initialize raises HERE, at construction —
    never a silent CPU oracle under a device label."""
    if backend == "cpu":
        for k in _DEVICE_SOLVER_KWARGS:
            kwargs.pop(k, None)
        return SpfSolver(node_name, **kwargs)
    if backend in ("tpu", "auto"):
        import jax

        from openr_tpu.decision.tpu_solver import TpuSpfSolver

        jax.devices()  # backend init failure surfaces now, with its message
        if backend == "auto":
            kwargs.setdefault("small_graph_nodes", small_graph_nodes)
        return TpuSpfSolver(node_name, **kwargs)
    raise ValueError(f"unknown solver backend {backend!r}")


class Decision(Actor):
    """ref Decision.h:130."""

    # deltas at/above this many routes provenance-stamp as one ledger
    # layer instead of one RouteProvenance per prefix (columnar spine)
    _BULK_STAMP_MIN = 4096

    def __init__(
        self,
        node_name: str,
        config: DecisionConfig,
        kvstore_updates_queue: RQueue,
        static_routes_queue: Optional[RQueue],
        route_updates_queue: ReplicateQueue,
        solver_backend: Optional[str] = None,
        solver_kwargs: Optional[dict] = None,
        persistent_store=None,
        log_sample_queue=None,
    ):
        super().__init__(f"decision:{node_name}")
        # crash-safe RibPolicy home (ref FLAGS_rib_policy_file role;
        # Decision.cpp:646-728 save/load with absolute-TTL adjustment)
        self._store = persistent_store
        self.node_name = node_name
        self.cfg = config
        self._kvstore_updates = kvstore_updates_queue
        self._static_routes = static_routes_queue
        self._route_updates_q = route_updates_queue
        # push side of the Monitor's LogSample queue (optional): the
        # sentinel anomaly path emits a structured event log through it
        self._log_samples = log_sample_queue

        self.area_link_states: dict[str, LinkState] = {}
        self.prefix_state = PrefixState()
        backend = solver_backend or config.solver_backend
        skw = dict(solver_kwargs or {})
        if config.enable_lfa:
            skw.setdefault("enable_lfa", True)
        if backend != "cpu":
            # "" -> default resolution (ops/xla_cache.py); "off" disables
            skw.setdefault("xla_cache_dir", config.xla_cache_dir or None)
            skw.setdefault(
                "enable_numerical_sentinels",
                config.enable_numerical_sentinels,
            )
            skw.setdefault("fuse_n_cap", config.fuse_n_cap)
            skw.setdefault("incremental_spf", config.incremental_spf)
            skw.setdefault(
                "incremental_cone_frac", config.incremental_cone_frac
            )
            skw.setdefault(
                "multichip_n_cap_threshold",
                config.multichip_n_cap_threshold,
            )
            skw.setdefault("multichip_batch", config.multichip_batch)
            skw.setdefault("spf_kernel", config.spf_kernel)
            skw.setdefault("transfer_guard", config.transfer_guard)
            # "" -> opt-in via $OPENR_TPU_AOT_CACHE (ops/xla_cache.py)
            skw.setdefault("aot_cache_dir", config.aot_cache_dir or None)
            skw.setdefault("aot_speculate", config.aot_speculate)
        self.solver = make_solver(
            node_name,
            backend,
            small_graph_nodes=config.auto_small_graph_nodes,
            **skw,
        )
        self.rib_policy: Optional[RibPolicy] = None

        self.pending = PendingUpdates()
        self.route_db = DecisionRouteDb()
        # gate: no route computation until KvStore initial sync completes
        # (ref initialKvStoreSynced_, Decision.cpp:998-1016)
        self._kvstore_synced = False
        self._first_build_done = False
        self._rebuild_debounced = None  # created on start (needs loop)
        # mid-flight solver failover state: a device/runtime error during
        # a full rebuild flips the node degraded (CPU oracle carries the
        # load) until a canary probe proves the primary healthy again
        self._degraded = False
        self._probe_backoff: Optional[ExponentialBackoff] = None
        # async device dispatch: when cfg.async_dispatch, rebuild_routes
        # only snapshots pending state onto this queue; a dedicated
        # supervised fiber (_dispatch_loop) coalesces and solves, so the
        # actor loop keeps ingesting LSDB events during the device round
        # trip. None = classic inline rebuilds.
        self._solve_q: Optional[asyncio.Queue] = None
        # what-if engine (decision/whatif.py): lazy, device backend only;
        # read-only planning workload riding the solver's resident mirrors
        self._whatif_engine = None
        # route provenance (observatory): prefix -> RouteProvenance side
        # map beside route_db, stamped per delta in _finish_rebuild;
        # _ingest_tags remembers each prefix's last originating kv event
        # across builds (topology-driven full rebuilds change routes
        # whose own advertisement is long past)
        self._provenance = ProvenanceLedger()
        self._ingest_tags: dict[str, tuple] = {}
        self._solve_epoch = 0
        # running seconds in the adjacency filter and in LinkState's
        # update; decision.lsdb_apply.update reports each publication's
        # share as filter_ms / link_state_ms
        self._filter_s = 0.0
        self._link_state_s = 0.0
        # per-epoch RIB digests (decision/rib_digest.py): the delta
        # digest of the last finish plus the rolling session chain —
        # stamped on every convergence trace and exported through the
        # counter fabric as the RIB-level divergence beacon
        self.last_rib_digest = GENESIS
        self._rib_rolling = GENESIS
        # input black-box recorder (runtime/replay_log.py): every
        # consumed publication delta + periodic LSDB snapshot anchors +
        # the per-epoch digest ledger, exported as the flight-recorder
        # `inputs` annex so incidents replay offline (tools/replay.py)
        self._replay: Optional[ReplayRecorder] = None
        if config.replay_recorder:
            self._replay = replay_register(ReplayRecorder(
                node_name,
                ring=config.replay_ring,
                snapshot_every=config.replay_snapshot_every_epochs,
                meta=self._replay_meta(backend),
            ))
        # overload control (runtime/overload.py): the process-wide
        # state ladder + per-key flap damper. Decision owns the
        # controller (it watches Decision's queue and enacts the
        # solver rungs); the Monitor and KvStore reach it through the
        # per-node registry to feed memory/SLO signals and defer
        # probes. None = the whole layer is off (bisection
        # kill-switch).
        self._overload: Optional[OverloadController] = None
        if config.overload_control:
            self._overload = overload_register(OverloadController(
                node_name,
                queue_watermark=config.overload_queue_watermark,
                coalesce_max_ms=config.overload_coalesce_max_ms,
                hbm_high_frac=config.overload_hbm_high_frac,
                hbm_clear_frac=config.overload_hbm_clear_frac,
                rss_high_mb=config.overload_rss_high_mb,
                rss_clear_mb=config.overload_rss_clear_mb,
                dwell_s=config.overload_dwell_s,
                damper=FlapDamper(
                    half_life_s=config.overload_damping_half_life_s,
                    penalty=config.overload_damping_penalty,
                    suppress_threshold=config.overload_damping_suppress,
                    reuse_threshold=config.overload_damping_reuse,
                    max_penalty=config.overload_damping_max_penalty,
                ),
                on_transition=self._on_overload_transition,
            ))
        # shedding overflow: while the ladder sheds, new solve
        # requests merge here instead of growing the dispatch queue
        # past the watermark; the batch re-enqueues after the next
        # solve completes (work is folded, never dropped)
        self._shed_overflow: Optional[PendingUpdates] = None

    # -- lifecycle ---------------------------------------------------------

    async def on_start(self) -> None:
        self._rebuild_debounced = AsyncDebounce(
            self.cfg.debounce_min_ms / 1e3,
            self.cfg.debounce_max_ms / 1e3,
            self.rebuild_routes,
        )
        self.add_supervised_task(
            self._kvstore_loop, name=f"{self.name}.kvstore"
        )
        if self.cfg.async_dispatch:
            self._solve_q = asyncio.Queue()
            self.add_supervised_task(
                self._dispatch_loop, name=f"{self.name}.dispatch"
            )
        if self._static_routes is not None:
            self.add_supervised_task(
                self._static_loop, name=f"{self.name}.static"
            )
        if self._overload is not None:
            self.add_supervised_task(
                self._overload_tick_loop, name=f"{self.name}.overload"
            )
        self._load_saved_rib_policy()

    async def on_fiber_restart(self, task_name: str) -> None:
        """A crashed ingest fiber may have died mid-apply, and a crashed
        dispatch fiber dies holding a coalesced pending snapshot: the
        LSDB itself is intact in both cases (mutations are synchronous
        on the loop), but batched/queued pending updates may have been
        lost — force a full rebuild so the next debounce re-derives
        routes from scratch."""
        self.pending.needs_full_rebuild = True
        self._trigger_rebuild()

    async def on_stop(self) -> None:
        if self._rebuild_debounced is not None:
            self._rebuild_debounced.cancel()
        if self._degraded:
            # the device-probe timer dies with the actor's loop, so a
            # stopped Decision can never promote — don't leave the
            # process-wide degraded gauge latched at 1
            self._degraded = False
            counters.set_counter("decision.solver.degraded", 0)
        if self._overload is not None:
            overload_unregister(self.node_name)

    # -- queue consumption -------------------------------------------------

    async def _kvstore_loop(self) -> None:
        while True:
            item = await self._kvstore_updates.get()
            # chaos seam: crash the ingest fiber between dequeue and
            # apply — the supervisor drill (restart + full-rebuild
            # recovery) needs a deterministic place to die
            maybe_fail("decision.ingest")
            if isinstance(item, Publication):
                self.process_publication(item)
            elif item == InitializationEvent.KVSTORE_SYNCED:
                self._kvstore_synced = True
                # initial build: force a full rebuild now that the LSDB is
                # complete (ref unblockInitialRoutesBuild)
                self.pending.needs_full_rebuild = True
                self._trigger_rebuild()

    async def _static_loop(self) -> None:
        while True:
            update = await self._static_routes.get()
            self.process_static_routes_update(update)

    def process_static_routes_update(self, update: DecisionRouteUpdate) -> None:
        """PrefixManager-sourced static routes (ref Decision.cpp:873);
        carries prepend-label MPLS routes too (the allocator's local
        label -> next-hop-group bindings)."""
        self.solver.update_static_unicast_routes(
            update.unicast_routes_to_update, update.unicast_routes_to_delete
        )
        if update.mpls_routes_to_update or update.mpls_routes_to_delete:
            self.solver.update_static_mpls_routes(
                update.mpls_routes_to_update, update.mpls_routes_to_delete
            )
            # static MPLS routes merge into the DB only in build_route_db
            # — the incremental branch copies the old mpls dict verbatim,
            # so a label change must force the full path or it never
            # programs (rare event: label allocation churn)
            self.pending.needs_full_rebuild = True
        changed = set(update.unicast_routes_to_update) | set(
            update.unicast_routes_to_delete
        )
        for p in changed:
            # statics have no kv event; tag the source module instead
            self.pending.provenance_tags[p] = ("", "prefix-manager", "")
        self.pending.apply_prefix_changes(changed)
        self._trigger_rebuild()

    # -- publication parsing (ref Decision.cpp:731-844) --------------------

    def process_publication(self, pub: Publication) -> None:
        area = pub.area
        ctx = tracer.context_of(pub)
        before = self.pending.count
        rec = self._replay
        recv_t = pub.recv_t
        # per-key flap damping (runtime/overload.py): every change of
        # an (area, key) pays into its figure of merit BEFORE touching
        # the LSDB; a suppressed key's events are withheld — latest
        # value held for re-ingest at release, recorded with the
        # `suppressed` marker so replay stays bit-identical — while
        # every other key converges at full speed
        damper = (
            self._overload.damper
            if self._overload is not None and self.cfg.overload_damping
            else None
        )
        damped = False
        with tracer.span(
            ctx, "decision.lsdb_apply", node=self.node_name
        ) as apply_sp:
            parent = apply_sp.span.span_id if apply_sp is not None else None
            # the damper's verdict per key, as it arrives: a suppressed
            # key is held and never decoded
            kvs: list[tuple] = []  # (key, value, suppressed)
            for key, value in pub.key_vals.items():
                if value.value is None:
                    continue  # ttl refresh only
                suppressed = damper is not None and damper.record_change(
                    area, key
                )
                if suppressed:
                    damper.hold(area, key, (
                        "kv", value.version, value.originator_id,
                        value.value,
                    ))
                    damped = True
                kvs.append((key, value, suppressed))
            with tracer.span(
                ctx, "decision.lsdb_apply.decode", parent_id=parent
            ) as decode_sp:
                decoded = [
                    None if suppressed else self._decode_key(key, value.value)
                    for key, value, suppressed in kvs
                ]
                if decode_sp is not None:
                    # what the decode was given and what it made, so a
                    # trace says what it cost per adjacency
                    given = [v.value for _, v, held in kvs if not held]
                    decode_sp.set(
                        keys=len(given),
                        bytes=sum(map(len, given)),
                        adjacencies=sum(
                            len(db.adjacencies) for db in decoded
                            if isinstance(db, AdjacencyDatabase)
                        ),
                    )
            filter0, link0 = self._filter_s, self._link_state_s
            with tracer.span(
                ctx, "decision.lsdb_apply.update", parent_id=parent
            ) as update_sp:
                # applied (and recorded) in arrival order, the
                # suppressed keys in their places
                for (key, value, suppressed), db in zip(kvs, decoded):
                    if not suppressed:
                        self._apply_decoded(area, db)
                        self._note_ingest(area, key, value.originator_id)
                    if rec is not None:
                        rec.record_kv(
                            area, key, value.version, value.originator_id,
                            value.value, recv_t, suppressed=suppressed,
                        )
                for key in pub.expired_keys:
                    # a withdrawal is a flap too (RFC 2439 counts both
                    # directions); a suppressed key's expiry is held as
                    # the latest state, not applied
                    if damper is not None and damper.record_change(
                        area, key
                    ):
                        damper.hold(area, key, ("expire",))
                        if rec is not None:
                            rec.record_expired(
                                area, key, recv_t, suppressed=True
                            )
                        damped = True
                        continue
                    self._delete_key_from_lsdb(area, key)
                    self._note_ingest(area, key, "<expired>")
                    if rec is not None:
                        rec.record_expired(area, key, recv_t)
                if update_sp is not None:
                    update_sp.set(
                        keys=len(kvs) + len(pub.expired_keys),
                        filter_ms=(self._filter_s - filter0) * 1e3,
                        link_state_ms=(self._link_state_s - link0) * 1e3,
                    )
        if ctx is not None:
            if self.pending.count == before:
                # nothing route-relevant changed; close so the trace
                # doesn't linger until eviction. A damped event closes
                # with its own status: suppressed churn must not count
                # as either converged or ignored (convergence_ms stays
                # clean)
                tracer.end_trace(
                    ctx, status="damped" if damped else "ignored"
                )
            elif self.pending.trace is None:
                self.pending.trace = ctx
            else:
                tracer.end_trace(ctx, status="coalesced")
        if self.pending.count > 0:
            self._trigger_rebuild()

    def _note_ingest(self, area: str, key: str, originator: str) -> None:
        """Record the originating-event tag for provenance stamping:
        prefix keys tag their prefix directly; adj keys become the
        batch's topology tag (a topology change re-routes prefixes whose
        own advertisement didn't move)."""
        tag = (key, originator, area)
        parsed = parse_prefix_key(key)
        if parsed is not None:
            self.pending.provenance_tags[parsed[2]] = tag
            return
        if parse_adj_key(key) is not None:
            self.pending.topo_tag = tag

    def _update_key_in_lsdb(self, area: str, key: str, raw: bytes) -> None:
        self._apply_decoded(area, self._decode_key(key, raw))

    def _decode_key(self, key: str, raw: bytes):
        """The database a key's value carries, or None where it carries
        none: an erase tombstone (KvStore unset — the withdrawal itself
        arrives via key expiry), a key Decision does not read, a value
        that does not parse."""
        if not raw:
            return None
        if parse_adj_key(key) is not None:
            kind = AdjacencyDatabase
        elif parse_prefix_key(key) is not None:
            kind = PrefixDatabase
        else:
            return None
        try:
            return deserialize(raw, kind)
        except Exception:
            counters.increment("decision.lsdb_parse_errors")
            log.exception(
                "%s: bad %s for %s", self.name,
                "adj db" if kind is AdjacencyDatabase else "prefix db", key,
            )
            return None

    def _apply_decoded(self, area: str, db) -> None:
        if isinstance(db, AdjacencyDatabase):
            self._update_adjacency_db(area, db)
        elif isinstance(db, PrefixDatabase):
            changed = self.prefix_state.update_prefix_database(db)
            self.pending.apply_prefix_changes(changed)

    def _update_adjacency_db(self, area: str, adj_db: AdjacencyDatabase) -> None:
        link_state = self.area_link_states.setdefault(area, LinkState(area))
        t0 = time.perf_counter()
        filtered = self._filter_adj_only_used_by_other_node(adj_db)
        t1 = time.perf_counter()
        change = link_state.update_adjacency_database(filtered)
        self._filter_s += t1 - t0
        self._link_state_s += time.perf_counter() - t1
        if change:
            self.pending.apply_link_state_change(change, adj_db.this_node_name)

    def _filter_adj_only_used_by_other_node(
        self, adj_db: AdjacencyDatabase
    ) -> AdjacencyDatabase:
        """Ordered cold-boot insertion (ref Decision.cpp:567-605): an
        adjacency flagged adj_only_used_by_other_node is dropped unless WE
        are that other node (the restarting node withholds transit use of
        the adjacency until it has programmed routes; its neighbor may use
        it immediately)."""
        if not any(a.adj_only_used_by_other_node for a in adj_db.adjacencies):
            return adj_db
        kept: list[Adjacency] = []
        for adj in adj_db.adjacencies:
            if adj.adj_only_used_by_other_node:
                if adj.other_node_name != self.node_name:
                    continue
                adj = replace(adj, adj_only_used_by_other_node=False)
            kept.append(adj)
        return replace(adj_db, adjacencies=tuple(kept))

    def _delete_key_from_lsdb(self, area: str, key: str) -> None:
        node = parse_adj_key(key)
        if node is not None:
            link_state = self.area_link_states.get(area)
            if link_state is not None:
                change = link_state.delete_adjacency_database(node)
                if change:
                    self.pending.apply_link_state_change(change, node)
            return
        parsed = parse_prefix_key(key)
        if parsed is not None:
            p_node, p_area, p_prefix = parsed
            # expiry withdraws exactly that (node, area, prefix)
            db = PrefixDatabase(
                this_node_name=p_node,
                prefix_entries=(PrefixEntry(prefix=p_prefix),),
                area=p_area,
                delete_prefix=True,
            )
            changed = self.prefix_state.update_prefix_database(db)
            self.pending.apply_prefix_changes(changed)

    # -- rebuild (ref Decision.cpp:919-996) --------------------------------

    def _trigger_rebuild(self) -> None:
        if not self._kvstore_synced:
            return  # initialization gating
        if self.pending.first_trigger is None:
            self.pending.first_trigger = time.monotonic()
        if self._rebuild_debounced is not None:
            self._rebuild_debounced()

    def rebuild_routes(self) -> None:
        if not self._kvstore_synced:
            return
        pending = self.pending
        self.pending = PendingUpdates()
        if pending.first_trigger is not None:
            # wait=True: the loop was free meanwhile, so the lag probe
            # (tracer.note_loop_lag) does not count it as work
            tracer.record_span(
                pending.trace, "decision.debounce",
                pending.first_trigger, time.monotonic(), wait=True,
            )
        if self._solve_q is not None:
            # async dispatch: hand the snapshot to the dispatch fiber
            # and return immediately — the actor loop stays free to
            # ingest LSDB events while the solve is in flight
            ctl = self._overload
            if ctl is not None:
                depth = self._solve_q.qsize()
                ctl.observe(queue_depth=depth)
                if ctl.shed(depth):
                    # shedding rung: past the watermark the snapshot
                    # folds into one overflow batch instead of growing
                    # the queue — bounded depth, and the folded work
                    # still solves (as one epoch) once pressure clears.
                    # The trace closes as "shed" so convergence_ms
                    # never averages in an epoch we chose not to run
                    if pending.trace is not None:
                        latency_budget.discard_trace(pending.trace)
                        tracer.end_trace(pending.trace, status="shed")
                        pending.trace = None
                    if self._shed_overflow is None:
                        self._shed_overflow = pending
                    else:
                        self._shed_overflow = self._merge_pending(
                            self._shed_overflow, pending
                        )
                    return
            self._solve_q.put_nowait(pending)
            counters.set_counter(
                "decision.dispatch.depth", self._solve_q.qsize()
            )
            return
        self._rebuild(pending)

    async def _dispatch_loop(self) -> None:
        """The async dispatch fiber: pending snapshots queue here while
        the actor loop keeps ingesting. Snapshots that arrive during a
        solve (or within the coalesce window) merge into ONE solve —
        superseded requests are never solved separately."""
        while True:
            pending = await self._solve_q.get()
            t_pickup = time.monotonic()
            coalesce_ms = float(self.cfg.dispatch_coalesce_ms)
            ctl = self._overload
            if ctl is not None:
                # adaptive admission: the controller scales the window
                # with queue depth and ladder level — under pressure one
                # solve absorbs more churn, capped at coalesce_max_ms
                ctl.observe(queue_depth=self._solve_q.qsize() + 1)
                coalesce_ms = ctl.coalesce_ms(coalesce_ms)
            if coalesce_ms > 0:
                await asyncio.sleep(coalesce_ms / 1e3)
            while not self._solve_q.empty():
                pending = self._merge_pending(
                    pending, self._solve_q.get_nowait()
                )
                counters.increment("decision.dispatch.coalesced")
            counters.set_counter(
                "decision.dispatch.depth", self._solve_q.qsize()
            )
            # latency budget: the epoch anchors at the trace's KvStore
            # receive stamp; [recv, pickup] is ingest_wait and
            # [pickup, now] the coalesce window (incl. merged deltas)
            bud = latency_budget.begin_for_trace(pending.trace)
            if bud is not None:
                bud.advance("ingest_wait", t_pickup)
                bud.advance("coalesce_hold")
            # chaos seam: crash the dispatch fiber between coalesce and
            # solve — the supervisor drill (restart + full-rebuild
            # recovery, on_fiber_restart) needs a deterministic place
            # to die
            maybe_fail("solver.dispatch")
            counters.increment("decision.dispatch.solves")
            await self._rebuild_async(pending)
            if self._shed_overflow is not None and (
                ctl is None or not ctl.still_shedding(self._solve_q.qsize())
            ):
                # pressure eased: the folded shed batch re-enters the
                # queue as one epoch so no churn is ever lost
                overflow, self._shed_overflow = self._shed_overflow, None
                self._solve_q.put_nowait(overflow)

    @staticmethod
    def _merge_pending(a: PendingUpdates, b: PendingUpdates) -> PendingUpdates:
        a.needs_full_rebuild = a.needs_full_rebuild or b.needs_full_rebuild
        a.updated_prefixes |= b.updated_prefixes
        a.count += b.count
        a.provenance_tags.update(b.provenance_tags)
        if b.topo_tag is not None:
            a.topo_tag = b.topo_tag
        if a.perf_events is None:
            a.perf_events = b.perf_events
        if b.trace is not None:
            if a.trace is None:
                a.trace = b.trace
            else:
                tracer.end_trace(b.trace, status="coalesced")
        return a

    def _begin_rebuild(self, pending: PendingUpdates):
        ctx = pending.trace
        # while degraded every rebuild is a full one on the CPU oracle:
        # the incremental path would still route through the primary
        full = (
            pending.needs_full_rebuild
            or not self._first_build_done
            or self._degraded
        )
        t0 = time.perf_counter()
        if self._replay is not None:
            # this is the one point where LSDB state and event cursor
            # are exactly the solve's input (no await between here and
            # the solver's LSDB read) — capture the epoch boundary, and
            # the snapshot anchor when one is due
            pending.replay_cursor = self._replay.cursor()
            if self._replay.snapshot_due():
                pending.replay_snapshot = self._replay.take_snapshot(
                    self.replay_snapshot_kv()
                )
        spf_sp = tracer.start_span(
            ctx, "decision.spf", node=self.node_name, full=full
        )
        return ctx, spf_sp, full, t0

    def _device_serves(self, pending: PendingUpdates, spf_sp) -> bool:
        """A prefix-only epoch that the device solver takes: the solver
        is the TPU's and says every route of the table is one of its
        rows' (`TpuSpfSolver.serves_prefix_epoch`). It then goes the
        full solve's way, where the solver runs the row stages alone."""
        serves = getattr(self.solver, "serves_prefix_epoch", None)
        if serves is None or not pending.updated_prefixes or not serves(
            self.area_link_states, self.prefix_state,
            pending.updated_prefixes,
        ):
            return False
        if spf_sp is not None:
            spf_sp.attributes["prefix_only"] = True
        return True

    def _incremental_db(self, pending: PendingUpdates) -> DecisionRouteDb:
        # incremental: recompute only changed prefixes. A lazy table is
        # copied as one: its rows stay columns, and the changed prefixes
        # join its host routes (`base`, where a route the device did not
        # compute is counted) and its deletions
        routes = self.route_db.unicast_routes
        lazy = isinstance(routes, LazyUnicastRoutes)
        new_db = DecisionRouteDb(
            unicast_routes=routes.snapshot() if lazy else dict(routes),
            mpls_routes=dict(self.route_db.mpls_routes),
        )
        for prefix in pending.updated_prefixes:
            route = self.solver.create_route_for_prefix_or_get_static(
                self.node_name,
                self.area_link_states,
                self.prefix_state,
                prefix,
            )
            if route is None:
                new_db.unicast_routes.pop(prefix, None)
            elif lazy:
                new_db.unicast_routes.set_host_route(prefix, route)
            else:
                new_db.unicast_routes[prefix] = route
        return new_db

    def _rebuild(self, pending: PendingUpdates) -> None:
        ctx, spf_sp, full, t0 = self._begin_rebuild(pending)
        if full or self._device_serves(pending, spf_sp):
            new_db = self._solve_full(ctx, spf_sp)
        else:
            new_db = self._incremental_db(pending)
        self._finish_rebuild(pending, ctx, spf_sp, t0, new_db, full)

    async def _rebuild_async(self, pending: PendingUpdates) -> None:
        """Dispatch-fiber rebuild: identical to _rebuild except the full
        solve's one blocking host sync runs off-loop (_solve_full_async),
        so LSDB ingestion continues during the device round trip."""
        ctx, spf_sp, full, t0 = self._begin_rebuild(pending)
        if full or self._device_serves(pending, spf_sp):
            new_db = await self._solve_full_async(ctx, spf_sp)
        else:
            new_db = self._incremental_db(pending)
            bud = latency_budget.of_trace(ctx)
            if bud is not None:
                bud.advance("device_exec")
        self._finish_rebuild(pending, ctx, spf_sp, t0, new_db, full)

    def _finish_rebuild(
        self, pending: PendingUpdates, ctx, spf_sp, t0, new_db, full=True
    ) -> None:
        if new_db is None:
            tracer.end_span(spf_sp)
            tracer.end_trace(ctx, status="not_in_lsdb")
            latency_budget.discard_trace(ctx)
            # keep the batch's advertisement memory: these events must
            # still attribute routes once we do appear in the LSDB
            self._ingest_tags.update(pending.provenance_tags)
            if self._replay is not None:
                # no epoch finished: a snapshot anchor captured for this
                # solve has no base epoch — re-arm instead of committing
                self._replay.abort_snapshot(pending.replay_snapshot)
            return  # we are not yet in the LSDB
        tracer.end_span(spf_sp)
        counters.add_stat_value(
            "decision.spf_ms", (time.perf_counter() - t0) * 1e3
        )
        self._fold_solver_timing(ctx, spf_sp)
        self._emit_sentinels(spf_sp)
        self._emit_retraces(spf_sp)

        t_mat = time.perf_counter()
        with tracer.span(
            ctx, "decision.rib_diff", node=self.node_name
        ) as diff_sp:
            if self.rib_policy is not None and self.rib_policy.is_active():
                self.rib_policy.apply_policy(new_db.unicast_routes)

            update = self.route_db.calculate_update(new_db)
            if diff_sp is not None:
                # O(rows) key structures built so far (prefix -> row
                # index, key sets): stands still across warm epochs
                diff_sp.set(key_index_builds=int(
                    counters.get_counter("decision.crib.key_index_builds")
                    or 0
                ))
        counters.add_stat_value(
            "decision.mat_ms", (time.perf_counter() - t_mat) * 1e3
        )
        if getattr(update, "fast_diff", False):
            counters.increment("decision.fast_unicast_diffs")
        update.type = (
            RouteUpdateType.INCREMENTAL
            if self._first_build_done
            else RouteUpdateType.FULL_SYNC
        )
        self.route_db = new_db
        build_ms = (time.perf_counter() - t0) * 1e3
        counters.add_stat_value("decision.route_build_ms", build_ms)
        counters.increment("decision.route_builds")
        self._solve_epoch += 1
        counters.set_counter("decision.solve_epoch", self._solve_epoch)
        update.solve_epoch = self._solve_epoch
        # per-epoch RIB digest: semantic fingerprint of this delta,
        # chained into the rolling session digest — the RIB-level
        # divergence beacon (counter fabric) and the replay harness's
        # bit-identity oracle (trace stamp + recorder ledger)
        t_dig = time.perf_counter()
        digest = delta_digest(update)
        self.last_rib_digest = digest
        self._rib_rolling = roll(self._rib_rolling, digest)
        counters.add_stat_value(
            "decision.rib_digest.compute_ms",
            (time.perf_counter() - t_dig) * 1e3,
        )
        counters.set_counter(
            "decision.rib_digest.epoch", self._solve_epoch
        )
        counters.set_counter(
            "decision.rib_digest.value", as_counter_value(digest)
        )
        counters.set_counter(
            "decision.rib_digest.rolling",
            as_counter_value(self._rib_rolling),
        )
        if spf_sp is not None:
            spf_sp.attributes["rib_digest"] = digest
        tracer.annotate(ctx, rib_digest=digest)
        if self._replay is not None:
            self._replay.record_epoch(
                epoch=self._solve_epoch,
                cursor=pending.replay_cursor,
                digest=digest,
                rolling=self._rib_rolling,
                solver_kind=self._solver_kind(full),
                spf_kernel=self.cfg.spf_kernel,
                full=full,
                snapshot=pending.replay_snapshot,
            )
        self._stamp_provenance(update, pending, full)

        if not self._first_build_done:
            # boot lifecycle (runtime/lifecycle.py): the first solve's
            # compile/device/mat split, then the first RIB delta push
            self._stamp_boot_first_solve(build_ms)
        if not self._first_build_done or not update.empty():
            perf = pending.perf_events or PerfEvents()
            add_perf_event(perf, self.node_name, "ROUTE_UPDATE")
            update.perf_events = perf
            bud = latency_budget.of_trace(ctx)
            if bud is not None:
                # RIB policy + diff + provenance stamping since the
                # solve landed is payload application
                bud.advance("payload_apply")
            self._route_updates_q.push(update, trace=ctx)
        else:
            # rebuild produced no RIB delta: the event converged here
            tracer.end_trace(ctx, status="no_change")
            latency_budget.discard_trace(ctx)
        if not self._first_build_done:
            self._first_build_done = True
            boot_tracer.phase_mark(
                "first_rib_delta",
                node=self.node_name,
                routes=len(new_db.unicast_routes),
                solve_epoch=self._solve_epoch,
            )
            self._route_updates_q.push(InitializationEvent.RIB_COMPUTED)

    # -- route provenance (observatory) ------------------------------------

    def _solver_kind(self, full: bool) -> str:
        """Which machinery materialized this build: "failover-cpu" while
        degraded (the oracle carries the load), "incremental" for the
        per-prefix path AND for full solves where the device dispatched
        the seed-from-previous SSSP kernel, else "full"."""
        if self._degraded:
            return "failover-cpu"
        if not full:
            return "incremental"
        tm = getattr(self.solver, "last_timing", None)
        if isinstance(tm, dict) and tm.get("incremental"):
            return "incremental"
        return "full"

    def _stamp_provenance(
        self, update: DecisionRouteUpdate, pending: PendingUpdates, full: bool
    ) -> None:
        """Tag every route this build changed with its originating
        event. Precedence per prefix: its own advertisement in this
        batch; else (full rebuilds) the batch's topology event; else the
        prefix's last-remembered advertisement from an earlier batch."""
        kind = self._solver_kind(full)
        now_ms = int(time.time() * 1000)
        topo = pending.topo_tag if full else None
        for prefix in update.unicast_routes_to_delete:
            self._provenance.pop(prefix, None)
            self._ingest_tags.pop(prefix, None)
        upd_map = update.unicast_routes_to_update
        if (
            update.columns is not None
            and len(upd_map) >= self._BULK_STAMP_MIN
        ):
            # columnar spine: one ledger LAYER for the whole delta —
            # the tags ride the columns' membership map and the actual
            # RouteProvenance records are built per-prefix on explain,
            # never in bulk on the hot path. Fallback inputs are
            # snapshotted so later ingest-tag mutation can't rewrite
            # history.
            ingest = (
                dict(self._ingest_tags)
                if topo is None and self._ingest_tags
                else None
            )
            self._provenance.stamp_layer(
                upd_map, dict(pending.provenance_tags), topo, ingest,
                self._solve_epoch, kind, now_ms,
            )
        else:
            for prefix in upd_map:
                tag = (
                    pending.provenance_tags.get(prefix)
                    or topo
                    or self._ingest_tags.get(prefix)
                    or ("", "", "")
                )
                self._provenance[prefix] = RouteProvenance(
                    kv_key=tag[0],
                    originator=tag[1],
                    area=tag[2],
                    solve_epoch=self._solve_epoch,
                    solver_kind=kind,
                    ts_ms=now_ms,
                )
        # remember each prefix's own advertisement for future builds
        # (after stamping: a delete+re-advertise in one batch must tag
        # with the new event, not the popped one)
        self._ingest_tags.update(pending.provenance_tags)

    # -- incident replay (runtime/replay_log.py, tools/replay.py) ----------

    def _replay_meta(self, backend: str) -> dict:
        """Recorder annex metadata: config fingerprint + capacity
        signature — enough for the replay harness to flag a bundle
        whose recording config differs from the replaying one."""
        cfg = self.cfg
        fingerprint = hashlib.blake2b(
            json.dumps(
                to_plain(cfg), sort_keys=True, default=str
            ).encode(),
            digest_size=8,
        ).hexdigest()
        return {
            "config_fingerprint": fingerprint,
            "capacity": {
                "fuse_n_cap": cfg.fuse_n_cap,
                "auto_small_graph_nodes": cfg.auto_small_graph_nodes,
                "multichip_n_cap_threshold": (
                    cfg.multichip_n_cap_threshold
                ),
                "multichip_batch": cfg.multichip_batch,
            },
            "solver_backend": backend,
            "spf_kernel": cfg.spf_kernel,
            "incremental_spf": cfg.incremental_spf,
        }

    def replay_snapshot_kv(self) -> dict:
        """Raw kv form of the parsed LSDB for the recorder's snapshot
        anchor: adjacency/prefix databases re-serialized under exactly
        the keys KvStore publishes, so replay ingests the anchor
        through the same deserialize/apply path as live events.
        Versions are synthetic (replay feeds Decision directly — no
        CRDT merge to win)."""
        out: dict[str, dict] = {}
        for area, ls in self.area_link_states.items():
            kvs = out.setdefault(area, {})
            for node, db in ls.get_adjacency_databases().items():
                kvs[adj_key(node)] = (1, node, serialize(db))
        for prefix, entries in self.prefix_state.prefixes().items():
            for (node, p_area), entry in entries.items():
                db = PrefixDatabase(
                    this_node_name=node,
                    prefix_entries=(entry,),
                    area=p_area,
                )
                out.setdefault(p_area, {})[
                    prefix_key(node, p_area, prefix)
                ] = (1, node, serialize(db))
        return out

    async def replay_status(self) -> dict:
        """ctrl.decision.replay payload: digest state + recorder
        health."""
        out = {
            "node": self.node_name,
            "solve_epoch": self._solve_epoch,
            "rib_digest": self.last_rib_digest,
            "rolling_digest": self._rib_rolling,
        }
        if self._replay is not None:
            out["recorder"] = self._replay.status()
        else:
            out["recorder"] = {"enabled": False}
        return out

    # -- overload control (runtime/overload.py) ----------------------------

    async def overload_report(self) -> dict:
        """ctrl.decision.overload payload: ladder state, damper report,
        transition history."""
        if self._overload is None:
            return {"node": self.node_name, "enabled": False}
        out = self._overload.report()
        out["enabled"] = True
        out["damping_enabled"] = bool(self.cfg.overload_damping)
        out["shed_held"] = (
            0 if self._shed_overflow is None else self._shed_overflow.count
        )
        return out

    def _on_overload_transition(self, entry: dict) -> None:
        """Ladder transition hook: log it, enact the solver-tier rung,
        and emit the LogSample the Monitor's trigger table maps to a
        flight-recorder bundle — every transition leaves evidence."""
        log.warning(
            "[%s] overload %s -> %s (depth=%s hbm=%s rss=%s slo=%s)",
            self.name, entry["from"], entry["to"], entry["queue_depth"],
            entry["hbm_frac"], entry["rss_mb"], entry["slo_burning"],
        )
        ctl = self._overload
        if ctl is not None and hasattr(self.solver, "force_single_chip"):
            # shedding rung: pin the solver to the single-chip tier
            # (releases the mesh's HBM); reverses with the ladder —
            # _sync_area re-puts the mirrors on the next tier flip
            self.solver.force_single_chip = not ctl.multichip_allowed()
        self._emit_overload_sample(entry)

    def _emit_overload_sample(self, entry: dict) -> None:
        if self._log_samples is None:
            return
        try:
            from openr_tpu.runtime.monitor import LogSample

            self._log_samples.push(LogSample(
                event="OVERLOAD_STATE_CHANGE",
                node_name=self.node_name,
                values={
                    "category": "overload",
                    "from": entry["from"],
                    "to": entry["to"],
                    "queue_depth": entry["queue_depth"],
                    "hbm_frac": entry["hbm_frac"],
                    "rss_mb": entry["rss_mb"],
                    "slo_burning": entry["slo_burning"],
                },
            ))
        # lint: allow(broad-except) telemetry must not wedge the ladder
        except Exception:  # pragma: no cover - sampler unavailable
            log.debug("%s: overload log sample failed", self.name)

    async def _overload_tick_loop(self) -> None:
        """Housekeeping fiber: re-evaluate the ladder on a clock (decay
        and dwell must progress even when no publication arrives),
        release calmed damped keys, and flush the shed overflow batch
        once pressure clears."""
        ctl = self._overload
        while True:
            await asyncio.sleep(self.cfg.overload_tick_s)
            depth = 0 if self._solve_q is None else self._solve_q.qsize()
            ctl.observe(queue_depth=depth)
            if self.cfg.overload_damping:
                self._release_damped()
            if (
                self._shed_overflow is not None
                and not ctl.still_shedding(depth)
                and self._solve_q is not None
            ):
                overflow, self._shed_overflow = self._shed_overflow, None
                self._solve_q.put_nowait(overflow)

    def _release_damped(self) -> None:
        """Re-ingest the held latest event of every damped key whose
        figure of merit has decayed below the reuse threshold: the LSDB
        converges to the key's final state the moment it calms — no
        stale-route window. Re-ingested events are recorded UNsuppressed
        (they perturb the RIB now, so replay must apply them)."""
        rec = self._replay
        released = 0
        damper = self._overload.damper
        # releasable() walks the damper's whole table on the loop, once a
        # tick: a hold no event asked for (the tracer's background track)
        with tracer.hold(
            "decision.damper_sweep", records=damper.tracked_count()
        ) as sweep:
            for area, key, held in damper.releasable():
                if held is None:
                    continue  # suppressed but never saw another event
                if held[0] == "kv":
                    _, version, originator, raw = held
                    self._update_key_in_lsdb(area, key, raw)
                    self._note_ingest(area, key, originator)
                    if rec is not None:
                        rec.record_kv(area, key, version, originator, raw)
                else:  # ("expire",)
                    self._delete_key_from_lsdb(area, key)
                    self._note_ingest(area, key, "<expired>")
                    if rec is not None:
                        rec.record_expired(area, key)
                released += 1
            if sweep is not None:
                sweep.set(released=released)
        if released and self.pending.count > 0:
            self._trigger_rebuild()

    # -- mid-flight solver failover ----------------------------------------

    def _solve_full(self, ctx, spf_sp):
        """Full rebuild through the primary solver, failing over to its
        CPU oracle mid-flight on a device/runtime error. Only solvers
        that carry a `cpu` fallback (TpuSpfSolver) can fail over; on the
        plain CPU backend the error propagates as before."""
        fallback = getattr(self.solver, "cpu", None)
        if not self._degraded:
            try:
                maybe_fail("solver.exec", span=spf_sp)
                return self.solver.build_route_db(
                    self.node_name, self.area_link_states, self.prefix_state
                )
            except Exception as e:
                if not self.cfg.enable_solver_failover or fallback is None:
                    raise
                self._enter_degraded(e)
        # degraded: the CPU oracle carries the load; stamp the evidence
        # onto the spf span AND the trace root so the closed trace shows
        # the event converged degraded
        if spf_sp is not None:
            spf_sp.attributes["degraded"] = True
        tracer.annotate(ctx, degraded=True)
        return fallback.build_route_db(
            self.node_name, self.area_link_states, self.prefix_state
        )

    async def _solve_full_async(self, ctx, spf_sp):
        """Async-dispatch variant of _solve_full. Phase 1
        (dispatch_route_db: every LSDB read + device dispatch) runs on
        the loop — LinkState/PrefixState are single-writer, owned by the
        loop. Phase 2 (collect_route_db: the at-most-ONE blocking host
        sync) touches only device buffers and the pending snapshot, so
        it moves to an executor and the loop keeps ingesting. Solvers
        without the dispatch/collect split (the CPU oracle) solve inline
        as before. Same mid-flight failover as the sync path."""
        fallback = getattr(self.solver, "cpu", None)
        dispatch = getattr(self.solver, "dispatch_route_db", None)
        bud = latency_budget.of_trace(ctx)
        if not self._degraded:
            try:
                maybe_fail("solver.exec", span=spf_sp)
                if dispatch is None:
                    db = self.solver.build_route_db(
                        self.node_name, self.area_link_states,
                        self.prefix_state,
                    )
                    if bud is not None:
                        bud.advance("device_exec")
                    return db
                build = dispatch(
                    self.node_name, self.area_link_states, self.prefix_state
                )
                if bud is not None:
                    # dispatch phase = LSDB delta reads + host->device
                    # upload, no blocking sync
                    bud.advance("host_sync")

                def _collect():
                    if bud is not None:
                        # executor picked the collect up: everything
                        # since dispatch returned was queueing gap
                        bud.advance("dispatch_gap")
                    return self.solver.collect_route_db(build)

                loop = asyncio.get_running_loop()
                # collect_route_db is @affinity.executor_safe: phase 2
                # reads only device buffers + the pending snapshot. The
                # budget stamp rides along: nothing else touches this
                # epoch's budget until the await returns.
                # lint: allow(executor-escape) budget cursor is epoch-private; collect is executor_safe
                db = await loop.run_in_executor(None, _collect)
                if bud is not None:
                    tm = getattr(self.solver, "last_timing", None) or {}
                    # the collect segment splits by the solver's own
                    # clocks: device kernels vs host materialize; the
                    # remainder (blocking sync + drain) is collect_block
                    bud.advance_split(
                        {
                            "device_exec": tm.get("exec_ms"),
                            "payload_apply": tm.get("mat_ms"),
                        },
                        primary="collect_block",
                    )
                return db
            except Exception as e:
                if not self.cfg.enable_solver_failover or fallback is None:
                    raise
                self._enter_degraded(e)
        if spf_sp is not None:
            spf_sp.attributes["degraded"] = True
        tracer.annotate(ctx, degraded=True)
        # the oracle reads LSDB state, so the degraded path stays on the
        # loop (blocking it — acceptable while degraded)
        db = fallback.build_route_db(
            self.node_name, self.area_link_states, self.prefix_state
        )
        if bud is not None:
            bud.advance("device_exec")
        return db

    def _enter_degraded(self, exc: Exception) -> None:
        self._degraded = True
        counters.set_counter("decision.solver.degraded", 1)
        counters.increment("decision.solver.failovers")
        log.error(
            "%s: device solver failed (%s: %s) — failing over to the "
            "CPU oracle, probing the device on backoff",
            self.name, type(exc).__name__, exc,
        )
        self._emit_solver_sample(
            "DECISION_SOLVER_DEGRADED",
            {"error": f"{type(exc).__name__}: {exc}"},
        )
        if self._probe_backoff is None:
            self._probe_backoff = ExponentialBackoff(
                self.cfg.solver_probe_initial_backoff_s,
                self.cfg.solver_probe_max_backoff_s,
            )
        self._probe_backoff.report_error()
        self._schedule_probe()

    def _schedule_probe(self) -> None:
        self.schedule(
            self._probe_backoff.time_until_retry_s(), self._probe_primary
        )

    def _probe_primary(self) -> None:
        """Canary the primary solver: a real device execution when the
        solver exposes one (TpuSpfSolver.probe_device re-runs its last
        compiled pipeline), else a tiny 2-node graph through the full
        build path. Healthy -> promote back; still broken -> bump the
        probe backoff and retry later."""
        if not self._degraded:
            return
        try:
            maybe_fail("solver.exec")
            probe = getattr(self.solver, "probe_device", None)
            if probe is not None:
                probe()
            else:
                self._canary_solve()
        except Exception as e:
            counters.increment("decision.solver.probe_failures")
            log.warning(
                "%s: device probe failed (%s: %s); staying degraded",
                self.name, type(e).__name__, e,
            )
            self._probe_backoff.report_error()
            self._schedule_probe()
            return
        self._promote()

    def _canary_solve(self) -> None:
        """Probe fallback for solvers without probe_device: solve a
        throwaway two-node topology and discard the result."""
        ls = LinkState("~canary")
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="~canary-a",
                adjacencies=(
                    Adjacency(
                        other_node_name="~canary-b",
                        if_name="c0",
                        other_if_name="c1",
                    ),
                ),
                area="~canary",
            )
        )
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="~canary-b",
                adjacencies=(
                    Adjacency(
                        other_node_name="~canary-a",
                        if_name="c1",
                        other_if_name="c0",
                    ),
                ),
                area="~canary",
            )
        )
        ps = PrefixState()
        ps.update_prefix_database(
            PrefixDatabase(
                this_node_name="~canary-b",
                prefix_entries=(PrefixEntry(prefix="192.0.2.1/32"),),
                area="~canary",
            )
        )
        self.solver.build_route_db("~canary-a", {"~canary": ls}, ps)

    def _promote(self) -> None:
        self._degraded = False
        counters.set_counter("decision.solver.degraded", 0)
        counters.increment("decision.solver.promotions")
        self._probe_backoff.report_success()
        log.warning(
            "%s: device solver healthy again — promoting back from the "
            "CPU fallback", self.name,
        )
        self._emit_solver_sample("DECISION_SOLVER_PROMOTED", {})
        # full rebuild through the primary so the RIB is re-derived by
        # the promoted backend (and any drift from the oracle heals)
        self.pending.needs_full_rebuild = True
        self._trigger_rebuild()

    def _emit_solver_sample(self, event: str, values: dict) -> None:
        if self._log_samples is None:
            return
        try:
            from openr_tpu.runtime.monitor import LogSample

            self._log_samples.push(
                LogSample(
                    event=event,
                    node_name=self.node_name,
                    values={"category": "sentinel", **values},
                )
            )
        # lint: allow(broad-except) best-effort telemetry must not kill
        except Exception:  # pragma: no cover - telemetry must not kill
            log.debug("%s: solver log sample failed", self.name)

    def _emit_sentinels(self, spf_sp) -> None:
        """Surface the solver's numerical-health sentinels
        (tpu_solver.last_sentinels): gauges always; when anomalous —
        metric saturation or bad UCMP weights, values that still parse
        as routes but are numerically suspect — also a counter bump, a
        structured LogSample, and an attribute on the spf span so the
        convergence trace carries the evidence."""
        sent = getattr(self.solver, "last_sentinels", None)
        if not isinstance(sent, dict) or not sent:
            return
        for k, v in sent.items():
            counters.set_counter(f"decision.sentinel.{k}", v)
        anomalous = (
            sent.get("saturated_rows", 0) > 0
            or sent.get("ucmp_bad_weights", 0) > 0
        )
        if not anomalous:
            return
        counters.increment("decision.sentinel.anomalies")
        if spf_sp is not None:
            spf_sp.attributes["sentinel_anomaly"] = True
            for k, v in sent.items():
                spf_sp.attributes[f"sentinel_{k}"] = v
        if self._log_samples is not None:
            from openr_tpu.runtime.monitor import LogSample

            self._log_samples.push(
                LogSample(
                    event="DECISION_SENTINEL_ANOMALY",
                    node_name=self.node_name,
                    values={"category": "sentinel", **sent},
                )
            )

    def _emit_retraces(self, spf_sp) -> None:
        """Surface retrace-after-warmup events the device sentinel
        (ops/xla_cache.retrace) queued during this solve: one
        DEVICE_RETRACE LogSample per event — category "sentinel" so the
        flight recorder retains the lead-up, and the event itself is in
        the Monitor's trigger table, so a retrace on a supposedly-warm
        kernel freezes a post-mortem bundle while routing continues."""
        try:
            from openr_tpu.ops.xla_cache import retrace

            events = retrace.drain_events()
        # lint: allow(broad-except) best-effort telemetry must not kill
        except Exception:  # pragma: no cover - telemetry must not kill
            return
        if not events:
            return
        if spf_sp is not None:
            spf_sp.attributes["device_retrace"] = len(events)
        for evt in events:
            self._emit_solver_sample("DEVICE_RETRACE", evt)

    def _fold_solver_timing(self, ctx, spf_sp) -> None:
        """Put the TPU pipeline's stages into the trace as children of
        decision.spf. The solver has no trace context; it stamps each
        stage where it runs (actor thread or materialization worker)
        and hands the intervals back in last_timing["spans"]."""
        if ctx is None or spf_sp is None:
            return
        tm = getattr(self.solver, "last_timing", None)
        if not isinstance(tm, dict) or spf_sp.end is None:
            return
        if tm.get("incremental"):
            # at least one area dispatched the incremental SSSP kernel
            # this solve (seed-from-previous, ops/incremental.py)
            spf_sp.attributes["incremental"] = True
        if tm.get("multichip"):
            # at least one area solved through the multichip capacity
            # tier (NamedSharding over the ('batch','graph') mesh)
            spf_sp.attributes["multichip"] = True
        # executed relaxation work (ops/relax.py round ledger): rounds
        # on every device solve; bucket epochs / halo exchanges when the
        # bucketed kernel or the multichip tier engaged
        for key in ("spf_kernel", "rounds", "bucket_epochs",
                    "halo_exchanges", "bytes_downloaded"):
            v = tm.get(key)
            if v:
                spf_sp.attributes[key] = v
        # a parent stands before its children in the list
        ids: dict[tuple, int] = {}
        for name, parent, start, end, attrs in tm.get("spans") or ():
            area = attrs.get("area")
            sp = tracer.record_span(
                ctx, name, start, end,
                parent_id=ids.get((area, parent), spf_sp.span_id),
                **attrs,
            )
            if sp is not None:
                ids[(area, name)] = sp.span_id

    def _stamp_boot_first_solve(self, build_ms: float) -> None:
        """Boot lifecycle: record the first full solve with its
        compile-vs-device-vs-materialize split — the solver's
        last_timing says what the device paid, the kernel ledger says
        what XLA compilation paid (runtime/lifecycle.py)."""
        if not boot_tracer.active(self.node_name):
            return
        attrs: dict = {"build_ms": round(build_ms, 3)}
        tm = getattr(self.solver, "last_timing", None)
        if isinstance(tm, dict):
            areas = tm.get("areas") or {"": tm}
            for stage, out in (
                ("sync_ms", "sync_ms"),
                ("exec_ms", "device_ms"),
                ("mat_ms", "mat_ms"),
            ):
                total = sum(
                    s.get(stage)
                    for s in areas.values()
                    if isinstance(s.get(stage), (int, float))
                )
                if total:
                    attrs[out] = round(total, 3)
            for key in ("spf_kernel", "rounds", "bucket_epochs",
                        "bytes_uploaded", "bytes_downloaded",
                        "multichip"):
                if tm.get(key):
                    attrs[key] = tm[key]
        # deferred: ops pulls in the device toolchain (same pattern as
        # the flight recorder)
        from openr_tpu.ops.xla_cache import ledger as kernel_ledger

        snap = kernel_ledger.snapshot()
        if snap:
            attrs["compile_ms"] = round(
                sum(e["compile_ms"] or 0.0 for e in snap.values()), 3
            )
            attrs["kernels_compiled"] = len(snap)
        boot_tracer.phase_mark("first_solve", node=self.node_name, **attrs)

    # -- module API (role of semifuture_* Decision.h:154-195) --------------

    async def get_decision_route_db(
        self, from_node: Optional[str] = None
    ) -> Optional[DecisionRouteDb]:
        """Computed RIB, optionally from another node's perspective — the
        RIB is a pure function of the LSDB (ref Decision.cpp:308-328)."""
        node = from_node or self.node_name
        if node == self.node_name:
            return self.route_db
        solver = make_solver(node, "cpu")
        return solver.build_route_db(
            node, self.area_link_states, self.prefix_state
        )

    # vantage bound for get_fabric_route_dbs' default all-nodes
    # expansion: the computation runs inline in the actor (like every
    # rebuild), and serializing ~100k full RIBs through ctrl would stall
    # route processing for the duration — beyond this, the caller must
    # name vantages explicitly
    MAX_FABRIC_VANTAGES = 4096

    async def get_fabric_route_dbs(
        self, from_nodes: Optional[list[str]] = None
    ) -> dict[str, Optional[DecisionRouteDb]]:
        """Whole-fabric RIBs: every requested vantage (default: every
        node in the LSDB, bounded by MAX_FABRIC_VANTAGES) computed in one
        sharded device pass when the TPU backend is active
        (TpuSpfSolver.build_fabric_route_dbs over the ('batch', 'graph')
        mesh), per-vantage through the SAME configured solver otherwise
        (so LFA / statics / v4 flags apply identically on both backends).
        Same purity argument as get_decision_route_db — any vantage's RIB
        is a function of the shared LSDB."""
        nodes = from_nodes
        if nodes is None:
            nodes = sorted(
                {
                    n
                    for ls in self.area_link_states.values()
                    for n in ls.node_names()
                }
            )
            if len(nodes) > self.MAX_FABRIC_VANTAGES:
                raise ValueError(
                    f"LSDB has {len(nodes)} nodes > "
                    f"{self.MAX_FABRIC_VANTAGES}; pass an explicit "
                    "vantage list"
                )
        fabric = getattr(self.solver, "build_fabric_route_dbs", None)
        if fabric is not None:
            return fabric(nodes, self.area_link_states, self.prefix_state)
        # CPU backend: same solver instance per vantage — build_route_db
        # is vantage-parameterized and carries the configured flags
        return {
            node: self.solver.build_route_db(
                node, self.area_link_states, self.prefix_state
            )
            for node in nodes
        }

    async def get_adj_dbs(self) -> dict[str, dict[str, AdjacencyDatabase]]:
        return {
            area: dict(ls.get_adjacency_databases())
            for area, ls in self.area_link_states.items()
        }

    async def get_received_routes(self):
        return self.prefix_state.received_routes()

    async def get_paths(
        self, src: str, dst: str, area: str = "", k: int = 2
    ) -> list[dict]:
        """k edge-disjoint paths src -> dst per area (ref `breeze
        decision path`, clis/decision.py PathCli, on LinkState's
        getKthPaths machinery). Each path: ordered hops with the egress
        interface and per-hop metric."""
        out: list[dict] = []
        for a, ls in self.area_link_states.items():
            if area and a != area:
                continue
            if not (ls.has_node(src) and ls.has_node(dst)):
                continue
            for ki in range(1, max(1, k) + 1):
                for path in ls.get_kth_paths(src, dst, ki):
                    hops, cur, cost = [], src, 0
                    for link in path:
                        m = link.metric_from_node(cur)
                        hops.append(
                            {
                                "node": cur,
                                "iface": link.iface_from_node(cur),
                                "next": link.other_node(cur),
                                "metric": m,
                            }
                        )
                        cost += m
                        cur = link.other_node(cur)
                    out.append(
                        {"area": a, "k": ki, "cost": cost, "hops": hops}
                    )
        return out

    async def get_prefix_dbs(self):
        """Announcer -> area -> prefix -> entry, as Decision currently
        sees the network (ref getDecisionPrefixDbs)."""
        out: dict = {}
        for prefix, entries in self.prefix_state.prefixes().items():
            for (node, area), entry in entries.items():
                out.setdefault(node, {}).setdefault(area, {})[prefix] = entry
        return out

    async def explain_route(self, prefix: str) -> dict:
        """Route provenance: where did this RIB entry come from — the
        originating kvstore key/node/area, the solve epoch that
        materialized it, and which solver kind (full / incremental /
        failover-cpu) produced it (ref none; observatory extension,
        `breeze decision explain`)."""
        canon = prefix
        if canon not in self.route_db.unicast_routes:
            import ipaddress

            try:
                canon = str(ipaddress.ip_network(prefix, strict=False))
            except ValueError:
                return {"prefix": prefix, "error": f"bad prefix {prefix!r}"}
        entry = self.route_db.unicast_routes.get(canon)
        if entry is None:
            return {"prefix": canon, "installed": False, "error": "no route"}
        out = {
            "prefix": canon,
            "installed": not entry.do_not_install,
            "igp_cost": entry.igp_cost,
            "best_node_area": list(entry.best_node_area),
            "nexthops": sorted(
                {nh.neighbor_node_name or nh.address for nh in entry.nexthops}
            ),
            "num_nexthops": len(entry.nexthops),
        }
        prov = self._provenance.get(canon)
        if prov is not None:
            out["provenance"] = {
                "kv_key": prov.kv_key,
                "originator": prov.originator,
                "area": prov.area,
                "solve_epoch": prov.solve_epoch,
                "solver_kind": prov.solver_kind,
                "ts_ms": prov.ts_ms,
            }
        return out

    # -- what-if engine (decision/whatif.py) -------------------------------
    #
    # Planning/TE workload over the solver's resident device mirrors.
    # Strictly LOWER priority than live convergence: every batched
    # dispatch first yields until the async solve queue is drained
    # (whatif.deferrals counts the waits), and every failure — including
    # an armed solver.whatif fault — is returned as an {"error": ...}
    # payload + whatif.errors, never routed into _enter_degraded.

    def _whatif(self):
        if self._whatif_engine is None:
            if not hasattr(self.solver, "_sync_area"):
                return None  # CPU backend: no resident mirror to sweep
            from openr_tpu.decision.whatif import WhatIfEngine

            self._whatif_engine = WhatIfEngine(self.solver, self.node_name)
        return self._whatif_engine

    async def _whatif_gate(self) -> Optional[dict]:
        """Admission gate for planning work. Returns a rejection payload
        when the overload ladder has closed the what-if class (brownout
        and above) — the caller returns it verbatim; otherwise yields
        until no live solve is queued (a sweep chunk never races a
        topology event for the device) and returns None."""
        if self._overload is not None and not self._overload.admit("whatif"):
            return {
                "error": (
                    "whatif rejected: overload state "
                    f"{self._overload.state!r} (see breeze decision "
                    "overload)"
                ),
                "overload_state": self._overload.state,
            }
        while self._solve_q is not None and not self._solve_q.empty():
            counters.increment("whatif.deferrals")
            await asyncio.sleep(0.005)
        return None

    async def whatif_sweep(
        self, order: int = 1, area: Optional[str] = None,
        roots: Optional[list[str]] = None, max_scenarios: int = 0,
        top: int = 0,
    ) -> dict:
        """Batched N-`order` link-failure sweep from this node's vantage
        (or explicit roots): per-scenario unreachable-pair counts, max
        metric stretch, and partition verdicts."""
        eng = self._whatif()
        if eng is None:
            return {"error": "whatif requires the device solver backend"}
        try:
            job = eng.plan_sweep(
                self.area_link_states, self.prefix_state, order=order,
                area=area, roots=roots, max_scenarios=max_scenarios,
            )
        except Exception as e:
            counters.increment("whatif.errors")
            return {"error": f"{type(e).__name__}: {e}"}
        loop = asyncio.get_running_loop()
        try:
            rows: list[dict] = []
            for chunk in job.chunks:
                rejected = await self._whatif_gate()
                if rejected is not None:
                    job.fail()
                    counters.increment("whatif.errors")
                    return rejected
                chunk.dispatch()
                # chunk.collect blocks only on its own device output
                # buffers; the LSDB snapshot was taken on-loop in
                # plan_sweep, so nothing it touches is actor-owned
                # lint: allow(executor-escape) reads device buffers only
                res = await loop.run_in_executor(None, chunk.collect)
                rows.extend(res)
            out = job.result(rows)
            if top:
                out["rows"] = out["rows"][:top]
            return out
        except Exception as e:
            job.fail()
            counters.increment("whatif.errors")
            return {"error": f"{type(e).__name__}: {e}"}

    async def whatif_drain(
        self, node: str = "", link: str = "", area: Optional[str] = None,
        roots: Optional[list[str]] = None, top: int = 10,
    ) -> dict:
        """Impact preview for draining a node or a link ('n1|n2')."""
        eng = self._whatif()
        if eng is None:
            return {"error": "whatif requires the device solver backend"}
        rejected = await self._whatif_gate()
        if rejected is not None:
            counters.increment("whatif.errors")
            return rejected
        try:
            return eng.drain(
                self.area_link_states, self.prefix_state,
                node=node or None, link=link or None, area=area,
                roots=roots, top=top,
            )
        except Exception as e:
            counters.increment("whatif.errors")
            return {"error": f"{type(e).__name__}: {e}"}

    async def whatif_optimize(
        self, demands: list[dict], area: Optional[str] = None,
        iters: int = 40, lr: float = 2.0, tau: float = 1.0,
    ) -> dict:
        """Gradient-descent link-weight optimization against a demand
        matrix ([{src, dst, volume}]); returns the proposed metric vector
        and its predicted max-link-utilization delta."""
        eng = self._whatif()
        if eng is None:
            return {"error": "whatif requires the device solver backend"}
        rejected = await self._whatif_gate()
        if rejected is not None:
            counters.increment("whatif.errors")
            return rejected
        try:
            job = eng.plan_optimize(
                self.area_link_states, self.prefix_state, demands,
                area=area, iters=iters, lr=lr, tau=tau,
            )
        except Exception as e:
            counters.increment("whatif.errors")
            return {"error": f"{type(e).__name__}: {e}"}
        loop = asyncio.get_running_loop()
        try:
            # the GD loop touches only device/host arrays — run it off
            # the actor loop so route processing stays live throughout
            # lint: allow(executor-escape) job snapshot taken on-loop
            return await loop.run_in_executor(None, job.run)
        except Exception as e:
            counters.increment("whatif.errors")
            return {"error": f"{type(e).__name__}: {e}"}

    _RIB_POLICY_KEY = "rib-policy"

    def _save_rib_policy(self) -> None:
        """Persist the active policy with a WALL-clock deadline so a
        restarted daemon can subtract elapsed downtime (ref
        saveRibPolicy, Decision.cpp:646-686)."""
        if self._store is None or not self.cfg.save_rib_policy:
            return
        if self.rib_policy is None:
            self._store.erase(self._RIB_POLICY_KEY)
            return
        self._store.store_obj(
            self._RIB_POLICY_KEY,
            {
                "statements": to_plain(self.rib_policy.statements),
                "ttl_secs": self.rib_policy.ttl_secs,
                "valid_until_wall": (
                    time.time() + self.rib_policy.remaining_ttl_secs()
                ),
            },
        )

    def _load_saved_rib_policy(self) -> None:
        """Re-arm a saved policy with its REMAINING validity; drop it if
        it expired while the daemon was down (ref readRibPolicy,
        Decision.cpp:688-728)."""
        if self._store is None or not self.cfg.save_rib_policy:
            return
        saved = self._store.load_obj(self._RIB_POLICY_KEY, dict)
        if not saved:
            return
        remaining = saved.get("valid_until_wall", 0) - time.time()
        if remaining <= 0:
            return
        policy = from_plain(
            {
                "statements": saved["statements"],
                "ttl_secs": saved["ttl_secs"],
            },
            RibPolicy,
        )
        policy.valid_until = time.monotonic() + remaining
        self.rib_policy = policy
        self.pending.needs_full_rebuild = True
        self._trigger_rebuild()
        self.schedule(remaining + 0.01, self._on_policy_expiry)

    async def set_rib_policy(self, policy: RibPolicy) -> None:
        policy.arm()
        self.rib_policy = policy
        self._save_rib_policy()
        self.pending.needs_full_rebuild = True
        self._trigger_rebuild()
        # re-arm a rebuild at policy expiry so its effects revert on time
        # (ref Decision.cpp rib policy ttl timer :646-728)
        self.schedule(
            policy.remaining_ttl_secs() + 0.01, self._on_policy_expiry
        )

    def _on_policy_expiry(self) -> None:
        if self.rib_policy is not None and not self.rib_policy.is_active():
            self.pending.needs_full_rebuild = True
            self._trigger_rebuild()

    async def get_rib_policy(self) -> Optional[RibPolicy]:
        return self.rib_policy

    async def clear_rib_policy(self) -> None:
        self.rib_policy = None
        self._save_rib_policy()
        self.pending.needs_full_rebuild = True
        self._trigger_rebuild()
