"""Ctrl server — the operator/control API.

Role of the reference's openr/ctrl-server/OpenrCtrlHandler.{h,cpp} +
OpenrThriftCtrlServer (service OpenrCtrl, OpenrCtrl.thrift:246-713): one
server fanning out to every module's async API, plus server-streaming
subscriptions for KvStore and Fib deltas with an initial snapshot
(ref OpenrCtrlHandler.h:351-389). Served over runtime/rpc.py (role of the
thrift server on :2018); the breeze CLI (cli/breeze.py) is the client.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Optional

from openr_tpu.messaging import QueueClosedError, ReplicateQueue
from openr_tpu.runtime.actor import Actor
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.rpc import RpcServer, Stream
from openr_tpu.runtime.tracing import tracer
from openr_tpu.serde import from_plain, to_plain
from openr_tpu.types import InitializationEvent, Publication

log = logging.getLogger(__name__)


class CtrlServer(Actor):
    """ref OpenrCtrlHandler.h — fans out to module semifuture APIs."""

    def __init__(
        self,
        node_name: str,
        kvstore=None,
        decision=None,
        fib=None,
        link_monitor=None,
        prefix_manager=None,
        spark=None,
        kvstore_updates_queue: Optional[ReplicateQueue] = None,
        fib_updates_queue: Optional[ReplicateQueue] = None,
        listen_port: int = 0,
        config=None,
        monitor=None,
        persistent_store=None,
    ):
        super().__init__(f"ctrl:{node_name}")
        self.node_name = node_name
        self.kvstore = kvstore
        self.decision = decision
        self.fib = fib
        self.link_monitor = link_monitor
        self.prefix_manager = prefix_manager
        self.spark = spark
        self._kvstore_updates_q = kvstore_updates_queue
        self._fib_updates_q = fib_updates_queue
        self._listen_port = listen_port
        self.config = config
        self.monitor = monitor
        self.persistent_store = persistent_store
        self.server = RpcServer(self.name)
        self.port: int = 0
        self.start_time = time.time()
        # initialization-event introspection (ref getInitializationEvents)
        self.initialization_events: dict[str, float] = {}
        # live-stream bookkeeping (ref getSubscriberInfo,
        # OpenrCtrl.thrift:72-83 + :407)
        self._subscribers: dict[int, dict] = {}
        self._next_subscriber_id = 0

    async def on_start(self) -> None:
        s = self.server
        s.register("openr.version", self._version)
        s.register("openr.initialization_events", self._get_init_events)
        s.register("openr.initialization_converged", self._init_converged)
        s.register("openr.initialization_duration", self._init_duration)
        s.register("openr.my_node_name", self._my_node_name)
        s.register("openr.build_info", self._build_info)
        s.register("monitor.counters", self._counters)
        s.register("monitor.statistics", self._statistics)
        s.register("monitor.traces", self._traces)
        s.register("monitor.traces.export_chrome", self._traces_chrome)
        s.register("monitor.event_logs", self._event_logs)
        s.register("ctrl.monitor.logs", self._event_logs)
        s.register("ctrl.monitor.fleet", self._monitor_fleet)
        s.register("ctrl.monitor.crashes", self._monitor_crashes)
        s.register("ctrl.monitor.slo", self._monitor_slo)
        s.register("ctrl.monitor.boot", self._monitor_boot)
        s.register("ctrl.monitor.dump", self._monitor_dump)
        s.register("ctrl.monitor.bundles", self._monitor_bundles)
        s.register("ctrl.monitor.record", self._monitor_record)
        # fault-injection registry (runtime/faults.py): arm / disarm /
        # inspect chaos drills on the live daemon
        s.register("ctrl.fault.inject", self._fault_inject)
        s.register("ctrl.fault.clear", self._fault_clear)
        s.register("ctrl.fault.list", self._fault_list)
        s.register("monitor.heap_profile.start", self._heap_profile_start)
        s.register("monitor.heap_profile.dump", self._heap_profile_dump)
        # device plane (runtime/device_stats.py + ops/xla_cache.ledger):
        # all of these degrade gracefully on CPU-only hosts
        s.register("ctrl.tpu.profiler.start", self._tpu_profiler_start)
        s.register("ctrl.tpu.profiler.stop", self._tpu_profiler_stop)
        s.register("ctrl.tpu.profiler.status", self._tpu_profiler_status)
        s.register("ctrl.tpu.kernels", self._tpu_kernels)
        s.register("ctrl.tpu.aot", self._tpu_aot)
        s.register("ctrl.tpu.devices", self._tpu_devices)
        s.register("ctrl.store.set", self._store_set)
        s.register("ctrl.store.get", self._store_get)
        s.register("ctrl.store.erase", self._store_erase)
        s.register("ctrl.store.dump", self._store_dump)
        if self.kvstore is not None:
            s.register("ctrl.kvstore.keyvals", self._kv_get)
            s.register("ctrl.kvstore.dump", self._kv_dump)
            s.register("ctrl.kvstore.hashes", self._kv_hashes)
            s.register("ctrl.kvstore.peers", self._kv_peers)
            s.register("ctrl.kvstore.set", self._kv_set)
            s.register("ctrl.kvstore.set_key", self._kv_set_key)
            s.register("ctrl.kvstore.areas", self._kv_area_summary)
            s.register("ctrl.kvstore.long_poll_adj", self._kv_long_poll_adj)
            s.register("ctrl.kvstore.flood_topo", self._kv_flood_topo)
            s.register("ctrl.kvstore.divergence", self._kv_divergence)
        s.register("ctrl.config.dryrun", self._dryrun_config)
        s.register("ctrl.config.get", self._get_config)
        s.register("openr.drain_state", self._drain_state)
        if self.decision is not None:
            s.register("ctrl.decision.routes", self._decision_routes)
            s.register(
                "ctrl.decision.fabric_routes", self._decision_fabric_routes
            )
            s.register("ctrl.decision.adj_dbs", self._decision_adj_dbs)
            s.register(
                "ctrl.decision.adjacencies_filtered",
                self._decision_adjacencies_filtered,
            )
            s.register("ctrl.decision.prefix_dbs", self._decision_prefix_dbs)
            s.register(
                "ctrl.decision.received_routes", self._decision_received
            )
            s.register("ctrl.decision.path", self._decision_path)
            s.register("ctrl.decision.explain", self._decision_explain)
            if self.kvstore is not None:
                s.register(
                    "ctrl.decision.validate", self._decision_validate
                )
            s.register("ctrl.decision.set_rib_policy", self._set_rib_policy)
            s.register("ctrl.decision.get_rib_policy", self._get_rib_policy)
            s.register(
                "ctrl.decision.clear_rib_policy", self._clear_rib_policy
            )
            s.register(
                "ctrl.decision.convergence", self._decision_convergence
            )
            s.register("ctrl.decision.budget", self._decision_budget)
            s.register("ctrl.decision.replay", self._decision_replay)
            s.register("ctrl.decision.overload", self._decision_overload)
            s.register("ctrl.decision.whatif.sweep", self._whatif_sweep)
            s.register("ctrl.decision.whatif.drain", self._whatif_drain)
            s.register(
                "ctrl.decision.whatif.optimize", self._whatif_optimize
            )
        if self.fib is not None:
            s.register("ctrl.fib.routes", self._fib_routes)
            s.register("ctrl.fib.mpls_routes", self._fib_mpls)
            s.register("ctrl.fib.routes_filtered", self._fib_routes_filtered)
            s.register("ctrl.fib.mpls_filtered", self._fib_mpls_filtered)
            s.register("ctrl.fib.perf", self._fib_perf)
            s.register("ctrl.fib.route_detail_db", self._fib_route_detail_db)
            if self.decision is not None:
                s.register("ctrl.fib.validate", self._fib_validate)
        s.register("ctrl.subscriber_info", self._subscriber_info)
        if self.link_monitor is not None:
            s.register("ctrl.lm.links", self._lm_links)
            s.register("ctrl.lm.interfaces", self._lm_interfaces)
            s.register("ctrl.lm.adjacencies", self._lm_adjacencies)
            s.register("ctrl.lm.set_node_overload", self._lm_set_overload)
            s.register("ctrl.lm.set_link_overload", self._lm_set_link_overload)
            s.register("ctrl.lm.set_link_metric", self._lm_set_link_metric)
            s.register("ctrl.lm.set_adj_metric", self._lm_set_adj_metric)
            s.register(
                "ctrl.lm.set_node_metric_increment",
                self._lm_set_node_metric_increment,
            )
            s.register(
                "ctrl.lm.set_link_metric_increment",
                self._lm_set_link_metric_increment,
            )
        if self.spark is not None:
            s.register("ctrl.spark.neighbors", self._spark_neighbors)
            s.register("ctrl.spark.flood_restarting", self._spark_flood_restarting)
        if self.prefix_manager is not None:
            s.register("ctrl.prefixmgr.advertised", self._pm_advertised)
            s.register("ctrl.prefixmgr.prefixes", self._pm_prefixes)
            s.register("ctrl.prefixmgr.prefixes_by_type", self._pm_prefixes_by_type)
            s.register("ctrl.prefixmgr.originated", self._pm_originated)
            s.register("ctrl.prefixmgr.advertise", self._pm_advertise)
            s.register("ctrl.prefixmgr.withdraw", self._pm_withdraw)
            s.register(
                "ctrl.prefixmgr.withdraw_by_type", self._pm_withdraw_by_type
            )
            s.register("ctrl.prefixmgr.sync_by_type", self._pm_sync_by_type)
        if self._kvstore_updates_q is not None:
            s.register("ctrl.kvstore.subscribe", self._subscribe_kvstore)
            self.add_task(
                self._watch_initialization(self._kvstore_updates_q),
                name=f"{self.name}.init-watch-kv",
            )
        if self._fib_updates_q is not None:
            s.register("ctrl.fib.subscribe", self._subscribe_fib)
            s.register("ctrl.fib.subscribe_detail", self._subscribe_fib_detail)
            self.add_task(
                self._watch_initialization(self._fib_updates_q),
                name=f"{self.name}.init-watch-fib",
            )
        ssl_ctx = None
        peer_verifier = None
        if self.config is not None:
            ts = self.config.raw.thrift_server
            if ts.enable_secure_thrift_server:
                from openr_tpu.config import (
                    build_server_ssl_context,
                    make_peer_verifier,
                )

                ssl_ctx = build_server_ssl_context(ts)
                peer_verifier = make_peer_verifier(ts.acceptable_peers)
        self.port = await s.start(
            port=self._listen_port, ssl=ssl_ctx, peer_verifier=peer_verifier
        )

    async def on_stop(self) -> None:
        await self.server.stop()

    # -- misc --------------------------------------------------------------

    async def _version(self) -> dict:
        return {
            "node": self.node_name,
            "version": 1,
            "uptime_s": time.time() - self.start_time,
        }

    async def _counters(self, prefix: str = "") -> dict:
        return counters.get_counters(prefix)

    async def _statistics(self, prefix: str = "") -> dict:
        """ref breeze monitor statistics: multi-window stat view."""
        return counters.get_statistics(prefix)

    async def _traces(
        self,
        limit: int = 20,
        trace_id: Optional[int] = None,
        include_active: bool = False,
    ) -> list:
        """Closed convergence traces (runtime/tracing.py span trees)."""
        return tracer.get_traces(
            limit=limit, trace_id=trace_id, include_active=include_active
        )

    async def _traces_chrome(
        self, trace_id: Optional[int] = None, limit: int = 20
    ) -> dict:
        """Chrome trace-event JSON for chrome://tracing / Perfetto."""
        return tracer.export_chrome(trace_id=trace_id, limit=limit)

    async def _decision_convergence(self, fleet: bool = False) -> dict:
        """Per-event convergence latency: percentile summary over the
        closed-trace ring, the windowed convergence_ms stat, and the
        solver's incremental/full dispatch split (decision.solver.*
        counters — incr.solves ran the seed-from-previous kernel,
        incr.full_fallbacks degraded to a full solve while incremental
        was enabled, full.solves is every cold/full dispatch). With
        fleet=True (breeze decision convergence --fleet) also folds in
        the FLEET view: every node's TTL'd conv-ack ring aggregated
        per origin event."""
        incr_stats = counters.get_statistics(
            "decision.solver.incr"
        )
        device_stats = counters.get_statistics("decision.device")
        out = {
            "summary": tracer.convergence_summary(),
            "stat": counters.get_statistics("convergence_ms").get(
                "convergence_ms", {}
            ),
            "solver": {
                "incremental_solves": counters.get_counter(
                    "decision.solver.incr.solves"
                ) or 0,
                "incremental_full_fallbacks": counters.get_counter(
                    "decision.solver.incr.full_fallbacks"
                ) or 0,
                "full_solves": counters.get_counter(
                    "decision.solver.full.solves"
                ) or 0,
                "cone_frac": incr_stats.get(
                    "decision.solver.incr.cone_frac", {}
                ),
                "changed_rows": incr_stats.get(
                    "decision.solver.incr.changed_rows", {}
                ),
                # executed relaxation work per solve (ops/relax.py
                # ledger): rounds everywhere, bucket_epochs when the
                # bucketed Δ-stepping kernel engaged, halo_exchanges in
                # the multichip tier (one per epoch under bucketed)
                "device_rounds": device_stats.get(
                    "decision.device.rounds", {}
                ),
                "device_bucket_epochs": device_stats.get(
                    "decision.device.bucket_epochs", {}
                ),
                "device_halo_exchanges": device_stats.get(
                    "decision.device.halo_exchanges", {}
                ),
                "device_bytes_downloaded": device_stats.get(
                    "decision.device.bytes_downloaded", {}
                ),
            },
        }
        # device-kernel rows for the LAST solve, whatever its shape —
        # solver.last_timing is refreshed by every device collect
        # (full, incremental seed-from-previous, prefix-only), so
        # these render after an incremental solve too, where the
        # windowed stats above can have already aged out
        solver = (
            getattr(self.decision, "solver", None)
            if self.decision is not None
            else None
        )
        tm = getattr(solver, "last_timing", None)
        if isinstance(tm, dict) and tm:
            last = {
                k: tm[k]
                for k in ("spf_kernel", "rounds", "bucket_epochs",
                          "halo_exchanges", "incremental",
                          "bytes_uploaded", "bytes_downloaded")
                if tm.get(k) is not None
            }
            out["solver"]["last_solve"] = last
            # windowed decision.device.* stats age out during idle (the
            # sample ring only answers for the trailing windows) and the
            # rows above render blank — fall back to the last_timing
            # snapshot, same pattern as the kernel rows
            for row, key in (
                ("device_rounds", "rounds"),
                ("device_bucket_epochs", "bucket_epochs"),
                ("device_halo_exchanges", "halo_exchanges"),
                ("device_bytes_downloaded", "bytes_downloaded"),
            ):
                if tm.get(key) is None:
                    continue
                win = out["solver"].get(row) or {}
                if all(
                    not (w or {}).get("count")
                    for w in win.values()
                    if isinstance(w, dict)
                ):
                    out["solver"][row] = {
                        "snapshot": tm[key],
                        "source": "last_timing",
                    }
        if fleet:
            out["fleet"] = await self._fleet_convergence()
        return out

    async def _decision_budget(self, fleet: bool = False) -> dict:
        """Latency-budget waterfall: the per-epoch churn-to-ack budget
        ledger's per-component windows, conservation accounting, and
        p50->p99 tail attribution (runtime/latency_budget.py). With
        fleet=True, joins the fleet conv-ack view so each origin event
        also names the straggler's dominant budget COMPONENT."""
        from openr_tpu.runtime.latency_budget import latency_budget

        out = latency_budget.report()
        out["node"] = self.node_name
        if fleet:
            out["fleet"] = await self._fleet_convergence()
        return out

    async def _fleet_convergence(self, limit: int = 20) -> dict:
        """Aggregate the `monitor:conv-ack:<node>` rings every node
        floods back into KvStore (fib.py stamps fleet_convergence_ms
        when a programmed route's trace carries a remote origin stamp).
        Grouped per origin event: fleet_ms is the LAST FIB ack's
        latency — origin publish → slowest node programmed — and the
        straggler is that node. Percentiles run across events."""
        import json as _json

        from openr_tpu.kvstore.kvstore import CONV_ACK_PREFIX
        from openr_tpu.runtime.counters import _percentile

        events: dict[str, dict] = {}
        reporting: set = set()
        if self.kvstore is not None:
            for area in list(getattr(self.kvstore, "areas", None) or []):
                vals = await self.kvstore.dump_all(area, CONV_ACK_PREFIX)
                for key, val in vals.items():
                    if val.value is None:
                        continue
                    try:
                        ring = _json.loads(val.value.decode())
                    except (ValueError, UnicodeDecodeError):
                        continue
                    reporting.add(key[len(CONV_ACK_PREFIX):])
                    for ack in ring.get("acks", []):
                        ev = events.setdefault(
                            ack.get("event", "?"),
                            {
                                "origin": ack.get("origin", ""),
                                "acks": {},
                                "ts_ms": 0,
                            },
                        )
                        node = ack.get("node", "?")
                        ms = float(ack.get("ms", 0.0))
                        # one node can re-program for the same origin
                        # event (coalesced floods) — keep its slowest ack
                        if ms >= ev["acks"].get(node, 0.0):
                            # the slowest ack's dominant budget component
                            # (fib.py threads it through the conv-ack) —
                            # names the straggler STAGE, not just the node
                            if ack.get("comp"):
                                ev.setdefault("comps", {})[node] = {
                                    "component": ack["comp"],
                                    "ms": float(ack.get("comp_ms", 0.0)),
                                }
                        ev["acks"][node] = max(
                            ev["acks"].get(node, 0.0), ms
                        )
                        ev["ts_ms"] = max(
                            ev["ts_ms"], int(ack.get("ts_ms", 0))
                        )
        rows = []
        for event_id, ev in events.items():
            straggler = max(ev["acks"], key=ev["acks"].get)
            row = {
                "event": event_id,
                "origin": ev["origin"],
                "ts_ms": ev["ts_ms"],
                "fleet_ms": round(ev["acks"][straggler], 3),
                "straggler": straggler,
                "nodes_acked": len(ev["acks"]),
                "acks": {
                    n: round(ms, 3) for n, ms in ev["acks"].items()
                },
            }
            comp = (ev.get("comps") or {}).get(straggler)
            if comp:
                row["straggler_component"] = comp["component"]
                row["straggler_component_ms"] = round(comp["ms"], 3)
            rows.append(row)
        rows.sort(key=lambda r: r["ts_ms"], reverse=True)
        fleet_ms = sorted(r["fleet_ms"] for r in rows)
        return {
            "local_node": self.node_name,
            "nodes_reporting": sorted(reporting),
            "events": rows[: max(1, limit)],
            "event_count": len(rows),
            "fleet_ms": {
                "count": len(fleet_ms),
                "p50": round(_percentile(fleet_ms, 50.0), 3),
                "p95": round(_percentile(fleet_ms, 95.0), 3),
                "p99": round(_percentile(fleet_ms, 99.0), 3),
                "max": fleet_ms[-1] if fleet_ms else 0.0,
            },
            "stat": counters.get_statistics("fleet_convergence_ms").get(
                "fleet_convergence_ms", {}
            ),
        }

    async def _monitor_slo(self) -> dict:
        """SLO burn-rate report (monitor.slo_report)."""
        if self.monitor is None:
            raise RuntimeError("no monitor wired to ctrl")
        return self.monitor.slo_report()

    async def _monitor_boot(self) -> dict:
        """Boot-to-first-RIB phase ledger (runtime/lifecycle.py). Unlike
        the other monitor endpoints this reads the process-global boot
        tracer — it answers even before/without a wired monitor."""
        from openr_tpu.runtime.lifecycle import boot_tracer

        return boot_tracer.report()

    async def _monitor_dump(self, reason: str = "manual") -> dict:
        """Operator-triggered flight-recorder bundle."""
        if self.monitor is None:
            raise RuntimeError("no monitor wired to ctrl")
        return await self.monitor.dump_flight_recorder(reason=reason)

    async def _monitor_bundles(self) -> dict:
        """Flight-recorder bundle listing (disk + memory)."""
        if self.monitor is None:
            raise RuntimeError("no monitor wired to ctrl")
        return await self.monitor.flight_recorder_bundles()

    async def _monitor_record(self, reason: str = "record") -> dict:
        """Operator-requested replayable bundle (inputs annex +
        snapshot re-anchor request)."""
        if self.monitor is None:
            raise RuntimeError("no monitor wired to ctrl")
        return await self.monitor.record_replay_bundle(reason=reason)

    async def _decision_replay(self) -> dict:
        """Input-recorder / RIB-digest status (runtime/replay_log.py)."""
        return await self.decision.replay_status()

    async def _decision_overload(self) -> dict:
        """Overload ladder / flap-damper state (runtime/overload.py)."""
        return await self.decision.overload_report()

    async def _watch_initialization(self, queue: ReplicateQueue) -> None:
        reader = queue.get_reader(f"{self.name}.init")
        try:
            while True:
                item = await reader.get()
                if isinstance(item, InitializationEvent):
                    self.initialization_events[item.name] = time.time()
        except QueueClosedError:
            pass

    async def _get_init_events(self) -> dict:
        return dict(self.initialization_events)

    # the reference's convergence signal (ref initializationConverged):
    # FIB_SYNCED marks the cold-boot pipeline complete end-to-end (the
    # RIB was computed AND programmed)
    _CONVERGENCE_EVENT = "FIB_SYNCED"

    async def _init_converged(self) -> bool:
        return self._CONVERGENCE_EVENT in self.initialization_events

    async def _init_duration(self) -> Optional[float]:
        """ref getInitializationDurationMs; None until converged."""
        ts = self.initialization_events.get(self._CONVERGENCE_EVENT)
        return None if ts is None else (ts - self.start_time) * 1e3

    async def _my_node_name(self) -> str:
        return self.node_name

    async def _build_info(self) -> dict:
        """ref getBuildInfo — platform/package provenance."""
        import platform as _platform

        try:
            from importlib.metadata import version as _pkg_version

            pkg = _pkg_version("openr-tpu")
        # lint: allow(broad-except) uninstalled checkout reports "dev"
        except Exception:
            pkg = "dev"
        return {
            "build_package": "openr_tpu",
            "build_version": pkg,
            "build_platform": _platform.platform(),
            "build_python": _platform.python_version(),
        }

    async def _heap_profile_start(self, frames: int = 1) -> dict:
        """ref MonitorBase::dumpHeapProfile hook (MonitorBase.h:54);
        tracemalloc is process-global, no Monitor actor required."""
        from openr_tpu.runtime.monitor import start_heap_profile

        return start_heap_profile(int(frames))

    async def _heap_profile_dump(
        self, top: int = 25, stop: bool = False
    ) -> dict:
        from openr_tpu.runtime.monitor import dump_heap_profile

        return await dump_heap_profile(int(top), bool(stop))

    async def _monitor_crashes(self) -> list:
        """Last task crashes (runtime/tasks.py ring), newest first."""
        from openr_tpu.runtime.tasks import recent_crashes

        return recent_crashes()

    # -- fault injection (runtime/faults.py) -------------------------------

    async def _fault_inject(
        self,
        site: str,
        probability: float = 0.0,
        every_nth: int = 0,
        one_shot: bool = False,
        window_s: float = 0.0,
        max_fires: int = 0,
        seed: Optional[int] = None,
        delay_ms: float = 0.0,
        rate: float = 0.0,
    ) -> dict:
        from openr_tpu.runtime.faults import registry

        return registry.arm(
            site,
            probability=float(probability),
            every_nth=int(every_nth),
            one_shot=bool(one_shot),
            window_s=float(window_s),
            max_fires=int(max_fires),
            seed=seed if seed is None else int(seed),
            delay_ms=float(delay_ms),
            rate=float(rate),
        )

    async def _fault_clear(self, site: Optional[str] = None) -> dict:
        from openr_tpu.runtime.faults import registry

        return registry.clear(site)

    async def _fault_list(self) -> dict:
        from openr_tpu.runtime.faults import registry

        return registry.list()

    async def _event_logs(self, category: Optional[str] = None) -> list:
        """ref getEventLogs — Monitor's LogSample ring, optionally
        filtered by event category (exact event, dotted prefix, or
        values["category"])."""
        if self.monitor is None:
            return []
        return await self.monitor.get_event_logs(category=category)

    # -- device plane ------------------------------------------------------

    async def _tpu_profiler_start(
        self,
        seconds: Optional[float] = None,
        out_dir: Optional[str] = None,
    ) -> dict:
        """On-demand XLA trace capture from the live daemon. Single-
        flight (the profiler is process-global); `seconds` arms an
        auto-stop so an abandoned capture cannot run forever."""
        from openr_tpu.runtime import device_stats

        try:
            return device_stats.profiler_start(
                out_dir or None,
                float(seconds) if seconds else None,
            )
        except RuntimeError as e:
            return {"ok": False, "error": str(e)}

    async def _tpu_profiler_stop(self) -> dict:
        from openr_tpu.runtime import device_stats

        try:
            return device_stats.profiler_stop()
        except RuntimeError as e:
            return {"ok": False, "error": str(e)}

    async def _tpu_profiler_status(self) -> dict:
        from openr_tpu.runtime import device_stats

        return device_stats.profiler_status()

    async def _tpu_aot(self) -> dict:
        """The persistent AOT executable cache: on-disk entries (kernel,
        signature, size, fingerprint, age) + this process's hit/miss
        ledger. `breeze tpu aot` renders it; a warm boot with misses > 0
        is the first thing the cold-start runbook checks."""
        from openr_tpu.ops.xla_cache import get_aot, retrace

        cache = get_aot()
        return {
            "summary": cache.summary(),
            "entries": cache.entries(),
            "aot_installs": retrace.snapshot().get("aot_installs", 0),
        }

    async def _tpu_devices(self) -> dict:
        """Per-device memory snapshot + live-array census (gauges'
        structured twin). backend="cpu" with bare device entries is the
        graceful no-HBM-accounting answer."""
        from openr_tpu.runtime import device_stats

        return device_stats.export_device_gauges()

    async def _tpu_kernels(self) -> dict:
        """The kernel cost ledger joined with the solver's measured
        exec times: per instrumented executable, compile cost + XLA's
        estimated flops/bytes; per area, the last solve's achieved
        throughput against the kernel that ran it."""
        from openr_tpu.ops.xla_cache import ledger
        from openr_tpu.runtime import device_stats

        kernels = ledger.snapshot()
        solver = (
            getattr(self.decision, "solver", None)
            if self.decision is not None
            else None
        )
        last_timing = getattr(solver, "last_timing", None) or {}
        achieved: list[dict] = []
        for area, stages in (last_timing.get("areas") or {}).items():
            kname = stages.get("kernel")
            exec_ms = stages.get("exec_ms")
            entry = kernels.get(kname)
            if not kname or entry is None or not exec_ms:
                continue
            row = {
                "area": area,
                "kernel": kname,
                "exec_ms": round(exec_ms, 3),
            }
            # exec_ms includes the result pull, so achieved numbers are
            # a lower bound on raw kernel throughput
            flops = entry.get("flops")
            if flops:
                row["estimated_gflops"] = round(flops / 1e9, 6)
                row["achieved_gflops_s"] = round(
                    flops / (exec_ms / 1e3) / 1e9, 3
                )
            nbytes = entry.get("bytes_accessed")
            if nbytes:
                row["achieved_gb_s"] = round(
                    nbytes / (exec_ms / 1e3) / 1e9, 3
                )
            achieved.append(row)
        from openr_tpu.ops.xla_cache import retrace

        return {
            "backend": device_stats.collect_device_stats()["backend"],
            "kernels": kernels,
            "achieved": achieved,
            "last_timing": last_timing,
            "sentinels": getattr(solver, "last_sentinels", None) or {},
            # per-namespace unexpected-recompile counts, cache-class
            # census, and the recent-retrace ring (namespace, kernel,
            # signature delta) — the triage view for a slow warm solve
            "retrace": retrace.snapshot(),
        }

    async def _monitor_fleet(self) -> dict:
        """Every node's TTL'd `monitor:health:<node>` card as flooded
        into KvStore — fleet health from any single node's ctrl port.
        A node missing here either never advertised or let its TTL
        lapse (both triage-worthy)."""
        import json as _json

        nodes: dict[str, dict] = {}
        if self.kvstore is not None:
            for area in list(getattr(self.kvstore, "areas", None) or []):
                vals = await self.kvstore.dump_all(area, "monitor:health:")
                for key, val in vals.items():
                    node = key[len("monitor:health:"):]
                    try:
                        card = _json.loads(val.value.decode())
                    except (ValueError, UnicodeDecodeError):
                        card = {"error": "unparseable health payload"}
                    cur = nodes.get(node)
                    if (
                        cur is None
                        or card.get("ts_ms", 0) > cur.get("ts_ms", 0)
                    ):
                        nodes[node] = card
        return {"local_node": self.node_name, "nodes": nodes}

    # -- persistent config store (ref setConfigKey/getConfigKey/eraseConfigKey,
    # OpenrCtrl.thrift:648-661) -----------------------------------------------

    async def _store_set(self, key: str, value: str) -> dict:
        if self.persistent_store is None:
            raise RuntimeError("no persistent store configured")
        self.persistent_store.store(f"ctrl:{key}", value.encode())
        return {"ok": True}

    async def _store_get(self, key: str) -> Optional[str]:
        if self.persistent_store is None:
            raise RuntimeError("no persistent store configured")
        raw = self.persistent_store.load(f"ctrl:{key}")
        return None if raw is None else raw.decode(errors="replace")

    async def _store_erase(self, key: str) -> dict:
        if self.persistent_store is None:
            raise RuntimeError("no persistent store configured")
        return {"erased": self.persistent_store.erase(f"ctrl:{key}")}

    async def _store_dump(self) -> dict:
        """Read-only inventory of EVERY persistent-store key — daemon
        state (link-monitor drain/overrides, rib-policy, allocator
        index) and ctrl:-namespaced operator keys — with sizes and a
        best-effort text preview (values may be binary serde)."""
        if self.persistent_store is None:
            raise RuntimeError("no persistent store configured")
        out = {}
        for key in sorted(self.persistent_store.keys()):
            raw = self.persistent_store.load(key) or b""
            preview = raw[:200].decode("utf-8", errors="replace")
            out[key] = {"bytes": len(raw), "preview": preview}
        return out

    # -- kvstore -----------------------------------------------------------

    async def _kv_get(self, area: str = "0", keys: Optional[list] = None) -> dict:
        vals = await self.kvstore.get_key_vals(area, keys or [])
        return {k: to_plain(v) for k, v in vals.items()}

    async def _kv_dump(self, area: str = "0", prefix: str = "") -> dict:
        vals = await self.kvstore.dump_all(area, prefix)
        return {k: to_plain(v) for k, v in vals.items()}

    async def _kv_peers(self, area: str = "0") -> dict:
        return {
            name: to_plain(spec)
            for name, spec in self.kvstore.get_peers(area).items()
        }

    async def _kv_set(self, area: str, key: str, value: dict) -> dict:
        from openr_tpu.types import Value

        await self.kvstore.set_key_vals(area, {key: from_plain(value, Value)})
        return {"ok": True}

    async def _kv_set_key(
        self,
        key: str,
        value: str,
        area: str = "0",
        version: Optional[int] = None,
        ttl_ms: Optional[int] = None,
    ) -> dict:
        """Operator key injection with TTL control (ref setKvStoreKeyVals
        with KeySetParams ttl, KvStore.thrift:749): version defaults to
        beating the live value."""
        from openr_tpu.types import TTL_INFINITY, Value

        if version is None:
            live = await self.kvstore.get_key_vals(area, [key])
            version = (live[key].version + 1) if key in live else 1
        val = Value(
            version=version,
            originator_id=f"breeze:{self.node_name}",
            value=value.encode(),
            ttl_ms=TTL_INFINITY if ttl_ms is None else ttl_ms,
        )
        await self.kvstore.set_key_vals(area, {key: val})
        return {"ok": True, "version": version}

    async def _kv_hashes(self, area: str = "0", prefix: str = "") -> dict:
        """Hash-only dump (ref getKvStoreHashFiltered) — the anti-entropy
        comparison view, value payloads stripped."""
        vals = await self.kvstore.dump_hashes(area, prefix)
        return {k: to_plain(v) for k, v in vals.items()}

    async def _kv_area_summary(self) -> dict:
        """ref getKvStoreAreaSummary."""
        return self.kvstore.get_area_summary()

    # -- decision ----------------------------------------------------------

    async def _decision_routes(self, from_node: Optional[str] = None) -> dict:
        db = await self.decision.get_decision_route_db(from_node)
        if db is None:
            return {"unicast": {}, "mpls": {}}
        return {
            "unicast": {p: to_plain(e) for p, e in db.unicast_routes.items()},
            "mpls": {str(l): to_plain(e) for l, e in db.mpls_routes.items()},
        }

    async def _decision_fabric_routes(
        self, from_nodes: Optional[list] = None
    ) -> dict:
        dbs = await self.decision.get_fabric_route_dbs(from_nodes)
        return {
            node: (
                None
                if db is None
                else {
                    "unicast": {
                        p: to_plain(e) for p, e in db.unicast_routes.items()
                    },
                    "mpls": {
                        str(l): to_plain(e)
                        for l, e in db.mpls_routes.items()
                    },
                }
            )
            for node, db in dbs.items()
        }

    async def _decision_adj_dbs(self) -> dict:
        dbs = await self.decision.get_adj_dbs()
        return {
            area: {node: to_plain(db) for node, db in nodes.items()}
            for area, nodes in dbs.items()
        }

    async def _decision_adjacencies_filtered(
        self,
        node_names: Optional[list] = None,
        areas: Optional[list] = None,
    ) -> dict:
        """ref getDecisionAreaAdjacenciesFiltered: adjacency DBs
        restricted to the requested node/area sets."""
        dbs = await self.decision.get_adj_dbs()
        return {
            area: {
                node: to_plain(db)
                for node, db in nodes.items()
                if not node_names or node in node_names
            }
            for area, nodes in dbs.items()
            if not areas or area in areas
        }

    async def _decision_prefix_dbs(self) -> dict:
        """ref getDecisionPrefixDbs: every announcer's prefix entries as
        Decision currently sees them."""
        dbs = await self.decision.get_prefix_dbs()
        return {
            node: {
                area: {p: to_plain(e) for p, e in prefixes.items()}
                for area, prefixes in areas.items()
            }
            for node, areas in dbs.items()
        }

    async def _decision_received(
        self,
        prefixes: Optional[list] = None,
        node: str = "",
        area: str = "",
    ) -> list:
        """ref getReceivedRoutes(Filtered) — ReceivedRouteFilter's
        prefixes / nodeName / areaName axes (OpenrCtrl.thrift:245-253)."""
        want = set(prefixes or [])
        return [
            [pfx, list(node_area), to_plain(entry)]
            for pfx, node_area, entry in await self.decision.get_received_routes()
            if (not want or pfx in want)
            and (not node or node_area[0] == node)
            and (not area or node_area[1] == area)
        ]

    async def _decision_path(
        self, src: str = "", dst: str = "", area: str = "", k: int = 2
    ) -> list:
        """ref `breeze decision path` (clis/decision.py PathCli): up to
        k edge-disjoint paths between two nodes from the live LSDB."""
        return await self.decision.get_paths(
            src or self.node_name, dst, area=area, k=int(k)
        )

    async def _decision_explain(self, prefix: str = "") -> dict:
        """Route provenance (`breeze decision explain`): the originating
        kvstore event, solve epoch and solver kind behind one RIB entry,
        joined with the Fib agent's programmed state for that prefix."""
        if not prefix:
            return {"error": "prefix required"}
        out = await self.decision.explain_route(prefix)
        if self.fib is not None and "error" not in out:
            out["fib"] = await self.fib.get_route_detail(out["prefix"])
        return out

    async def _whatif_sweep(
        self, order: int = 1, area: str = "",
        roots: Optional[list] = None, max_scenarios: int = 0,
        top: int = 0,
    ) -> dict:
        """Batched N-k failure sweep on the resident graph
        (decision/whatif.py): per-scenario partition/stretch verdicts."""
        return await self.decision.whatif_sweep(
            order=int(order), area=area or None, roots=roots,
            max_scenarios=int(max_scenarios), top=int(top),
        )

    async def _whatif_drain(
        self, node: str = "", link: str = "", area: str = "",
        roots: Optional[list] = None, top: int = 10,
    ) -> dict:
        """Drain impact preview for a node or link ('n1|n2')."""
        return await self.decision.whatif_drain(
            node=node, link=link, area=area or None, roots=roots,
            top=int(top),
        )

    async def _whatif_optimize(
        self, demands: Optional[list] = None, area: str = "",
        iters: int = 40, lr: float = 2.0, tau: float = 1.0,
    ) -> dict:
        """Differentiable link-weight TE against a demand matrix
        ([{src, dst, volume}])."""
        return await self.decision.whatif_optimize(
            demands or [], area=area or None, iters=int(iters),
            lr=float(lr), tau=float(tau),
        )

    async def _decision_validate(self) -> dict:
        """ref DecisionValidateCmd (commands/decision.py:434): per area,
        Decision's view of the LSDB must mirror KvStore's keys — report
        node sets present in one but not the other."""
        from openr_tpu.types import parse_adj_key, parse_prefix_key

        out: dict[str, dict] = {}
        adj_dbs = await self.decision.get_adj_dbs()
        prefix_dbs = await self.decision.get_prefix_dbs()
        areas = list(getattr(self.kvstore, "areas", None) or adj_dbs)
        for area in areas:
            kv = await self.kvstore.dump_all(area)
            kv_adj = {
                n for n in (parse_adj_key(key) for key in kv) if n
            }
            kv_prefix = set()
            for key in kv:
                parsed = parse_prefix_key(key)
                if parsed and parsed[1] == area:
                    kv_prefix.add(parsed[0])
            dec_adj = set(adj_dbs.get(area, {}))
            dec_prefix = {
                node
                for node, by_area in prefix_dbs.items()
                if area in by_area
            }
            report = {
                "adj_only_in_kvstore": sorted(kv_adj - dec_adj),
                "adj_only_in_decision": sorted(dec_adj - kv_adj),
                "prefix_only_in_kvstore": sorted(kv_prefix - dec_prefix),
                "prefix_only_in_decision": sorted(dec_prefix - kv_prefix),
            }
            report["ok"] = not any(v for v in report.values())
            out[area] = report
        return out

    async def _fib_validate(self) -> dict:
        """ref FibValidateRoutesCmd (commands/fib.py:216): Decision's
        computed routes vs Fib's programmed state must agree (the Fib
        actor's dirty/retry machinery closes transient gaps — persistent
        deltas mean routes stuck unprogrammed)."""
        dec = await self.decision.get_decision_route_db(None)
        fib_unicast = await self.fib.get_route_db()
        fib_mpls = await self.fib.get_mpls_route_db()
        dec_unicast = dict(dec.unicast_routes) if dec else {}
        dec_mpls = dict(dec.mpls_routes) if dec else {}
        mismatched = sorted(
            p
            for p in set(dec_unicast) & set(fib_unicast)
            if dec_unicast[p].nexthops != fib_unicast[p].nexthops
        )
        report = {
            "unicast_only_in_decision": sorted(
                set(dec_unicast) - set(fib_unicast)
            ),
            "unicast_only_in_fib": sorted(
                set(fib_unicast) - set(dec_unicast)
            ),
            "unicast_nexthop_mismatch": mismatched,
            "mpls_only_in_decision": sorted(
                set(dec_mpls) - set(fib_mpls)
            ),
            "mpls_only_in_fib": sorted(set(fib_mpls) - set(dec_mpls)),
            "fib_synced": self.fib.synced,
        }
        report["ok"] = self.fib.synced and not any(
            v for k, v in report.items() if k not in ("ok", "fib_synced")
        )
        return report

    async def _set_rib_policy(self, policy: dict) -> dict:
        from openr_tpu.decision.rib_policy import RibPolicy

        await self.decision.set_rib_policy(from_plain(policy, RibPolicy))
        return {"ok": True}

    async def _get_rib_policy(self) -> Optional[dict]:
        policy = await self.decision.get_rib_policy()
        if policy is None:
            return None
        out = to_plain(policy)
        out["remaining_ttl_secs"] = policy.remaining_ttl_secs()
        return out

    async def _clear_rib_policy(self) -> dict:
        await self.decision.clear_rib_policy()
        return {"ok": True}

    # -- fib ---------------------------------------------------------------

    async def _fib_routes(self) -> dict:
        routes = await self.fib.get_route_db()
        return {p: to_plain(e) for p, e in routes.items()}

    async def _fib_mpls(self) -> dict:
        routes = await self.fib.get_mpls_route_db()
        return {str(l): to_plain(e) for l, e in routes.items()}

    async def _fib_route_detail_db(self) -> dict:
        """ref getRouteDetailDb (OpenrCtrl.thrift:392): programmed routes
        WITH the selection detail FibService never sees — the winning
        PrefixEntry (best_prefix_entry), best node/area, igp cost, LFA
        backups — in RouteDatabaseDetail shape."""
        return {
            "node": self.node_name,
            "unicast": await self._fib_routes(),
            "mpls": await self._fib_mpls(),
        }

    async def _fib_routes_filtered(self, prefixes: list) -> dict:
        """ref getUnicastRoutesFiltered: exact-prefix selection."""
        routes = await self.fib.get_route_db()
        want = set(prefixes or [])
        return {
            p: to_plain(e) for p, e in routes.items() if p in want
        }

    async def _fib_mpls_filtered(self, labels: list) -> dict:
        """ref getMplsRoutesFiltered."""
        routes = await self.fib.get_mpls_route_db()
        want = {int(x) for x in labels or []}
        return {
            str(l): to_plain(e) for l, e in routes.items() if l in want
        }

    async def _fib_perf(self) -> list:
        return [to_plain(p) for p in await self.fib.get_perf_db()]

    # -- link monitor ------------------------------------------------------

    async def _lm_links(self) -> dict:
        return await self.link_monitor.get_links()

    async def _lm_interfaces(self) -> dict:
        return {
            name: to_plain(info)
            for name, info in (await self.link_monitor.get_interfaces()).items()
        }

    async def _lm_set_overload(self, overloaded: bool) -> dict:
        await self.link_monitor.set_node_overload(overloaded)
        return {"ok": True}

    async def _lm_set_link_overload(self, if_name: str, overloaded: bool) -> dict:
        await self.link_monitor.set_link_overload(if_name, overloaded)
        return {"ok": True}

    async def _lm_set_link_metric(
        self, if_name: str, metric: Optional[int] = None
    ) -> dict:
        await self.link_monitor.set_link_metric(if_name, metric)
        return {"ok": True}

    async def _lm_set_adj_metric(
        self, if_name: str, neighbor: str, metric: Optional[int] = None
    ) -> dict:
        """ref set/unsetAdjacencyMetric (OpenrCtrl.thrift:581-586);
        metric None unsets."""
        await self.link_monitor.set_adjacency_metric(
            if_name, neighbor, metric
        )
        return {"ok": True}

    async def _lm_set_node_metric_increment(self, increment: int = 0) -> dict:
        """ref set/unsetNodeInterfaceMetricIncrement; 0 unsets."""
        await self.link_monitor.set_node_metric_increment(increment)
        return {"ok": True}

    async def _lm_set_link_metric_increment(
        self, if_name: str, increment: int = 0
    ) -> dict:
        """ref set/unsetInterfaceMetricIncrement; 0 unsets."""
        await self.link_monitor.set_link_metric_increment(if_name, increment)
        return {"ok": True}

    async def _lm_adjacencies(self, area: Optional[str] = None) -> list:
        """ref getLinkMonitorAdjacencies(Filtered)."""
        return [
            to_plain(db)
            for db in await self.link_monitor.get_adjacencies(area)
        ]

    # -- spark / prefix manager --------------------------------------------

    async def _spark_neighbors(self) -> list:
        return [
            {
                "node": nb.node_name,
                "if_name": nb.if_name,
                "state": nb.state.name,
                "area": nb.area,
                "rtt_us": nb.rtt_us,
            }
            for nb in await self.spark.get_neighbors()
        ]

    async def _pm_advertised(
        self,
        prefixes: Optional[list] = None,
        ptype: Optional[str] = None,
        area: str = "",
    ) -> dict:
        """ref getAdvertisedRoutes(Filtered) + getAreaAdvertisedRoutes —
        AdvertisedRouteFilter's prefixes / prefixType axes
        (OpenrCtrl.thrift:64-67) plus the destination-area view."""
        want = set(prefixes or [])
        pt = self._parse_prefix_type(ptype) if ptype is not None else None
        if area:
            routes = await self.prefix_manager.get_area_advertised_routes(
                area
            )
        else:
            routes = await self.prefix_manager.get_advertised_routes()
        return {
            p: to_plain(e)
            for p, e in routes.items()
            if (not want or p in want) and (pt is None or e.type == pt)
        }

    async def _pm_prefixes(self) -> dict:
        return {
            p: to_plain(e)
            for p, e in (await self.prefix_manager.get_prefixes()).items()
        }

    async def _pm_prefixes_by_type(self, ptype) -> dict:
        """ref getPrefixesByType."""
        pt = self._parse_prefix_type(ptype)
        return {
            p: to_plain(e)
            for p, e in (await self.prefix_manager.get_prefixes()).items()
            if e.type == pt
        }

    async def _pm_originated(self) -> dict:
        """ref getOriginatedPrefixes: config-originated supernodes with
        their install state."""
        out = {}
        for prefix, st in self.prefix_manager.originated.items():
            out[prefix] = {
                "config": to_plain(st.conf),
                "supporting_prefixes": sorted(st.supporting),
                "advertised": st.advertised,
            }
        return out

    @staticmethod
    def _parse_prefix_type(ptype):
        from openr_tpu.types import PrefixType

        if isinstance(ptype, str):
            return PrefixType[ptype.upper()]
        return PrefixType(ptype)

    def _parse_entries(self, prefixes: list, ptype) -> tuple:
        from openr_tpu.types import PrefixEntry, replace

        pt = self._parse_prefix_type(ptype)
        entries = []
        for p in prefixes:
            if isinstance(p, str):
                entries.append(PrefixEntry(prefix=p, type=pt))
            else:
                e = from_plain(p, PrefixEntry)
                entries.append(replace(e, type=pt))
        return pt, entries

    async def _pm_advertise(
        self, prefixes: list, ptype="BREEZE", dest_areas: Optional[list] = None
    ) -> dict:
        """Operator prefix injection (ref advertisePrefixes,
        OpenrCtrl.thrift:299): entries may be plain prefix strings or
        full PrefixEntry payloads."""
        pt, entries = self._parse_entries(prefixes, ptype)
        self.prefix_manager.advertise_prefixes(
            entries, pt, tuple(dest_areas or ())
        )
        return {"ok": True, "advertised": len(entries)}

    async def _pm_withdraw(self, prefixes: list, ptype="BREEZE") -> dict:
        """ref withdrawPrefixes (OpenrCtrl.thrift:307)."""
        pt, entries = self._parse_entries(prefixes, ptype)
        self.prefix_manager.withdraw_prefixes(entries, pt)
        return {"ok": True, "withdrawn": len(entries)}

    async def _pm_withdraw_by_type(self, ptype) -> dict:
        """ref withdrawPrefixesByType (OpenrCtrl.thrift:314)."""
        self.prefix_manager.withdraw_prefixes_by_type(
            self._parse_prefix_type(ptype)
        )
        return {"ok": True}

    async def _pm_sync_by_type(self, prefixes: list, ptype) -> dict:
        """ref syncPrefixesByType (OpenrCtrl.thrift:323): the given set
        REPLACES everything of that type."""
        pt, entries = self._parse_entries(prefixes, ptype)
        self.prefix_manager.sync_prefixes_by_type(entries, pt)
        return {"ok": True, "synced": len(entries)}

    async def _spark_flood_restarting(self) -> dict:
        """ref floodRestartingMsg: graceful-restart hellos out of every
        interface now (operator-initiated GR prep)."""
        await self.spark.send_restarting_hellos()
        return {"ok": True}

    async def _get_config(self) -> dict:
        """Running config dump (ref getRunningConfig)."""
        if self.config is None:
            return {}
        return to_plain(self.config.raw)

    async def _drain_state(self) -> dict:
        """ref getDrainState: node-level drain plus per-link overrides."""
        if self.link_monitor is None:
            return {}
        st = self.link_monitor.state
        return {
            "is_drained": st.is_overloaded,
            "overloaded_links": sorted(st.overloaded_links),
            "link_metric_overrides": dict(st.link_metric_overrides),
        }

    async def _kv_flood_topo(self, area: str = "0") -> dict:
        """DUAL spanning-tree state (ref getSpmsimFloodTopo-style
        introspection): per-root state/parent/children, the active SPT
        peer set, and whether flooding is tree- or mesh-mode."""
        st = self.kvstore.areas.get(area)
        if st is None or st.dual is None:
            return {"enabled": False}
        spt = st.dual.flood_peers()
        return {
            "enabled": True,
            "mode": "spt" if spt is not None else "full-mesh",
            "flood_peers": sorted(spt) if spt is not None else None,
            "roots": st.dual.status(),
        }

    async def _kv_divergence(self, resolve: bool = True) -> dict:
        """LSDB divergence beacons (`breeze kv divergence`): compare
        peers' advertised digests against our recent local digests; with
        resolve, pull each suspect's key hashes and name the first
        divergent key."""
        return await self.kvstore.divergence_report(resolve=bool(resolve))

    async def _kv_long_poll_adj(
        self,
        area: str = "0",
        snapshot: Optional[dict] = None,
        timeout_s: float = 290.0,
    ) -> dict:
        """Long-poll for adjacency-key changes (ref
        longPollKvStoreAdjArea, OpenrCtrl.thrift:262 + the handler's
        long-poll fiber bookkeeping): `snapshot` maps adj: key ->
        version as the client last saw it; the call returns
        {"changed": true} as soon as any adjacency key in the area is
        new, bumped, or gone relative to the snapshot, or
        {"changed": false} at timeout. An empty snapshot returns
        immediately with the current truth (any adj key counts as
        changed)."""
        from openr_tpu.types import ADJ_DB_MARKER

        snap = {k: int(v) for k, v in (snapshot or {}).items()}

        def changed_vs_snapshot(cur: dict) -> bool:
            for k, ver in cur.items():
                if snap.get(k, -1) < ver:
                    return True
            return any(k not in cur for k in snap)

        def adj_versions(vals: dict) -> dict:
            return {
                k: v.version
                for k, v in vals.items()
                if k.startswith(ADJ_DB_MARKER)
            }

        # Register the reader BEFORE taking the snapshot: a publication
        # landing between dump_all and reader creation would otherwise be
        # missed and the poll sleeps its full timeout (ref installs the
        # kvstore callback before snapshotting for the same reason).
        reader = None
        if self._kvstore_updates_q is not None:
            reader = self._kvstore_updates_q.get_reader(f"{self.name}.longpoll")
        try:
            current = adj_versions(await self.kvstore.dump_all(area))
            if changed_vs_snapshot(current):
                return {"changed": True}
            if reader is None:
                return {"changed": False}
            deadline = time.monotonic() + timeout_s
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {"changed": False}
                try:
                    item = await asyncio.wait_for(reader.get(), remaining)
                except asyncio.TimeoutError:
                    return {"changed": False}
                if not isinstance(item, Publication) or item.area != area:
                    continue
                pub_adj = adj_versions(item.key_vals)
                if changed_vs_snapshot({**current, **pub_adj}):
                    return {"changed": True}
                if any(
                    k.startswith(ADJ_DB_MARKER) for k in item.expired_keys
                ):
                    return {"changed": True}
        finally:
            if reader is not None:
                self._kvstore_updates_q.remove_reader(reader)

    async def _dryrun_config(self, config: dict) -> dict:
        """Validate a config payload without applying it (ref
        dryrunConfig, OpenrCtrl.thrift:269-277): returns the parsed,
        defaulted config on success or the validation error."""
        from openr_tpu.config import Config, ConfigError, OpenrConfig

        try:
            cfg = Config(from_plain(config, OpenrConfig))
        except (ConfigError, TypeError, ValueError, KeyError) as e:
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}
        return {"ok": True, "config": to_plain(cfg.raw)}

    # -- streaming subscriptions (ref OpenrCtrlHandler.h:351-389) ----------

    def _register_stream(self, stream: Stream, kind: str) -> int:
        """Track a live stream for getSubscriberInfo (ref
        StreamSubscriberInfo, OpenrCtrl.thrift:72-83): every push stamps
        last-sent time and bumps the message count."""
        sid = self._next_subscriber_id
        self._next_subscriber_id += 1
        info = {
            "subscriber_id": sid,
            "type": kind,
            "started": time.time(),
            "last_msg_sent_time": 0.0,
            "total_streamed_msgs": 0,
        }
        self._subscribers[sid] = info
        orig_push = stream.push

        def push(item):
            info["total_streamed_msgs"] += 1
            info["last_msg_sent_time"] = time.time()
            orig_push(item)

        stream.push = push
        return sid

    async def _subscriber_info(self, type: str = "") -> list:
        """ref getSubscriberInfo(type): stats for every live streaming
        subscription, optionally filtered by kind (kvstore / fib /
        fib_detail)."""
        now = time.time()
        return [
            {
                "subscriber_id": i["subscriber_id"],
                "type": i["type"],
                "uptime_ms": int((now - i["started"]) * 1e3),
                "last_msg_sent_time": i["last_msg_sent_time"],
                "total_streamed_msgs": i["total_streamed_msgs"],
            }
            for i in self._subscribers.values()
            if not type or i["type"] == type
        ]

    def _start_subscription(
        self, kind: str, snapshot, queue, reader_suffix: str, on_item
    ) -> Stream:
        """Common tail of every subscribe handler: acquire the queue
        reader (fallible — the producer may have closed the queue),
        register the subscriber, push the pre-serialized snapshot, spawn
        the pump. Every fallible step precedes registration so a failing
        subscribe can't leak a phantom ctrl.subscriber_info entry."""
        stream = Stream()
        reader = queue.get_reader(f"{self.name}.{reader_suffix}")
        sid = self._register_stream(stream, kind)
        if snapshot is not None:
            stream.push(snapshot)
        self.add_task(
            self._pump_subscription(
                stream, reader, queue, lambda item: on_item(stream, item), sid
            ),
            name=f"{self.name}.{kind}-sub",
        )
        return stream

    async def _subscribe_kvstore(self, area: str = "0") -> Stream:
        """Snapshot + live deltas (ref subscribeAndGetKvStoreFiltered)."""
        snapshot = await self.kvstore.dump_all(area)
        payload = {
            "snapshot": {k: to_plain(v) for k, v in snapshot.items()},
            "area": area,
        }

        def on_item(stream, item):
            if isinstance(item, Publication) and item.area == area:
                stream.push({"delta": to_plain(item)})

        return self._start_subscription(
            "kvstore", payload, self._kvstore_updates_q, "sub", on_item
        )

    @staticmethod
    def _fib_delta(stream, item):
        if not isinstance(item, InitializationEvent):
            stream.push({"delta": to_plain(item)})

    async def _subscribe_fib(self) -> Stream:
        """Snapshot + programmed-route deltas (ref subscribeAndGetFib)."""
        payload = None
        if self.fib is not None:
            routes = await self.fib.get_route_db()
            payload = {
                "snapshot": {p: to_plain(e) for p, e in routes.items()}
            }
        return self._start_subscription(
            "fib", payload, self._fib_updates_q, "sub", self._fib_delta
        )

    async def _subscribe_fib_detail(self) -> Stream:
        """ref subscribeAndGetFibDetail (OpenrCtrlCpp.thrift:53-55):
        RouteDatabaseDetail-shaped snapshot (node name + unicast incl.
        best_prefix_entry + mpls) followed by live deltas."""
        payload = None
        if self.fib is not None:
            payload = {"snapshot": await self._fib_route_detail_db()}
        return self._start_subscription(
            "fib_detail", payload, self._fib_updates_q, "subd",
            self._fib_delta,
        )

    async def _pump_subscription(
        self, stream, reader, queue, on_item, sid: Optional[int] = None
    ) -> None:
        """Forward queue items into a stream until it closes. reader.get()
        races stream closure so a disconnected client's queue reader is
        unregistered promptly instead of on the next (possibly never)
        published item."""
        close_wait = asyncio.ensure_future(stream.wait_closed())
        get_t = None
        try:
            while not stream.closed:
                get_t = asyncio.ensure_future(reader.get())
                # mark any exception retrieved up front: the task can be
                # abandoned mid-flight (stream close, or this pump task
                # cancelled at actor stop) and then completed by the
                # queue closing — without this the loop logs "Task
                # exception was never retrieved"
                get_t.add_done_callback(
                    lambda t: t.cancelled() or t.exception()
                )
                await asyncio.wait(
                    {get_t, close_wait}, return_when=asyncio.FIRST_COMPLETED
                )
                if not get_t.done():
                    get_t.cancel()
                    break
                # lint: allow(blocking-call) task is done() — no wait
                on_item(get_t.result())
        except QueueClosedError:
            pass
        finally:
            if get_t is not None and not get_t.done():
                get_t.cancel()
            close_wait.cancel()
            stream.close()
            queue.remove_reader(reader)
            if sid is not None:
                self._subscribers.pop(sid, None)
