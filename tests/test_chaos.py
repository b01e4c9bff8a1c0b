"""Chaos drills — fault-injected system tests (ISSUE 4 acceptance).

Full node stacks on a MockIoMesh, with faults armed through the same
registry `breeze fault inject` drives in production:

  - kill-the-TPU: arm `solver.exec` mid-convergence on a 3-node topology;
    routes must keep converging through the CPU fallback, the node must
    report degraded (gauge + fleet health + trace stamp), and the device
    must be promoted back once the fault clears.
  - decision fiber crash: arm `decision.ingest`; the supervisor must
    restart the fiber within budget and the pipeline must keep working.
  - spark graceful restart: a restarting node's routes must be held
    through the GR window and flushed when it expires.

Marked slow (out of the tier-1 lane) + chaos (the CI chaos lane).
"""

import asyncio
import contextlib

import pytest

from openr_tpu.config import DecisionConfig, MonitorConfig, SparkConfig
from openr_tpu.kvstore.wrapper import wait_until
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.faults import registry
from openr_tpu.runtime.monitor import Monitor
from openr_tpu.runtime.openr_wrapper import OpenrWrapper
from openr_tpu.runtime.tracing import tracer
from openr_tpu.spark import MockIoMesh
from openr_tpu.types import Value
from tests.conftest import run_async

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

CONVERGENCE_S = 20.0


async def start_mesh(names, links, **wrapper_kwargs):
    """test_system.start_mesh, plus per-node wrapper kwargs (solver
    backend, probe-tuned decision config, spark GR timers)."""
    mesh = MockIoMesh()
    kv_ports: dict[str, int] = {}
    nodes = {
        n: OpenrWrapper(n, mesh.provider(n), kv_ports, **wrapper_kwargs)
        for n in names
    }
    for a, if_a, b, if_b in links:
        mesh.connect(a, if_a, b, if_b)
    ifaces = {n: [] for n in names}
    for a, if_a, b, if_b in links:
        ifaces[a].append(if_a)
        ifaces[b].append(if_b)
    for n, w in nodes.items():
        await w.start(*ifaces[n])
    return mesh, nodes


async def stop_all(nodes):
    for w in nodes.values():
        with contextlib.suppress(Exception):
            await w.stop()


def loopback(i: int) -> str:
    return f"10.0.0.{i + 1}/32"


def _counter(key):
    return counters.get_counter(key) or 0


def _degraded_trace_closed():
    return any(
        t["spans"][0]["attributes"].get("degraded") is True
        and t["status"] == "ok"
        for t in tracer.get_traces(limit=500)
    )


class TestKillTheTpuDrill:
    @run_async
    async def test_solver_failover_mid_convergence(self):
        """Triangle a-b-c on the TPU backend; the device 'dies' (armed
        solver.exec) right before a topology change."""
        registry.clear()
        counters.set_counter("decision.solver.degraded", 0)
        names = ["node-0", "node-1", "node-2"]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-20", "node-0", "if-02"),
        ]
        mesh, nodes = await start_mesh(
            names,
            links,
            solver_backend="tpu",
            decision_config=DecisionConfig(
                debounce_min_ms=5,
                debounce_max_ms=25,
                solver_probe_initial_backoff_s=0.2,
                solver_probe_max_backoff_s=0.5,
            ),
        )
        mon = Monitor(
            "node-0",
            MonitorConfig(),
            nodes["node-0"].log_sample_queue.get_reader("drill"),
        )
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))

            def converged():
                for i, n in enumerate(names):
                    expect = {loopback(j) for j in range(3) if j != i}
                    if set(nodes[n].fib_routes) != expect:
                        return False
                return True

            await wait_until(converged, timeout_s=CONVERGENCE_S)
            failovers0 = _counter("decision.solver.failovers")
            promotions0 = _counter("decision.solver.promotions")

            # the device dies mid-flight...
            registry.arm("solver.exec")
            # ...and then the topology changes: cut node-0 <-> node-2
            mesh.disconnect("node-0", "if-02", "node-2", "if-20")

            def rerouted_degraded():
                entry = nodes["node-0"].fib_routes.get(loopback(2))
                if entry is None:
                    return False
                via_b = {
                    nh.neighbor_node_name for nh in entry.nexthops
                } == {"node-1"}
                return via_b and _counter("decision.solver.degraded") == 1

            # routes converge anyway — carried by the CPU oracle
            await wait_until(rerouted_degraded, timeout_s=CONVERGENCE_S)
            assert _counter("decision.solver.failovers") > failovers0
            # the node reports degraded in fleet health...
            assert mon.health_summary()["solver_degraded"] is True
            # ...and the convergence trace closed stamped degraded=true
            await wait_until(_degraded_trace_closed, timeout_s=CONVERGENCE_S)
            # probes keep failing while the fault is armed
            await wait_until(
                lambda: _counter("decision.solver.probe_failures") >= 1,
                timeout_s=CONVERGENCE_S,
            )
            assert _counter("decision.solver.degraded") == 1

            # the device heals: clear the fault, probes promote it back
            registry.clear("solver.exec")
            await wait_until(
                lambda: _counter("decision.solver.degraded") == 0
                and _counter("decision.solver.promotions") > promotions0,
                timeout_s=CONVERGENCE_S,
            )
            assert mon.health_summary()["solver_degraded"] is False

            # the promoted pipeline still routes fresh state end to end
            nodes["node-2"].advertise_prefix("10.77.0.0/24")
            await wait_until(
                lambda: "10.77.0.0/24" in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
        finally:
            registry.clear()
            counters.set_counter("decision.solver.degraded", 0)
            await stop_all(nodes)


class TestSloBurnFlightRecorderDrill:
    @run_async
    async def test_failover_trips_slo_burn_and_flight_recorder(self):
        """ISSUE 11 drill: an armed solver.exec fault mid-convergence
        must (1) auto-trigger a flight-recorder bundle attributed to the
        failover (DECISION_SOLVER_DEGRADED), (2) burn the
        solver_degraded_s SLO into an alert through the Monitor's
        metrics loop, and (3) freeze a post-mortem bundle whose trace
        ring holds the degraded-mode convergence roots."""
        import json
        import os
        import tempfile

        registry.clear()
        counters.set_counter("decision.solver.degraded", 0)
        rec_dir = tempfile.mkdtemp(prefix="openr-tpu-flightrec-drill-")
        names = ["node-0", "node-1", "node-2"]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-20", "node-0", "if-02"),
        ]
        mesh, nodes = await start_mesh(
            names,
            links,
            solver_backend="tpu",
            decision_config=DecisionConfig(
                debounce_min_ms=5,
                debounce_max_ms=25,
                solver_probe_initial_backoff_s=5.0,
                solver_probe_max_backoff_s=5.0,
            ),
        )
        mon = Monitor(
            "node-0",
            MonitorConfig(
                # drill-scale SLO: degraded for >1s starts breaching,
                # a half-burned 2s window alerts — so the whole state
                # machine runs in seconds instead of operator-minutes
                slos={
                    "solver_degraded_s": {
                        "kind": "gauge_duration",
                        "source": "decision.solver.degraded",
                        "threshold": 1.0,
                        "fast_window_s": 2.0,
                        "slow_window_s": 4.0,
                    }
                },
                slo_fast_window_s=2.0,
                slo_slow_window_s=4.0,
                flight_recorder_dir=rec_dir,
                flight_recorder_ring=64,
                flight_recorder_min_interval_s=0.0,
            ),
            nodes["node-0"].log_sample_queue.get_reader("slo-drill"),
            interval_s=0.1,
        )
        alerts_key = "monitor.slo.solver_degraded_s.alerts"
        alerts0 = _counter(alerts_key)
        await mon.start()
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))

            def converged():
                for i, n in enumerate(names):
                    expect = {loopback(j) for j in range(3) if j != i}
                    if set(nodes[n].fib_routes) != expect:
                        return False
                return True

            await wait_until(converged, timeout_s=CONVERGENCE_S)

            # the device dies, then the topology changes
            registry.arm("solver.exec")
            mesh.disconnect("node-0", "if-02", "node-2", "if-20")
            await wait_until(
                lambda: _counter("decision.solver.degraded") == 1
                and loopback(2) in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )

            # (1) the failover LogSample auto-triggered a bundle that
            # NAMES the failover in its trigger attribution
            await wait_until(
                lambda: any(
                    b["reason"] == "solver_failover"
                    for b in mon.flight_recorder.bundles
                ),
                timeout_s=CONVERGENCE_S,
            )
            fo = next(
                b
                for b in mon.flight_recorder.bundles
                if b["reason"] == "solver_failover"
            )
            with open(os.path.join(fo["path"], "bundle.json")) as f:
                fo_doc = json.load(f)
            assert fo_doc["schema"] == "openr-tpu-flight-recorder/1"
            assert fo_doc["trigger"]["reason"] == "solver_failover"
            assert (
                fo_doc["trigger"]["detail"]["event"]
                == "DECISION_SOLVER_DEGRADED"
            ), fo_doc["trigger"]
            assert os.path.exists(os.path.join(fo["path"], "trace.json"))

            # the degraded-mode reroute closes its stamped trace before
            # the SLO window can fill
            await wait_until(_degraded_trace_closed, timeout_s=CONVERGENCE_S)

            # (2) the sustained degraded gauge burns the SLO: the engine
            # raises the alert, logs it, and counts it
            await wait_until(
                lambda: _counter(alerts_key) > alerts0,
                timeout_s=CONVERGENCE_S,
            )
            rep = mon.slo_report()
            assert rep["enabled"] is True
            state = rep["slos"]["solver_degraded_s"]["state"]
            assert state in ("fast_burn", "sustained_burn"), rep
            assert any(
                s.event == "SLO_BURN_ALERT"
                and s.values.get("slo") == "solver_degraded_s"
                for s in mon.event_logs
            ), [s.event for s in mon.event_logs]
            assert _counter("monitor.slo.solver_degraded_s.burning") >= 1

            # (3) the burn auto-froze a bundle whose trace ring holds
            # the degraded convergence roots and whose SLO annex shows
            # the burning objective
            await wait_until(
                lambda: any(
                    b["reason"].startswith("slo_burn:")
                    for b in mon.flight_recorder.bundles
                ),
                timeout_s=CONVERGENCE_S,
            )
            sb = next(
                b
                for b in mon.flight_recorder.bundles
                if b["reason"].startswith("slo_burn:")
            )
            with open(os.path.join(sb["path"], "bundle.json")) as f:
                sb_doc = json.load(f)
            assert sb_doc["trigger"]["reason"] == (
                "slo_burn:solver_degraded_s"
            )
            assert any(
                t["spans"][0]["attributes"].get("degraded") is True
                for t in sb_doc["traces"]
            ), [t["spans"][0]["attributes"] for t in sb_doc["traces"]]
            assert (
                sb_doc["slo"]["slos"]["solver_degraded_s"]["state"]
                != "ok"
            ), sb_doc["slo"]
            # the bundle carries the lead-up: counter history ticks and
            # the noted anomaly events
            assert len(sb_doc["counter_history"]) >= 1
            assert _counter("monitor.flight_recorder.triggers") >= 2
        finally:
            registry.clear()
            counters.set_counter("decision.solver.degraded", 0)
            with contextlib.suppress(Exception):
                await mon.stop()
            await stop_all(nodes)


class TestIncrementalSolverFailoverDrill:
    @run_async
    async def test_fault_during_incremental_solve_fails_over(self):
        """ISSUE 7 drill: a warm solver on the incremental (seed-from-
        previous) path takes an armed solver.exec fault mid-churn. The
        failover must carry the event to the CPU oracle with NO stale-
        route window — the fib lands on the post-churn next-hop set —
        and after the device heals, churn re-engages the incremental
        path. Engagement is driven by pumping prefix events (the
        wrapper's own adjacency re-origination makes any single
        topology event race the root-signature gate)."""
        registry.clear()
        counters.set_counter("decision.solver.degraded", 0)
        # 4-node ring: node-0 reaches node-2 via ECMP {node-1, node-3},
        # and the 1<->2 edge is NOT one of node-0's root links, so its
        # churn is exactly the incremental path's home turf
        names = [f"node-{i}" for i in range(4)]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-23", "node-3", "if-32"),
            ("node-3", "if-30", "node-0", "if-03"),
        ]
        mesh, nodes = await start_mesh(
            names,
            links,
            solver_backend="tpu",
            decision_config=DecisionConfig(
                debounce_min_ms=5,
                debounce_max_ms=25,
                incremental_spf=True,
                solver_probe_initial_backoff_s=0.2,
                solver_probe_max_backoff_s=0.5,
            ),
        )
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))

            def nh_set(pfx):
                entry = nodes["node-0"].fib_routes.get(pfx)
                if entry is None:
                    return set()
                return {nh.neighbor_node_name for nh in entry.nexthops}

            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-1", "node-3"},
                timeout_s=CONVERGENCE_S,
            )

            async def pump_incremental(tag):
                """Flap the (non-root-for-node-0) 1<->2 link until an
                incremental solve lands; leaves the link connected.
                Each half waits for fib convergence, so a pass also
                proves the warm path kept routing correct."""
                incr0 = _counter("decision.solver.incr.solves")
                for _ in range(10):
                    mesh.disconnect(
                        "node-1", "if-12", "node-2", "if-21"
                    )
                    await wait_until(
                        lambda: nh_set(loopback(2)) == {"node-3"},
                        timeout_s=CONVERGENCE_S,
                    )
                    mesh.connect("node-1", "if-12", "node-2", "if-21")
                    await wait_until(
                        lambda: nh_set(loopback(2))
                        == {"node-1", "node-3"},
                        timeout_s=CONVERGENCE_S,
                    )
                    if (
                        _counter("decision.solver.incr.solves") > incr0
                    ):
                        return
                raise AssertionError(
                    f"incremental path never engaged ({tag})"
                )

            # healthy churn first: the warm solvers must take the
            # seed-from-previous path
            await pump_incremental(0)

            # topology churn away from node-0's root links
            mesh.disconnect("node-1", "if-12", "node-2", "if-21")
            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-3"},
                timeout_s=CONVERGENCE_S,
            )

            # the device dies; the link comes back. The solve for this
            # event would be incremental — the armed fault must push it
            # to the CPU oracle, which lands the restored ECMP set
            # directly (no window serving the stale single-path route)
            failovers0 = _counter("decision.solver.failovers")
            promotions0 = _counter("decision.solver.promotions")
            registry.arm("solver.exec")
            mesh.connect("node-1", "if-12", "node-2", "if-21")
            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-1", "node-3"}
                and _counter("decision.solver.degraded") == 1,
                timeout_s=CONVERGENCE_S,
            )
            assert _counter("decision.solver.failovers") > failovers0

            # heal: probes promote the device back, and the next churn
            # runs incremental again off a freshly seeded plane
            registry.clear("solver.exec")
            await wait_until(
                lambda: _counter("decision.solver.degraded") == 0
                and _counter("decision.solver.promotions") > promotions0,
                timeout_s=CONVERGENCE_S,
            )
            await pump_incremental(1)
        finally:
            registry.clear()
            counters.set_counter("decision.solver.degraded", 0)
            await stop_all(nodes)


class TestMultichipSolverFailoverDrill:
    @run_async
    async def test_fault_during_multichip_solve_fails_over(self):
        """Multichip capacity-tier drill: with the tier forced on
        (threshold below the 4-node ring's n_cap, 8 virtual devices),
        an armed solver.exec fault lands on a sharded solve mid-churn.
        The failover must carry the event to the CPU oracle with NO
        stale-route window — the fib lands directly on the post-churn
        ECMP set — and after the device heals, the probe canary must
        re-promote the node back onto the multichip path (the tier's
        dispatch counter advances on post-heal churn)."""
        registry.clear()
        counters.set_counter("decision.solver.degraded", 0)
        names = [f"node-{i}" for i in range(4)]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-23", "node-3", "if-32"),
            ("node-3", "if-30", "node-0", "if-03"),
        ]
        mesh, nodes = await start_mesh(
            names,
            links,
            solver_backend="tpu",
            decision_config=DecisionConfig(
                debounce_min_ms=5,
                debounce_max_ms=25,
                multichip_n_cap_threshold=2,
                solver_probe_initial_backoff_s=0.2,
                solver_probe_max_backoff_s=0.5,
            ),
        )
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))

            def nh_set(pfx):
                entry = nodes["node-0"].fib_routes.get(pfx)
                if entry is None:
                    return set()
                return {nh.neighbor_node_name for nh in entry.nexthops}

            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-1", "node-3"},
                timeout_s=CONVERGENCE_S,
            )
            # the tier must actually be live before the drill means
            # anything: the initial convergence solves were sharded
            assert _counter("decision.solver.multichip.engaged") > 0
            assert _counter("decision.solver.multichip.dispatches") > 0

            # topology churn away from node-0's root links
            mesh.disconnect("node-1", "if-12", "node-2", "if-21")
            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-3"},
                timeout_s=CONVERGENCE_S,
            )

            # the device dies; the link comes back. The solve for this
            # event would run through the multichip tier — the armed
            # fault must push it to the CPU oracle, which lands the
            # restored ECMP set directly (no window serving the stale
            # single-path route)
            failovers0 = _counter("decision.solver.failovers")
            promotions0 = _counter("decision.solver.promotions")
            registry.arm("solver.exec")
            mesh.connect("node-1", "if-12", "node-2", "if-21")
            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-1", "node-3"}
                and _counter("decision.solver.degraded") == 1,
                timeout_s=CONVERGENCE_S,
            )
            assert _counter("decision.solver.failovers") > failovers0

            # heal: the probe canary promotes the device back and churn
            # dispatches through the multichip tier again
            registry.clear("solver.exec")
            await wait_until(
                lambda: _counter("decision.solver.degraded") == 0
                and _counter("decision.solver.promotions") > promotions0,
                timeout_s=CONVERGENCE_S,
            )
            mc_disp0 = _counter("decision.solver.multichip.dispatches")
            mesh.disconnect("node-1", "if-12", "node-2", "if-21")
            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-3"}
                and _counter("decision.solver.multichip.dispatches")
                > mc_disp0,
                timeout_s=CONVERGENCE_S,
            )
            mesh.connect("node-1", "if-12", "node-2", "if-21")
            await wait_until(
                lambda: nh_set(loopback(2)) == {"node-1", "node-3"},
                timeout_s=CONVERGENCE_S,
            )
        finally:
            registry.clear()
            counters.set_counter("decision.solver.degraded", 0)
            await stop_all(nodes)


class TestBucketedKernelFailoverDrill:
    @run_async
    async def test_fault_during_bucketed_solve_fails_over(self):
        """Δ-stepping drill: a 10-ring is the smallest live topology
        whose plan forms shift classes (build_plan's usefulness floor
        is 8 edges per delta), so the bucketed kernel actually engages
        (delta_exp > 0) instead of silently falling back to sync. An
        armed solver.exec fault lands on a bucketed solve mid-churn:
        the failover must carry the event to the CPU oracle with NO
        stale-route window — the fib lands directly on the post-churn
        ECMP set — and after the device heals, churn runs bucketed
        epochs again (the decision.device.bucket_epochs stat advances
        post-heal)."""
        registry.clear()
        counters.set_counter("decision.solver.degraded", 0)
        n = 10
        names = [f"node-{i}" for i in range(n)]
        links = [
            (
                f"node-{i}", f"if-{i}{(i + 1) % n}",
                f"node-{(i + 1) % n}", f"if-{(i + 1) % n}{i}",
            )
            for i in range(n)
        ]

        def epoch_count():
            return (
                counters.get_counters("decision.device.bucket_epochs")
                .get("decision.device.bucket_epochs.count.60", 0)
            )

        mesh, nodes = await start_mesh(
            names,
            links,
            solver_backend="tpu",
            decision_config=DecisionConfig(
                debounce_min_ms=5,
                debounce_max_ms=25,
                spf_kernel="bucketed",
                solver_probe_initial_backoff_s=0.2,
                solver_probe_max_backoff_s=0.5,
            ),
        )
        try:
            for i, nm in enumerate(names):
                nodes[nm].advertise_prefix(loopback(i))

            def nh_set(pfx):
                entry = nodes["node-0"].fib_routes.get(pfx)
                if entry is None:
                    return set()
                return {nh.neighbor_node_name for nh in entry.nexthops}

            # node-5 is diametrically opposite node-0: 5 hops either
            # way around the ring -> ECMP over both ring neighbors
            await wait_until(
                lambda: nh_set(loopback(5)) == {"node-1", "node-9"},
                timeout_s=CONVERGENCE_S,
            )
            # the drill is meaningless unless the Δ-stepping kernel is
            # actually live: the convergence solves ran bucket epochs
            assert epoch_count() > 0, "bucketed kernel never engaged"

            # churn away from node-0's root links: cutting 4<->5 leaves
            # only the counter-clockwise path
            mesh.disconnect("node-4", "if-45", "node-5", "if-54")
            await wait_until(
                lambda: nh_set(loopback(5)) == {"node-9"},
                timeout_s=CONVERGENCE_S,
            )

            # the device dies; the link comes back. The solve for this
            # event would run bucketed epochs — the armed fault must
            # push it to the CPU oracle, which lands the restored ECMP
            # set directly (no window serving the stale single-path
            # route)
            failovers0 = _counter("decision.solver.failovers")
            promotions0 = _counter("decision.solver.promotions")
            registry.arm("solver.exec")
            mesh.connect("node-4", "if-45", "node-5", "if-54")
            await wait_until(
                lambda: nh_set(loopback(5)) == {"node-1", "node-9"}
                and _counter("decision.solver.degraded") == 1,
                timeout_s=CONVERGENCE_S,
            )
            assert _counter("decision.solver.failovers") > failovers0

            # heal: probes promote the device back and post-heal churn
            # runs bucket epochs again
            registry.clear("solver.exec")
            await wait_until(
                lambda: _counter("decision.solver.degraded") == 0
                and _counter("decision.solver.promotions") > promotions0,
                timeout_s=CONVERGENCE_S,
            )
            epochs0 = epoch_count()
            mesh.disconnect("node-4", "if-45", "node-5", "if-54")
            await wait_until(
                lambda: nh_set(loopback(5)) == {"node-9"}
                and epoch_count() > epochs0,
                timeout_s=CONVERGENCE_S,
            )
            mesh.connect("node-4", "if-45", "node-5", "if-54")
            await wait_until(
                lambda: nh_set(loopback(5)) == {"node-1", "node-9"},
                timeout_s=CONVERGENCE_S,
            )
        finally:
            registry.clear()
            counters.set_counter("decision.solver.degraded", 0)
            await stop_all(nodes)


class TestDecisionFiberCrashDrill:
    @run_async
    async def test_supervisor_restarts_crashed_ingest_fiber(self):
        registry.clear()
        names = ["node-0", "node-1"]
        links = [("node-0", "if-01", "node-1", "if-10")]
        mesh, nodes = await start_mesh(names, links)
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))
            await wait_until(
                lambda: loopback(1) in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            restarts0 = _counter("runtime.supervisor.restarts")

            # next two publications popped by a Decision ingest fiber
            # (either node — the registry is process-global) kill it
            registry.arm("decision.ingest", every_nth=1, max_fires=2)
            kv = nodes["node-0"].kvstore
            area = next(iter(kv.areas))
            for i in range(2):
                await kv.set_key_vals(
                    area,
                    {
                        f"chaos:junk-{i}": Value(
                            version=1,
                            originator_id="node-0",
                            value=b"x",
                            ttl_ms=-1,
                            ttl_version=0,
                            hash=None,
                        )
                    },
                )
                await asyncio.sleep(0.05)

            # both crashes restarted within the (default 3) budget
            await wait_until(
                lambda: _counter("runtime.supervisor.restarts")
                >= restarts0 + 2
                and not registry.list()["armed"],
                timeout_s=CONVERGENCE_S,
            )
            from openr_tpu.runtime.tasks import recent_crashes

            assert any(
                c["task"].startswith("decision:")
                and "injected fault" in c["error"]
                for c in recent_crashes()
            )

            # the restarted fiber still ingests: a fresh prefix converges
            nodes["node-1"].advertise_prefix("10.99.0.0/24")
            await wait_until(
                lambda: "10.99.0.0/24" in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
        finally:
            registry.clear()
            await stop_all(nodes)


class TestDispatchFiberKillDrill:
    @run_async
    async def test_supervisor_restarts_crashed_dispatch_fiber(self):
        """Async-dispatch mesh (ISSUE 5): kill the dedicated dispatch
        fiber mid-solve via the solver.dispatch seam. The supervisor
        must restart it, on_fiber_restart must force a full rebuild (the
        crashed fiber died holding a coalesced pending snapshot), and
        fresh topology state must keep converging end to end."""
        registry.clear()
        names = ["node-0", "node-1"]
        links = [("node-0", "if-01", "node-1", "if-10")]
        mesh, nodes = await start_mesh(
            names,
            links,
            decision_config=DecisionConfig(
                debounce_min_ms=5,
                debounce_max_ms=25,
                async_dispatch=True,
            ),
        )
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))
            await wait_until(
                lambda: loopback(1) in nodes["node-0"].fib_routes
                and loopback(0) in nodes["node-1"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            assert _counter("decision.dispatch.solves") >= 1
            restarts0 = _counter("runtime.supervisor.restarts")

            # the next two solves popped by a dispatch fiber (either
            # node — the registry is process-global) kill it
            registry.arm("solver.dispatch", every_nth=1, max_fires=2)
            nodes["node-1"].advertise_prefix("10.88.0.0/24")

            await wait_until(
                lambda: _counter("runtime.supervisor.restarts")
                >= restarts0 + 2
                and not registry.list()["armed"],
                timeout_s=CONVERGENCE_S,
            )
            from openr_tpu.runtime.tasks import recent_crashes

            assert any(
                c["task"].startswith("decision:")
                and c["task"].endswith(".dispatch")
                and "injected fault" in c["error"]
                for c in recent_crashes()
            )

            # the restarted fiber's forced full rebuild recovers the
            # snapshot lost in the crash...
            await wait_until(
                lambda: "10.88.0.0/24" in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            # ...and keeps solving fresh state
            nodes["node-0"].advertise_prefix("10.89.0.0/24")
            await wait_until(
                lambda: "10.89.0.0/24" in nodes["node-1"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
        finally:
            registry.clear()
            await stop_all(nodes)


class TestSparkGracefulRestartDrill:
    @run_async
    async def test_routes_held_through_gr_window_then_flushed(self):
        registry.clear()
        names = ["node-0", "node-1"]
        links = [("node-0", "if-01", "node-1", "if-10")]
        mesh, nodes = await start_mesh(
            names,
            links,
            spark_config=SparkConfig(
                hello_time_s=0.08,
                fastinit_hello_time_ms=20,
                keepalive_time_s=0.05,
                hold_time_s=0.4,
                graceful_restart_time_s=2.5,
                handshake_time_ms=40,
                min_packets_per_sec=0,
            ),
        )
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))
            await wait_until(
                lambda: loopback(0) in nodes["node-1"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            gr_expired0 = _counter("spark.neighbor.gr_expired")

            # node-0 announces a graceful restart, then goes dark
            await nodes["node-0"].spark.send_restarting_hellos()
            await nodes["node-0"].stop()

            # well past hold_time (0.4s) but inside the GR window (2.5s):
            # node-1 must still hold node-0's route
            await asyncio.sleep(1.0)
            assert loopback(0) in nodes["node-1"].fib_routes

            # node-0 never comes back: GR expiry flushes the route
            await wait_until(
                lambda: loopback(0) not in nodes["node-1"].fib_routes,
                timeout_s=10,
            )
            assert _counter("spark.neighbor.gr_expired") > gr_expired0
        finally:
            registry.clear()
            await stop_all(nodes)


class TestPerfRegressionDrill:
    @run_async
    async def test_latency_fault_trips_baseline_drift(self):
        """ISSUE 14 drill: an armed solver.exec LATENCY fault (delay_ms)
        inflates decision.spf_ms while routing keeps converging — no
        failover, no route loss, just a slower kernel. The
        baseline_drift SLO must compare the live window against the
        pre-seeded perf-ledger baseline, burn into an alert, and freeze
        a perf_regression bundle whose ledger delta shows
        baseline-vs-live."""
        import json
        import os
        import tempfile

        from openr_tpu.runtime import perf_ledger
        from openr_tpu.runtime.perf_ledger import PerfLedger

        registry.clear()
        ledger_dir = tempfile.mkdtemp(prefix="openr-tpu-perf-drill-")
        rec_dir = tempfile.mkdtemp(prefix="openr-tpu-flightrec-perf-")
        # the baseline a healthy fleet accreted before this "restart":
        # p95 solve latency ~5ms
        seed = PerfLedger(ledger_dir)
        for _ in range(8):
            seed.record(
                "solve", {"device_ms": 5.0}, signature="live", variant="live"
            )
        names = ["node-0", "node-1", "node-2"]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-20", "node-0", "if-02"),
        ]
        mesh, nodes = await start_mesh(
            names,
            links,
            decision_config=DecisionConfig(
                debounce_min_ms=5, debounce_max_ms=25
            ),
        )
        mon = Monitor(
            "node-0",
            MonitorConfig(
                slos={
                    "solve_drift": {
                        "kind": "baseline_drift",
                        "source": "decision.spf_ms",
                        "threshold": 1.5,
                        "min_count": 2,
                        # drill-scale: no cold-start exclusion (the mesh
                        # converges before the fault arms) and 2s/4s
                        # burn windows so the machine runs in seconds
                        "warmup_s": 0.0,
                        "fast_window_s": 2.0,
                        "slow_window_s": 4.0,
                    }
                },
                slo_fast_window_s=2.0,
                slo_slow_window_s=4.0,
                perf_ledger_dir=ledger_dir,
                flight_recorder_dir=rec_dir,
                flight_recorder_ring=64,
                flight_recorder_min_interval_s=0.0,
            ),
            nodes["node-0"].log_sample_queue.get_reader("perf-drill"),
            interval_s=0.1,
        )
        await mon.start()
        stop_churn = asyncio.Event()

        async def churn():
            """Flap a link-metric override: a link-ATTRIBUTE change
            forces full rebuilds (the incremental path has no
            solver.exec site), keeping decision.spf_ms measuring the
            delayed solves; the topology itself never changes, so
            routing stays converged throughout."""
            flip = False
            while not stop_churn.is_set():
                flip = not flip
                await nodes["node-0"].link_monitor.set_link_metric(
                    "if-01", 10 if flip else None
                )
                await asyncio.sleep(0.15)

        churn_task = None
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))

            def converged():
                for i, n in enumerate(names):
                    expect = {loopback(j) for j in range(3) if j != i}
                    if not expect <= set(nodes[n].fib_routes):
                        return False
                return True

            await wait_until(converged, timeout_s=CONVERGENCE_S)
            failovers0 = _counter("decision.solver.failovers")

            # every solve now pays +40ms — slower, NOT broken
            registry.arm("solver.exec", delay_ms=40.0)
            churn_task = asyncio.ensure_future(churn())

            # the latency fault actually fires (and never raises)
            await wait_until(
                lambda: _counter("runtime.fault.solver.exec.delayed") > 0,
                timeout_s=CONVERGENCE_S,
            )
            # the drift SLO burns and the monitor freezes a
            # perf_regression bundle (NOT a generic slo_burn)
            await wait_until(
                lambda: any(
                    b["reason"] == "perf_regression"
                    for b in mon.flight_recorder.bundles
                ),
                timeout_s=CONVERGENCE_S,
            )
            rep = mon.slo_report()["slos"]["solve_drift"]
            assert rep["state"] in ("fast_burn", "sustained_burn"), rep
            assert rep["baseline"] == 5.0
            assert rep["live"] > rep["baseline"]

            pr = next(
                b
                for b in mon.flight_recorder.bundles
                if b["reason"] == "perf_regression"
            )
            with open(os.path.join(pr["path"], "bundle.json")) as f:
                doc = json.load(f)
            assert doc["trigger"]["reason"] == "perf_regression"
            assert doc["trigger"]["detail"]["kind"] == "baseline_drift"
            delta = doc["perf_ledger_delta"]
            assert delta["slo"] == "solve_drift"
            assert delta["baseline"] == 5.0
            assert delta["live"] > 5.0
            assert delta["ratio"] > 1.5
            assert delta["threshold"] == 1.5
            # the bundled ledger snapshot holds the live-solve key the
            # baseline came from
            assert any(
                k.startswith("solve|live|live|")
                for k in delta["ledger"]["keys"]
            ), list(delta["ledger"]["keys"])
            assert doc["slo"]["slos"]["solve_drift"]["state"] != "ok"

            # the whole time: a PERF regression, not an availability
            # event — no failover, no degraded mode, routes intact
            assert _counter("decision.solver.failovers") == failovers0
            assert _counter("decision.solver.degraded") == 0
            assert converged()
        finally:
            registry.clear()
            stop_churn.set()
            if churn_task is not None:
                with contextlib.suppress(Exception):
                    await churn_task
            with contextlib.suppress(Exception):
                await mon.stop()
            await stop_all(nodes)
            perf_ledger.configure("")


class TestDeviceRetraceFlightRecorderDrill:
    @run_async
    async def test_injected_cache_fork_trips_retrace_bundle(self):
        """ISSUE 15 drill: an injected cache-class fork — the live jit
        executables dropped out from under a warm mesh — must be caught
        by the retrace sentinel on the next solve: the recompile is
        attributed (namespace + signature delta), surfaced as a
        DEVICE_RETRACE LogSample, and freezes a flight-recorder bundle,
        while routing reconverges without a blip. All three nodes run in
        one process and share the module-global factory caches, so the
        process-global event queue may be drained by ANY node's Decision
        — the drill monitors every node and asserts the bundle lands
        somewhere, which is exactly the per-process production shape."""
        import json
        import os
        import tempfile

        from openr_tpu.ops import xla_cache
        from openr_tpu.ops.xla_cache import retrace

        def _clear_factories():
            # the injection: python-level caches drop their executables
            # WITHOUT the eviction path's retrace.forget() — the next
            # dispatch re-jits a kernel the sentinel considers warm
            for fn in xla_cache._BOUNDED_CACHES:
                fn.cache_clear()

        def _retraces():
            return sum(
                counters.get_counters("xla_cache.retraces.").values()
            )

        registry.clear()
        _clear_factories()
        retrace.reset()  # initial convergence compiles = clean warmup
        rec_root = tempfile.mkdtemp(prefix="openr-tpu-retrace-drill-")
        names = ["node-0", "node-1", "node-2"]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-20", "node-0", "if-02"),
        ]
        mesh, nodes = await start_mesh(
            names,
            links,
            solver_backend="tpu",
            decision_config=DecisionConfig(
                debounce_min_ms=5,
                debounce_max_ms=25,
            ),
        )
        mons = {}
        for n in names:
            mons[n] = Monitor(
                n,
                MonitorConfig(
                    flight_recorder_dir=os.path.join(rec_root, n),
                    flight_recorder_min_interval_s=0.0,
                ),
                nodes[n].log_sample_queue.get_reader("retrace-drill"),
                interval_s=0.1,
            )
            await mons[n].start()

        def _bundles(reason):
            return [
                b
                for mon in mons.values()
                for b in mon.flight_recorder.bundles
                if b["reason"] == reason
            ]

        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))

            def converged():
                for i, n in enumerate(names):
                    expect = {loopback(j) for j in range(3) if j != i}
                    if set(nodes[n].fib_routes) != expect:
                        return False
                return True

            await wait_until(converged, timeout_s=CONVERGENCE_S)
            await asyncio.sleep(0.3)  # let trailing rebuilds settle
            retraces0 = _retraces()
            bundles0 = len(_bundles("device_retrace"))

            # INJECT the fork, then change the topology: the rebuild's
            # re-jit of a supposedly-warm kernel is the retrace
            _clear_factories()
            mesh.disconnect("node-0", "if-02", "node-2", "if-20")

            await wait_until(
                lambda: _retraces() > retraces0, timeout_s=CONVERGENCE_S
            )
            await wait_until(
                lambda: len(_bundles("device_retrace")) > bundles0,
                timeout_s=CONVERGENCE_S,
            )
            fo = _bundles("device_retrace")[-1]
            with open(os.path.join(fo["path"], "bundle.json")) as f:
                doc = json.load(f)
            assert doc["trigger"]["reason"] == "device_retrace"
            assert doc["trigger"]["detail"]["event"] == "DEVICE_RETRACE"
            # the attribution carries the namespace and signature delta
            # the operator triages from (docs/Operations.md)
            assert "namespace" in doc["trigger"]["detail"]
            assert "signature_delta" in doc["trigger"]["detail"]

            # the whole time: a telemetry event, not an availability
            # event — routing reconverged through node-1
            await wait_until(converged, timeout_s=CONVERGENCE_S)
            assert _counter("decision.solver.degraded") == 0
        finally:
            registry.clear()
            for mon in mons.values():
                with contextlib.suppress(Exception):
                    await mon.stop()
            await stop_all(nodes)


class TestWarmCacheRestartDrill:
    @run_async
    async def test_decision_restart_mid_churn_recovers_without_compile(self):
        """ISSUE 20 drill: a Decision restart mid-churn with a warm AOT
        cache must recover WITHOUT recompiling — every executable the
        reconvergence dispatches is deserialized from disk. The cold
        generation converges and absorbs a link flap (populating the
        cache), then the whole stack is stopped mid-churn and the
        in-memory half of a process restart is simulated
        (clear_all_jit_caches + jax.clear_caches); a fresh generation
        on the same disk cache must reconverge with zero in-scope XLA
        compiles, zero cache misses, and no sentinel events."""
        import shutil
        import tempfile

        import jax

        from openr_tpu.ops.xla_cache import (
            baker,
            clear_all_jit_caches,
            configure_aot,
            retrace,
        )

        registry.clear()
        names = ["node-0", "node-1", "node-2"]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-20", "node-0", "if-02"),
        ]
        dcfg = DecisionConfig(debounce_min_ms=5, debounce_max_ms=25)
        cache_dir = tempfile.mkdtemp(prefix="openr-tpu-aot-drill-")
        aot = configure_aot(cache_dir)
        aot.reset_stats()
        baker.reset()
        # the cold generation's compiles are warmup, not retraces
        clear_all_jit_caches()
        retrace.reset()

        def converged(nodes):
            def check():
                for i, n in enumerate(names):
                    expect = {loopback(j) for j in range(3) if j != i}
                    if set(nodes[n].fib_routes) != expect:
                        return False
                return True

            return check

        nodes = {}
        try:
            # -- cold generation: converge + flap = cache population
            mesh, nodes = await start_mesh(
                names, links, solver_backend="tpu", decision_config=dcfg
            )
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))
            await wait_until(converged(nodes), timeout_s=CONVERGENCE_S)
            # churn: cut a link and reconverge through the long way
            mesh.disconnect("node-0", "if-02", "node-2", "if-20")

            def rerouted_via_b():
                entry = nodes["node-0"].fib_routes.get(loopback(2))
                return entry is not None and {
                    nh.neighbor_node_name for nh in entry.nexthops
                } == {"node-1"}

            await wait_until(rerouted_via_b, timeout_s=CONVERGENCE_S)
            assert aot.summary()["writes"] >= 1, aot.summary()
            # mid-churn: fresh state is in flight when the stack dies
            nodes["node-2"].advertise_prefix("10.99.0.0/24")
            await stop_all(nodes)

            # -- the restart: drop every piece of in-memory compiled
            # state a process exit would drop; the disk cache survives
            clear_all_jit_caches()
            jax.clear_caches()
            retrace.reset()
            aot.reset_stats()
            pre = aot.preload()  # the aot_load boot phase
            assert pre["loaded"] >= 1, pre
            scoped0 = _counter("xla_cache.scoped_compiles")

            # -- warm generation: same fabric, same churn shape
            mesh, nodes = await start_mesh(
                names, links, solver_backend="tpu", decision_config=dcfg
            )
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))
            await wait_until(converged(nodes), timeout_s=CONVERGENCE_S)
            # supervised recovery keeps absorbing churn, still warm
            nodes["node-2"].advertise_prefix("10.99.0.0/24")
            await wait_until(
                lambda: "10.99.0.0/24" in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            await asyncio.sleep(0.3)  # let trailing rebuilds settle

            s = aot.summary()
            assert s["hits"] >= 1, s
            assert s["misses"] == 0, s  # every install came from disk
            assert s["hit_rate"] == 1.0, s
            # the sentinel's census proves no XLA compile fired inside
            # any solver scope, and nothing paged
            assert _counter("xla_cache.scoped_compiles") == scoped0
            snap = retrace.snapshot()
            assert sum(snap["retraces"].values()) == 0, snap
            assert snap["aot_installs"] >= 1, snap
        finally:
            registry.clear()
            await stop_all(nodes)
            configure_aot("off")
            retrace.reset()
            shutil.rmtree(cache_dir, ignore_errors=True)
