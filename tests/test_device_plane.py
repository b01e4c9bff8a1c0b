"""Device-plane observability tests: HBM gauges + live-buffer census,
profiler single-flight capture, the XLA kernel cost ledger, numerical-
health sentinels, watchdog gauge pruning, event-log drop accounting,
and KvStore-advertised fleet health. All on the virtual-CPU backend —
the graceful-degradation path (no memory_stats) is itself under test."""

import asyncio
import os
import time
from types import SimpleNamespace

import numpy as np

from openr_tpu.config import MonitorConfig, WatchdogConfig
from openr_tpu.kvstore.wrapper import wait_until
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.runtime import device_stats
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.monitor import LogSample, Monitor, Watchdog
from tests.conftest import run_async


# -- counter erase API ------------------------------------------------------

def test_counter_erase_and_prefix():
    counters.set_counter("erasetest.a", 1)
    counters.set_counter("erasetest.ab", 2)
    assert counters.erase("erasetest.a") is True
    assert counters.erase("erasetest.a") is False
    assert counters.get_counter("erasetest.a") is None
    assert counters.get_counter("erasetest.ab") == 2
    # trailing-dot discipline: erasing reader "r" must not swallow "r2"
    counters.set_counter("erasetest.q.reader.r.depth", 3)
    counters.set_counter("erasetest.q.reader.r2.depth", 4)
    n = counters.erase_prefix("erasetest.q.reader.r.")
    assert n == 1
    assert counters.get_counter("erasetest.q.reader.r.depth") is None
    assert counters.get_counter("erasetest.q.reader.r2.depth") == 4
    counters.erase_prefix("erasetest.")


# -- device snapshot + census ----------------------------------------------

def test_collect_device_stats_cpu_backend():
    snap = device_stats.collect_device_stats(allow_import=True)
    assert snap["backend"] == "cpu"
    assert len(snap["devices"]) == 8  # conftest's virtual mesh
    for entry in snap["devices"]:
        # graceful degradation: no memory_stats on cpu -> id/platform only
        assert "hbm_in_use_mb" not in entry
        assert entry["platform"] == "cpu"


def test_live_buffer_census_attributes_pools():
    import jax

    held = [jax.device_put(np.zeros(1024, np.float32))]
    device_stats.register_pool("censustest", lambda: held)
    try:
        census = device_stats.live_buffer_census(allow_import=True)
        pool = census["pools"]["censustest"]
        assert pool["count"] == 1
        assert pool["bytes"] == 4096
        assert census["bytes"] >= pool["bytes"]
        # other pools (earlier tests' solvers) may attribute bytes too —
        # ours must at least be carved out of the unattributed remainder
        assert census["other_bytes"] <= census["bytes"] - pool["bytes"]

        snap = device_stats.export_device_gauges(allow_import=True)
        assert snap["backend"] == "cpu"
        assert counters.get_counter("device.count") == 8
        assert counters.get_counter("device.pool.censustest.count") == 1
    finally:
        device_stats.unregister_pool("censustest")
    # unregister erases the pool's gauges from the fabric
    assert counters.get_counter("device.pool.censustest.count") is None
    assert device_stats.peak_hbm_mb() == (None, "cpu")


def test_solver_registers_weakref_pool():
    """Each TpuSpfSolver registers a census pool that must not pin the
    solver alive; after the solver goes away the pool reads empty."""
    import gc

    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from tests.test_spf_solver import prefix_db, square_states

    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    solver = TpuSpfSolver("a")
    solver.build_route_db("a", square_states(), ps)
    census = device_stats.live_buffer_census()
    assert census["pools"]["tpu_solver:a"]["count"] > 0
    del solver
    gc.collect()
    census = device_stats.live_buffer_census()
    assert census["pools"]["tpu_solver:a"]["count"] == 0
    device_stats.unregister_pool("tpu_solver:a")


# -- profiler capture -------------------------------------------------------

def test_profiler_round_trip_and_single_flight(tmp_path):
    import jax

    out = str(tmp_path / "trace")
    started = device_stats.profiler_start(out)
    assert started["ok"] and started["out_dir"] == out
    # single-flight: the XLA profiler is process-global
    try:
        device_stats.profiler_start()
        raise AssertionError("second start must refuse")
    except RuntimeError as e:
        assert "already capturing" in str(e)
    assert device_stats.profiler_status()["capturing"] is True
    # some device work so the trace is non-empty
    jax.jit(lambda x: x * 2)(np.arange(16)).block_until_ready()
    stopped = device_stats.profiler_stop()
    assert stopped["ok"] and stopped["files"] > 0
    # the capture is anchored and reduced: the annotation was found on
    # the profiler's clock, the tracer's spans lie beside the xplane,
    # and a backend with no device plane has no scope to report
    assert stopped["anchor"]["trace_ns"] >= 0
    assert stopped["anchor"]["mono_ns"] <= time.monotonic_ns()
    assert os.path.exists(stopped["spans_file"])
    assert stopped["by_scope"] == {}
    assert device_stats.profiler_status() == {"capturing": False}
    try:
        device_stats.profiler_stop()
        raise AssertionError("stop without start must refuse")
    except RuntimeError:
        pass


def test_profiler_auto_stop(tmp_path):
    started = device_stats.profiler_start(
        str(tmp_path / "auto"), seconds=0.2
    )
    assert started["auto_stop_s"] == 0.2
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if not device_stats.profiler_status()["capturing"]:
            break
        time.sleep(0.05)
    assert device_stats.profiler_status() == {"capturing": False}


# -- kernel cost ledger -----------------------------------------------------

def test_instrument_jit_records_cost_and_calls():
    import jax

    from openr_tpu.ops.xla_cache import instrument_jit, ledger

    fn = instrument_jit(
        "ledgertest", jax.jit(lambda x: (x * 2 + 1).sum())
    )
    x = np.arange(64, dtype=np.float32)
    assert float(fn(x)) == float((x * 2 + 1).sum())
    fn(x)
    entry = ledger.snapshot()["ledgertest"]
    assert entry["calls"] == 2
    assert entry["aot_loaded"] is False
    assert entry["compile_ms"] >= 0.0
    assert entry["flops"] > 0  # cost_analysis saw the adds/muls


def test_solver_build_populates_ledger():
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.ops.xla_cache import ledger
    from tests.test_spf_solver import prefix_db, square_states

    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    solver = TpuSpfSolver("a")
    solver.build_route_db("a", square_states(), ps)
    kname = solver.last_timing["areas"]["0"]["kernel"]
    assert kname.startswith("pipeline[")
    assert kname in ledger.snapshot()
    assert ledger.snapshot()[kname]["calls"] >= 1
    device_stats.unregister_pool("tpu_solver:a")


# -- numerical-health sentinels --------------------------------------------

def test_ucmp_weight_anomalies_dtype_aware():
    from openr_tpu.decision.tpu_solver import _ucmp_weight_anomalies

    assert _ucmp_weight_anomalies(
        np.array([1.0, np.nan, np.inf, 2.0])
    ) == 2
    assert _ucmp_weight_anomalies(np.array([1, -3, 2], np.int64)) == 1
    assert _ucmp_weight_anomalies(np.array([1, 2], np.uint32)) == 0


def test_pipeline_sentinels_count_unreachable_rows():
    """An announced-but-disconnected node must show up in the pipeline's
    tail sentinels without disturbing the routes themselves."""
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from tests.test_link_state import adj, adj_db
    from tests.test_spf_solver import prefix_db, square_states

    states = square_states()
    # an island (e -- f) the root can never reach, announcing a prefix
    states["0"].update_adjacency_database(
        adj_db("e", [adj("e", "f")], node_label=105)
    )
    states["0"].update_adjacency_database(
        adj_db("f", [adj("f", "e")], node_label=106)
    )
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    ps.update_prefix_database(prefix_db("e", "fd00::e/128"))
    solver = TpuSpfSolver("a")
    db = solver.build_route_db("a", states, ps)
    assert "fd00::d/128" in db.unicast_routes
    assert "fd00::e/128" not in db.unicast_routes  # unreachable announcer
    assert solver.last_sentinels["unreachable_rows"] >= 1
    assert solver.last_sentinels["saturated_rows"] == 0
    device_stats.unregister_pool("tpu_solver:a")


def test_pipeline_sentinels_kill_switch():
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from tests.test_spf_solver import prefix_db, square_states

    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    solver = TpuSpfSolver("a", enable_numerical_sentinels=False)
    db = solver.build_route_db("a", square_states(), ps)
    assert "fd00::d/128" in db.unicast_routes
    assert solver.last_sentinels == {}
    device_stats.unregister_pool("tpu_solver:a")


@run_async
async def test_decision_emits_sentinel_anomaly():
    """Decision folds solver sentinels into gauges; an anomalous build
    additionally produces the counter bump, a categorized LogSample,
    and span attributes."""
    from openr_tpu.decision.decision import Decision

    q = ReplicateQueue("sentinel-logs")
    reader = q.get_reader()
    fake = SimpleNamespace(
        solver=SimpleNamespace(
            last_sentinels={"saturated_rows": 2, "unreachable_rows": 0}
        ),
        _log_samples=q,
        node_name="node-s",
    )
    span = SimpleNamespace(attributes={})
    before = counters.get_counter("decision.sentinel.anomalies") or 0
    Decision._emit_sentinels(fake, span)
    assert counters.get_counter("decision.sentinel.saturated_rows") == 2
    assert (
        counters.get_counter("decision.sentinel.anomalies") == before + 1
    )
    assert span.attributes["sentinel_anomaly"] is True
    assert span.attributes["sentinel_saturated_rows"] == 2
    sample = await asyncio.wait_for(reader.get(), 5)
    assert sample.event == "DECISION_SENTINEL_ANOMALY"
    assert sample.values["category"] == "sentinel"
    assert sample.values["saturated_rows"] == 2

    # a clean build publishes gauges but raises no anomaly
    fake.solver.last_sentinels = {
        "saturated_rows": 0, "unreachable_rows": 3,
    }
    span2 = SimpleNamespace(attributes={})
    Decision._emit_sentinels(fake, span2)
    assert (
        counters.get_counter("decision.sentinel.anomalies") == before + 1
    )
    assert span2.attributes == {}
    assert counters.get_counter("decision.sentinel.unreachable_rows") == 3


# -- monitor: drop accounting + category filter ----------------------------

class TestMonitorEventLogs:
    @run_async
    async def test_drop_counting_and_category_filter(self):
        q = ReplicateQueue("logSamples-dp")
        mon = Monitor(
            "node1",
            # no SLO tracks: the stats they read are the process's, and a
            # slow convergence of an earlier test in this worker would put
            # its SLO_BURN_ALERT into this ring of three
            MonitorConfig(max_event_log_entries=3, slos={}),
            q.get_reader(),
            interval_s=0.05,
        )
        await mon.start()
        try:
            q.push(LogSample(event="SPF_A", node_name="node1"))
            q.push(LogSample(event="SPF_B", node_name="node1"))
            q.push(LogSample(
                event="OTHER",
                node_name="node1",
                values={"category": "sentinel"},
            ))
            await wait_until(lambda: len(mon.event_logs) == 3)
            before = (
                counters.get_counter("monitor.event_logs.dropped") or 0
            )
            # ring is full: the next two appends evict (and count)
            q.push(LogSample(event="SPF_C", node_name="node1"))
            q.push(LogSample(event="SPF_D", node_name="node1"))
            await wait_until(
                lambda: (
                    counters.get_counter("monitor.event_logs.dropped")
                    or 0
                )
                == before + 2
            )
            # category filter: exact event / dotted prefix / values tag
            logs = await mon.get_event_logs(category="OTHER")
            assert len(logs) == 1
            logs = await mon.get_event_logs(category="sentinel")
            assert len(logs) == 1 and "OTHER" in logs[0]
            logs = await mon.get_event_logs(category="NO_SUCH")
            assert logs == []
            assert len(await mon.get_event_logs()) == 3
        finally:
            await mon.stop()


# -- watchdog: gauge pruning for disappeared readers -----------------------

class TestWatchdogPruning:
    @run_async
    async def test_reader_gauges_pruned_after_removal(self):
        wd = Watchdog(
            "node1",
            WatchdogConfig(interval_s=0.05, thread_timeout_s=60,
                           max_memory_mb=100_000),
            crash_handler=lambda reason: None,
        )
        q = ReplicateQueue("prunetest")
        r1 = q.get_reader("r")
        q.get_reader("r2")
        q.push(1)
        wd.watch_queue(q)
        await wd.start()
        base = "messaging.queue.prunetest"
        try:
            await wait_until(
                lambda: counters.get_counter(f"{base}.reader.r.depth")
                == 1
            )
            q.remove_reader(r1)
            # next sweep prunes r's gauges; r2 (shared prefix) survives
            await wait_until(
                lambda: counters.get_counter(f"{base}.reader.r.depth")
                is None
            )
            assert (
                counters.get_counter(f"{base}.reader.r.reads") is None
            )
            assert (
                counters.get_counter(f"{base}.reader.r2.depth")
                is not None
            )
        finally:
            await wd.stop()
            counters.erase_prefix(f"{base}.")


# -- monitor health summary -------------------------------------------------

class TestHealthSummary:
    @run_async
    async def test_health_summary_fields(self):
        q = ReplicateQueue("logSamples-hs")
        mon = Monitor(
            "node-h", MonitorConfig(), q.get_reader(), interval_s=0.05
        )
        wd = Watchdog(
            "node-h",
            WatchdogConfig(interval_s=0.05, thread_timeout_s=60,
                           max_memory_mb=100_000),
            crash_handler=lambda reason: None,
        )
        mon.attach_fleet_sources(watchdog=wd)
        await mon.start()
        try:
            card = mon.health_summary()
            assert card["node"] == "node-h"
            assert card["rss_mb"] > 0
            assert card["watchdog_fired"] is None
            assert card["backend"] in ("cpu", "unavailable")
            assert card["hbm_in_use_mb"] is None  # cpu: no accounting
            assert card["ts_ms"] > 0
        finally:
            await mon.stop()
