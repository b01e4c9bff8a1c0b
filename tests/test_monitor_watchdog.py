"""Monitor + Watchdog actor tests (ref openr/watchdog/Watchdog.h:28-51,
openr/monitor/MonitorBase.h:32)."""

import asyncio
import time

from openr_tpu.config import MonitorConfig, WatchdogConfig
from openr_tpu.kvstore.wrapper import wait_until
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.runtime.actor import Actor
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.monitor import LogSample, Monitor, Watchdog
from tests.conftest import run_async


class TestMonitor:
    @run_async
    async def test_event_log_retention(self):
        q = ReplicateQueue("logSamples")
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
            for i in range(5):
                q.push(LogSample(event=f"EVENT_{i}", node_name="node1"))
            await wait_until(lambda: len(mon.event_logs) == 3)
            logs = await mon.get_event_logs()
            # ring: only the last 3 retained
            assert '"event": "EVENT_4"' in logs[-1]
            assert all("EVENT_0" not in line for line in logs)
        finally:
            await mon.stop()

    @run_async
    async def test_process_gauges_exported(self):
        q = ReplicateQueue("logSamples")
        mon = Monitor("node1", MonitorConfig(), q.get_reader(), interval_s=0.02)
        await mon.start()
        try:
            await wait_until(
                lambda: counters.get_counter("process.memory.rss_mb") is not None
            )
            assert counters.get_counter("process.memory.rss_mb") > 0
            assert counters.get_counter("process.uptime_s") is not None
            # the live gauge and the high-water mark are distinct
            # counters; current can never (meaningfully) exceed peak
            max_rss = counters.get_counter("process.memory.max_rss_mb")
            assert max_rss is not None and max_rss > 0
            assert (
                counters.get_counter("process.memory.rss_mb")
                <= max_rss * 1.05
            )
        finally:
            await mon.stop()

    def test_current_rss_is_live_not_peak(self):
        """ru_maxrss is a high-water mark; the live gauge must come
        from /proc/self/statm and sit at or under the peak."""
        from openr_tpu.runtime.monitor import current_rss_mb, rss_mb

        cur, peak = current_rss_mb(), rss_mb()
        assert cur > 0 and peak > 0
        # small slop: the peak snapshot races the current read
        assert cur <= peak * 1.05, (cur, peak)


class TestWatchdog:
    @run_async
    async def test_fires_on_stalled_actor(self):
        fired = []
        wd = Watchdog(
            "node1",
            # ceiling high enough that suite-wide RSS can't trip it —
            # this test is about stall detection; the memory ceiling
            # has its own test below
            WatchdogConfig(interval_s=0.05, thread_timeout_s=0.2,
                           max_memory_mb=100_000),
            crash_handler=fired.append,
        )
        victim = Actor("victim")
        await victim.start()
        await wd.start()
        try:
            await asyncio.sleep(0.2)
            assert not fired  # healthy heartbeat
            wd.watch_actor(victim)
            # simulate a stall: stop the heartbeat task but keep watching
            await victim.stop()
            victim.last_alive_ts = time.monotonic() - 10
            await wait_until(lambda: fired, timeout_s=3)
            assert "victim" in fired[0]
            assert wd.fired is not None
        finally:
            await wd.stop()

    @run_async
    async def test_memory_ceiling(self):
        fired = []
        wd = Watchdog(
            "node1",
            WatchdogConfig(interval_s=0.05, thread_timeout_s=60, max_memory_mb=1),
            crash_handler=fired.append,
        )
        await wd.start()
        try:
            await wait_until(lambda: fired, timeout_s=3)
            assert "memory" in fired[0]
        finally:
            await wd.stop()

    @run_async
    async def test_queue_depth_counters(self):
        wd = Watchdog(
            "node1",
            WatchdogConfig(interval_s=0.05, thread_timeout_s=60,
                           max_memory_mb=100_000),
            crash_handler=lambda reason: None,
        )
        q = ReplicateQueue("testq")
        reader = q.get_reader("r")
        for _ in range(7):
            q.push(1)
        wd.watch_queue(q)
        await wd.start()
        try:
            await wait_until(
                lambda: counters.get_counter("messaging.queue.testq.max_depth")
                == 7
            )
            # per-reader visibility: a wedged reader (depth growing,
            # reads flat) must be observable from the counter fabric
            base = "messaging.queue.testq"
            assert counters.get_counter(f"{base}.replicas") == 1
            assert counters.get_counter(f"{base}.reader.r.depth") == 7
            assert counters.get_counter(f"{base}.reader.r.reads") == 0
            for _ in range(3):
                await reader.get()
            await wait_until(
                lambda: counters.get_counter(f"{base}.reader.r.reads") == 3
            )
            assert counters.get_counter(f"{base}.reader.r.depth") == 4
        finally:
            await wd.stop()


def test_stat_multi_windowed_single_pass():
    """fb303-style multi-window view: nesting (60 within 600 within
    3600), exact aggregates, and the truncation flag when the sample
    ring cannot honor a long window."""
    from openr_tpu.runtime.counters import _Stat

    s = _Stat()
    for i in range(10):
        s.add(float(i))
    out = s.multi_windowed((60.0, 600.0, 3600.0))
    for w in ("60", "600", "3600"):
        assert out[w]["count"] == 10
        assert out[w]["max"] == 9.0
        assert abs(out[w]["avg"] - 4.5) < 1e-9
        assert out[w]["truncated"] is False
    # overflow the ring: long windows flag truncation, a tiny window
    # (whose cutoff is newer than the eviction horizon) does not
    for _ in range(5000):
        s.add(1.0)
    out = s.multi_windowed((0.0, 3600.0))
    assert out["3600"]["truncated"] is True
    assert out["3600"]["count"] == 4096  # ring capacity, not a lie


class TestSloEngine:
    """Burn-rate state machines over the counter fabric (ISSUE 11)."""

    @staticmethod
    def _engine(slos, fast=0.2, slow=0.4, burn=0.5):
        from openr_tpu.runtime.monitor import SloEngine

        cfg = MonitorConfig(
            slos=slos,
            slo_fast_window_s=fast,
            slo_slow_window_s=slow,
            slo_burn_threshold=burn,
        )
        return SloEngine("node-slo", cfg)

    def test_counter_delta_baseline_is_not_retroactive(self):
        src = "slotest.delta.preexisting"
        counters.set_counter(src, 100.0)
        eng = self._engine(
            {"d": {"kind": "counter_delta", "source": src, "threshold": 1.0}}
        )
        # first tick only establishes the baseline: the 100 that
        # predate the engine must not count as a breach
        assert eng.evaluate() == []
        rep = eng.report()["slos"]["d"]
        assert rep["state"] == "ok" and rep["value"] == 0.0
        # a real jump past the threshold burns the (1-sample) window
        counters.set_counter(src, 105.0)
        alerts = eng.evaluate()
        assert [a["slo"] for a in alerts] == ["d"]
        assert alerts[0]["value"] == 5.0
        assert eng.report()["slos"]["d"]["state"] == "fast_burn"
        # sub-threshold drift keeps breach fraction falling, not rising
        counters.set_counter(src, 105.5)
        eng.evaluate()
        assert eng.report()["slos"]["d"]["value"] == 0.5

    def test_stat_quantile_breach_and_empty_window(self):
        src = "slotest.stat.latency_ms"
        eng = self._engine(
            {"s": {"kind": "stat", "source": src, "threshold": 10.0,
                   "quantile": "p50"}},
            fast=60.0, slow=60.0,
        )
        # no samples at all: no breach, value 0
        assert eng.evaluate() == []
        assert eng.report()["slos"]["s"]["state"] == "ok"
        for v in (50.0, 60.0, 70.0):
            counters.add_stat_value(src, v)
        alerts = eng.evaluate()
        assert [a["slo"] for a in alerts] == ["s"]
        rep = eng.report()["slos"]["s"]
        assert rep["state"] == "fast_burn" and rep["value"] > 10.0

    def test_gauge_duration_escalates_then_deasserts(self):
        src = "slotest.gauge.degraded"
        counters.set_counter(src, 0.0)
        eng = self._engine(
            {"g": {"kind": "gauge_duration", "source": src,
                   "threshold": 0.0}}
        )
        assert eng.evaluate() == []  # clean tick
        counters.set_counter(src, 1.0)
        alerts = eng.evaluate()  # breach tick: 1/1 fast samples burn
        assert [a["slo"] for a in alerts] == ["g"]
        assert counters.get_counter("monitor.slo.g.alerts") >= 1
        assert counters.get_counter("monitor.slo.g.burning") == 1.0
        time.sleep(0.05)
        assert eng.evaluate() == []  # escalation is NOT a new page
        rep = eng.report()["slos"]["g"]
        assert rep["state"] == "sustained_burn", rep
        assert counters.get_counter("monitor.slo.g.burning") == 2.0
        # recovery: gauge clears, the fast window drains past the 2x
        # hysteresis, the state machine de-asserts without a page
        counters.set_counter(src, 0.0)
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            assert eng.evaluate() == []
            if eng.report()["slos"]["g"]["state"] == "ok":
                break
            time.sleep(0.05)
        rep = eng.report()["slos"]["g"]
        assert rep["state"] == "ok", rep
        assert counters.get_counter("monitor.slo.g.burning") == 0.0
        assert rep["alerts"] == 1  # the whole episode paged exactly once


class TestFlightRecorder:
    @staticmethod
    def _recorder(tmp, **kw):
        from openr_tpu.runtime.monitor import FlightRecorder

        defaults = dict(
            flight_recorder_dir=tmp,
            flight_recorder_ring=4,
            flight_recorder_min_interval_s=60.0,
        )
        defaults.update(kw)
        return FlightRecorder("node-fr", MonitorConfig(**defaults))

    def test_trigger_writes_bundle_rate_limits_and_forces(self, tmp_path):
        import json as _json
        import os

        fr = self._recorder(str(tmp_path))
        for _ in range(10):
            fr.record_tick()
        fr.note_event("SOMETHING_ODD", {"n": 1})
        sup0 = counters.get_counter(
            "monitor.flight_recorder.suppressed") or 0
        r1 = fr.trigger("unit_test", detail={"why": "drill"})
        assert r1 is not None and r1["reason"] == "unit_test"
        doc = _json.load(open(os.path.join(r1["path"], "bundle.json")))
        assert doc["schema"] == "openr-tpu-flight-recorder/1"
        assert doc["node"] == "node-fr"
        assert doc["trigger"]["detail"] == {"why": "drill"}
        # ring bound holds even after 10 ticks
        assert len(doc["counter_history"]) == 4
        assert any(e["event"] == "SOMETHING_ODD" for e in doc["events"])
        assert os.path.exists(os.path.join(r1["path"], "trace.json"))
        # second auto trigger inside the interval is suppressed...
        assert fr.trigger("unit_test_again") is None
        assert (counters.get_counter("monitor.flight_recorder.suppressed")
                > sup0)
        # ...but a manual dump bypasses the limit
        r3 = fr.trigger("manual", force=True)
        assert r3 is not None
        assert [b["reason"] for b in fr.bundles] == ["unit_test", "manual"]

    def test_write_failure_is_counted_not_raised(self, tmp_path):
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("file in the way")
        fr = self._recorder(str(blocker / "sub"))
        errs0 = counters.get_counter(
            "monitor.flight_recorder.write_errors") or 0
        assert fr.trigger("doomed", force=True) is None
        assert (counters.get_counter("monitor.flight_recorder.write_errors")
                == errs0 + 1)
        assert list(fr.bundles) == []


class TestMonitorObservability:
    @run_async
    async def test_trigger_events_map_to_bundles_and_manual_dump(
        self, tmp_path
    ):
        q = ReplicateQueue("logSamplesObs")
        mon = Monitor(
            "node-obs",
            MonitorConfig(
                slos={},  # engine off: slo_report must say so
                enable_fleet_health=False,
                flight_recorder_dir=str(tmp_path),
                flight_recorder_min_interval_s=60.0,
            ),
            q.get_reader(),
            interval_s=0.05,
        )
        assert mon.slo_engine is None and mon.flight_recorder is not None
        await mon.start()
        try:
            rep = mon.slo_report()
            assert rep["enabled"] is False and rep["slos"] == {}
            # an anomaly LogSample auto-triggers with attribution
            q.push(LogSample(
                event="DECISION_SENTINEL_ANOMALY",
                node_name="node-obs",
                values={"category": "sentinel", "metric": "spf_ms"},
            ))
            await wait_until(
                lambda: any(
                    b["reason"] == "sentinel_anomaly"
                    for b in mon.flight_recorder.bundles
                )
            )
            # a second trigger event inside the rate window is noted
            # (supervisor category) but writes no second bundle
            q.push(LogSample(
                event="SUPERVISOR_RESTART",
                node_name="node-obs",
                values={"category": "supervisor", "task": "t"},
            ))
            await wait_until(
                lambda: any(
                    e["event"] == "SUPERVISOR_RESTART"
                    for e in mon.flight_recorder._events
                )
            )
            assert len(mon.flight_recorder.bundles) == 1
            # the operator's manual dump bypasses the rate limit
            res = await mon.dump_flight_recorder(reason="manual-drill")
            assert res["ok"] is True and res["reason"] == "manual-drill"
            assert len(mon.flight_recorder.bundles) == 2
        finally:
            await mon.stop()

    @run_async
    async def test_dump_without_recorder_reports_error(self):
        q = ReplicateQueue("logSamplesObs2")
        mon = Monitor(
            "node-obs2",
            MonitorConfig(
                enable_flight_recorder=False, enable_fleet_health=False
            ),
            q.get_reader(),
        )
        assert mon.flight_recorder is None
        res = await mon.dump_flight_recorder()
        assert res["ok"] is False and "error" in res
