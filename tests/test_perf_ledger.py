"""Perf-baseline observatory tests (ISSUE 14).

Three layers: the PerfLedger store itself (rolling windows, atomic
persistence, corrupt-file recovery, fingerprint keying), the
``baseline_drift`` SLO kind in SloEngine (no-baseline never breaches,
cold-start warmup exclusion, min-count guard, alert payload, de-assert
hysteresis), and the ``tools/perf_diff.py`` verdict CLI (direction
inference, envelope unwrap, exit codes, ledger mode).
"""

import json
import os
import time

import pytest

from openr_tpu.config import MonitorConfig
from openr_tpu.runtime import perf_ledger
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.monitor import SloEngine
from openr_tpu.runtime.perf_ledger import PerfLedger
from tools import perf_diff


@pytest.fixture
def ledger_dir(tmp_path):
    """Point the process ledger at a tmpdir; restore the disabled
    default afterwards so other tests stay hermetic."""
    d = str(tmp_path / "perf")
    perf_ledger.configure(d)
    yield d
    perf_ledger.configure("")


class TestPerfLedger:
    def test_disabled_ledger_is_a_no_op(self):
        lg = PerfLedger("")
        assert lg.enabled is False
        assert lg.path == ""
        lg.record("solve", {"device_ms": 5.0})
        assert lg.observations("solve") == []
        assert lg.baseline("solve", "device_ms") is None
        assert lg.snapshot()["keys"] == {}

    def test_record_baseline_and_persistence(self, tmp_path):
        d = str(tmp_path)
        lg = PerfLedger(d)
        for v in (4.0, 5.0, 6.0, 5.0, 5.0):
            lg.record("solve", {"device_ms": v, "note": "x"},
                      signature="live", variant="live")
        base = lg.baseline("solve", "device_ms",
                           signature="live", variant="live", quantile="p50")
        assert base == 5.0
        assert lg.baseline("solve", "device_ms",
                           signature="live", variant="live") >= 5.0  # p95
        # non-numeric fields are dropped, ts_ms is stamped
        obs = lg.observations("solve", signature="live", variant="live")
        assert len(obs) == 5 and "note" not in obs[0] and obs[0]["ts_ms"] > 0
        # the file is a schema-stamped JSON a fresh instance reads back
        with open(lg.path) as f:
            doc = json.load(f)
        assert doc["schema"] == "openr-tpu-perf-ledger/1"
        again = PerfLedger(d)
        assert len(again.observations("solve",
                                      signature="live", variant="live")) == 5

    def test_rolling_window_is_bounded(self, tmp_path):
        lg = PerfLedger(str(tmp_path))
        for i in range(perf_ledger.MAX_OBSERVATIONS + 10):
            lg.record("solve", {"device_ms": float(i)})
        obs = lg.observations("solve")
        assert len(obs) == perf_ledger.MAX_OBSERVATIONS
        # oldest were evicted: the window holds the LAST 64
        assert obs[0]["device_ms"] == 10.0

    def test_corrupt_file_recovers_fresh(self, tmp_path):
        d = str(tmp_path)
        with open(os.path.join(d, perf_ledger.LEDGER_FILE), "w") as f:
            f.write("{not json")
        errs0 = counters.get_counter("perf.ledger.load_errors") or 0
        lg = PerfLedger(d)
        assert lg.observations("solve") == []
        assert (counters.get_counter("perf.ledger.load_errors") or 0) > errs0
        # and the store still works after the loss
        lg.record("solve", {"device_ms": 5.0})
        assert lg.baseline("solve", "device_ms") == 5.0

    def test_fingerprint_isolates_baselines(self, tmp_path):
        """A toolchain bump starts a fresh baseline — observations under
        one fingerprint are invisible under another."""
        lg = PerfLedger(str(tmp_path))
        lg.record("solve", {"device_ms": 5.0}, fp="jaxA")
        assert lg.baseline("solve", "device_ms", fp="jaxA") == 5.0
        assert lg.baseline("solve", "device_ms", fp="jaxB") is None

    def test_prewarm_summary_attributes_bakes(self, tmp_path):
        lg = PerfLedger(str(tmp_path))
        lg.record("prewarm", {"bake_ms": 100.0}, signature="n4",
                  variant="mesh4")
        lg.record("prewarm", {"bake_ms": 50.0}, signature="n4",
                  variant="lsdb100k")
        lg.record("solve", {"device_ms": 5.0})  # not a prewarm key
        summary = lg.prewarm_summary()
        assert summary["baked_ms"] == 150.0
        assert summary["namespaces"] == {"mesh4": 100.0, "lsdb100k": 50.0}

    def test_snapshot_is_bounded_quantiles_not_raw_dumps(self, tmp_path):
        lg = PerfLedger(str(tmp_path))
        for v in (1.0, 2.0, 3.0):
            lg.record("solve", {"device_ms": v}, signature="live",
                      variant="live")
        snap = lg.snapshot()
        [(key, entry)] = snap["keys"].items()
        assert key.startswith("solve|live|live|")
        assert entry["count"] == 3
        assert entry["metrics"]["device_ms"]["p50"] == 2.0
        assert "observations" not in entry

    def test_configure_repoints_the_singleton(self, tmp_path):
        d = str(tmp_path)
        try:
            lg = perf_ledger.configure(d)
            assert perf_ledger.get_ledger() is lg and lg.enabled
            # idempotent for the same dir — cached data survives
            assert perf_ledger.configure(d) is lg
            assert perf_ledger.configure("") is not lg
        finally:
            perf_ledger.configure("")


def _engine(slos, fast=0.2, slow=0.4, burn=0.5):
    return SloEngine(
        "node-slo",
        MonitorConfig(
            slos=slos,
            slo_fast_window_s=fast,
            slo_slow_window_s=slow,
            slo_burn_threshold=burn,
        ),
    )


def _drift_spec(source, **over):
    spec = {
        "kind": "baseline_drift",
        "source": source,
        "threshold": 1.5,
        "min_count": 1,
        "warmup_s": 0.0,
    }
    spec.update(over)
    return spec


def _seed_baseline(device_ms=5.0, n=5):
    for _ in range(n):
        perf_ledger.get_ledger().record(
            "solve", {"device_ms": device_ms}, signature="live",
            variant="live",
        )


class TestBaselineDriftSlo:
    def test_no_baseline_never_breaches(self, ledger_dir):
        """An empty ledger (fresh fleet, toolchain bump) must never
        page, no matter how slow the live window looks."""
        src = "test.drift.nobase_ms"
        eng = _engine({"d": _drift_spec(src)})
        for _ in range(5):
            counters.add_stat_value(src, 1000.0)
        for _ in range(4):
            assert eng.evaluate() == []
        rep = eng.report()["slos"]["d"]
        assert rep["state"] == "ok" and rep["value"] == 0.0
        assert "baseline" not in rep  # nothing to compare against

    def test_cold_start_warmup_is_excluded(self, ledger_dir):
        """A restarting node's compile-heavy first solves are not
        drift: inside warmup_s the SLO measures 0/no-breach."""
        _seed_baseline(5.0)
        src = "test.drift.warmup_ms"
        eng = _engine({"d": _drift_spec(src, warmup_s=60.0)})
        for _ in range(5):
            counters.add_stat_value(src, 1000.0)
        assert eng.evaluate() == []
        assert eng.report()["slos"]["d"]["state"] == "ok"
        # identical live data breaches once the engine is past warmup
        hot = _engine({"d": _drift_spec(src, warmup_s=0.0)})
        alerts = hot.evaluate()
        assert alerts and alerts[0]["state"] == "fast_burn"

    def test_min_count_guards_thin_windows(self, ledger_dir):
        _seed_baseline(5.0)
        src = "test.drift.thin_ms"
        eng = _engine({"d": _drift_spec(src, min_count=3)})
        counters.add_stat_value(src, 1000.0)  # one sample: not enough
        assert eng.evaluate() == []
        counters.add_stat_value(src, 1000.0)
        counters.add_stat_value(src, 1000.0)
        alerts = eng.evaluate()
        assert alerts and alerts[0]["slo"] == "d"

    def test_breach_alert_carries_kind_baseline_live(self, ledger_dir):
        _seed_baseline(5.0)
        src = "test.drift.breach_ms"
        eng = _engine({"d": _drift_spec(src)})
        for _ in range(5):
            counters.add_stat_value(src, 50.0)
        [alert] = eng.evaluate()
        assert alert["kind"] == "baseline_drift"
        assert alert["baseline"] == 5.0
        assert alert["live"] == 50.0
        assert alert["value"] == 10.0  # the ratio, not a raw timing
        assert alert["state"] == "fast_burn"
        # the report annotates the objective with both sides too
        rep = eng.report()["slos"]["d"]
        assert rep["baseline"] == 5.0 and rep["live"] == 50.0

    def test_ratio_below_threshold_never_alerts(self, ledger_dir):
        _seed_baseline(5.0)
        src = "test.drift.ok_ms"
        eng = _engine({"d": _drift_spec(src)})
        for _ in range(5):
            counters.add_stat_value(src, 6.0)  # 1.2x < 1.5x
        assert eng.evaluate() == []
        rep = eng.report()["slos"]["d"]
        assert rep["state"] == "ok" and rep["value"] == pytest.approx(1.2)

    def test_deassert_hysteresis(self, ledger_dir):
        """Recovery needs the fast window drained to half the burn
        threshold AND a clean current tick — the alert can't strobe."""
        _seed_baseline(5.0)
        src = "test.drift.recover_ms"
        eng = _engine({"d": _drift_spec(src)})
        for _ in range(5):
            counters.add_stat_value(src, 50.0)
        assert eng.evaluate()  # burning
        assert eng.report()["slos"]["d"]["state"] == "fast_burn"
        # an immediate clean-ish tick is NOT enough: the fast window
        # still remembers the breach
        eng.evaluate()
        assert eng.report()["slos"]["d"]["state"] != "ok"
        # after the breach ages out of BOTH the stats window and the
        # fast burn window, a healthy tick de-asserts
        time.sleep(1.05)
        counters.add_stat_value(src, 5.0)
        eng.evaluate()
        assert eng.report()["slos"]["d"]["state"] == "ok"


class TestPerfDiff:
    def test_flatten_and_direction(self):
        flat = perf_diff.flatten(
            {"configs": {"mesh4": {"tpu_ms": 2.0, "speedup": 3.0,
                                   "routes": 12}}, "value": 9.0}
        )
        assert flat == {
            "configs.mesh4.tpu_ms": 2.0,
            "configs.mesh4.speedup": 3.0,
            "configs.mesh4.routes": 12.0,
            "value": 9.0,
        }
        assert perf_diff.direction("configs.mesh4.tpu_ms") == "lower"
        assert perf_diff.direction("configs.mesh4.speedup") == "higher"
        assert perf_diff.direction("configs.mesh4.routes") == "info"
        assert perf_diff.direction("value") == "lower"

    def test_compare_verdicts(self):
        base = {"a_ms": 10.0, "b_ms": 10.0, "speedup": 4.0,
                "routes": 10.0, "tiny_ms": 0.2, "only_base_ms": 1.0}
        cand = {"a_ms": 20.0, "b_ms": 10.5, "speedup": 8.0,
                "routes": 99.0, "tiny_ms": 0.6}
        rows = {r["metric"]: r["verdict"]
                for r in perf_diff.compare(base, cand, 0.25, 1.0)}
        assert rows == {
            "a_ms": "regressed",     # 2x slower
            "b_ms": "neutral",       # within band
            "speedup": "improved",   # higher-better doubled
            "routes": "info",        # a count is a fact, not a verdict
        }
        # tiny_ms skipped (both under the floor); only_base_ms has no
        # candidate side, so it never appears

    def test_envelope_unwrap_and_exit_codes(self, tmp_path):
        """Committed BENCH_rNN baselines are driver envelopes with the
        bench line under "parsed"; raw and enveloped inputs must
        flatten to the same paths."""
        bench = {"configs": {"mesh4": {"tpu_ms": 10.0}},
                 "rig_rtt_ms": 40.0}
        base = tmp_path / "base.json"
        base.write_text(json.dumps(
            {"n": 5, "cmd": "bench", "rc": 0, "parsed": bench}))
        same = tmp_path / "same.json"
        same.write_text(json.dumps(bench))
        slow = tmp_path / "slow.json"
        slow.write_text(json.dumps(
            {"configs": {"mesh4": {"tpu_ms": 30.0}}, "rig_rtt_ms": 999.0}))
        assert perf_diff.main([str(base), str(same), "--json"]) == 0
        assert perf_diff.main([str(base), str(slow), "--json"]) == 1
        # rig_rtt_ms is the machine's property — excluded even though it
        # "regressed" 25x
        flat = perf_diff._load_bench(str(slow))
        assert "rig_rtt_ms" not in flat

    def test_vanished_lane_is_a_regression(self, tmp_path):
        """ISSUE 17 satellite: a lane present in the baseline but
        missing from the candidate is an explicit regression (exit 1),
        never a neutral skip — a bench config silently not running must
        not pass the CI gate."""
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"configs": {
            "mesh4": {"tpu_ms": 10.0},
            "flapstorm_tg1k": {"ack_p99_ms": 20.0},
        }}))
        cand = tmp_path / "cand.json"
        cand.write_text(json.dumps({"configs": {
            "mesh4": {"tpu_ms": 10.0},
        }}))
        # default: EVERY baseline lane is expected -> exit 1 with a
        # regressed MISSING row naming the lane
        assert perf_diff.main([str(base), str(cand), "--json"]) == 1
        rows = perf_diff.vanished_lane_rows(
            perf_diff._load_bench(str(base)),
            perf_diff._load_bench(str(cand)),
        )
        assert [r["metric"] for r in rows] == ["configs.flapstorm_tg1k"]
        assert rows[0]["verdict"] == "regressed"
        assert rows[0]["candidate"] == "MISSING"

    def test_expect_lanes_narrows_the_vanished_check(self, tmp_path):
        """--expect-lanes lets the smoke gate (which only runs mesh4)
        pass against the full multi-lane baseline, while a listed lane
        vanishing still fails."""
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"configs": {
            "mesh4": {"tpu_ms": 10.0},
            "flapstorm_tg1k": {"ack_p99_ms": 20.0},
        }}))
        cand = tmp_path / "cand.json"
        cand.write_text(json.dumps({"configs": {
            "mesh4": {"tpu_ms": 10.0},
        }}))
        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps({"configs": {}}))
        assert perf_diff.main(
            [str(base), str(cand), "--json", "--expect-lanes", "mesh4"]
        ) == 0
        assert perf_diff.main(
            [str(base), str(empty), "--json", "--expect-lanes", "mesh4"]
        ) == 1

    def test_ledger_mode(self, tmp_path):
        lg = PerfLedger(str(tmp_path / "ledger"))
        for v in (10.0, 10.0, 10.0):
            lg.record("solve[mesh4]", {"tpu_ms": v}, signature="n4",
                      variant="default")
        bench = tmp_path / "bench.json"
        bench.write_text(json.dumps(
            {"configs": {"mesh4": {"tpu_ms": 30.0}}}))
        rc = perf_diff.main(
            [str(bench), "--ledger", str(tmp_path / "ledger"), "--json"])
        assert rc == 1  # 3x the stored p95 baseline
        fast = tmp_path / "fast.json"
        fast.write_text(json.dumps(
            {"configs": {"mesh4": {"tpu_ms": 9.0}}}))
        assert perf_diff.main(
            [str(fast), "--ledger", str(tmp_path / "ledger"), "--json"]) == 0
