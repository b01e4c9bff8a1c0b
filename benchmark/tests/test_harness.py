"""The benchmark's yardstick, checked on the CPU at sizes a test run can
hold: the plans' invariants, the arithmetic, the plain reference against
the repo's oracle, the trace reduction on a recorded trace, every traffic
kind end to end, the controls, and a cell added as files alone."""

from __future__ import annotations

import json
import os

import pytest

import control
import files
import harness
import lsdb as lsdb_mod
import metrics
import reduce_trace
import reference
import run

REHEARSAL = os.path.join(files.ROOT, "rehearsal")
# (cell of rehearsal/BENCHMARK.json, events to look at)
SMALL_CELLS = ["grid12.flap", "fabric-small.plane", "fabric-small.flap"]
REAL_CELLS = ["lsdb100k.flap", "fabric10k.plane"]


def cell_of(name: str, root: str = REHEARSAL):
    cell = run.find_cell(files.load_benchmark(root), name)
    config = lsdb_mod.load_config(cell["config"], root)
    traffic = harness.load_traffic(cell["traffic"], cell["config"], root)
    return config, traffic


def plan_of(name: str, seed: int, root: str = REHEARSAL):
    config, traffic = cell_of(name, root)
    lsdb = lsdb_mod.build(config)
    kind = harness.load_kind(traffic["kind"], root)
    return config, traffic, lsdb, kind.plan(lsdb, traffic, seed)


# -- the plans ---------------------------------------------------------------


@pytest.mark.parametrize("name", SMALL_CELLS)
def test_every_event_changes_a_route(name):
    config, _, lsdb, plan = plan_of(name, seed=11)
    lfa = bool(config["decision_config"].get("enable_lfa"))
    me = config["vantage"]
    before = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, lfa)
    for i in range(40):
        event = next(plan)
        assert lsdb.apply(event["ops"]), f"event {i} touches no node"
        after = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, lfa)
        assert after != before, f"event {i} ({event['class']}) moves no route"
        before = after


@pytest.mark.parametrize("name", SMALL_CELLS)
def test_the_mix_is_the_same_whatever_the_seed(name):
    def mix(seed):
        _, _, lsdb, plan = plan_of(name, seed)
        out = []
        for _ in range(48):
            event = next(plan)
            out.append((event["class"], len(lsdb.apply(event["ops"]))))
        return out

    first = mix(1)
    assert mix(2**31 + 12345) == first
    assert mix(7) == first
    # and the seed does draw something
    def links(seed):
        _, _, _, plan = plan_of(name, seed)
        return [tuple(next(plan)["ops"][0][1:3]) for _ in range(16)]

    assert links(1) != links(7) or "fabric-small.plane" == name


@pytest.mark.parametrize("name", ["grid12.flap", "fabric-small.flap"])
def test_a_restore_gives_back_the_generators_database(name):
    _, traffic, lsdb, plan = plan_of(name, seed=3)
    kind = harness.load_kind(traffic["kind"])
    start = list(lsdb.adj_dbs)
    for _ in range(kind.rotation_events(traffic)):
        lsdb.apply(next(plan)["ops"])
    assert lsdb.adj_dbs == start
    assert lsdb.replay(1).adj_dbs != start
    assert lsdb.replay(len(lsdb.log)).adj_dbs == start


def test_a_drain_is_timed_and_given_back_untimed():
    _, traffic, lsdb, plan = plan_of("fabric-small.plane", seed=3)
    ssw = [n for n in lsdb.index if "ssw" in n]
    start = list(lsdb.adj_dbs)
    for i in range(8):
        event = next(plan)
        lsdb.apply(event["ops"])
        cut_off = {
            n for n in ssw if not lsdb.adj_dbs[lsdb.index[n]].adjacencies
        }
        if i % 2 == 0:  # one whole plane is out, and the window times it
            assert event["class"] == "drain" and event.get("timed", True)
            assert len(cut_off) == 2 and len({n[:8] for n in cut_off}) == 1
        else:  # and is given back, untimed, before the next goes
            assert event["class"] == "restore" and event["timed"] is False
            assert event["after"] == traffic["restore_after"]
            assert not cut_off and lsdb.adj_dbs == start


def test_untimed_events_are_sent_acked_and_left_out_of_the_metric():
    window = {
        "events": [
            {"due": 1.0, "sent": 1.0, "acked": 1.5, "ack_epoch": 4,
             "class": "drain", "stratum": "00", "timed": True},
            {"due": 2.0, "sent": 2.0, "acked": 2.7, "ack_epoch": 5,
             "class": "restore", "stratum": "00", "timed": False},
            {"due": 3.0, "sent": 3.0, "acked": 3.4, "ack_epoch": 6,
             "class": "drain", "stratum": "01", "timed": True},
        ],
        "acks": [
            {"epoch": e, "routes": r, "evidence": {
                "sync_ms": 1.0, "exec_ms": x, "mat_ms": 0.5, "rounds": 5}}
            for e, r, x in ((4, 30, 20.0), (5, 31, 99.0), (6, 32, 22.0))
        ],
        "compiles": [], "seconds": 4.0, "collections": [],
    }
    s = metrics.series_of(window, {}, {}, {})
    assert s["event.ack_ms"] == pytest.approx([500.0, 400.0])
    assert metrics.medians_by(s) == pytest.approx(
        {"drain": 450.0, "restore": 700.0}
    )
    assert s["epoch.exec_ms"] == [20.0, 22.0]  # the timed events' epochs
    assert s["window.events"] == [3] and s["window.epochs"] == [3]
    assert len(s["event.late_ms"]) == 3


@pytest.mark.parametrize("name,root", [
    ("grid12.flap", REHEARSAL), ("lsdb100k.flap", files.ROOT),
])
def test_the_warmup_bursts_change_every_count_of_links(name, root):
    """The delta scatter compiles for each count of changed slots: after a
    whole cycle, the mix's bursts each lie inside one run of changes or of
    restores, so a burst of n changes n links, and between them they make
    every count up to the number of strata; and the warm-up ends where a
    cycle begins."""
    _, traffic, lsdb, plan = plan_of(name, seed=8, root=root)
    kind = harness.load_kind(traffic["kind"])
    cycle = kind.rotation_events(traffic)
    for _ in range(cycle):
        next(plan)
    counts = set()
    for burst in traffic["warmup_bursts"]:
        events = [next(plan) for _ in range(burst)]
        assert len({ev["class"] for ev in events}) == 1
        changed = {tuple(sorted(ev["ops"][0][1:3])) for ev in events}
        assert len(changed) == burst
        counts.add(burst)
    assert counts == set(range(1, cycle // 2 + 1))
    assert sum(traffic["warmup_bursts"]) % cycle == 0


@pytest.mark.parametrize("name", REAL_CELLS)
def test_no_key_comes_within_the_dampers_reach(name):
    """The cell as committed, at its own size and period, for longer than
    the longest window: each adj: key's figure of merit (penalty 1 a
    change, half-life 10 s, as config.py's defaults) stays far under the
    suppress threshold of 25, and the count of events is the window over
    the period."""
    from openr_tpu.config import DecisionConfig

    cfg = DecisionConfig()
    config, traffic, lsdb, plan = plan_of(name, seed=5, root=files.ROOT)
    assert len(lsdb.adj_dbs) == config["nodes"]
    period_s = traffic["period_ms"] / 1e3
    figure: dict[str, tuple[float, float]] = {}
    worst = 0.0
    due = -period_s
    while due < 60:
        event = next(plan)
        if event.get("timed", True):
            now = due = due + period_s
        else:
            now = due + event["after"] * period_s
        for node in lsdb.apply(event["ops"]):
            value, then = figure.get(node, (1.0, -60.0))  # the load's write
            value = value * 0.5 ** (
                (now - then) / cfg.overload_damping_half_life_s
            ) + cfg.overload_damping_penalty
            figure[node] = (value, now)
            worst = max(worst, value)
    assert worst < cfg.overload_damping_suppress / 3, worst


# -- the arithmetic ----------------------------------------------------------


def test_percentile_and_lateness_arithmetic():
    assert metrics.percentile([], 50) is None
    assert metrics.percentile([5.0], 95) == 5.0
    assert metrics.percentile([4, 1, 3, 2], 50) == 2.5
    assert metrics.percentile(range(1, 101), 95) == pytest.approx(95.05)
    window = {
        "events": [
            {"due": 10.0, "sent": 10.001, "acked": 10.080, "ack_epoch": 7,
             "class": "change", "stratum": "east"},
            {"due": 10.1, "sent": 10.130, "acked": 10.190, "ack_epoch": 8,
             "class": "restore"},
            {"due": 10.2, "sent": 10.2, "acked": None, "ack_epoch": None,
             "class": "change"},
        ],
        "acks": [
            {"epoch": e, "routes": 3, "evidence": {
                "sync_ms": 1.0, "exec_ms": 20.0, "mat_ms": 0.5, "rounds": 5}}
            for e in (6, 7, 8)
        ],
        "compiles": [], "seconds": 0.3,
        "collections": [(0, 0.001), (2, 0.5)],
    }
    s = metrics.series_of(window, {"keys": 100, "load_s": 4.0}, {}, {})
    assert s["event.ack_ms"] == pytest.approx([80.0, 90.0])
    assert s["event.late_ms"] == pytest.approx([1.0, 30.0, 0.0])
    assert s["event.ack_ms.restore"] == pytest.approx([90.0])
    assert metrics.medians_by(s, "stratum.ack_ms.")["east"] == (
        pytest.approx(80.0)
    )
    assert s["window.epochs"] == [2] and s["window.events"] == [3]
    assert s["host.gc2_pause_ms"] == [500.0]
    read = metrics.read_json_metric
    assert read({"series": ["event.ack_ms"], "reduce": "median"}, s) == (
        pytest.approx(85.0)
    )
    assert read({"series": ["setup.keys", "setup.load_s"], "reduce": "last",
                 "combine": "ratio"}, s) == 25.0
    assert read({"series": ["window.events", "window.epochs"],
                 "reduce": "last", "combine": "ratio"}, s) == 1.5
    # a reader that finds nothing to read gives nothing
    assert read({"series": ["span.fib.diff"], "reduce": "mean"}, s) is None


def test_every_metric_of_benchmark_json_has_a_reader():
    benchmark = files.load_benchmark()
    for group, directory in (("end_to_end", "end_to_end"),
                             ("per_layer", "layer_metrics")):
        for metric in benchmark[group]:
            assert metrics.read_metric(metric["name"], directory, {}) is None


# -- the plain reference -----------------------------------------------------


def test_reference_agrees_with_the_repos_oracle_on_a_fabric_with_lfa():
    """Non-unit metrics, so that some routes do carry a loop-free
    alternate; a link down; the vantage's own link changed."""
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.models import topologies

    adj, pfx = topologies.fabric(
        pods=4, planes=3, ssws_per_plane=2, rsws_per_pod=4
    )
    lsdb = lsdb_mod.Lsdb(adj, pfx)
    lsdb.apply([
        ("down", "pod001-rsw01", "pod001-fsw02"),
        ("metric", "pod000-rsw00", "pod000-fsw01", 3),
        ("metric", "pod002-fsw00", "zspine00-ssw01", 2),
    ])
    me = "pod000-rsw00"
    states, prefix_state = topologies.build_states(lsdb.adj_dbs, pfx)
    for lfa in (True, False):
        db = SpfSolver(me, enable_lfa=lfa).build_route_db(
            me, states, prefix_state
        )
        got = reference.programmed(dict(db.unicast_routes))
        want = reference.routes(lsdb.adj_dbs, pfx, me, lfa)
        check = reference.compare(got, want)
        assert (check["missing"], check["extra"], check["differing"]) == (
            0, 0, 0), check
        backups = sum(1 for route in want.values() if route[2])
        assert (backups > 0) == lfa


def test_compare_sees_one_wrong_route():
    from openr_tpu.models import topologies

    adj, pfx = topologies.grid(5, node_labels=False)
    want = reference.routes(adj, pfx, "node-2-2", False)
    got = dict(want)
    prefix = sorted(got)[3]
    cost, hops, backups = got[prefix]
    got[prefix] = (cost + 1, hops, backups)
    check = reference.compare(got, want)
    assert check["differing"] == 1 and check["examples"][0]["prefix"] == prefix
    del got[prefix]
    assert reference.compare(got, want)["missing"] == 1


# -- the trace reduction -----------------------------------------------------


def test_reduction_by_hand():
    ops = [["a", 0, 10, 0], ["b", 5, 10, 0], ["a", 40, 10, 0]]
    spans = [["convergence", 0, 60], ["decision.spf", 0, 20],
             ["fib.diff", 30, 45]]
    out = reduce_trace.reduce(ops, (0, 100), spans)
    assert out["busy_s"] == pytest.approx(25e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert dict(out["device_ops"]) == pytest.approx({"a": 20e-9, "b": 10e-9})
    assert dict(out["idle_gaps"]) == pytest.approx({
        "waiting_for_event": 40e-9, "between_stages": 20e-9,
        "fib.diff": 10e-9, "decision.spf": 5e-9,
    })


def test_reduction_of_the_recorded_trace():
    """testdata/trace_lsdb100k.json: the first two seconds of a traced
    window of lsdb100k.flap on the v5e, as run.py reduces it, with the
    numbers this reduction gave when it was recorded."""
    recorded = files.load_json(
        os.path.join(files.ROOT, "testdata", "trace_lsdb100k.json")
    )
    out = reduce_trace.reduce(
        recorded["device_ops"], tuple(recorded["window_ns"]),
        recorded["host_spans"],
    )
    want = recorded["reduced"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["device_ops"][0][0] == want["device_ops"][0][0]
    assert dict(out["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    # the parts add up: busy + every idle gap is the window
    gaps = sum(seconds for _, seconds in out["idle_gaps"])
    assert out["busy_s"] + gaps == pytest.approx(out["window_s"], rel=1e-3)
    assert 0 < out["busy_s"] < out["window_s"]


# -- whole runs, rehearsed ---------------------------------------------------


def rehearse(capsys, main, argv) -> tuple[dict, list[dict]]:
    assert main(argv + ["--rehearse"]) == 0
    lines = [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    assert lines[-1]["rehearsal"] is True  # never a result line
    return lines[-1]["would_print"], lines


@pytest.mark.parametrize("name", SMALL_CELLS)
def test_a_rehearsed_run_is_correct_and_steady(name, capsys):
    result, lines = rehearse(capsys, run.main, [
        "--workload", name, "--seed", str(2**31 + 7), "--seconds", "2",
        "--trace", "1", "--root", REHEARSAL,
    ])
    assert result["correct"] is True and result["failed"] == 0
    names = {m["name"] for m in files.load_benchmark()["per_layer"]
             if "workloads" not in m}  # those every cell owes
    # on the CPU the profiler sees no device: those three have nothing to
    # read from
    assert names - set(result["metrics"]) <= {"device_busy_ms_per_epoch"}
    # a busy test machine can make two events share an epoch, no more
    assert result["metrics"]["events_per_epoch"]["value"] < 1.2
    checks = [l for l in lines if "routes_compared" in l]
    assert len(checks) in (2, 3) and all(
        c["routes_compared"] > 0 for c in checks
    ), lines


@pytest.mark.parametrize("which", sorted(control.CONTROLS))
@pytest.mark.parametrize("name", ["grid12.flap", "fabric-small.plane"])
def test_a_control_comes_out_as_not_correct(name, which, capsys, monkeypatch):
    monkeypatch.setattr(lsdb_mod, "load_config", lsdb_mod.load_config)
    monkeypatch.setattr(
        harness.ServedStack, "start", harness.ServedStack.start
    )
    result, lines = rehearse(capsys, control.main, [
        "--control", which, "--workload", name, "--seed", "5",
        "--seconds", "2", "--trace", "0", "--root", REHEARSAL,
    ])
    assert result["correct"] is False
    assert result["failed"] == 0  # the harness itself ran to its end
    if which == "host_solver":
        hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
        assert not hiding["tpu_solver"]
        assert not hiding["no_host_computed_route"]
    else:
        checks = [l for l in lines if "routes_compared" in l]
        assert checks and all(
            c["differing"] >= 1 and c["missing"] == c["extra"] == 0
            for c in checks
        )


def test_a_lost_update_is_seen(capsys, monkeypatch):
    """The timed path broken underneath: KvStore drops every fourth write
    of the window. The events still seem acked (the next solve's ack
    covers them), so only the table comparisons can see it."""
    from openr_tpu.kvstore.kvstore import KvStore

    real = KvStore.set_key_vals
    calls = {"n": 0}

    async def lossy(self, area, key_vals):
        calls["n"] += 1
        if len(key_vals) <= 2 and calls["n"] % 4 == 0:
            return
        await real(self, area, key_vals)

    monkeypatch.setattr(KvStore, "set_key_vals", lossy)
    result, _ = rehearse(capsys, run.main, [
        "--workload", "grid12.flap", "--seed", "9", "--seconds", "3",
        "--trace", "0", "--root", REHEARSAL,
    ])
    assert result["correct"] is False


# -- a cell added as files alone ---------------------------------------------


def test_a_configuration_a_mix_and_a_metric_added_as_files(tmp_path, capsys):
    """What a later PR does: new files and new entries, no edit."""
    root = tmp_path / "more"
    (root / "configs").mkdir(parents=True)
    (root / "traffic").mkdir()
    (root / "layer_metrics").mkdir()
    (root / "configs" / "ring16.json").write_text(json.dumps({
        "name": "ring16", "source": "a toy",
        "generator": {"call": "ring", "args": [16]},
        "vantage": "node-0", "solver_backend": "tpu", "decision_config": {},
    }))
    (root / "traffic" / "far-side.json").write_text(json.dumps({
        "kind": "link_flap", "op": "metric", "metric_range": [2, 2],
        "base_metric": 1, "warmup_rotations": 2, "period_ms": 80,
        "strata": [
            {"name": name, "path": {"template": "node-{i}", "from": a, "to": b}}
            for name, a, b in (("cw-near", 1, 4), ("ccw-near", 15, 12),
                               ("cw-far", 4, 7), ("ccw-far", 12, 9))
        ],
    }))
    (root / "layer_metrics" / "routes_per_event.json").write_text(json.dumps({
        "series": ["epoch.routes"], "reduce": "mean",
    }))
    (root / "layer_metrics" / "slowest_class_ms.py").write_text(
        "def read(series):\n"
        "    meds = [sorted(xs)[len(xs) // 2] for name, xs in series.items()\n"
        "            if name.startswith('event.ack_ms.')]\n"
        "    return max(meds) if meds else None\n"
    )
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "ring16", "source": "a toy", "reduced": [],
                     "file": "more/configs/ring16.json", "why": "a toy"}],
        "workloads": [{"name": "ring16.far-side", "config": "ring16",
                       "traffic": "far-side", "chips": 1, "why": "a toy"}],
        "per_layer": [
            {"name": "routes_per_event", "unit": "routes", "better": "lower",
             "source": "program_counter", "layer": "Fib",
             "moves": "churn_to_ack_p50_ms",
             "workloads": ["ring16.far-side"]},
            {"name": "slowest_class_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "generator",
             "moves": "churn_to_ack_p50_ms",
             "workloads": ["ring16.far-side"]},
        ],
    }))
    result, _ = rehearse(capsys, run.main, [
        "--workload", "ring16.far-side", "--seed", "4", "--seconds", "2",
        "--trace", "1", "--root", str(root),
    ])
    assert result["correct"] is True and result["attempted"] == 25
    assert result["metrics"]["routes_per_event"]["value"] > 0
    assert result["metrics"]["slowest_class_ms"]["unit"] == "ms"
    assert "rib_diff_ms" in result["metrics"]  # and the shared ones
    # in the real cells the new metrics are not asked for
    benchmark = files.load_benchmark(str(root))
    assert "routes_per_event" not in metrics.metrics_of(
        benchmark, "lsdb100k.flap", True, {}
    )


def test_no_tpu_no_result_line(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "lsdb100k.flap", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code == 2
    assert capsys.readouterr().out == ""
