"""wan50k_region, the 50,000-router WAN in 51 areas from inside region 25,
and its cell wan50k_region.exit, checked on the CPU.

What `rehearsal_wan_region/` is for: a root (`run.py --root`) that holds
the cell at a size a test can serve in seconds: `wan-small-region`
(`wan_rtt` at 3 regions of 2 core, 3 aggregation and 6 access routers, from
the access router r00-acc0004: 11 routers and 57 prefix: keys in area r00,
32 routes, 22 of them inter-area with two advertisers each), its mix
`exit-shift.wan-small-region.json`, and a BENCHMARK.json that names the
cell's three per-layer metrics again with the small cell as their workload
(files.load_benchmark appends its lists to the real file's). A builder
rehearses the cell there before a chip call; the driver's runs name no
root and never see it.

Here: the configuration, the cell and its metrics found in BENCHMARK.json
BY NAME (a later PR appends after them); the full-size file's counts
against the model (1,000 routers, 2,056 links, 197,010 prefix: keys,
49,999 routes) without serving anything; the candidates the kind's rule
finds at full size and the tables the reference gives for each (ISSUE 47's
geometry); the plan's cycles; the damper's reach at the committed period;
and the small cell through run.py with `correct` true, and the controls
not correct: control.py's two, which break the program, and
`wan-small-region-keeps-drained.exit`, the same cell compared by a
reference whose selection among advertisers lacks the drained-advertiser
filter (`rehearsal_wan_region/references/region_keeps_drained.py`), which
breaks what this configuration brought.
"""

from __future__ import annotations

import os

import pytest

import control
import files
import harness
import run
from test_harness import rehearse

ROOT = os.path.join(files.ROOT, "rehearsal_wan_region")
CELL = "wan50k_region.exit"
SMALL = "wan-small-region.exit"
KEEPS_DRAINED = "wan-small-region-keeps-drained.exit"
ME = "r25-acc0000"
METRICS = {
    "announcers_per_row": (
        "cells/row", "lower", "program_counter", "device programs"),
    "routes_moved_per_epoch": (
        "routes/epoch", "lower", "program_counter", "solver host side"),
    "wide_epoch_share": ("%", "lower", "program_counter", "device programs"),
}


def cell_of(name: str, root: str):
    cell = run.find_cell(files.load_benchmark(root), name)
    config = files.load_config(cell["config"], root)
    traffic = harness.load_traffic(cell["traffic"], cell["config"], root)
    return cell, config, traffic


# -- the configuration and the cell as committed -----------------------------


def test_the_configuration_is_wan50ks_network_cut_into_areas():
    region = files.load_config("wan50k_region")
    wan = files.load_config("wan50k")
    for key in ("generator", "vantage", "solver_backend", "decision_config",
                "chips"):
        assert region[key] == wan[key], key
    assert region["reduced"] == ["ksp2_prefixes"]  # `areas` no longer
    assert region["reduced_from"] == {
        "ksp2_prefixes": wan["reduced_from"]["ksp2_prefixes"]}
    assert region["lsdb_module"] == region["reference_module"] == "region"
    assert (region["nodes"], region["nodes_in_network"]) == (1000, 50000)
    assert (region["areas"], region["advertisers_per_remote_prefix"]) == (
        51, 4)
    assert region["keys"] == region["nodes"] + region["prefix_keys"]
    for key in ("size", "plane_km", "tier_radius_km", "rtt_us",
                "core_agg_ports", "agg_access_ports", "router_ports",
                "region_graph", "generator_seed", "prefixes_per_node",
                "decision_config"):
        assert region["assumed"][key] == wan["assumed"][key], key
    for key in ("backbone_links", "import_policy", "igp_cost",
                "drained_border_router", "own_core_tie", "vantage", "prefix"):
        assert region["assumed"][key], key
    assert "49,999 routes" in region["guarantees"][1]
    assert "no host-computed route" in region["guarantees"][2]
    benchmark = files.load_benchmark()
    (entry,) = [c for c in benchmark["configs"]
                if c["name"] == "wan50k_region"]
    assert entry["source"] == region["source"] and len(entry["source"]) <= 200
    assert "PrefixManager.cpp:1662-1765" in entry["source"]
    sources = [c["source"] for c in benchmark["configs"]]
    assert len(set(sources)) == len(sources)
    assert entry["file"] == "benchmark/configs/wan50k_region.json"
    assert entry["reduced"] == ["ksp2_prefixes"] and len(entry["why"]) <= 200
    cell = run.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "wan50k_region", "exit-shift", 1)
    assert 0 < len(cell["why"]) <= 200
    assert sum(c["config"] == "wan50k_region"
               for c in benchmark["workloads"]) == 1
    # its three metrics, each for this cell alone
    mine = {m["name"]: m for m in benchmark["per_layer"]
            if m["name"] in METRICS}
    assert sorted(mine) == sorted(METRICS)
    for name, metric in mine.items():
        unit, better, source, layer = METRICS[name]
        assert metric == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "churn_to_ack_p50_ms",
            "workloads": [CELL],
        }
        assert os.path.exists(os.path.join(
            files.ROOT, "layer_metrics", name + ".py"))
    rooted = files.load_benchmark(ROOT)["per_layer"]
    for name in METRICS:
        assert [m["workloads"] for m in rooted if m["name"] == name] == [
            [CELL], [SMALL, KEEPS_DRAINED]], name
    # the accepted gauges of the layers this cell runs list it (appended,
    # nothing else of those entries changed) ...
    listed = {m["name"]: m for m in benchmark["per_layer"]}
    for name in ("lfa_backup_share", "prefix_rows", "prefix_row_fill",
                 "spf_lanes", "spf_lane_fill", "residual_edge_share",
                 "residual_fill"):
        assert listed[name]["workloads"][-1] == CELL, name
    # ... but for the overload bit's three, which test_fabric10k_pfx_drain
    # holds to their own cell alone: this root names them for the cell
    for name in ("overload_flips_per_epoch", "drain_pack_ms",
                 "mbuf_put_mb_per_epoch"):
        assert CELL not in listed[name]["workloads"], name
        assert [m["workloads"] for m in rooted if m["name"] == name][-1] == [
            CELL], name


def test_the_mix_is_the_issues_cycle_at_a_period_over_the_dampers_floor():
    _, _, traffic = cell_of(CELL, files.ROOT)
    assert traffic["kind"] == "exit_shift"
    assert traffic["factor_range"] == [1.5, 3.0]
    assert traffic["candidates"] == {"links": 2, "border_routers": 1}
    assert traffic["warmup_rotations"] == 2
    assert traffic["warmup_bursts"] == [1, 2, 2, 1]
    assert traffic["vantage"] == ME
    assert traffic["period_ms"] % 10 == 0 and traffic["period_ms"] >= 1000
    for word in ("sweep", "r25-agg38", "r25-agg20", "r25-core0"):
        assert word in traffic["doc"], word
    kind = harness.load_kind(traffic["kind"])
    assert kind.rotation_events(traffic) == 6


# -- the model, the rule and the reference at full size ----------------------


@pytest.fixture(scope="module")
def full_size():
    """The committed cell's model, built once (197,010 prefix databases:
    several seconds), with its reference and its kind."""
    _, config, traffic = cell_of(CELL, files.ROOT)
    lsdb = files.lsdb_module(config).build(config)
    return (config, traffic, lsdb, files.reference_module(config),
            harness.load_kind(traffic["kind"]))


def test_the_files_counts_are_the_models(full_size):
    config, _, lsdb, ref, _ = full_size
    assert lsdb.areas() == ["r25"] and lsdb.network.areas == config["areas"]
    assert lsdb.network.routers == config["nodes_in_network"]
    assert len(lsdb.index) == len(lsdb.adj_dbs) == config["nodes"] == 1000
    assert len(lsdb.links()) == config["links"] == 2056
    assert len(lsdb.prefix_dbs) == config["prefix_keys"] == 197010
    assert len(lsdb.network.borders) == 200
    assert lsdb.network.my_borders == [f"r25-core{i}" for i in range(4)]
    far = [db for db in lsdb.prefix_dbs if db.prefix_entries[0].area_stack]
    assert len(far) == 49_000 * 4 + 10
    by = {}
    for db in far:
        (entry,) = db.prefix_entries
        key = (entry.metrics.distance, entry.area_stack[-1],
               len(entry.area_stack))
        by[key] = by.get(key, 0) + 1
    assert by == {(2, "bb", 2): 48_804 * 4, (1, "bb", 1): 196 * 4 + 10}
    # r25-core0 and r25-core3 are as near through the region as through the
    # backbone: neither re-advertises the other's prefix (ISSUE 47 reckoned
    # 12 such entries; the rule gives 10)
    own = {b: sorted(sent[2]) for b, sent in lsdb.network.sent.items()}
    assert own == {
        "r25-core0": ["r25-core1", "r25-core2"],
        "r25-core1": ["r25-core0", "r25-core2", "r25-core3"],
        "r25-core2": ["r25-core0", "r25-core1", "r25-core3"],
        "r25-core3": ["r25-core1", "r25-core2"],
    }
    want = ref.routes(lsdb, ME, config)
    assert len(want) == config["routes"] == 49_999


def exits(table) -> tuple:
    (shape,) = table.inter_area
    assert shape["routes"] == 49_000
    return shape["metric"], shape["next_hops"], shape["alternate"]


def test_the_rule_finds_the_two_uplinks_and_core0_and_the_issues_tables(
    full_size,
):
    config, traffic, lsdb, ref, kind = full_size
    found = kind.find_candidates(lsdb, traffic)
    name = found["area"].names
    assert [(name[u], name[v]) for u, v in found["links"]] == [
        (ME, "r25-agg20"), (ME, "r25-agg38")]
    assert [name[b] for b in found["border_routers"]] == ["r25-core0"]
    then = lsdb.replay(0)
    assert exits(ref.routes(then, ME, config)) == (
        7, ["r25-agg20", "r25-agg38"], [])
    for ops, back, want in (
        ([("metric", ME, "r25-agg38", 2)], [("metric", ME, "r25-agg38", 1)],
         (7, ["r25-agg20"], [["r25-agg38", 8]])),
        ([("metric", ME, "r25-agg38", 3)], [("metric", ME, "r25-agg38", 1)],
         (7, ["r25-agg20"], [["r25-agg38", 9]])),
        ([("metric", ME, "r25-agg20", 3)], [("metric", ME, "r25-agg20", 2)],
         (7, ["r25-agg38"], [["r25-agg20", 8]])),
        ([("metric", ME, "r25-agg20", 6)], [("metric", ME, "r25-agg20", 2)],
         (7, ["r25-agg38"], [["r25-agg20", 11]])),
        ([("drain", "r25-core0")], [("undrain", "r25-core0")],
         (7, ["r25-agg38"], [["r25-agg20", 8]])),
    ):
        then.apply(ops)
        table = ref.routes(then, ME, config)
        assert exits(table) == want, ops
        assert list(table.held) == then.held() == [list(ops[0])]
        then.apply(back)
        assert then.held() == []
    # a drain of any other border router alone moves no inter-area route
    for node in ("r25-core1", "r25-core2", "r25-core3"):
        then.apply([("drain", node)])
        assert exits(ref.routes(then, ME, config)) == (
            7, ["r25-agg20", "r25-agg38"], []), node
        then.apply([("undrain", node)])


def test_a_cycle_is_six_timed_events_one_thing_held_at_a_time(full_size):
    config, traffic, lsdb, ref, kind = full_size
    lsdb = lsdb.replay(0)
    a, b, d = "r25-acc0000--r25-agg20", "r25-acc0000--r25-agg38", "r25-core0"
    orders = []
    for seed in (7, 2**31 + 47):
        plan = kind.plan(lsdb, traffic, seed)
        seen = []
        for cycle in range(12):
            order = []
            for _ in range(3):
                change, back = next(plan), next(plan)
                assert change.get("timed", True) and back.get("timed", True)
                assert change["stratum"] == back["stratum"]
                assert (change["class"], back["class"]) in (
                    ("step", "unstep"), ("drain", "undrain"))
                lsdb.apply(change["ops"])
                assert len(lsdb.held()) == 1  # one thing held at a time
                if change["class"] == "step":
                    (_, me, other, m), = change["ops"]
                    base = {"r25-agg38": 1, "r25-agg20": 2}[other]
                    assert me == ME and base < m <= 3 * base
                    assert m >= max(-(-3 * base // 2), base + 1)
                    assert back["ops"] == [("metric", ME, other, base)]
                else:
                    assert change["ops"] == [("drain", d)]
                    assert back["ops"] == [("undrain", d)]
                lsdb.apply(back["ops"])
                assert lsdb.held() == []
                order.append(change["stratum"])
            assert sorted(order) == [a, b, d]
            seen.append(tuple(order))
        # the warm-up's four cycles are fixed, the seed draws the rest
        assert seen[:4] == [(a, b, d), (a, b, d), (b, a, d), (b, a, d)]
        orders.append(seen[4:])
    assert orders[0] != orders[1] and len(set(orders[0])) > 1


def test_the_vantages_key_stays_under_the_dampers_threshold(full_size):
    """Over 90 s at the committed period (a window, its set-up's 24 + 12
    events and more) no adj: key's penalty passes half of what suppresses:
    the vantage's own key changes in four of a cycle's six events."""
    from openr_tpu.config import DecisionConfig

    cfg = DecisionConfig()
    config, traffic, lsdb, _, kind = full_size
    lsdb = lsdb.replay(0)
    plan = kind.plan(lsdb, traffic, 2**31 + 48)
    figure, worst, now = {}, {}, 0.0
    while now < 90:
        pub = lsdb.publication(lsdb.apply(next(plan)["ops"]))
        now += traffic["period_ms"] / 1e3
        assert list(pub) == ["r25"]
        for key in pub["r25"]:
            assert key.startswith("adj:")
            value, then = figure.get(key, (0.0, -90.0))
            value = value * 0.5 ** (
                (now - then) / cfg.overload_damping_half_life_s
            ) + cfg.overload_damping_penalty
            figure[key] = (value, now)
            worst[key] = max(worst.get(key, 0.0), value)
    assert set(worst) == {"adj:r25-acc0000", "adj:r25-agg20",
                          "adj:r25-agg38", "adj:r25-core0"}
    assert max(worst, key=worst.get) == "adj:r25-acc0000"
    assert worst["adj:r25-acc0000"] < cfg.overload_damping_suppress / 2


# -- whole runs, rehearsed ---------------------------------------------------


def test_a_rehearsed_run_is_correct_and_names_what_each_table_held(capsys):
    result, lines = rehearse(capsys, run.main, [
        "--workload", SMALL, "--seed", str(2**31 + 47),
        "--seconds", "6", "--trace", "1", "--root", ROOT,
    ])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 12
    checks = [l for l in lines if "routes_compared" in l]
    assert len(checks) == 3 and all(
        c["routes_compared"] == 32
        and c["missing"] == c["extra"] == c["differing"] == 0
        for c in checks
    ), checks
    for c in checks:
        (shape,) = c["inter_area"]
        assert shape["routes"] == 22 and shape["metric"] == 5
        # two exits at rest, one while something is held
        assert len(shape["next_hops"]) == (1 if c["held"] else 2), c
        assert len(c["held"]) <= 1
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    assert all(hiding.values()), hiding
    counted = next(l for l in lines if "compiles_in_window" in l)
    assert counted["compiles_in_window"] == 0
    assert not counted["overload"]["plan_keys_damped"]
    assert set(counted["ack_ms_median_by_class"]) == {
        "step", "unstep", "drain", "undrain"}
    got = result["metrics"]
    assert got["events_per_epoch"]["value"] == 1.0
    assert got["announcers_per_row"] == {
        "value": 57 / 33, "unit": "cells/row"}
    # an uplink's step moves what every row shares; the drain's rows are
    # few enough here for the candidates' path (at full size they are not)
    assert got["wide_epoch_share"]["value"] == pytest.approx(100.0 * 8 / 12)
    # no full result at this size: 32 routes fit a delta pull, so the
    # counter has no sample and the metric is left out
    assert "routes_moved_per_epoch" not in got


@pytest.mark.parametrize("which", sorted(control.CONTROLS))
def test_the_controls_are_not_correct(which, capsys, monkeypatch):
    monkeypatch.setattr(files, "load_config", files.load_config)
    monkeypatch.setattr(
        harness.ServedStack, "start", harness.ServedStack.start
    )
    result, lines = rehearse(capsys, control.main, [
        "--control", which, "--workload", SMALL, "--seed", "9",
        "--seconds", "3", "--trace", "0", "--root", ROOT,
    ])
    assert result["correct"] is False
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    checks = [l for l in lines if "routes_compared" in l]
    if which == "host_solver":
        assert not hiding["tpu_solver"]
    else:
        assert result["failed"] == 0 and all(hiding.values())
        assert checks and all(c["differing"] >= 1 for c in checks)


def test_a_selection_that_keeps_a_drained_border_router_is_not_correct(
    capsys,
):
    """The control of what this configuration brought, the selection among
    a prefix's advertisers: with the drained-advertiser filter off, the
    reference keeps the exit through a drained border router. The table
    compared while r00-core1 is drained differs in every inter-area route
    and in no other; the tables at rest agree (seed 9 draws one of each)."""
    result, lines = rehearse(capsys, run.main, [
        "--workload", KEEPS_DRAINED, "--seed", "9",
        "--seconds", "4", "--trace", "0", "--root", ROOT,
    ])
    assert result["correct"] is False and result["failed"] == 0
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    assert all(hiding.values()), hiding
    checks = [l for l in lines if "routes_compared" in l]
    drained = [c for c in checks if ["drain", "r00-core1"] in c["held"]]
    assert drained and len(drained) < len(checks)
    for c in checks:
        assert c["routes_compared"] == 32 and c["missing"] == c["extra"] == 0
        assert c["differing"] == (22 if c in drained else 0), c
    # what the wrong reference expects there: both exits still
    (shape,) = drained[0]["inter_area"]
    assert shape["routes"] == 22 and len(shape["next_hops"]) == 2
