"""fabric10k_pfxchurn, the 10,000-switch fabric under its source's own
traffic, and its cell fabric10k_pfxchurn.churn, checked on the CPU: the
configuration against fabric10k_pfx's (the same network, value for value),
the committed overlay's strata against fabric10k_pfx.flap's, the plan at
full size (one prefix an event, never two advertisers, 319,220 to 319,244
prefixes) and at rehearsal size (rehearsal_fabric_pfxchurn/:
fabric-small-pfxchurn, 96 switches, 3,072 prefixes in 4,096 rows) where
every event adds or deletes exactly one route of the vantage, the damper's
reach, and rehearsed runs: the cell correct with every epoch prefix-only on
the device, both controls and a reference that keeps a withdrawn prefix
not correct."""

from __future__ import annotations

import ipaddress
import os

import pytest

import control
import files
import harness
import run
from test_harness import rehearse

ROOT = os.path.join(files.ROOT, "rehearsal_fabric_pfxchurn")
CELL = "fabric10k_pfxchurn.churn"
SMALL = "fabric-small-pfxchurn.churn"
KEPT = "fabric-small-pfxchurn-kept.churn"
PER_NODE = 32
METRICS = {
    "prefix_sync_ms": ("ms", "lower", "program_span", "solver host side"),
    "prefix_rows_changed_per_epoch": (
        "rows/epoch", "lower", "program_counter", "device programs"),
    "prefix_only_epoch_share": (
        "%", "higher", "program_counter", "Decision host"),
}


def cell_of(name: str, root: str):
    cell = run.find_cell(files.load_benchmark(root), name)
    config = files.load_config(cell["config"], root)
    traffic = harness.load_traffic(cell["traffic"], cell["config"], root)
    return cell, config, traffic


def plan_of(name: str, seed: int, root: str):
    _, config, traffic = cell_of(name, root)
    lsdb = files.lsdb_module(config, root).build(config)
    kind = harness.load_kind(traffic["kind"], root)
    return config, traffic, lsdb, kind, kind.plan(lsdb, traffic, seed)


# -- the configuration and the cell as committed -----------------------------


def test_the_configuration_is_fabric10k_pfxs_network_value_for_value():
    churn = files.load_config("fabric10k_pfxchurn")
    pfx = files.load_config("fabric10k_pfx")
    for key in ("generator", "vantage", "solver_backend", "decision_config",
                "nodes", "keys", "chips", "reduced"):
        assert churn[key] == pfx[key], key
    assert churn["reduced"] == []
    assert churn["lsdb_module"] == churn["reference_module"] == "prefix_churn"
    assert "lsdb_module" not in pfx and "reference_module" not in pfx
    for key in ("link_metric", "advertisers", "prefix", "decision_config",
                "planes", "pods"):
        assert churn["assumed"][key] == pfx["assumed"][key], key
    for key in ("prefixes_per_node", "traffic", "fresh_prefixes"):
        assert churn["assumed"][key], key
    assert churn["assumed"]["prefixes_per_node"].startswith("32, ")
    assert "NO HOST-COMPUTED ROUTE" in churn["guarantees"][2]
    assert "a prefix-only epoch runs on the TPU" in churn["guarantees"][2]
    assert "319,188 to 319,212 routes" in churn["guarantees"][1]
    benchmark = files.load_benchmark()
    entry = benchmark["configs"][-1]
    assert entry["name"] == "fabric10k_pfxchurn"
    assert entry["source"] == churn["source"] and len(entry["source"]) <= 200
    sources = [c["source"] for c in benchmark["configs"]]
    assert len(set(sources)) == len(sources)
    assert "DecisionBenchmark.cpp:67" in entry["source"]
    assert entry["file"] == "benchmark/configs/fabric10k_pfxchurn.json"
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    cell = benchmark["workloads"][-1]
    assert cell == run.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fabric10k_pfxchurn", "pfx-churn", 1)
    assert 0 < len(cell["why"]) <= 200
    assert sum(c["config"] == "fabric10k_pfxchurn"
               for c in benchmark["workloads"]) == 1
    # its three metrics, appended, each for this cell alone
    assert [m["name"] for m in benchmark["per_layer"][-3:]] == list(METRICS)
    for metric in benchmark["per_layer"][-3:]:
        unit, better, source, layer = METRICS[metric["name"]]
        assert metric == {
            "name": metric["name"], "unit": unit, "better": better,
            "source": source, "layer": layer,
            "moves": "churn_to_ack_p50_ms", "workloads": [CELL],
        }
        assert any(os.path.exists(os.path.join(
            files.ROOT, "layer_metrics", metric["name"] + ending
        )) for ending in (".json", ".py"))
    # the accepted gauges of the layers this cell runs list it in the
    # rehearsal root meanwhile (a benchmark PR appends it in BENCHMARK.json)
    listed = {m["name"]: m for m in benchmark["per_layer"]}
    rooted = files.load_benchmark(ROOT)["per_layer"]
    for name in ("prefix_rows", "prefix_row_fill", "residual_edge_share",
                 "residual_fill", "spf_lanes", "spf_lane_fill"):
        assert CELL not in listed[name]["workloads"], name
        there = [m["workloads"] for m in rooted if m["name"] == name][-1]
        assert SMALL in there and CELL in there, name


def test_the_overlay_has_fabric10k_pfx_flaps_strata_and_pacing():
    _, _, churn = cell_of(CELL, files.ROOT)
    _, _, flap = cell_of("fabric10k_pfx.flap", files.ROOT)
    assert churn["strata"] == flap["strata"] and len(churn["strata"]) == 12
    assert churn["kind"] == "prefix_churn" and churn["fresh"] == "fd00:c::/48"
    assert churn["period_ms"] % 10 == 0 and 50 <= churn["period_ms"] <= 1500
    # fabric10k_pfx.flap's pacing unless the sweep said otherwise
    assert churn["period_ms"] == flap["period_ms"] == 300
    assert "sweep" in churn["doc"]
    # no burst of the warm-up is needed: a count of rows compiles nothing
    assert churn["warmup_bursts"] == [] and churn["warmup_rotations"] == 2
    kind = harness.load_kind(churn["kind"])
    assert kind.rotation_events(churn) == 48
    assert 45_000 // churn["period_ms"] == 150


@pytest.fixture(scope="module")
def full_size():
    """The committed cell's LSDB and plan, built once (319,232 prefix
    databases: several seconds)."""
    return plan_of(CELL, 2**31 + 41, files.ROOT)


def test_two_rotations_at_full_size_one_prefix_an_event(full_size):
    config, traffic, lsdb, kind, plan = full_size
    me = config["vantage"]
    assert len(lsdb.adj_dbs) == config["nodes"] == 9976
    assert len(lsdb.prefix_dbs) == 319232 == config["keys"] - 9976
    fresh = ipaddress.ip_network(traffic["fresh"])
    names = [s["name"] for s in traffic["strata"]]
    seen_fresh = set()
    count = 319232
    for i in range(2 * kind.rotation_events(traffic)):
        event = next(plan)
        visit, k = divmod(i % 48, 12)
        assert event["stratum"] == names[k]
        assert event["class"] == ("withdraw", "advertise")[visit in (1, 2)]
        assert event.get("timed", True)
        (op, node, what), = event["ops"]
        assert op == event["class"]
        assert "-rsw" in node and not node.startswith(me[:6]), event
        prefix = what if op == "withdraw" else what.prefix
        is_fresh = ipaddress.ip_network(prefix).subnet_of(fresh)
        assert is_fresh == (visit >= 2), event
        if visit == 2:
            assert prefix not in seen_fresh
            seen_fresh.add(prefix)
            assert str(ipaddress.ip_network(prefix)) == prefix  # canonical
        # the model refuses a second advertiser, and a withdraw of what
        # the node does not advertise: neither happens
        nodes, mine = lsdb.apply(event["ops"])
        assert nodes == [] and mine == event["ops"]
        count += 1 if op == "advertise" else -1
        assert 319220 <= count <= 319244
        pub = lsdb.publication((nodes, mine))
        (key, value), = pub["0"].items()
        assert key.startswith(f"prefix:{node}:") and value.version >= 2
    assert count == 319232 and not lsdb.withdrawn and not lsdb.advertised
    assert len(lsdb.retired) == 24 == len(seen_fresh)


# -- the plan at rehearsal size ----------------------------------------------


@pytest.mark.parametrize("seed", [7, 2**31 + 41])
def test_every_event_adds_or_deletes_exactly_one_route(seed):
    config, traffic, lsdb, kind, plan = plan_of(SMALL, seed, ROOT)
    ref = files.reference_module(config, ROOT)
    me = config["vantage"]
    before = ref.routes(lsdb, me, config)
    assert len(before) == 3072 - PER_NODE
    for i in range(2 * kind.rotation_events(traffic)):
        event = next(plan)
        (op, node, what), = event["ops"]
        lsdb.apply(event["ops"])
        after = ref.routes(lsdb.replay(len(lsdb.log)), me, config)
        assert after == ref.routes(lsdb, me, config)
        moved = before.keys() ^ after.keys()
        assert len(moved) == 1, (i, event)
        (prefix,) = moved
        assert prefix == (what if op == "withdraw" else what.prefix)
        assert (prefix in after) == (op == "advertise")
        assert all(after[p] == before[p] for p in after.keys() & before.keys())
        if op == "advertise":
            cost, hops, backups = after[prefix]
            assert cost == 4 and len(hops) == 6 and not backups
        before = after
    # and the model gives back the generator's LSDB
    assert lsdb.replay(0).key_vals() == files.lsdb_module(
        config, ROOT).build(config).key_vals()


def test_no_prefix_key_comes_within_the_dampers_reach():
    """At the cell's period, over 60 s, no prefix: key's figure of merit
    comes within half the damper's suppress threshold; a key is written
    twice in a rotation (its withdraw and its give-back, or its
    advertisement and its withdraw) and, but for a repeated draw, never
    again. At rehearsal size a stratum holds 8 x 32 prefixes where the
    full-size one holds 672 x 32 or more: repeats are likelier here."""
    from openr_tpu.config import DecisionConfig

    cfg = DecisionConfig()
    _, _, full = cell_of(CELL, files.ROOT)
    for seed in (7, 2**31 + 40):
        config, traffic, lsdb, kind, plan = plan_of(SMALL, seed, ROOT)
        figure, worst, now = {}, 0.0, 0.0
        while now < 60:
            event = next(plan)
            pub = lsdb.publication(lsdb.apply(event["ops"]))
            now += full["period_ms"] / 1e3
            for key in pub["0"]:
                assert key.startswith("prefix:")
                value, then = figure.get(key, (1.0, -60.0))
                value = value * 0.5 ** (
                    (now - then) / cfg.overload_damping_half_life_s
                ) + cfg.overload_damping_penalty
                figure[key] = (value, now)
                worst = max(worst, value)
        assert worst < cfg.overload_damping_suppress / 2, worst


# -- whole runs, rehearsed ---------------------------------------------------


def test_a_rehearsed_run_is_correct_and_every_epoch_prefix_only(capsys):
    result, lines = rehearse(capsys, run.main, [
        "--workload", SMALL, "--seed", str(2**31 + 41),
        "--seconds", "4", "--trace", "1", "--root", ROOT,
    ])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 40
    checks = [l for l in lines if "routes_compared" in l]
    assert len(checks) == 3 and all(
        3040 - 4 <= c["routes_compared"] <= 3040 + 4
        and c["missing"] == c["extra"] == c["differing"] == 0
        for c in checks
    ), checks
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    assert all(hiding.values()), hiding
    counted = next(l for l in lines if "compiles_in_window" in l)
    assert counted["compiles_in_window"] == 0
    assert not counted["overload"]["plan_keys_damped"]
    assert set(counted["ack_ms_median_by_class"]) == {"withdraw", "advertise"}
    got = result["metrics"]
    assert got["prefix_only_epoch_share"] == {"value": 100.0, "unit": "%"}
    per_epoch = got["prefix_rows_changed_per_epoch"]
    assert per_epoch["unit"] == "rows/epoch"
    assert per_epoch["value"] == got["events_per_epoch"]["value"]
    assert 1.0 <= per_epoch["value"] <= 1.1
    assert 0.0 < got["prefix_sync_ms"]["value"] < 50.0
    assert got["relax_rounds"]["value"] == 0.0
    assert got["prefix_rows"]["value"] == 4096.0
    assert 74.9 < got["prefix_row_fill"]["value"] < 75.2
    assert got["spf_lanes"]["value"] == 8.0


@pytest.mark.parametrize("which", sorted(control.CONTROLS))
def test_the_controls_are_not_correct(which, capsys, monkeypatch):
    # what the control breaks, put back when the test ends
    monkeypatch.setattr(files, "load_config", files.load_config)
    monkeypatch.setattr(
        harness.ServedStack, "start", harness.ServedStack.start
    )
    result, lines = rehearse(capsys, control.main, [
        "--control", which, "--workload", SMALL, "--seed", "9",
        "--seconds", "2", "--trace", "0", "--root", ROOT,
    ])
    assert result["correct"] is False and result["failed"] == 0
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    checks = [l for l in lines if "routes_compared" in l]
    if which == "host_solver":
        assert not hiding["tpu_solver"]
        assert not hiding["no_host_computed_route"]
    else:
        assert all(hiding.values())
        assert checks and all(c["differing"] >= 1 for c in checks)


def test_a_reference_that_keeps_a_withdrawn_prefix_is_not_correct(capsys):
    result, lines = rehearse(capsys, run.main, [
        "--workload", KEPT, "--seed", str(2**31 + 41),
        "--seconds", "4", "--trace", "0", "--root", ROOT,
    ])
    assert result["correct"] is False and result["failed"] == 0
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    assert all(hiding.values())  # the program was sound: the reference not
    checks = [l for l in lines if "routes_compared" in l]
    # the reference wants the routes of what was withdrawn: the table
    # misses them, and nothing else differs
    assert all(c["missing"] >= 1 and c["extra"] == c["differing"] == 0
               for c in checks), checks
