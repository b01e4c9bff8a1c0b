"""wan50k_agg, the WAN as an aggregation router sees it, and its cell
wan50k_agg.flap, checked on the CPU: the configuration against wan50k's
(the same LSDB, another vantage), the committed overlay's strata on the
committed graph, the plan's invariants at rehearsal size
(rehearsal_wan_agg/: wan-small-agg, 516 routers, a vantage of 52 links),
the plain reference against the repo's oracle from that vantage, and a
rehearsed run that reports 64 lanes."""

from __future__ import annotations

import os

import files
import harness
import lsdb as lsdb_mod
import reference
import run
import test_wan50k
from test_harness import cell_of, plan_of, rehearse

WAN_AGG = os.path.join(files.ROOT, "rehearsal_wan_agg")
CELL = "wan50k_agg.flap"
SMALL = "wan-small-agg.flap"


# -- the configuration and the cell as committed -----------------------------


def test_the_configuration_is_wan50ks_lsdb_from_an_aggregation_router():
    agg = lsdb_mod.load_config("wan50k_agg", files.ROOT)
    acc = lsdb_mod.load_config("wan50k", files.ROOT)
    assert agg["generator"] == acc["generator"]
    for key in ("solver_backend", "decision_config", "nodes", "keys",
                "chips", "reduced", "reduced_from", "areas",
                "ksp2_prefixes"):
        assert agg[key] == acc[key], key
    assert agg["vantage"] == "r25-agg20" != acc["vantage"]
    assert {**agg["assumed"], "vantage": ""} == {
        **acc["assumed"], "vantage": ""}
    for key in ("source", "deployment", "guarantees", "reference"):
        assert agg[key], key
    benchmark = files.load_benchmark()
    entry = next(c for c in benchmark["configs"] if c["name"] == "wan50k_agg")
    assert entry["source"] == agg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == agg["reduced"] == ["areas", "ksp2_prefixes"]
    cell = run.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "wan50k_agg", "sptlink-paced", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"]: m for m in benchmark["per_layer"]}
    for name, unit, better in (("spf_lanes", "lanes", "lower"),
                               ("spf_lane_fill", "%", "higher")):
        assert listed[name]["workloads"] == [CELL]
        assert (listed[name]["unit"], listed[name]["better"]) == (unit, better)
        assert listed[name]["moves"] == "churn_to_ack_p50_ms"
        assert listed[name]["layer"] == "device programs"


def test_the_vantage_has_52_links_and_twelve_strata_that_spare_them():
    config, traffic, lsdb, plan = plan_of(CELL, 2**31 + 33, files.ROOT)
    me = config["vantage"]
    assert traffic["vantage"] == me == "r25-agg20"
    assert len(lsdb.adj_dbs) == config["nodes"] == 50000
    mine = lsdb.adj_dbs[lsdb.index[me]].adjacencies
    tiers = [a.other_node_name.split("-")[1][:3] for a in mine]
    assert (len(mine), tiers.count("acc"), tiers.count("agg"),
            tiers.count("cor")) == (52, 48, 2, 2)
    assert {a.other_node_name for a in mine if "-agg" in a.other_node_name
            } < {f"r25-agg{i:02d}" for i in range(64)}
    assert 1 == min(a.metric for a in mine) < max(a.metric for a in mine) <= 7
    # the access vantage's own second uplink
    assert me in lsdb.neighbors("r25-acc0000")
    # the same strata as wan50k.flap, by the same expression
    _, acc_traffic = cell_of("wan50k.flap", files.ROOT)
    assert traffic["strata"] == acc_traffic["strata"]
    assert traffic["group"] == acc_traffic["group"]
    assert traffic["factor_range"] == [1.5, 3.0]
    kind = harness.load_kind(traffic["kind"])
    _, cands = kind.strata_candidates(lsdb, traffic)
    assert len(cands) == 12 and min(len(c) for c in cands) >= 16
    root = lsdb.index[me]
    assert all(root not in (u, v) for c in cands for u, v, _ in c)
    for _ in range(48):
        event = next(plan)
        assert me not in event["ops"][0][1:3]
        lsdb.apply(event["ops"])


def test_no_key_comes_within_the_dampers_reach():
    """test_wan50k.py's check of the accepted cells, on this one: at the
    cell's own period, over 60 s, each adj: key's figure of merit stays
    under a third of the suppress threshold."""
    test_wan50k.test_no_key_comes_within_the_dampers_reach(CELL)


def test_the_warmup_bursts_change_every_count_of_links():
    for name, root, strata in ((CELL, files.ROOT, 12), (SMALL, WAN_AGG, 6)):
        _, traffic, lsdb, plan = plan_of(name, seed=8, root=root)
        kind = harness.load_kind(traffic["kind"])
        cycle = kind.rotation_events(traffic)
        assert cycle == 2 * strata
        for _ in range(cycle):
            next(plan)
        for burst in traffic["warmup_bursts"]:
            events = [next(plan) for _ in range(burst)]
            assert len({ev["class"] for ev in events}) == 1, name
            changed = {tuple(sorted(ev["ops"][0][1:3])) for ev in events}
            assert len(changed) == burst, name
        assert set(traffic["warmup_bursts"]) == set(range(1, strata + 1))
        assert sum(traffic["warmup_bursts"]) % cycle == 0


# -- the plan at rehearsal size ----------------------------------------------


def test_every_one_of_80_events_moves_a_route_and_held_links_never_nest():
    config, traffic, lsdb, plan = plan_of(SMALL, 2**31 + 5, WAN_AGG)
    me = config["vantage"]
    assert traffic["vantage"] == me
    assert len(lsdb.neighbors(me)) >= 33
    kind = harness.load_kind(traffic["kind"])
    tree = kind.Tree(lsdb, me)
    owners = {
        db.prefix_entries[0].prefix: db.this_node_name
        for db in lsdb.prefix_dbs
    }
    held: dict[tuple, set] = {}
    before = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, True)
    assert max(len(route[1]) for route in before.values()) >= 2
    for i in range(80):
        event = next(plan)
        (op, a, b, metric), = event["ops"]
        assert op == "metric" and me not in (a, b)
        assert len(lsdb.apply(event["ops"])) == 2
        after = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, True)
        moved = {p for p in after if after[p] != before.get(p)}
        assert {a, b} & {owners[p] for p in moved}, (i, event)
        before = after
        if event["class"] == "restore":
            del held[a, b]
            continue
        v = tree.index[b]
        below = tree.reach(v, 10**9)
        for (_, other), under in held.items():
            assert tree.index[other] not in below and v not in under
        held[a, b] = below


# -- the plain reference from 52 links ---------------------------------------


def test_reference_agrees_with_the_repos_oracle_from_the_64_lane_vantage():
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.models import topologies

    config, _ = cell_of(SMALL, WAN_AGG)
    me = config["vantage"]
    lsdb = lsdb_mod.build(config)
    mine = lsdb.neighbors(me)
    lsdb.apply([
        ("metric", "r00-agg01", "r00-core0", 40),
        ("down", me, mine[5]),
        ("metric", me, mine[9], 3),
    ])
    states, prefix_state = topologies.build_states(
        lsdb.adj_dbs, lsdb.prefix_dbs
    )
    db = SpfSolver(me, enable_lfa=True).build_route_db(
        me, states, prefix_state
    )
    got = reference.programmed(dict(db.unicast_routes))
    want = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, True)
    check = reference.compare(got, want)
    assert (check["missing"], check["extra"], check["differing"]) == (
        0, 0, 0), check
    backed = [p for p, route in want.items() if route[2]]
    assert len(backed) * 2 >= len(want) == len(lsdb.adj_dbs) - 1
    # alternates are drawn from several links: the tie-break had a choice
    assert len({next(iter(want[p][2]))[:2] for p in backed}) >= 3
    cost, hops, _ = got[backed[0]]
    assert reference.compare(
        {**got, backed[0]: (cost, hops, frozenset())}, want
    )["differing"] == 1


# -- a whole run, rehearsed --------------------------------------------------


def test_a_rehearsed_run_reports_64_lanes_and_is_correct(capsys):
    result, lines = rehearse(capsys, run.main, [
        "--workload", SMALL, "--seed", str(2**31 + 33),
        "--seconds", "2", "--trace", "1", "--root", WAN_AGG,
    ])
    assert result["correct"] is True and result["failed"] == 0
    checks = [l for l in lines if "routes_compared" in l]
    assert len(checks) in (2, 3) and all(
        c["routes_compared"] == 515 and c["differing"] == 0 for c in checks
    ), lines
    got = result["metrics"]
    assert got["spf_lanes"] == {"value": 64.0, "unit": "lanes"}
    assert got["spf_lane_fill"] == {"value": 81.25, "unit": "%"}
    assert got["lfa_backup_share"]["value"] > 50.0
    assert 0.0 < got["residual_fill"]["value"] <= 100.0
    assert got["events_per_epoch"]["value"] < 1.3
    overload = next(l for l in lines if "overload" in l)["overload"]
    assert overload["plan_keys_damped"] == []
