"""fabric10k_pfx, the 10,000-switch fabric with 32 prefixes a switch, and
its cell fabric10k_pfx.flap, checked on the CPU: the configuration against
fabric10k's (the same generator call but for the one key), the committed
overlay's strata against fabric10k.flap's, the plan at rehearsal size
(rehearsal_fabric_pfx/: fabric-small-pfx, 96 switches, 3,072 prefixes in
4,096 rows) where every event moves exactly 32 routes of the vantage, and
a rehearsed run that reports the prefix plane's rows and their fill."""

from __future__ import annotations

import os

import pytest

import files
import lsdb as lsdb_mod
import reference
import run
import test_wan50k
from test_harness import cell_of, plan_of, rehearse

FABRIC_PFX = os.path.join(files.ROOT, "rehearsal_fabric_pfx")
CELL = "fabric10k_pfx.flap"
SMALL = "fabric-small-pfx.flap"
PER_NODE = 32
GAUGES = ("residual_edge_share", "residual_fill", "lfa_backup_share",
          "spf_lanes", "spf_lane_fill")


# -- the configuration and the cell as committed -----------------------------


def test_the_configuration_is_fabric10ks_generator_with_32_prefixes_a_node():
    pfx = lsdb_mod.load_config("fabric10k_pfx", files.ROOT)
    one = lsdb_mod.load_config("fabric10k", files.ROOT)
    kwargs = dict(pfx["generator"]["kwargs"])
    assert kwargs.pop("prefixes_per_node") == PER_NODE
    assert {**pfx["generator"], "kwargs": kwargs} == one["generator"]
    for key in ("vantage", "solver_backend", "decision_config", "nodes",
                "chips", "reduced"):
        assert pfx[key] == one[key], key
    assert pfx["reduced"] == []
    # one adj: key a switch and one prefix: key a prefix
    assert pfx["keys"] == pfx["nodes"] * (1 + PER_NODE) == 329208
    assert one["keys"] == one["nodes"] * 2
    for key in ("link_metric", "decision_config", "planes", "pods"):
        assert pfx["assumed"][key] == one["assumed"][key], key
    assert pfx["assumed"]["prefixes_per_node"].startswith("32 ")
    assert one["assumed"]["prefixes_per_node"] == 1
    for key in ("source", "deployment", "guarantees", "reference"):
        assert pfx[key], key
    assert "319,200 routes" in pfx["guarantees"][1]
    benchmark = files.load_benchmark()
    entry = next(
        c for c in benchmark["configs"] if c["name"] == "fabric10k_pfx"
    )
    assert entry["source"] == pfx["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/fabric10k_pfx.json"
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    cell = run.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fabric10k_pfx", "flap-paced", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"]: m for m in benchmark["per_layer"]}
    for name, unit, better in (("prefix_rows", "rows", "lower"),
                               ("prefix_row_fill", "%", "higher")):
        assert listed[name]["workloads"] == [CELL]
        assert (listed[name]["unit"], listed[name]["better"]) == (unit, better)
        assert listed[name]["source"] == "program_counter"
        assert listed[name]["moves"] == "churn_to_ack_p50_ms"
        assert listed[name]["layer"] == "device programs"
    # the accepted gauges of the layers this cell runs: BENCHMARK.json's lists
    # are pinned by the accepted cells' tests, so the rehearsal root names the
    # full-size cell for them (--root .../rehearsal_fabric_pfx --trace 1)
    rooted = files.load_benchmark(FABRIC_PFX)["per_layer"]
    for name in GAUGES:
        assert CELL not in listed[name]["workloads"], name
        assert [m["workloads"] for m in rooted if m["name"] == name][-1] == [
            SMALL, CELL], name


def test_the_overlay_is_fabric10k_flaps_but_for_the_period():
    _, pfx = cell_of(CELL, files.ROOT)
    _, one = cell_of("fabric10k.flap", files.ROOT)
    assert pfx["strata"] == one["strata"] and len(pfx["strata"]) == 12
    assert pfx["warmup_bursts"] == one["warmup_bursts"]
    assert set(pfx["warmup_bursts"]) == set(range(1, 13))
    for key in ("kind", "op", "warmup_rotations"):
        assert pfx[key] == one[key], key
    assert pfx["op"] == "updown"
    assert pfx["period_ms"] % 10 == 0 and 50 <= pfx["period_ms"] <= 1500
    assert set(pfx) - {"doc", "period_ms"} == set(one) - {"doc", "period_ms"}


@pytest.fixture(scope="module")
def full_size():
    """The committed cell's LSDB and plan, built once (319,232 prefix
    databases: several seconds)."""
    return plan_of(CELL, 2**31 + 36, files.ROOT)


def test_the_fabric_holds_319232_prefixes_and_the_events_spare_the_vantage(
        full_size):
    config, traffic, lsdb, plan = full_size
    me = config["vantage"]
    assert len(lsdb.adj_dbs) == config["nodes"] == 9976
    assert len(lsdb.prefix_dbs) == 9976 * PER_NODE == 319232
    assert len(lsdb.adj_dbs) + len(lsdb.prefix_dbs) == config["keys"]
    owners: dict = {}
    for db in lsdb.prefix_dbs:
        (entry,) = db.prefix_entries
        owners.setdefault(db.this_node_name, set()).add(entry.prefix)
    assert len(owners) == 9976
    assert all(len(p) == PER_NODE for p in owners.values())
    assert sum(len(p) for p in owners.values()) == len(
        set().union(*owners.values()))
    held: dict = {}
    for i in range(48):
        event = next(plan)
        (op, a, b), = event["ops"]
        assert me[:6] not in (a[:6], b[:6]), event
        assert {a[7:10], b[7:10]} == {"rsw", "fsw"} and a[:6] == b[:6]
        if op == "down":
            held[event["stratum"]] = (a, b)
        else:
            assert op == "up" and held.pop(event["stratum"]) == (a, b)
        lsdb.apply(event["ops"])


def test_no_key_comes_within_the_dampers_reach():
    """test_wan50k.py's check of the accepted cells, on this one: at the
    cell's own period, over 60 s, each adj: key's figure of merit stays
    under a third of the suppress threshold."""
    test_wan50k.test_no_key_comes_within_the_dampers_reach(CELL)


# -- the plan at rehearsal size ----------------------------------------------


def test_every_event_moves_exactly_32_routes_of_the_vantage():
    config, traffic, lsdb, plan = plan_of(SMALL, 2**31 + 5, FABRIC_PFX)
    me = config["vantage"]
    assert config["generator"]["kwargs"]["prefixes_per_node"] == PER_NODE
    small, _ = cell_of("fabric-small.flap")
    kwargs = dict(config["generator"]["kwargs"])
    del kwargs["prefixes_per_node"]
    assert kwargs == small["generator"]["kwargs"]
    owners = {
        db.prefix_entries[0].prefix: db.this_node_name
        for db in lsdb.prefix_dbs
    }
    assert len(owners) == len(lsdb.adj_dbs) * PER_NODE == 3072
    before = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, True)
    assert len(before) == 3072 - PER_NODE
    width = max(len(route[1]) for route in before.values())
    assert width == config["generator"]["kwargs"]["planes"]
    for i in range(40):
        event = next(plan)
        (op, a, b), = event["ops"]
        rsw = a if "rsw" in a else b
        assert len(lsdb.apply(event["ops"])) == 2
        after = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, True)
        assert after.keys() == before.keys()
        moved = {p for p in after if after[p] != before[p]}
        assert len(moved) == PER_NODE and {owners[p] for p in moved} == {rsw}
        hops = {len(after[p][1]) for p in moved}
        assert hops == ({width - 1} if op == "down" else {width}), (i, event)
        assert not any(after[p][2] for p in moved)  # unit metrics: no LFA
        before = after


# -- a whole run, rehearsed --------------------------------------------------


def test_a_rehearsed_run_reports_the_prefix_rows_and_is_correct(capsys):
    result, lines = rehearse(capsys, run.main, [
        "--workload", SMALL, "--seed", str(2**31 + 36),
        "--seconds", "2", "--trace", "1", "--root", FABRIC_PFX,
    ])
    assert result["correct"] is True and result["failed"] == 0
    checks = [l for l in lines if "routes_compared" in l]
    assert len(checks) in (2, 3) and all(
        c["routes_compared"] == 3072 - PER_NODE and c["differing"] == 0
        for c in checks
    ), lines
    got = result["metrics"]
    rows = got["prefix_rows"]["value"]
    assert got["prefix_rows"]["unit"] == "rows" and rows == 4096.0
    assert int(rows) & (int(rows) - 1) == 0
    assert got["prefix_row_fill"] == {"value": 100.0 * 3072 / 4096, "unit": "%"}
    # fabric-small's mirror, lanes and LFA: part of the edges in the residual
    # (all of them at full size), the vantage's 6 uplinks in 8 lanes, LFA on
    # and (unit metrics) no alternate
    assert 0.0 < got["residual_edge_share"]["value"] <= 100.0
    assert 0.0 < got["residual_fill"]["value"] <= 100.0
    assert got["lfa_backup_share"]["value"] == 0.0
    assert got["spf_lanes"]["value"] == 8.0
    assert got["spf_lane_fill"]["value"] == 75.0
    assert got["compiles_in_window"]["value"] == 0.0
    assert got["events_per_epoch"]["value"] < 1.3
    names = {m["name"] for m in files.load_benchmark()["per_layer"]
             if "workloads" not in m}  # those every cell owes
    assert names - set(got) <= {"device_busy_ms_per_epoch"}
    overload = next(l for l in lines if "overload" in l)["overload"]
    assert overload["plan_keys_damped"] == []
