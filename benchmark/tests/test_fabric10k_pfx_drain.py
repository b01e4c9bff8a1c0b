"""fabric10k_pfx_drain, the 10,000-switch fabric under device maintenance,
and its cell fabric10k_pfx_drain.node, checked on the CPU: the
configuration against fabric10k_pfx's (the same network, value for value),
the committed overlay's strata against fabric10k_pfx.flap's, the plan at
full size (fabric switches of pods 001-172 alone, none twice in a window,
one out at a time) and at rehearsal size
(rehearsal_fabric_pfx_drain/: fabric-small-pfx-drain, 96 switches, 3,072
prefixes in 4,096 rows) where every drain takes one next hop from the
routes behind the switch and from no other, the damper's reach, the
model's replay, and rehearsed runs: the cell correct with one flip and one
whole put an epoch, the controls not correct."""

from __future__ import annotations

import os
import re

import pytest

import control
import files
import harness
import run
from test_fabric10k_pfxchurn import cell_of, plan_of
from test_harness import rehearse

ROOT = os.path.join(files.ROOT, "rehearsal_fabric_pfx_drain")
CELL = "fabric10k_pfx_drain.node"
SMALL = "fabric-small-pfx-drain.node"
PER_NODE = 32
METRICS = {
    "drain_pack_ms": ("ms", "lower", "program_span", "solver host side"),
    "mbuf_put_mb_per_epoch": (
        "MB/epoch", "lower", "program_counter", "solver host side"),
    "overload_flips_per_epoch": (
        "flips/epoch", "lower", "program_counter", "Decision host"),
}
FSW = re.compile(r"^pod(\d{3})-fsw(\d{2})$")


# -- the configuration and the cell as committed -----------------------------


def test_the_configuration_is_fabric10k_pfxs_network_value_for_value():
    drain = files.load_config("fabric10k_pfx_drain")
    pfx = files.load_config("fabric10k_pfx")
    for key in ("generator", "vantage", "solver_backend", "decision_config",
                "nodes", "keys", "chips", "reduced"):
        assert drain[key] == pfx[key], key
    assert drain["reduced"] == [] and drain["chips"] == 1
    assert drain["decision_config"] == {"enable_lfa": True}
    assert drain["lsdb_module"] == drain["reference_module"] == "node_drain"
    for key in ("link_metric", "advertisers", "decision_config", "planes",
                "pods"):
        assert drain["assumed"][key] == pfx["assumed"][key], key
    for key in ("prefixes_per_node", "vantage", "one_at_a_time",
                "which_switches", "hard_drain"):
        assert drain["assumed"][key], key
    assert drain["assumed"]["prefixes_per_node"].startswith("32, ")
    assert "drained switch and all" in drain["guarantees"][1]
    assert "319,200 routes" in drain["guarantees"][1]
    assert "no host-computed route" in drain["guarantees"][2]
    benchmark = files.load_benchmark()
    # found by name, not by place: a later PR appends after them
    (entry,) = [c for c in benchmark["configs"]
                if c["name"] == "fabric10k_pfx_drain"]
    assert entry["source"] == drain["source"] and len(entry["source"]) <= 200
    assert "LinkMonitor.h:158-193" in entry["source"]
    sources = [c["source"] for c in benchmark["configs"]]
    assert len(set(sources)) == len(sources)
    assert entry["file"] == "benchmark/configs/fabric10k_pfx_drain.json"
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    cell = run.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fabric10k_pfx_drain", "node-drain", 1)
    # no figure the chip gave: a `why` goes stale where it quotes one
    assert 0 < len(cell["why"]) <= 200 and not re.search(r"\d", cell["why"])
    assert sum(c["config"] == "fabric10k_pfx_drain"
               for c in benchmark["workloads"]) == 1
    # its three metrics, each for this cell alone
    mine = [m for m in benchmark["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    for metric in mine:
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


def test_the_overlay_has_fabric10k_pfx_flaps_strata_and_its_own_pacing():
    _, _, drain = cell_of(CELL, files.ROOT)
    _, _, flap = cell_of("fabric10k_pfx.flap", files.ROOT)
    assert drain["strata"] == flap["strata"] and len(drain["strata"]) == 12
    assert drain["kind"] == "node_drain" and drain["restore_after"] == 0.5
    # 400 ms, or 1.25 x the shortest clean period of the sweep, and never
    # under the siblings' 300
    assert drain["period_ms"] % 10 == 0 and 300 <= drain["period_ms"] <= 1500
    assert "sweep" in drain["doc"]
    assert drain["warmup_bursts"] == [2] and drain["warmup_rotations"] == 2
    kind = harness.load_kind(drain["kind"])
    assert kind.rotation_events(drain) == 12


@pytest.fixture(scope="module")
def full_size():
    """The committed cell's LSDB and plan, built once (319,232 prefix
    databases: several seconds)."""
    return plan_of(CELL, 2**31 + 43, files.ROOT)


def test_a_window_at_full_size_drains_fabric_switches_of_other_pods(
    full_size,
):
    config, traffic, lsdb, kind, plan = full_size
    assert len(lsdb.adj_dbs) == config["nodes"] == 9976
    names = [s["name"] for s in traffic["strata"]]
    pools = [kind.switches(lsdb, spec) for spec in traffic["strata"]]
    assert sum(map(len, pools)) == 172 * 8 == 1376
    assert len(set().union(*pools)) == 1376  # no switch in two strata
    for spec, pool in zip(traffic["strata"], pools):
        assert all(FSW.match(n) and not n.startswith("pod000") for n in pool)
        lo, hi = (int(x) for x in spec["name"][len("pods"):].split("-"))
        assert {int(FSW.match(n).group(1)) for n in pool} == set(
            range(lo, hi + 1))
    # a window and its warm-up: 45 s and two rotations and a burst
    slots = 45_000 // traffic["period_ms"] + 2 * 12 + 2
    drawn = []
    for i in range(slots):
        drain, back = next(plan), next(plan)
        (op, node), = drain["ops"]
        assert op == drain["class"] == "drain" and drain.get("timed", True)
        assert drain["stratum"] == names[i % 12] and node in pools[i % 12]
        assert back == {
            "ops": [("undrain", node)], "class": "undrain",
            "stratum": drain["stratum"], "timed": False, "after": 0.5,
        }
        assert lsdb.apply(drain["ops"]) == [node]
        assert lsdb.drained == {node}  # one out at a time
        db = lsdb.adj_dbs[lsdb.index[node]]
        assert db.is_overloaded and len(db.adjacencies) == 36 + 48
        pub = lsdb.publication([node])
        assert list(pub["0"]) == [f"adj:{node}"]
        assert lsdb.apply(back["ops"]) == [node] and not lsdb.drained
        drawn.append(node)
    # none twice: no adj: key comes back inside the damper's memory
    assert len(set(drawn)) == len(drawn) == slots
    assert lsdb.replay(len(lsdb.log)).adj_dbs == lsdb.adj_dbs
    assert lsdb.replay(1).drained == {drawn[0]}


def test_a_stratum_used_up_starts_again():
    _, traffic, lsdb, kind, plan = plan_of(SMALL, 11, ROOT)
    drawn = [next(plan)["ops"][0][1] for _ in range(2 * 4 * 24)][::2]
    by_stratum = [drawn[k::4] for k in range(4)]
    for pool in by_stratum:
        assert len(pool) == 24 and len(set(pool[:6])) == 6
        assert set(pool[:6]) == set(pool[6:12]) == set(pool[18:])


# -- the plan at rehearsal size ----------------------------------------------


@pytest.mark.parametrize("seed", [7, 2**31 + 43])
def test_every_drain_takes_one_next_hop_from_the_routes_behind(seed):
    config, traffic, lsdb, kind, plan = plan_of(SMALL, seed, ROOT)
    ref = files.reference_module(config, ROOT)
    me = config["vantage"]
    before = ref.routes(lsdb, me, config)
    assert len(before) == 3072 - PER_NODE and before.drained == ()
    for i in range(2 * kind.rotation_events(traffic)):
        drain, back = next(plan), next(plan)
        (_, node), = drain["ops"]
        pod = FSW.match(node).group(1)
        lsdb.apply(drain["ops"])
        out = ref.routes(lsdb.replay(len(lsdb.log)), me, config)
        assert out == ref.routes(lsdb, me, config)
        assert out.drained == (node,) and out.keys() == before.keys()
        moved = {p for p in out if out[p] != before[p]}
        assert len(moved) == 8 * PER_NODE, (i, node)
        for p in moved:
            (cost, hops, alt), (cost0, hops0, alt0) = out[p], before[p]
            assert cost == cost0 == 4 and not alt and not alt0
            assert len(hops0) == 6 and len(hops) == 5 and hops < hops0
        # the switch's own prefixes keep their route: the all-drained
        # fallback, at the distance to the switch
        own = [p for p, (cost, hops, _) in out.items()
               if cost == 3 and len(hops) == 1
               and next(iter(hops))[0] == f"pod000-fsw{node[-2:]}"]
        assert len(own) == 5 * PER_NODE
        lsdb.apply(back["ops"])
        assert ref.routes(lsdb, me, config) == before
    assert lsdb.replay(0).key_vals() == files.lsdb_module(
        config, ROOT).build(config).key_vals()


def test_no_adj_key_comes_within_the_dampers_reach():
    """Over 60 s at the rehearsal's period no adj: key's figure of merit
    comes within half the damper's suppress threshold, though at 24
    switches every one comes back every 24 events; at the full-size
    cell's period and pools none comes back at all
    (test_a_window_at_full_size_...)."""
    from openr_tpu.config import DecisionConfig

    cfg = DecisionConfig()
    for seed in (7, 2**31 + 42):
        config, traffic, lsdb, kind, plan = plan_of(SMALL, seed, ROOT)
        figure, worst, now = {}, 0.0, 0.0
        while now < 60:
            event = next(plan)
            pub = lsdb.publication(lsdb.apply(event["ops"]))
            now += traffic["period_ms"] / 1e3 / 2
            for key in pub["0"]:
                assert key.startswith("adj:")
                value, then = figure.get(key, (0.0, -60.0))
                value = value * 0.5 ** (
                    (now - then) / cfg.overload_damping_half_life_s
                ) + cfg.overload_damping_penalty
                figure[key] = (value, now)
                worst = max(worst, value)
        assert worst < cfg.overload_damping_suppress / 2, worst


# -- whole runs, rehearsed ---------------------------------------------------


def test_a_rehearsed_run_is_correct_with_one_flip_and_one_put_an_epoch(
    capsys,
):
    result, lines = rehearse(capsys, run.main, [
        "--workload", SMALL, "--seed", str(2**31 + 43),
        "--seconds", "4", "--trace", "1", "--root", ROOT,
    ])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 40  # 20 drains, 20 give-backs
    checks = [l for l in lines if "routes_compared" in l]
    assert len(checks) == 3 and all(
        c["routes_compared"] == 3040
        and c["missing"] == c["extra"] == c["differing"] == 0
        for c in checks
    ), checks
    # every line says which switches its LSDB held drained; the window's
    # end holds none
    assert all(len(c["drained"]) <= 1 for c in checks)
    assert checks[-1]["drained"] == []
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    assert all(hiding.values()), hiding
    counted = next(l for l in lines if "compiles_in_window" in l)
    assert counted["compiles_in_window"] == 0
    assert not counted["overload"]["plan_keys_damped"]
    assert set(counted["ack_ms_median_by_class"]) == {"drain", "undrain"}
    got = result["metrics"]
    assert got["events_per_epoch"]["value"] == 1.0
    assert got["overload_flips_per_epoch"] == {
        "value": 1.0, "unit": "flips/epoch"}
    # 6 planes x 4,096 rows x 2 advertisers x 4 bytes, once an epoch
    assert got["mbuf_put_mb_per_epoch"] == {
        "value": 6 * 4096 * 2 * 4 / 1e6, "unit": "MB/epoch"}
    assert 0.0 < got["drain_pack_ms"]["value"] < 50.0
    assert got["drain_pack_ms"]["value"] < got["solver_sync_ms"]["value"]
    assert got["relax_rounds"]["value"] >= 1.0
    assert got["prefix_rows"]["value"] == 4096.0
    assert got["prefix_row_fill"]["value"] == 75.0
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
    assert result["correct"] is False
    hiding = next(l for l in lines if "no_hiding" in l)["no_hiding"]
    checks = [l for l in lines if "routes_compared" in l]
    if which == "host_solver":
        # a second a solve on the CPU: where a drain and its give-back
        # come to share a solve epoch no route changes and neither is
        # acked, so `failed` may count some
        assert not hiding["tpu_solver"]
    else:
        assert result["failed"] == 0
        assert all(hiding.values())
        assert checks and all(c["differing"] >= 1 for c in checks)
