"""The WAN's configuration, its traffic kind and the two cells this file's
PR added (wan50k.flap, fabric10k.flap), checked on the CPU: the plans'
invariants at rehearsal size (rehearsal_wan/: wan-small, 240 routers) and
on the cells as committed, the new readers, the plain reference against
the repo's oracle where alternates exist, and a rehearsed run."""

from __future__ import annotations

import os
import re

import numpy as np
import pytest
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

import files
import harness
import lsdb as lsdb_mod
import metrics
import reference
import run
from test_harness import cell_of, plan_of, rehearse

WAN = os.path.join(files.ROOT, "rehearsal_wan")
NEW_CELLS = ["wan50k.flap", "fabric10k.flap"]


def _dist(lsdb, me: str) -> np.ndarray:
    """Distances from the vantage, by a Dijkstra that is neither the
    program's nor the traffic kind's."""
    index = lsdb.index
    rows, cols, w = [], [], []
    for db in lsdb.adj_dbs:
        for adj in db.adjacencies:
            rows.append(index[db.this_node_name])
            cols.append(index[adj.other_node_name])
            w.append(adj.metric)
    n = len(index)
    graph = csr_matrix((np.asarray(w, float), (rows, cols)), shape=(n, n))
    return dijkstra(graph, directed=True, indices=index[me])


# -- the plan at rehearsal size ----------------------------------------------


@pytest.mark.parametrize("seed", [11, 2**31 + 3])
def test_every_one_of_80_events_changes_a_route(seed):
    config, traffic, lsdb, plan = plan_of("wan-small.flap", seed, WAN)
    me = config["vantage"]
    assert traffic["vantage"] == me
    before = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, True)
    for i in range(80):
        event = next(plan)
        (op, a, b, metric), = event["ops"]
        assert op == "metric" and me not in (a, b)
        assert len(lsdb.apply(event["ops"])) == 2
        after = reference.routes(lsdb.adj_dbs, lsdb.prefix_dbs, me, True)
        assert after != before, f"event {i} ({event['class']}) moves no route"
        # the far end's own route is among those that moved
        moved = {p for p in after if after[p] != before.get(p)}
        owners = {
            db.prefix_entries[0].prefix: db.this_node_name
            for db in lsdb.prefix_dbs
        }
        assert {a, b} & {owners[p] for p in moved}, (i, a, b)
        before = after


def test_the_mix_is_the_same_whatever_the_seed():
    def mix(seed):
        _, _, lsdb, plan = plan_of("wan-small.flap", seed, WAN)
        return [
            (ev["class"], ev["stratum"], len(lsdb.apply(ev["ops"])))
            for ev in (next(plan) for _ in range(48))
        ]

    def drawn(seed):
        _, _, _, plan = plan_of("wan-small.flap", seed, WAN)
        return [next(plan)["ops"][0][1:] for _ in range(12)]

    first = mix(1)
    assert mix(2**31 + 12345) == first and mix(7) == first
    assert [c for c, _, _ in first[:12]] == ["change"] * 6 + ["restore"] * 6
    # farthest level first, restored in reverse
    assert [s.split("-")[0] for _, s, _ in first[:12]] == [
        "far", "far", "mid", "mid", "near", "near",
        "near", "near", "mid", "mid", "far", "far",
    ]
    assert drawn(1) != drawn(7)  # the seed does draw the link and factor
    assert drawn(7) == drawn(7)


def test_a_restore_gives_back_the_generators_database():
    _, traffic, lsdb, plan = plan_of("wan-small.flap", 3, WAN)
    kind = harness.load_kind(traffic["kind"])
    start = list(lsdb.adj_dbs)
    for cycle in range(3):
        for _ in range(kind.rotation_events(traffic)):
            lsdb.apply(next(plan)["ops"])
        assert lsdb.adj_dbs == start, cycle
    assert lsdb.replay(1).adj_dbs != start
    assert lsdb.replay(len(lsdb.log)).adj_dbs == start


def test_a_change_is_an_rtt_step_and_held_links_never_nest():
    config, traffic, lsdb, plan = plan_of("wan-small.flap", 5, WAN)
    kind = harness.load_kind(traffic["kind"])
    tree = kind.Tree(lsdb, config["vantage"])
    held: dict[tuple, set] = {}
    lo, hi = traffic["factor_range"]
    for _ in range(20 * kind.rotation_events(traffic)):
        event = next(plan)
        (_, a, b, metric), = event["ops"]
        u, v = tree.index[a], tree.index[b]
        base = tree.metric[u, v]
        if event["class"] == "restore":
            assert metric == base
            del held[a, b]
            continue
        assert tree.parents[v] == 1 and v in tree.children[u]
        assert base < metric <= max(int(np.ceil(base * hi)), base + 1)
        assert metric >= min(int(np.ceil(base * lo)), base + 1)
        below = tree.reach(v, 10**9)
        for (_, other), under in held.items():
            assert tree.index[other] not in below and v not in under
        held[a, b] = below
    assert not held


def test_a_stratum_with_too_few_candidates_is_an_error():
    config, traffic = cell_of("wan-small.flap", WAN)
    lsdb = lsdb_mod.build(config)
    kind = harness.load_kind(traffic["kind"])
    with pytest.raises(ValueError, match="widen its band"):
        next(kind.plan(lsdb, {**traffic, "min_candidates": 50}, 1))
    with pytest.raises(ValueError, match="fewer strata"):
        next(kind.plan(lsdb, {**traffic, "warmup_bursts": [7]}, 1))


# -- the cells as committed --------------------------------------------------


@pytest.mark.parametrize("name,root", [
    ("wan-small.flap", WAN), ("wan50k.flap", files.ROOT),
    ("fabric10k.flap", files.ROOT),
])
def test_the_warmup_bursts_change_every_count_of_links(name, root):
    """As test_harness.py's for lsdb100k.flap: after a whole cycle each
    burst lies inside one run of changes or of restores, a burst of n
    changes n links, and between them they make every count up to the
    number of strata (twelve in the cells as committed)."""
    _, traffic, lsdb, plan = plan_of(name, seed=8, root=root)
    kind = harness.load_kind(traffic["kind"])
    cycle = kind.rotation_events(traffic)
    assert cycle == (12 if root == WAN else 24)
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


@pytest.mark.parametrize("name", NEW_CELLS)
def test_no_key_comes_within_the_dampers_reach(name):
    """The cell as committed, at its own size and period, over 60 s: each
    adj: key's figure of merit (penalty 1 a change, half-life 10 s) stays
    under a third of the suppress threshold."""
    from openr_tpu.config import DecisionConfig

    cfg = DecisionConfig()
    config, traffic, lsdb, plan = plan_of(name, seed=5, root=files.ROOT)
    assert len(lsdb.adj_dbs) == config["nodes"]
    period_s = traffic["period_ms"] / 1e3
    figure: dict[str, tuple[float, float]] = {}
    worst, now = 0.0, 0.0
    while now < 60:
        for node in lsdb.apply(next(plan)["ops"]):
            value, then = figure.get(node, (1.0, -60.0))  # the load's write
            value = value * 0.5 ** (
                (now - then) / cfg.overload_damping_half_life_s
            ) + cfg.overload_damping_penalty
            figure[node] = (value, now)
            worst = max(worst, value)
        now += period_s
    assert worst < cfg.overload_damping_suppress / 3, worst


def test_fabric10k_flap_has_twelve_strata_that_cut_pods_1_to_172():
    config, traffic, lsdb, plan = plan_of("fabric10k.flap", 2, files.ROOT)
    assert traffic["op"] == "updown" and len(traffic["strata"]) == 12
    pods = [f"{i:03d}" for i in range(173)]
    seen: list[str] = []
    for spec in traffic["strata"]:
        rsw, fsw = (re.compile(e) for e in spec["between"])
        mine = [p for p in pods if rsw.search(f"pod{p}-rsw07")]
        assert mine == [p for p in pods if fsw.search(f"pod{p}-fsw03")]
        assert len(mine) in (14, 15) and mine == sorted(mine)
        assert spec["name"] == f"pods{mine[0]}-{mine[-1]}"
        seen += mine
    assert seen == pods[1:]  # consecutive, whole, without the vantage's
    vantage_pod = config["vantage"][:6]
    for i in range(48):
        event = next(plan)
        (op, a, b), = event["ops"]
        assert op == ("down" if i % 24 < 12 else "up")
        assert "-rsw" in a and "-fsw" in b and a[:6] == b[:6] != vantage_pod


def test_wan50k_is_at_its_stated_size_and_every_event_moves_a_route():
    config, traffic, lsdb, plan = plan_of("wan50k.flap", 2**31 + 9,
                                          files.ROOT)
    me = config["vantage"]
    assert traffic["vantage"] == me and "-acc" in me
    assert len(lsdb.adj_dbs) == config["nodes"] == 50000
    assert config["decision_config"] == {"enable_lfa": True}
    degree = [len(db.adjacencies) for db in lsdb.adj_dbs]
    assert min(degree) == 2 and 48 < max(degree) <= 64
    uplinks = [a.metric for a in lsdb.adj_dbs[lsdb.index[me]].adjacencies]
    assert len(uplinks) == 2 and uplinks[0] != uplinks[1]
    spread = {a.metric for db in lsdb.adj_dbs for a in db.adjacencies}
    assert min(spread) == 1 and max(spread) > 300
    kind = harness.load_kind(traffic["kind"])
    _, cands = kind.strata_candidates(lsdb, traffic)
    assert len(cands) == 12 and min(len(c) for c in cands) >= 8
    before = _dist(lsdb, me)
    for i in range(36):
        event = next(plan)
        assert me not in event["ops"][0][1:3]
        lsdb.apply(event["ops"])
        after = _dist(lsdb, me)
        moved = int((after != before).sum())
        lo, hi = next(
            s["desc"] for level in traffic["strata"] for s in level
            if s["name"] == event["stratum"]
        )
        # the far end's distance moves; at most desc(v) distances do when
        # a link is stepped with nothing nested under it
        assert 1 <= moved, (i, event)
        if i < 12:
            assert moved <= hi, (i, event, moved)
        before = after


# -- the readers -------------------------------------------------------------


def test_the_new_readers_read_the_programs_gauges_or_nothing():
    from openr_tpu.runtime.counters import counters

    names = ("residual_edge_share", "residual_fill", "lfa_backup_share")
    gauges = {
        "decision.tpu.residual_edges": 300, "decision.tpu.shift_edges": 100,
        "decision.tpu.residual_r_cap": 64, "decision.tpu.residual_k_cap": 8,
        "decision.lfa.routes": 50, "decision.lfa.routes_with_backup": 20,
    }
    saved = {key: counters.get_counter(key) for key in gauges}
    window = {"window.epochs": [3]}

    def read(name, series):
        return metrics.read_metric(name, "layer_metrics", series)

    try:
        for key, value in gauges.items():
            counters.set_counter(key, value)
        assert [read(n, {}) for n in names] == [None] * 3  # no window
        assert read("residual_edge_share", window) == pytest.approx(75.0)
        assert read("residual_fill", window) == pytest.approx(
            100 * 300 / 512
        )
        assert read("lfa_backup_share", window) == pytest.approx(40.0)
        # a program that lacks the gauges (the parent's): nothing, no error
        with counters._lock:
            for key in gauges:
                del counters._counters[key]
        assert [read(n, window) for n in names] == [None] * 3
        # no residual at all (a grid): no fill to speak of
        counters.set_counter("decision.tpu.residual_edges", 0)
        counters.set_counter("decision.tpu.residual_r_cap", 0)
        counters.set_counter("decision.tpu.residual_k_cap", 0)
        assert read("residual_fill", window) is None
    finally:
        with counters._lock:
            for key, value in saved.items():
                counters._counters.pop(key, None)
                if value is not None:
                    counters._counters[key] = value
    benchmark = files.load_benchmark()
    listed = {m["name"]: m for m in benchmark["per_layer"]}
    assert listed["lfa_backup_share"]["workloads"] == ["wan50k.flap"]
    for name in names[:2]:
        assert listed[name]["workloads"] == NEW_CELLS
    assert all(listed[n]["moves"] == "churn_to_ack_p50_ms" for n in names)


# -- the plain reference where alternates exist ------------------------------


@pytest.mark.parametrize("me", ["r03-acc0000", "r01-agg02", "r04-core1"])
def test_reference_agrees_with_the_repos_oracle_on_the_wan_with_lfa(me):
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.models import topologies

    config, _ = cell_of("wan-small.flap", WAN)
    lsdb = lsdb_mod.build(config)
    lsdb.apply([
        ("metric", "r00-agg01", "r00-core0", 40),
        ("down", "r02-acc0003", lsdb.neighbors("r02-acc0003")[0]),
        ("metric", "r03-acc0000", lsdb.neighbors("r03-acc0000")[1], 3),
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
    backups = sum(1 for route in want.values() if route[2])
    assert backups * 4 >= len(want) == len(lsdb.adj_dbs) - 1
    # and a backup that is dropped, or moved to another link, is seen
    prefix = next(p for p, route in want.items() if route[2])
    cost, hops, _ = got[prefix]
    assert reference.compare(
        {**got, prefix: (cost, hops, frozenset())}, want
    )["differing"] == 1


# -- a whole run, rehearsed --------------------------------------------------


def test_a_rehearsed_run_of_the_wan_is_correct(capsys):
    result, lines = rehearse(capsys, run.main, [
        "--workload", "wan-small.flap", "--seed", str(2**31 + 7),
        "--seconds", "2", "--trace", "1", "--root", WAN,
    ])
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["events_per_epoch"]["value"] < 1.2
    checks = [l for l in lines if "routes_compared" in l]
    assert len(checks) in (2, 3) and all(
        c["routes_compared"] == 239 and c["differing"] == 0 for c in checks
    ), lines
    got = result["metrics"]
    assert got["lfa_backup_share"]["value"] > 25.0
    assert 0.0 < got["residual_edge_share"]["value"] <= 100.0
    assert 0.0 < got["residual_fill"]["value"] <= 100.0
    assert got["residual_fill"]["unit"] == "%"
    overload = next(l for l in lines if "overload" in l)["overload"]
    assert overload["plan_keys_damped"] == []
