"""The WAN as an aggregation router sees it (benchmark configuration
wan50k_agg): a vantage at the 52-link cap, so 52 SPF sources in 64 lanes
of the device's [lanes, nodes] distance plane, where every other test of
the TPU solver runs at 4 to 32. 3 regions x (2 + 4 + 80) = 258 routers:
80 access routers dual-homed onto 4 aggregation routers fill the widest
of them to their 48-access cap (+ 2 ring neighbours + 2 cores).

Against the oracle: metric, next-hop set (several links wide: equal-RTT
access links) and loop-free alternate (the tie-break among dozens of
candidates) of every route, on the full solve and on incremental ones;
the plain reference of the benchmark against the same oracle from the
same vantage; the two gauges that say how many lanes the plane has and
how many hold a link; the benchmark's readers of them.
"""

import importlib.util
import math
import os
import random

import pytest

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.ops.edgeplan import _next_pow2
from openr_tpu.runtime.counters import counters
from tests.test_incremental_spf import _Churn
from tests.test_tpu_solver import assert_rib_equal
from tests.test_wan_rtt_solver import _backups, _metric

SIZE = {"regions": 3, "cores": 2, "aggs": 4, "access": 80}
SEEDS = [1, 2, 3]
CAP = 52  # 48 access links, 2 ring neighbours, 2 cores
BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)


def _wan(seed: int):
    adj_dbs, prefix_dbs = topologies.wan_rtt(**SIZE, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    return adj_dbs, prefix_dbs, states, ps


def _widest(adj_dbs, tier: str) -> str:
    """The router of a tier with most links; the first by name of those."""
    return max(
        (db for db in adj_dbs if f"-{tier}" in db.this_node_name),
        key=lambda db: (len(db.adjacencies), db.this_node_name[::-1]),
    ).this_node_name


def _bench_module(*parts: str):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3], path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("mode", ["full", "incremental"])
@pytest.mark.parametrize("seed", SEEDS)
def test_64_lanes_match_the_oracle(seed, mode):
    adj_dbs, _, states, ps = _wan(seed)
    me = _widest(adj_dbs, "agg")
    links = states["0"].ordered_links_from_node(me)
    assert len(links) == CAP
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(me, enable_lfa=True)
    tpu = TpuSpfSolver(
        me, enable_lfa=True, incremental_spf=(mode == "incremental")
    )
    assert tpu.small_graph_nodes == 0
    seen = {"incremental": 0, "wide": 0, "backups": 0}

    def solve(ctx: str):
        want = cpu.build_route_db(me, states, ps)
        got = tpu.build_route_db(me, states, ps)
        assert_rib_equal(want, got, f"{ctx} ({mode})")
        assert len(want.unicast_routes) == len(adj_dbs) - 1, ctx
        stats = tpu.last_device_stats
        assert (stats["spf_lanes"], stats["spf_sources"]) == (64, len(
            states["0"].ordered_links_from_node(me)
        )), ctx
        seen["incremental"] += bool(
            stats.get("incremental") and not stats.get("fell_back")
        )
        seen["wide"] = max(seen["wide"], max(
            len(r.nexthops) for r in want.unicast_routes.values()
        ))
        seen["backups"] = max(seen["backups"], _backups(want))

    solve("the first, full solve")
    assert not tpu.last_device_stats.get("incremental")
    rng = random.Random(f"{seed}/agg64")
    edges = [e for e in churn.edges() if me not in e]
    mine = [e for e in churn.edges() if me in e]
    # RTT steps and restores anywhere, then on two of the vantage's own
    # links: an access link (one lane's seed moves) and its last by name
    for step, (u, v) in enumerate(rng.sample(edges, 4) + [mine[0], mine[-1]]):
        m = _metric(churn, u, v)
        stepped = max(math.ceil(m * rng.uniform(1.5, 3.0)), m + 1)
        churn.set_metric(u, v, stepped)
        solve(f"step {step}: {u} - {v} metric {m} -> {stepped}")
        churn.set_metric(u, v, m)
        solve(f"step {step}: {u} - {v} metric back to {m}")
    # links down and up again, one of them the vantage's own: 51 sources
    # in the same 64 lanes while it is away
    for step, (u, v) in enumerate(rng.sample(edges, 2) + mine[7:8]):
        saved = churn.dbs[u], churn.dbs[v]
        churn.link_down(u, v)
        solve(f"down {step}: {u} - {v}")
        churn.link_up(u, v, *saved)
        solve(f"up {step}: {u} - {v}")
    # two steps held at once, given back in the other order
    (a, b), (c, d) = rng.sample(edges, 2)
    ma, mc = _metric(churn, a, b), _metric(churn, c, d)
    churn.set_metric(a, b, 3 * ma)
    churn.set_metric(c, d, 2 * mc + 1)
    solve("two links stepped in one epoch")
    churn.set_metric(a, b, ma)
    solve("the first given back")
    churn.set_metric(c, d, mc)
    solve("the second given back")
    # what 64 lanes are there for: routes several links wide, and an
    # alternate to choose among many on most of them
    assert seen["wide"] >= 2, seen
    assert seen["backups"] >= len(adj_dbs) // 2, seen
    if mode == "incremental":
        assert seen["incremental"] >= 10, seen
    else:
        assert seen["incremental"] == 0, seen


@pytest.mark.parametrize("seed", SEEDS)
def test_the_plain_reference_matches_the_oracle_from_52_links(seed):
    """benchmark/reference.py, the comparison that decides the benchmark's
    `correct`, from the vantage the 64-lane cell looks from: next-hop sets
    several wide and RFC 5286's tie-break (the link that sorts first)
    among dozens of candidate links of equal alternate cost."""
    reference = _bench_module("reference.py")
    adj_dbs, prefix_dbs, states, ps = _wan(seed)
    me = _widest(adj_dbs, "agg")
    db = SpfSolver(me, enable_lfa=True).build_route_db(me, states, ps)
    got = reference.programmed(dict(db.unicast_routes))
    want = reference.routes(adj_dbs, prefix_dbs, me, True)
    check = reference.compare(got, want)
    assert (check["missing"], check["extra"], check["differing"]) == (
        0, 0, 0), check
    assert len(want) == len(adj_dbs) - 1
    assert max(len(route[1]) for route in want.values()) >= 2
    backed = [p for p, route in want.items() if route[2]]
    assert len(backed) * 2 >= len(want)
    # links of equal metric did compete to be the alternate
    metrics = [l.metric_from_node(me) for l in
               states["0"].ordered_links_from_node(me)]
    assert len(metrics) == CAP and len(set(metrics)) < CAP // 2
    # a backup moved to the next link, or dropped, is seen
    cost, hops, backup = got[backed[0]]
    assert reference.compare(
        {**got, backed[0]: (cost, hops, frozenset())}, want
    )["differing"] == 1


@pytest.mark.parametrize("tier", ["acc", "agg", "core"])
def test_the_lane_gauges_say_links_and_their_power_of_two(tier):
    """decision.tpu.spf_sources / spf_lanes, last_device_stats and the
    tpu.sync and tpu.dispatch spans: one lane a link of the vantage,
    padded to a power of two of at least 4, and the same over incremental
    epochs (a change elsewhere moves no lane)."""
    adj_dbs, _, states, ps = _wan(2)
    me = _widest(adj_dbs, tier)
    n_links = len(states["0"].ordered_links_from_node(me))
    assert n_links == {"acc": 2, "agg": CAP, "core": 7}[tier]
    lanes = _next_pow2(n_links, 4)
    assert lanes == {"acc": 4, "agg": 64, "core": 8}[tier]
    solver = TpuSpfSolver(me, enable_lfa=True, incremental_spf=True)
    churn = _Churn(adj_dbs, states)

    def check(ctx: str):
        stats = solver.last_device_stats
        assert (stats["spf_sources"], stats["spf_lanes"]) == (
            n_links, lanes), ctx
        assert counters.get_counter("decision.tpu.spf_sources") == n_links
        assert counters.get_counter("decision.tpu.spf_lanes") == lanes
        spans = {name: attrs for name, _, _, _, attrs
                 in solver.last_timing["spans"]}
        assert spans["tpu.sync"]["spf_sources"] == n_links, ctx
        assert spans["tpu.sync"]["spf_lanes"] == lanes, ctx
        assert spans["tpu.dispatch"]["lanes"] == lanes, ctx
        assert f"d={lanes}" in spans["tpu.dispatch"]["kernel"], ctx

    solver.build_route_db(me, states, ps)
    check("the full solve")
    warm = 0
    for u, v in [e for e in churn.edges() if me not in e][:3]:
        m = _metric(churn, u, v)
        churn.set_metric(u, v, 2 * m + 1)
        solver.build_route_db(me, states, ps)
        check(f"{u} - {v} stepped")
        warm += bool(solver.last_device_stats.get("incremental"))
        churn.set_metric(u, v, m)
        solver.build_route_db(me, states, ps)
        check(f"{u} - {v} given back")
        warm += bool(solver.last_device_stats.get("incremental"))
    assert warm == 6


def test_the_lane_readers_read_the_gauges_or_nothing():
    """benchmark/layer_metrics/spf_lanes.py and spf_lane_fill.py: None
    with no window observed, None where the program has no gauge (the
    parent of the PR that added them), else lanes and sources / lanes."""
    readers = [
        _bench_module("layer_metrics", f"{name}.py")
        for name in ("spf_lanes", "spf_lane_fill")
    ]
    keys = ("decision.tpu.spf_sources", "decision.tpu.spf_lanes")
    saved = {key: counters.get_counter(key) for key in keys}
    window = {"window.epochs": [3]}
    try:
        counters.set_counter(keys[0], 52)
        counters.set_counter(keys[1], 64)
        assert [r.read({}) for r in readers] == [None, None]
        assert [r.read({"window.epochs": []}) for r in readers] == [None] * 2
        assert readers[0].read(window) == 64
        assert readers[1].read(window) == pytest.approx(81.25)
        with counters._lock:
            for key in keys:
                del counters._counters[key]
        assert [r.read(window) for r in readers] == [None, None]
        # lanes without sources: no share to speak of; sources alone: none
        counters.set_counter(keys[1], 64)
        assert readers[1].read(window) is None
        with counters._lock:
            del counters._counters[keys[1]]
        counters.set_counter(keys[0], 52)
        assert [r.read(window) for r in readers] == [None, None]
    finally:
        with counters._lock:
            for key, value in saved.items():
                counters._counters.pop(key, None)
                if value is not None:
                    counters._counters[key] = value
