"""The WAN with RTT metrics (topologies.wan_rtt, benchmark configuration
wan50k) through the TPU solver with LFA on, against the oracle: metric,
next-hop set and loop-free alternate of every route, on the full solve and
on incremental ones, after seeded sequences of RTT steps, restores, link
downs and ups. 4 regions x (2 + 6 + 52) = 240 routers; the backend is the
TPU solver itself (no small-graph delegation: Decision's
auto_small_graph_nodes would hand 240 nodes to the host), from an access,
an aggregation and a core router.

What the irregular, weighted graph works that the grid and the fabric do
not: every link has a metric of its own (the relaxation's weights, the
bucketed kernel's light/heavy split, cones seeded by a weighted increase
and decrease), most edges are index-irregular (the residual ELL), and the
second uplink of a dual-homed router is a loop-free alternate (the LFA
columns of the delta pull carry real slots).
"""

import math
import random

import pytest

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.runtime.counters import counters
from tests.test_incremental_spf import _Churn
from tests.test_tpu_solver import assert_rib_equal

SIZE = {"regions": 4, "cores": 2, "aggs": 6, "access": 52}
VANTAGES = {"access": "r01-acc0000", "agg": "r02-agg03", "core": "r00-core1"}
SEEDS = [1, 2, 3]


def _wan(seed: int):
    adj_dbs, prefix_dbs = topologies.wan_rtt(**SIZE, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    return adj_dbs, states, ps


def _metric(churn: _Churn, u: str, v: str) -> int:
    return next(
        a.metric for a in churn.dbs[u].adjacencies if a.other_node_name == v
    )


def _backups(route_db) -> int:
    return sum(1 for r in route_db.unicast_routes.values() if r.lfa_nexthops)


@pytest.mark.parametrize("kind", sorted(VANTAGES))
@pytest.mark.parametrize("seed", SEEDS)
def test_full_and_incremental_solves_match_the_oracle(seed, kind):
    me = VANTAGES[kind]
    adj_dbs, states, ps = _wan(seed)
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(me, enable_lfa=True)
    full = TpuSpfSolver(me, enable_lfa=True, incremental_spf=False)
    incr = TpuSpfSolver(me, enable_lfa=True, incremental_spf=True)
    assert full.small_graph_nodes == incr.small_graph_nodes == 0
    seen = {"incremental": 0, "backups": [], "kernels": set()}

    def solve(ctx: str):
        want = cpu.build_route_db(me, states, ps)
        cold = full.build_route_db(me, states, ps)
        warm = incr.build_route_db(me, states, ps)
        # RibUnicastEntry equality: prefix, igp_cost, nexthops (neighbour,
        # interface, metric) and lfa_nexthops
        assert_rib_equal(want, cold, f"{ctx}: cold against the oracle")
        assert_rib_equal(want, warm, f"{ctx}: warm against the oracle")
        assert len(want.unicast_routes) == len(adj_dbs) - 1, ctx
        stats = incr.last_device_stats
        seen["incremental"] += bool(
            stats.get("incremental") and not stats.get("fell_back")
        )
        seen["kernels"].add(stats["spf_kernel"])
        seen["backups"].append(_backups(want))

    solve("the first, full solve")
    assert not incr.last_device_stats.get("incremental")
    rng = random.Random(f"{seed}/{kind}")
    edges = [e for e in churn.edges() if me not in e]
    mine = [e for e in churn.edges() if me in e]
    # RTT steps and restores anywhere, then on the vantage's own link
    for step, (u, v) in enumerate(rng.sample(edges, 4) + mine[:1]):
        m = _metric(churn, u, v)
        stepped = max(math.ceil(m * rng.uniform(1.5, 3.0)), m + 1)
        churn.set_metric(u, v, stepped)
        solve(f"step {step}: {u} - {v} metric {m} -> {stepped}")
        churn.set_metric(u, v, m)
        solve(f"step {step}: {u} - {v} metric back to {m}")
    # links down and up again, one of them the vantage's own
    for step, (u, v) in enumerate(rng.sample(edges, 2) + mine[-1:]):
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
    # the warm path did run warm, and the LFA comparison had something to
    # compare: at least one route in four carries a backup
    assert seen["incremental"] >= 8, seen
    assert seen["backups"][0] >= len(adj_dbs) // 4, seen["backups"]


@pytest.mark.parametrize("kind", sorted(VANTAGES))
def test_the_comparison_sees_dropped_lfa_columns_and_unit_weights(kind):
    """The oracle comparison above is not vacuous: a solve without the LFA
    pass, or one that reads every weight as 1, differs from the oracle."""
    me = VANTAGES[kind]
    adj_dbs, states, ps = _wan(5)
    want = SpfSolver(me, enable_lfa=True).build_route_db(me, states, ps)
    assert _backups(want) >= len(adj_dbs) // 4
    no_lfa = TpuSpfSolver(me, enable_lfa=False).build_route_db(
        me, states, ps
    )
    with pytest.raises(AssertionError):
        assert_rib_equal(want, no_lfa, "LFA columns dropped")
    # the same routes but for the backups
    assert {
        p: (r.igp_cost, r.nexthops) for p, r in want.unicast_routes.items()
    } == {
        p: (r.igp_cost, r.nexthops)
        for p, r in no_lfa.unicast_routes.items()
    }
    unit_dbs = [
        type(db)(
            this_node_name=db.this_node_name, area=db.area,
            node_label=db.node_label,
            adjacencies=tuple(
                type(a)(**{**a.__dict__, "metric": 1}) for a in db.adjacencies
            ),
        )
        for db in adj_dbs
    ]
    unit_states, unit_ps = topologies.build_states(
        unit_dbs, topologies.wan_rtt(**SIZE, seed=5)[1]
    )
    unit = TpuSpfSolver(me, enable_lfa=True).build_route_db(
        me, unit_states, unit_ps
    )
    with pytest.raises(AssertionError):
        assert_rib_equal(want, unit, "weights read as 1")
    costs = [r.igp_cost for r in want.unicast_routes.values()]
    hops = [r.igp_cost for r in unit.unicast_routes.values()]
    assert max(costs) > 5 * max(hops)  # RTT distances, not hop counts


@pytest.mark.parametrize("seed", SEEDS)
def test_lfa_tie_break_matches_the_oracle_from_every_aggregation_router(seed):
    """An aggregation router has some twenty links, many of them access
    links of equal metric: where several neighbours offer an alternate at
    the same cost the lowest link in sort order wins, in the device's
    argmin over slots as in the oracle's walk."""
    adj_dbs, states, ps = _wan(seed)
    tied = 0
    for g in range(SIZE["regions"]):
        me = f"r{g:02d}-agg{seed:02d}"
        want = SpfSolver(me, enable_lfa=True).build_route_db(me, states, ps)
        got = TpuSpfSolver(me, enable_lfa=True).build_route_db(
            me, states, ps
        )
        assert_rib_equal(want, got, me)
        links = states["0"].ordered_links_from_node(me)
        metrics = [l.metric_from_node(me) for l in links]
        tied += len(metrics) - len(set(metrics))
    assert tied > 0  # equal-metric links did exist


def test_the_mirrors_occupancy_is_reported():
    """decision.tpu.* gauges, last_device_stats and the tpu.sync.plan
    span's attributes say where the edges live; decision.lfa.* and the
    tpu.mat span's attribute say how many routes carry a backup."""
    me = VANTAGES["access"]
    adj_dbs, states, ps = _wan(4)
    directed = sum(len(db.adjacencies) for db in adj_dbs)
    solver = TpuSpfSolver(me, enable_lfa=True)
    route_db = solver.build_route_db(me, states, ps)
    stats = solver.last_device_stats
    assert stats["residual_edges"] + stats["shift_edges"] == directed
    assert stats["residual_edges"] > 0
    assert stats["residual_k_cap"] >= stats["k_res"] > 0
    assert stats["residual_r_cap"] >= 8
    plan = solver._area_dev["0"].plan
    assert stats["delta_exp"] == plan.delta_exp
    assert stats["residual_edges"] == int((plan.res_nbr >= 0).sum())
    assert stats["shift_edges"] == int(plan._shift_occ.sum())
    for key in ("residual_edges", "shift_edges", "residual_r_cap",
                "residual_k_cap", "delta_exp"):
        assert counters.get_counter(f"decision.tpu.{key}") == stats[key]
    backed = _backups(route_db)
    assert backed >= len(adj_dbs) // 4
    assert stats["lfa_routes"] == backed
    assert counters.get_counter("decision.lfa.routes_with_backup") == backed
    assert counters.get_counter("decision.lfa.routes") == len(
        route_db.unicast_routes
    )
    spans = {name: attrs for name, _, _, _, attrs
             in solver.last_timing["spans"]}
    assert spans["tpu.sync.plan"]["residual_edges"] == stats["residual_edges"]
    assert spans["tpu.sync.plan"]["delta_exp"] == stats["delta_exp"]
    assert spans["tpu.mat"]["lfa_routes"] == backed
    # a link that goes and comes back keeps its slots: the counts stand
    churn = _Churn(adj_dbs, states)
    u, v = next(e for e in churn.edges() if me not in e)
    saved = churn.dbs[u], churn.dbs[v]
    churn.link_down(u, v)
    solver.build_route_db(me, states, ps)
    churn.link_up(u, v, *saved)
    solver.build_route_db(me, states, ps)
    after = solver.last_device_stats
    assert (after["residual_edges"], after["shift_edges"]) == (
        stats["residual_edges"], stats["shift_edges"]
    )


def test_without_lfa_no_backup_gauge_is_touched():
    counters.set_counter("decision.lfa.routes_with_backup", -1)
    adj_dbs, prefix_dbs = topologies.grid(4, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    solver = TpuSpfSolver("node-1-1")
    solver.build_route_db("node-1-1", states, ps)
    assert "lfa_routes" not in solver.last_device_stats
    assert counters.get_counter("decision.lfa.routes_with_backup") == -1
    # the grid decomposes into shift classes whole: nothing in the residual
    assert solver.last_device_stats["residual_edges"] == 0
    assert solver.last_device_stats["shift_edges"] == sum(
        len(db.adjacencies) for db in adj_dbs
    )
