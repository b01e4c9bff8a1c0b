"""Multi-chip sharded fabric-path tests, on the virtual 8-CPU device mesh
(conftest sets xla_force_host_platform_device_count=8).

The sharded pipeline (parallel/sharding.py) computes EVERY vantage's
routes in one pass: roots data-parallel over the 'batch' mesh axis, the
graph's node columns sharded over 'graph' with a pmin halo exchange per
relaxation. TpuSpfSolver.build_fabric_route_dbs wraps it with trip-bound
derivation (measured single-chip trips, convergence-vote verified,
doubling retry) and full route materialization; results must equal the
per-vantage CPU oracle exactly.
"""

import numpy as np
import pytest

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.parallel.sharding import Unconverged, make_mesh, sharded_fabric_step
from openr_tpu.types import Adjacency, AdjacencyDatabase
from tests.test_tpu_solver import assert_rib_equal


def test_make_mesh_factors_devices():
    mesh = make_mesh(8)
    assert mesh.shape["batch"] * mesh.shape["graph"] == 8
    assert mesh.shape["graph"] == 2  # both axes exercised at >= 4 devices


def fabric_vs_oracle(states, ps, roots, mesh=None, **solver_kw):
    tpu = TpuSpfSolver(roots[0], **solver_kw)
    dbs = tpu.build_fabric_route_dbs(roots, states, ps, mesh=mesh)
    for root in roots:
        cpu_db = SpfSolver(root, **solver_kw).build_route_db(root, states, ps)
        if cpu_db is None:
            assert dbs[root] is None, root
            continue
        assert_rib_equal(cpu_db, dbs[root], f"fabric vantage {root}")
    return tpu, dbs


def test_fabric_route_dbs_grid_all_vantage_parity():
    adj_dbs, prefix_dbs = topologies.grid(8)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    roots = [db.this_node_name for db in adj_dbs[::7]]  # 10 vantages
    tpu, dbs = fabric_vs_oracle(states, ps, roots, mesh=make_mesh(8))
    assert len(dbs) == len(roots)


def test_fabric_route_dbs_with_lfa():
    """LFA backups computed on the sharded path match the oracle."""
    adj_dbs, prefix_dbs = topologies.grid(6)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    roots = ["node-0-0", "node-2-3", "node-5-5"]
    # parity incl. lfa_nexthops is asserted inside fabric_vs_oracle
    fabric_vs_oracle(states, ps, roots, enable_lfa=True)


def test_fabric_route_dbs_drained_and_churn():
    adj_dbs, prefix_dbs = topologies.random_mesh(30, seed=3)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    ls = states["0"]
    victim = next(d for d in adj_dbs if d.this_node_name == "node-7")
    ls.update_adjacency_database(
        AdjacencyDatabase(
            this_node_name="node-7",
            adjacencies=victim.adjacencies,
            is_overloaded=True,
            area="0",
        )
    )
    roots = ["node-0", "node-7", "node-15"]
    tpu, _ = fabric_vs_oracle(states, ps, roots)
    # metric churn, then the same solver instance recomputes correctly
    ls.update_adjacency_database(
        AdjacencyDatabase(
            this_node_name="node-3",
            adjacencies=tuple(
                Adjacency(**{**a.__dict__, "metric": 9})
                for a in next(
                    d for d in adj_dbs if d.this_node_name == "node-3"
                ).adjacencies
            ),
            area="0",
        )
    )
    dbs = tpu.build_fabric_route_dbs(roots, states, ps)
    for root in roots:
        cpu_db = SpfSolver(root).build_route_db(root, states, ps)
        assert_rib_equal(cpu_db, dbs[root], f"after churn {root}")


def test_fabric_unknown_root_returns_none():
    adj_dbs, prefix_dbs = topologies.grid(4)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    tpu = TpuSpfSolver("node-0-0")
    dbs = tpu.build_fabric_route_dbs(
        ["node-0-0", "not-a-node"], states, ps
    )
    assert dbs["not-a-node"] is None
    assert dbs["node-0-0"] is not None


def test_fabric_trip_bound_retry_from_cold_solver():
    """A fresh solver has no measured trip count (last_trips == 0); the
    seed bound is tiny and the convergence vote must drive the doubling
    retry to a correct result on a high-diameter graph."""
    adj_dbs, prefix_dbs = topologies.grid(8)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    tpu = TpuSpfSolver("node-0-0")
    assert tpu.last_trips == 0
    dbs = tpu.build_fabric_route_dbs(["node-0-0", "node-7-7"], states, ps)
    cpu_db = SpfSolver("node-0-0").build_route_db("node-0-0", states, ps)
    assert_rib_equal(cpu_db, dbs["node-0-0"], "retry path")


def test_sharded_step_unconverged_raises():
    """Directly under-bound the trip count: the kernel's convergence
    vote must raise instead of returning too-large distances."""
    from openr_tpu.ops.csr import build_prefix_matrix
    from openr_tpu.ops.edgeplan import INF32E, build_plan

    adj_dbs, prefix_dbs = topologies.grid(10, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    ls = states["0"]
    plan = build_plan(ls)
    matrix = build_prefix_matrix(ps, plan.node_index, "0")
    mesh = make_mesh(4)
    batch = mesh.shape["batch"]
    roots_names = [plan.node_names[0]] * batch
    roots = np.array([plan.node_index[n] for n in roots_names], np.int32)
    outs = [plan.out_links(ls, n) for n in roots_names]
    d_cap = max(o[0].shape[0] for o in outs)
    out_nbr = np.full((batch, d_cap), -1, np.int32)
    out_w = np.full((batch, d_cap), int(INF32E), np.int32)
    for i, (nbr, w, _l) in enumerate(outs):
        out_nbr[i, : nbr.shape[0]] = nbr
        out_w[i, : w.shape[0]] = w
    try:
        sharded_fabric_step(mesh, plan, matrix, roots, out_nbr, out_w, 1)
    except Unconverged:
        pass
    else:  # pragma: no cover
        raise AssertionError("expected Unconverged for a 1-trip bound")


def test_fabric_matches_single_chip_solver():
    """The sharded path and the single-chip resident pipeline are two
    implementations of the same function."""
    adj_dbs, prefix_dbs = topologies.grid(6)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    single = TpuSpfSolver("node-3-3")
    single_db = single.build_route_db("node-3-3", states, ps)
    fabric = TpuSpfSolver("node-3-3")
    dbs = fabric.build_fabric_route_dbs(["node-3-3"], states, ps)
    assert_rib_equal(single_db, dbs["node-3-3"], "single vs fabric")


def test_fabric_non_divisible_graph_axis_pads():
    """A graph axis of 3 does not divide grid(8)'s node capacity (64);
    sharded_fabric_step must pad the node axis up to the mesh
    factorization instead of asserting divisibility, and the padded
    columns must never leak finite distances into the result."""
    adj_dbs, prefix_dbs = topologies.grid(8)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    mesh = make_mesh(6, batch=2)
    assert mesh.shape["graph"] == 3
    roots = ["node-0-0", "node-3-4", "node-7-7"]
    fabric_vs_oracle(states, ps, roots, mesh=mesh)


# -- multichip capacity tier (production single-vantage path) ---------------


def _churn_node(ls, victim, bump):
    """Metric-churn one node's adjacencies through the changelog path
    (generation bump); bump=0 restores the pristine metrics."""
    ls.update_adjacency_database(
        AdjacencyDatabase(
            this_node_name=victim.this_node_name,
            adjacencies=tuple(
                Adjacency(**{**a.__dict__, "metric": a.metric + bump})
                for a in victim.adjacencies
            ),
            area="0",
        )
    )


@pytest.mark.parametrize(
    "incr, mirror", [(False, "grid"), (True, "grid"), (True, "split-wan")]
)
def test_multichip_production_path_parity(incr, mirror, monkeypatch):
    """build_route_db through the multichip capacity tier (threshold
    forced below the graph's n_cap): RIBs bit-identical to BOTH the CPU
    oracle and the single-chip tier — including LFA backups — across
    cold solve, metric churn, restore, link flap, and flap restore, on
    the full-solve and incremental solvers. Tier observability
    (counters, stats, per-shard timings) is asserted alongside.

    `split-wan`: a small RTT-metric WAN whose mirror keeps a residual two
    slots wide, so its aggregation routers span several rows, churned at
    its two widest routers: the twin's parent forest comes from the
    residual find it shares with the single-chip solve
    (ops/incremental.residual_parents)."""
    from openr_tpu.ops import edgeplan
    from openr_tpu.runtime.counters import counters

    if mirror == "grid":
        adj_dbs, prefix_dbs = topologies.grid(8)
        churned, flapped = adj_dbs[1], adj_dbs[5]
    else:
        monkeypatch.setattr(edgeplan, "_residual_width", lambda degrees: 2)
        adj_dbs, prefix_dbs = topologies.wan_rtt(
            regions=3, cores=2, aggs=4, access=20, seed=7
        )
        churned, flapped = sorted(
            adj_dbs[1:], key=lambda db: -len(db.adjacencies)
        )[:2]
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    root = adj_dbs[0].this_node_name
    ls = states["0"]
    cpu = SpfSolver(root, enable_lfa=True)
    single = TpuSpfSolver(root, enable_lfa=True, incremental_spf=incr)
    mc = TpuSpfSolver(
        root, enable_lfa=True, incremental_spf=incr,
        multichip_n_cap_threshold=32, multichip_batch=4,
    )
    eng0 = counters.get_counter("decision.solver.multichip.engaged") or 0
    dis0 = counters.get_counter("decision.solver.multichip.dispatches") or 0

    def check(ctx):
        cpu_db = cpu.build_route_db(root, states, ps)
        mc_db = mc.build_route_db(root, states, ps)
        assert_rib_equal(cpu_db, mc_db, f"mc vs oracle: {ctx}")
        assert_rib_equal(
            single.build_route_db(root, states, ps), mc_db,
            f"mc vs single-chip: {ctx}",
        )

    check("cold")
    mc_info = mc.last_timing["multichip"]
    assert mc_info["shards"] == 8
    assert mc_info["batch"] == 4 and mc_info["graph"] == 2
    assert len(mc_info["shard_ms"]) == 8
    assert mc.last_device_stats["multichip"]["shards"] == 8

    _churn_node(ls, churned, 7)
    check("metric churn")
    if mirror == "split-wan":
        occupancy = mc._area_dev["0"].plan.occupancy()
        assert occupancy["residual_split_rows"] > 0, occupancy
        stats = mc.last_device_stats
        assert stats.get("incremental") and stats.get("cone") > 0, stats
        assert not stats.get("fell_back"), stats
    _churn_node(ls, churned, 0)
    check("restore")
    victim = flapped
    ls.update_adjacency_database(
        AdjacencyDatabase(
            this_node_name=victim.this_node_name,
            adjacencies=(), area="0",
        )
    )
    check("flap down")
    ls.update_adjacency_database(
        AdjacencyDatabase(
            this_node_name=victim.this_node_name,
            adjacencies=tuple(
                Adjacency(**{**a.__dict__, "metric": 3})
                for a in victim.adjacencies
            ),
            area="0",
        )
    )
    check("flap restore")
    eng1 = counters.get_counter("decision.solver.multichip.engaged") or 0
    dis1 = counters.get_counter("decision.solver.multichip.dispatches") or 0
    assert eng1 >= eng0 + 5, (eng0, eng1)
    assert dis1 >= dis0 + 5, (dis0, dis1)


def test_multichip_tier_stays_off_below_threshold():
    """The same graph under the default threshold (n_cap far below it)
    must never touch the sharded path: no mc stats, no engage ticks."""
    from openr_tpu.runtime.counters import counters

    adj_dbs, prefix_dbs = topologies.grid(8)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    root = adj_dbs[0].this_node_name
    eng0 = counters.get_counter("decision.solver.multichip.engaged") or 0
    tpu = TpuSpfSolver(root)
    cpu_db = SpfSolver(root).build_route_db(root, states, ps)
    assert_rib_equal(
        cpu_db, tpu.build_route_db(root, states, ps), "below threshold"
    )
    assert not tpu.last_timing.get("multichip")
    assert "multichip" not in tpu.last_device_stats
    eng1 = counters.get_counter("decision.solver.multichip.engaged") or 0
    assert eng1 == eng0
