"""CPU-vs-TPU solver differential tests (the golden harness, SURVEY §4
takeaway (5)): both backends are pure functions of (areaLinkStates,
prefixState); their full RIBs must match exactly on every topology
generator, including drained nodes, anycast selection, metric churn, and
link flaps. Runs on the virtual-CPU JAX platform (conftest)."""

import functools
import zlib

import jax
import numpy as np
import pytest

from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver, _plan_sssp
from openr_tpu.models import topologies
from openr_tpu.ops import relax as relax_ops
from openr_tpu.ops.edgeplan import INF32E, build_plan
from openr_tpu.types import (
    Adjacency,
    AdjacencyDatabase,
    PrefixForwardingAlgorithm,
    PrefixMetrics,
)
from tests.test_link_state import adj, adj_db
from tests.test_spf_solver import prefix_db, square_states


def assert_rib_equal(cpu_db, tpu_db, context=""):
    assert cpu_db.unicast_routes.keys() == tpu_db.unicast_routes.keys(), context
    for pfx, cpu_route in cpu_db.unicast_routes.items():
        tpu_route = tpu_db.unicast_routes[pfx]
        assert cpu_route == tpu_route, f"{context}: mismatch for {pfx}:\n{cpu_route}\nvs\n{tpu_route}"
    assert cpu_db.mpls_routes == tpu_db.mpls_routes, context


def run_both(my_node, states, ps, **kw):
    cpu = SpfSolver(my_node, **kw)
    tpu = TpuSpfSolver(my_node, **kw)
    cpu_db = cpu.build_route_db(my_node, states, ps)
    tpu_db = tpu.build_route_db(my_node, states, ps)
    if cpu_db is None:
        assert tpu_db is None
        return None, None
    assert_rib_equal(cpu_db, tpu_db, my_node)
    return cpu_db, tpu_db


# -- SSSP kernel against Dijkstra ------------------------------------------

def sssp_vs_dijkstra(link_state, sample_roots=None):
    """The production SSSP (the shift-decomposed mirror of build_plan
    under _plan_sssp, with the round loop the plan's Δ selects) against
    LinkState.run_spf: the [D, N] plane from the root's out-slot
    neighbours in G-minus-root, folded to one distance per node as the
    pipeline's `select` stage folds it."""
    plan = build_plan(link_state)
    kernel = "bucketed" if plan.delta_exp > 0 else "sync"

    @functools.lru_cache(None)
    def sssp_for(d_cap):
        return jax.jit(functools.partial(
            _plan_sssp, s_cap=plan.s_cap, has_res=plan.k_res > 0,
            n_cap=plan.n_cap, d_cap=d_cap,
            max_trips=relax_ops.max_trips(plan.n_cap), kernel=kernel,
            delta_exp=plan.delta_exp,
        ))

    for root in sample_roots or plan.node_names:
        root_idx = plan.node_index[root]
        root_nbr, root_w, _ = plan.out_links(link_state, root)
        dist_d, _, _ = sssp_for(root_nbr.shape[0])(
            plan.deltas, plan.shift_w, plan.res_rows, plan.res_nbr,
            plan.res_w, np.int32(root_idx), root_nbr, root_w,
        )
        via = root_w[:, None] + np.asarray(dist_d)
        dist = np.minimum(via.min(axis=0), INF32E)
        dist[root_idx] = 0
        spf = link_state.run_spf(root)
        for name in plan.node_names:
            expect = spf[name].metric if name in spf else int(INF32E)
            got = int(dist[plan.node_index[name]])
            assert got == expect, (root, name, got, expect)


def test_sssp_matches_dijkstra_grid():
    adj_dbs, _ = topologies.grid(5)
    states, _ = topologies.build_states(adj_dbs, [])
    sssp_vs_dijkstra(states["0"])


def test_sssp_matches_dijkstra_random_mesh_with_overloads():
    adj_dbs, _ = topologies.random_mesh(30, seed=7)
    states, _ = topologies.build_states(adj_dbs, [])
    ls = states["0"]
    # drain two nodes + vary some metrics
    for i, db in enumerate(adj_dbs):
        if i in (3, 11):
            ls.update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=db.this_node_name,
                    adjacencies=tuple(
                        Adjacency(**{**a.__dict__, "metric": 1 + (hash(a.other_node_name) % 5)})
                        for a in db.adjacencies
                    ),
                    is_overloaded=True,
                    area="0",
                )
            )
    sssp_vs_dijkstra(ls)


def test_sssp_matches_dijkstra_fat_tree():
    adj_dbs, _ = topologies.fat_tree()
    states, _ = topologies.build_states(adj_dbs, [])
    sssp_vs_dijkstra(states["0"], sample_roots=["rsw-0-0", "ssw-1-3", "fsw-1-0"])


# -- full RIB differential -------------------------------------------------

def test_rib_differential_square_basic():
    states = square_states()
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    ps.update_prefix_database(prefix_db("b", "fd00::b/128"))
    ps.update_prefix_database(prefix_db("a", "fd00::a/128"))  # self: skipped
    cpu_db, _ = run_both("a", states, ps)
    assert set(cpu_db.unicast_routes) == {"fd00::d/128", "fd00::b/128"}


def test_rib_differential_anycast_preferences_distance():
    states = square_states()
    ps = PrefixState()
    ps.update_prefix_database(
        prefix_db("b", "fd00::100/128", metrics=PrefixMetrics(path_preference=500))
    )
    ps.update_prefix_database(
        prefix_db("d", "fd00::100/128", metrics=PrefixMetrics(path_preference=1000))
    )
    ps.update_prefix_database(
        prefix_db("b", "fd00::200/128", metrics=PrefixMetrics(distance=3))
    )
    ps.update_prefix_database(
        prefix_db("d", "fd00::200/128", metrics=PrefixMetrics(distance=1))
    )
    ps.update_prefix_database(
        prefix_db("c", "fd00::300/128", metrics=PrefixMetrics(source_preference=900))
    )
    ps.update_prefix_database(prefix_db("d", "fd00::300/128"))
    run_both("a", states, ps)


def test_rib_differential_drained_announcers():
    states = square_states()
    states["0"].update_adjacency_database(
        adj_db("d", [adj("d", "b"), adj("d", "c")], node_label=104, is_overloaded=True)
    )
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("b", "fd00::100/128"))
    ps.update_prefix_database(prefix_db("d", "fd00::100/128"))
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))  # all-drained fallback
    run_both("a", states, ps)


def test_rib_differential_min_nexthop():
    states = square_states()
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("b", "fd00::100/128", min_nexthop=2))
    ps.update_prefix_database(prefix_db("d", "fd00::200/128", min_nexthop=2))
    cpu_db, _ = run_both("a", states, ps)
    assert set(cpu_db.unicast_routes) == {"fd00::200/128"}


def test_rib_differential_grid_all_vantages():
    adj_dbs, prefix_dbs = topologies.grid(4)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    for me in ("node-0-0", "node-1-2", "node-3-3"):
        run_both(me, states, ps)


def test_rib_differential_fat_tree():
    adj_dbs, prefix_dbs = topologies.fat_tree()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    run_both("rsw-0-0", states, ps)
    run_both("ssw-0-0", states, ps)


def test_rib_differential_random_mesh_churn():
    """Metric churn + link flap: mirror must refresh on generation bump."""
    adj_dbs, prefix_dbs = topologies.random_mesh(25, seed=11)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    ls = states["0"]
    cpu = SpfSolver("node-0")
    tpu = TpuSpfSolver("node-0")
    assert_rib_equal(
        cpu.build_route_db("node-0", states, ps),
        tpu.build_route_db("node-0", states, ps),
        "initial",
    )
    # flap: drop node-5's links entirely, then restore with new metrics
    victim = next(d for d in adj_dbs if d.this_node_name == "node-5")
    ls.update_adjacency_database(
        AdjacencyDatabase(this_node_name="node-5", adjacencies=(), area="0")
    )
    assert_rib_equal(
        cpu.build_route_db("node-0", states, ps),
        tpu.build_route_db("node-0", states, ps),
        "after flap down",
    )
    ls.update_adjacency_database(
        AdjacencyDatabase(
            this_node_name="node-5",
            adjacencies=tuple(
                Adjacency(**{**a.__dict__, "metric": 7}) for a in victim.adjacencies
            ),
            area="0",
        )
    )
    assert_rib_equal(
        cpu.build_route_db("node-0", states, ps),
        tpu.build_route_db("node-0", states, ps),
        "after restore",
    )


def test_rib_differential_mesh_4node():
    """BASELINE config 1: every node's RIB matches on the 4-node mesh."""
    adj_dbs, prefix_dbs = topologies.full_mesh(4)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    for me in (db.this_node_name for db in adj_dbs):
        run_both(me, states, ps)


def test_small_graph_delegates_to_cpu_oracle():
    """The "auto" backend's small-graph heuristic: below the node
    threshold the whole build runs on the CPU oracle (no device state is
    created), and results are identical by construction."""
    adj_dbs, prefix_dbs = topologies.full_mesh(4)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    tpu = TpuSpfSolver("node-0", small_graph_nodes=64)
    cpu = SpfSolver("node-0")
    assert_rib_equal(
        cpu.build_route_db("node-0", states, ps),
        tpu.build_route_db("node-0", states, ps),
        "small-graph delegation",
    )
    assert not tpu._area_dev, "device path must not run below the threshold"


def test_make_solver_auto_passes_threshold():
    from openr_tpu.decision.decision import make_solver

    solver = make_solver("node-0", "auto", small_graph_nodes=128)
    if isinstance(solver, TpuSpfSolver):
        assert solver.small_graph_nodes == 128
    # explicit "tpu" backend never delegates
    solver = make_solver("node-0", "tpu")
    assert solver.small_graph_nodes == 0


def test_ksp2_and_ucmp_fall_back_to_cpu_identically():
    states = square_states()
    ps = PrefixState()
    ps.update_prefix_database(
        prefix_db(
            "d",
            "fd00::d/128",
            forwarding_type=1,  # SR_MPLS
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        )
    )
    ps.update_prefix_database(prefix_db("b", "fd00::b/128"))  # fast path
    cpu_db, tpu_db = run_both("a", states, ps)
    assert set(cpu_db.unicast_routes) == {"fd00::d/128", "fd00::b/128"}


def test_multi_area_falls_back_to_cpu():
    ls0 = LinkState("0")
    ls0.update_adjacency_database(adj_db("a", [adj("a", "b")], area="0"))
    ls0.update_adjacency_database(adj_db("b", [adj("b", "a")], area="0"))
    ls1 = LinkState("1")
    ls1.update_adjacency_database(adj_db("a", [adj("a", "c")], area="1"))
    ls1.update_adjacency_database(adj_db("c", [adj("c", "a")], area="1"))
    states = {"0": ls0, "1": ls1}
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("b", "fd00::100/128", area="0"))
    ps.update_prefix_database(prefix_db("c", "fd00::100/128", area="1"))
    cpu_db, tpu_db = run_both("a", states, ps)
    assert "fd00::100/128" in cpu_db.unicast_routes


def test_topology_change_renumbering_invalidates_matrix_cache():
    """Regression (code review r2 #1): adding a node that shifts node
    indices must refresh the cached announcer matrix even when prefix
    state is untouched."""
    states = square_states()
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    cpu = SpfSolver("b")
    tpu = TpuSpfSolver("b")
    assert_rib_equal(
        cpu.build_route_db("b", states, ps),
        tpu.build_route_db("b", states, ps),
        "before renumber",
    )
    # 'aa' sorts before every existing node -> all indices shift by one
    states["0"].update_adjacency_database(adj_db("aa", [adj("aa", "a")]))
    states["0"].update_adjacency_database(
        adj_db("a", [adj("a", "b"), adj("a", "c"), adj("a", "aa")], node_label=101)
    )
    assert_rib_equal(
        cpu.build_route_db("b", states, ps),
        tpu.build_route_db("b", states, ps),
        "after renumber",
    )


def test_any_vantage_queries_do_not_share_root_cache():
    """Regression (code review r2 #2): back-to-back solves from different
    vantage nodes with unchanged generations must not reuse the previous
    root's out-edge table."""
    states = square_states()
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    ps.update_prefix_database(prefix_db("a", "fd00::a/128"))
    tpu = TpuSpfSolver("a")
    for me in ("a", "b", "c", "a", "b"):
        cpu_db = SpfSolver(me).build_route_db(me, states, ps)
        tpu_db = tpu.build_route_db(me, states, ps)
        assert_rib_equal(cpu_db, tpu_db, f"vantage {me}")


def test_new_node_with_no_links_bumps_generation():
    """Regression (code review r2 #3): a first-time adjacency db with no
    usable links still adds the node and must refresh mirrors."""
    states = square_states()
    ls = states["0"]
    tpu = TpuSpfSolver("a")
    ps = PrefixState()
    tpu.build_route_db("a", states, ps)  # warm the mirror
    g1 = ls.generation
    ls.update_adjacency_database(
        AdjacencyDatabase(this_node_name="zz", adjacencies=(), area="0")
    )
    assert ls.generation > g1
    assert ls.has_node("zz")
    # solving from the new node: CPU yields empty-but-present db; TPU must
    # not KeyError on a stale mirror
    cpu_db = SpfSolver("zz").build_route_db("zz", states, ps)
    tpu_db = tpu.build_route_db("zz", states, ps)
    assert (cpu_db is None) == (tpu_db is None)
    if cpu_db is not None:
        assert_rib_equal(cpu_db, tpu_db, "new node vantage")


def test_node_labels_via_tpu_backend():
    states = square_states()
    cpu_db, tpu_db = run_both(
        "a", states, PrefixState(), enable_node_segment_label=True
    )
    assert set(cpu_db.mpls_routes) == {101, 102, 103, 104}


# -- UCMP on device --------------------------------------------------------
# The oracle's resolve_ucmp_weights heap walk (ref LinkState.cpp:913-1033)
# vs the device segment-sum fixpoint (ops/ucmp.py via _UcmpAccel).

def ucmp_states():
    """Two-level DAG with multipath, unit metrics:
        r - {a, b}; a - {c, d}; b - {d, e}; c - l1; d - {l1, l2}; e - l2
    l1/l2 are equidistant (3) from r and (2) from a/b."""
    ls = LinkState("0")
    topo = {
        "r": ["a", "b"],
        "a": ["r", "c", "d"],
        "b": ["r", "d", "e"],
        "c": ["a", "l1"],
        "d": ["a", "b", "l1", "l2"],
        "e": ["b", "l2"],
        "l1": ["c", "d"],
        "l2": ["d", "e"],
    }
    for node, others in topo.items():
        ls.update_adjacency_database(
            adj_db(node, [adj(node, o, weight=10 + ord(o[0]) % 7) for o in others])
        )
    return {"0": ls}


def ucmp_prefix_state(algo, weights=(3, 5)):
    ps = PrefixState()
    for node, w in zip(("l1", "l2"), weights):
        ps.update_prefix_database(
            prefix_db(
                node, "fd00::100/128", forwarding_algorithm=algo, weight=w
            )
        )
    return ps


def test_ucmp_differential_prefix_weight_propagation():
    states = ucmp_states()
    ps = ucmp_prefix_state(
        PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION
    )
    for me in ("r", "a", "b"):
        cpu = SpfSolver(me, enable_ucmp=True)
        tpu = TpuSpfSolver(me, enable_ucmp=True)
        cpu_db = cpu.build_route_db(me, states, ps)
        tpu_db = tpu.build_route_db(me, states, ps)
        assert_rib_equal(cpu_db, tpu_db, f"ucmp prefix-weight vantage {me}")
        route = tpu_db.unicast_routes["fd00::100/128"]
        assert route.ucmp_weight is not None
        assert any(nh.weight for nh in route.nexthops)
        # the device resolver actually answered (no host fallback)
        assert any(
            v is not None for v in tpu._ucmp_accel.results.values()
        ), "device UCMP path did not engage"


def test_ucmp_differential_adj_weight_propagation():
    states = ucmp_states()
    ps = ucmp_prefix_state(
        PrefixForwardingAlgorithm.SP_UCMP_ADJ_WEIGHT_PROPAGATION
    )
    for me in ("r", "a", "b"):
        cpu = SpfSolver(me, enable_ucmp=True)
        tpu = TpuSpfSolver(me, enable_ucmp=True)
        cpu_db = cpu.build_route_db(me, states, ps)
        tpu_db = tpu.build_route_db(me, states, ps)
        assert_rib_equal(cpu_db, tpu_db, f"ucmp adj-weight vantage {me}")
        assert tpu._ucmp_accel.results, "device UCMP path did not engage"


def test_ucmp_differential_through_churn():
    """Metric churn changes the DAG; per-generation caches (edges, base
    field, result memo) must invalidate and re-agree with the oracle."""
    states = ucmp_states()
    ls = states["0"]
    ps = ucmp_prefix_state(
        PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION
    )
    cpu = SpfSolver("r", enable_ucmp=True)
    tpu = TpuSpfSolver("r", enable_ucmp=True)
    assert_rib_equal(
        cpu.build_route_db("r", states, ps),
        tpu.build_route_db("r", states, ps),
        "before churn",
    )
    # stretch r-a: the whole left arm leaves the shortest DAG
    ls.update_adjacency_database(
        adj_db("r", [adj("r", "a", metric=5), adj("r", "b")])
    )
    assert_rib_equal(
        cpu.build_route_db("r", states, ps),
        tpu.build_route_db("r", states, ps),
        "after churn",
    )
    # heal it back
    ls.update_adjacency_database(
        adj_db("r", [adj("r", "a"), adj("r", "b")])
    )
    assert_rib_equal(
        cpu.build_route_db("r", states, ps),
        tpu.build_route_db("r", states, ps),
        "after heal",
    )


def test_ucmp_anycast_shares_one_resolve():
    """Anycast prefixes with identical (leaves, weights, mode) resolve
    once on device (the result memo), and every prefix still matches."""
    states = ucmp_states()
    ps = ucmp_prefix_state(
        PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION
    )
    for node, w in zip(("l1", "l2"), (3, 5)):
        ps.update_prefix_database(
            prefix_db(
                node, "fd00::200/128",
                forwarding_algorithm=(
                    PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION
                ),
                weight=w,
            )
        )
    cpu = SpfSolver("r", enable_ucmp=True)
    tpu = TpuSpfSolver("r", enable_ucmp=True)
    assert_rib_equal(
        cpu.build_route_db("r", states, ps),
        tpu.build_route_db("r", states, ps),
        "anycast ucmp",
    )
    assert len(tpu._ucmp_accel.results) == 1  # shared leafset memo


def test_ucmp_random_mesh_differential():
    """Random mesh: announcer distances differ, so only the best-metric
    subset becomes leaves; RIBs must match across vantages and modes."""
    adj_dbs, _ = topologies.random_mesh(24, seed=11)
    states, _ = topologies.build_states(adj_dbs, [])
    names = [db.this_node_name for db in adj_dbs]
    for algo in (
        PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION,
        PrefixForwardingAlgorithm.SP_UCMP_ADJ_WEIGHT_PROPAGATION,
    ):
        ps = PrefixState()
        for node, w in zip(names[3:9], (2, 4, 6, 3, 5, 7)):
            ps.update_prefix_database(
                prefix_db(node, "fd00::a0/128", forwarding_algorithm=algo, weight=w)
            )
        for me in names[:4]:
            cpu = SpfSolver(me, enable_ucmp=True)
            tpu = TpuSpfSolver(me, enable_ucmp=True)
            cpu_db = cpu.build_route_db(me, states, ps)
            tpu_db = tpu.build_route_db(me, states, ps)
            assert_rib_equal(cpu_db, tpu_db, f"random ucmp {algo} {me}")


def test_ucmp_overflow_falls_back_to_host():
    """Leaf weights beyond the int32-safe bound must not go through the
    device fixpoint; the host walk (exact Python ints) answers and the
    differential still holds."""
    states = ucmp_states()
    big = 1 << 31  # > float-shadow threshold
    ps = ucmp_prefix_state(
        PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION,
        weights=(big, big * 2),
    )
    cpu = SpfSolver("r", enable_ucmp=True)
    tpu = TpuSpfSolver("r", enable_ucmp=True)
    cpu_db = cpu.build_route_db("r", states, ps)
    tpu_db = tpu.build_route_db("r", states, ps)
    assert_rib_equal(cpu_db, tpu_db, "ucmp overflow fallback")
    route = tpu_db.unicast_routes["fd00::100/128"]
    # exact (multipath-multiplied), far beyond anything int32 could hold
    assert route.ucmp_weight > (1 << 32)
    # the fallback is memoized as a sentinel so sibling anycast prefixes
    # skip the wasted device round trip
    assert all(
        v is NotImplemented for v in tpu._ucmp_accel.results.values()
    )


def test_ucmp_huge_adjacency_weight_falls_back_exactly():
    """Adjacency weights beyond the int32-safe bound skip the device
    fixpoint (no silent clipping) and the host walk keeps the ratios
    exact."""
    states = ucmp_states()
    ls = states["0"]
    big = (1 << 31) + 6  # would clip/wrap on device
    ls.update_adjacency_database(
        adj_db(
            "d",
            [
                adj("d", "a", weight=big),
                adj("d", "b", weight=big),
                adj("d", "l1", weight=big),
                adj("d", "l2", weight=big * 2),
            ],
        )
    )
    ps = ucmp_prefix_state(
        PrefixForwardingAlgorithm.SP_UCMP_ADJ_WEIGHT_PROPAGATION
    )
    cpu = SpfSolver("r", enable_ucmp=True)
    tpu = TpuSpfSolver("r", enable_ucmp=True)
    assert_rib_equal(
        cpu.build_route_db("r", states, ps),
        tpu.build_route_db("r", states, ps),
        "huge adj weight",
    )


def test_ucmp_zero_metric_edge_terminates_via_host_fallback():
    """Regression (ISSUE 1): a live zero-metric edge makes BOTH of its
    directions satisfy the DAG membership predicate (du + 0 == dv), so
    the device fixpoint's "DAG" has a 2-cycle and used to oscillate in
    an unbounded while_loop — a daemon hang. The edge set now flags
    zero_w_unsafe and the exact host walk answers instead."""
    states = ucmp_states()
    ls = states["0"]
    ls.update_adjacency_database(
        adj_db("c", [adj("c", "a"), adj("c", "l1", metric=0)])
    )
    ls.update_adjacency_database(
        adj_db("l1", [adj("l1", "c", metric=0), adj("l1", "d")])
    )
    ps = ucmp_prefix_state(
        PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION
    )
    cpu = SpfSolver("r", enable_ucmp=True)
    tpu = TpuSpfSolver("r", enable_ucmp=True)
    cpu_db = cpu.build_route_db("r", states, ps)
    tpu_db = tpu.build_route_db("r", states, ps)
    assert_rib_equal(cpu_db, tpu_db, "zero-metric ucmp")
    # fallback memoized as a sentinel: no device round trips attempted
    assert tpu._ucmp_accel.results
    assert all(
        v is NotImplemented for v in tpu._ucmp_accel.results.values()
    )


def test_ucmp_device_fixpoint_bounded_on_zero_weight_cycle():
    """Defense in depth behind zero_w_unsafe: feed the raw device
    fixpoint a zero-weight 2-cycle whose weighted path counts grow every
    round (changed never quiesces). The iteration bound must fire and
    surface the non-convergence as overflow=True instead of hanging."""
    from openr_tpu.ops.ucmp import INF_E, _ucmp_fn

    e_cap = n_cap = 8
    src = np.zeros(e_cap, np.int32)
    dst = np.zeros(e_cap, np.int32)
    w_eff = np.full(e_cap, INF_E, np.int32)
    adj_w = np.zeros(e_cap, np.int32)
    # 0 <-> 1 at weight 0 (the cycle), both feeding leaf 2 at weight 1
    for i, (s, d, w) in enumerate(
        [(0, 1, 0), (1, 0, 0), (0, 2, 1), (1, 2, 1)]
    ):
        src[i], dst[i], w_eff[i] = s, d, w
    dist = np.full(n_cap, INF_E, np.int32)
    dist[0] = dist[1] = 5
    dist[2] = 6
    leaf_mask = np.zeros(n_cap, bool)
    leaf_mask[2] = True
    leaf_w = np.zeros(n_cap, np.int32)
    leaf_w[2] = 3
    fn = _ucmp_fn(e_cap, n_cap, True)
    _reach, _w, overflow, rounds = fn(
        src, dst, w_eff, adj_w, dist, leaf_mask, leaf_w
    )
    assert bool(overflow)
    # the bound fired: executed rounds == the shared fixpoint ledger
    from openr_tpu.ops.relax import fixpoint_bound

    assert int(rounds) == fixpoint_bound(n_cap)


def test_prewarm_tool_bakes_cache(
    tmp_path, monkeypatch, fresh_xla_cache_state
):
    """openr-tpu-prewarm compiles a capacity class into the persistent
    cache (shapes only — correctness covered by the differentials)."""
    from openr_tpu.tools.prewarm import main as prewarm_main

    # --cache-dir loses to jax's own variable (ops/xla_cache.py)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    rc = prewarm_main(["--nodes", "16", "--cache-dir", str(tmp_path / "xla")])
    assert rc == 0
    assert (tmp_path / "xla").is_dir()


# -- randomized churn soak ---------------------------------------------------

def test_churn_soak_differential():
    """Long mixed-mutation soak: random link flaps, metric changes,
    drains, prefix adds/withdrawals (incl. UCMP and LFA) — the CPU
    oracle and the TPU solver must agree after EVERY step. This is the
    strongest guard against stale-cache bugs in the incremental device
    path (plan deltas, matrix memo, KSP2 state, UCMP memos, vantage
    output deltas all churn together)."""
    import random

    rng = random.Random(20260730)
    adj_dbs, prefix_dbs = topologies.random_mesh(28, seed=5)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    ls = states["0"]
    names = [db.this_node_name for db in adj_dbs]
    by_name = {db.this_node_name: db for db in adj_dbs}
    me = "node-0"
    cpu = SpfSolver(me, enable_ucmp=True, enable_lfa=True)
    tpu = TpuSpfSolver(me, enable_ucmp=True, enable_lfa=True)

    def mutate(step):
        kind = rng.randrange(5)
        victim = rng.choice(names[1:])  # never isolate the vantage
        db = by_name[victim]
        if kind == 0:  # flap down
            ls.update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=victim, adjacencies=(), area="0"
                )
            )
        elif kind == 1:  # restore / metric churn
            ls.update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=victim,
                    adjacencies=tuple(
                        Adjacency(
                            **{
                                **a.__dict__,
                                # crc32, not hash(): PYTHONHASHSEED must
                                # not change the replayed sequence
                                "metric": 1
                                + (
                                    step
                                    + zlib.crc32(
                                        a.other_node_name.encode()
                                    )
                                )
                                % 9,
                            }
                        )
                        for a in db.adjacencies
                    ),
                    area="0",
                )
            )
        elif kind == 2:  # drain toggle
            ls.update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=victim,
                    adjacencies=db.adjacencies,
                    is_overloaded=(step % 2 == 0),
                    area="0",
                )
            )
        elif kind == 3:  # anycast UCMP prefix add
            algo = rng.choice(
                [
                    PrefixForwardingAlgorithm.SP_ECMP,
                    PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION,
                    PrefixForwardingAlgorithm.SP_UCMP_ADJ_WEIGHT_PROPAGATION,
                ]
            )
            for node in rng.sample(names[1:], 3):
                ps.update_prefix_database(
                    prefix_db(
                        node,
                        f"fd00:5{step % 8}::/64",
                        forwarding_algorithm=algo,
                        weight=rng.randrange(1, 9),
                    )
                )
        else:  # withdraw
            node = rng.choice(names[1:])
            ps.update_prefix_database(
                prefix_db(node, f"fd00:5{step % 8}::/64", delete=True)
            )

    for step in range(30):
        mutate(step)
        cpu_db = cpu.build_route_db(me, states, ps)
        tpu_db = tpu.build_route_db(me, states, ps)
        if cpu_db is None:
            assert tpu_db is None
            continue
        assert_rib_equal(cpu_db, tpu_db, f"soak step {step}")
