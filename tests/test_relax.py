"""Bucketed Δ-stepping kernel (ops/relax.py, ISSUE 13).

Unit tests of the shared round ledger plus randomized churn parity of
the bucketed kernel against BOTH the synchronous kernel and the CPU
oracle on every engagement path — full, incremental, multichip, and
what-if — on mesh5 / grid4 / fat_tree. The contract under test is the
module's one promise: sync and bucketed reach the identical int32
fixpoint bit-for-bit, so Δ steers performance only, never results.
"""

import numpy as np
import pytest

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.ops import relax as relax_ops
from tests.test_edgeplan import SPLIT_GRAPHS, _rows_of
from tests.test_incremental_spf import _Churn
from tests.test_tpu_solver import assert_rib_equal

AREA = "0"

FABRICS = [
    (lambda: topologies.full_mesh(5), "node-0"),
    (lambda: topologies.grid(4, node_labels=False), "node-1-1"),
    (lambda: topologies.fat_tree(pods=2, planes=2), "rsw-0-0"),
]
FABRIC_IDS = ["mesh5", "grid4", "fat_tree"]


# -- round ledger units ----------------------------------------------------


def test_round_ledger_units():
    # sync trip bound: ceil(n/UNROLL) + 2 slack, floor of 2
    assert relax_ops.max_trips(1) == 3
    assert relax_ops.max_trips(64) == 64 // relax_ops.UNROLL + 2
    assert relax_ops.max_trips(100) > relax_ops.max_trips(10)
    # shared fixpoint bound (consumed by ops/ucmp.py)
    assert relax_ops.fixpoint_bound(64) == 66
    # rung-doubling depth: 2^depth covers n_cap, clamped to [4, 16]
    assert relax_ops.ladder_depth(2) == 4
    assert relax_ops.ladder_depth(64) == 7
    assert relax_ops.ladder_depth(1 << 20) == 16
    # a pass over a residual is dear beside the no-change test: test
    # after every one; a pass of rolls alone is not: trips of UNROLL
    assert relax_ops.sync_quantum(True) == 1
    assert relax_ops.sync_quantum(False) == relax_ops.UNROLL


def _counted_run_sync(step, quantum, trips_of_unroll=relax_ops.max_trips(64)):
    """A jitted run_sync under the bound a caller holding
    `trips_of_unroll` trips of UNROLL passes at this quantum."""
    import jax

    bound = trips_of_unroll * relax_ops.UNROLL // quantum
    return jax.jit(
        lambda x, start: relax_ops.run_sync(
            step, x, bound, quantum, start=start
        )
    )


@pytest.mark.parametrize("moved", [0, 1, 5, 8, 9])
@pytest.mark.parametrize("quantum", [1, 2, 8])
def test_run_sync_counts_executed_applications(quantum, moved):
    """`rounds` is every application run: the `moved` that changed the
    state and the tail that found nothing, in whole trips."""
    import jax.numpy as jnp

    run = _counted_run_sync(lambda x: jnp.maximum(x - 1, 0), quantum)
    state, trips, rounds = run(jnp.array([moved, 0], jnp.int32), True)
    assert not np.asarray(state).any()
    assert int(rounds) == quantum * (-(-moved // quantum) + 1)
    assert int(trips) * quantum == int(rounds)


@pytest.mark.parametrize("quantum", [1, 2, 8])
def test_run_sync_bound_is_on_applications(quantum):
    """A step that never settles is cut at max_trips * UNROLL
    applications whatever the trip's length."""
    run = _counted_run_sync(lambda x: x + 1, quantum)
    state, trips, rounds = run(np.zeros(2, np.int32), True)
    cap = relax_ops.max_trips(64) * relax_ops.UNROLL
    assert int(rounds) == cap and int(trips) == cap // quantum
    assert (np.asarray(state) == cap).all()


@pytest.mark.parametrize("quantum", [1, 8])
def test_run_sync_does_not_start_without_cause(quantum):
    """`start` is the carried flag the loop opens with: a traced False
    runs no application, even of a step that would move the state."""
    run = _counted_run_sync(lambda x: x + 1, quantum)
    state, trips, rounds = run(np.full(2, 7, np.int32), False)
    assert int(rounds) == 0 and int(trips) == 0
    assert (np.asarray(state) == 7).all()


def test_derive_delta_exp_boundaries():
    INF = relax_ops.INF_E
    # no shift classes at all -> ineligible
    assert relax_ops.derive_delta_exp(
        np.zeros(4, np.int32), np.full((4, 8), INF, np.int32)
    ) == 0
    assert relax_ops.derive_delta_exp(
        np.zeros(0, np.int32), np.zeros((0, 8), np.int32)
    ) == 0
    # all-INF weights (occupied classes, no live edges) -> ineligible
    deltas = np.array([1, -1, 0, 0], np.int32)
    assert relax_ops.derive_delta_exp(
        deltas, np.full((4, 8), INF, np.int32)
    ) == 0
    # uniform metrics: Δ = pow2 ceiling of the one weight -> EVERY edge
    # classifies light (one bucket, ladder covers the whole graph)
    w = np.full((4, 8), INF, np.int32)
    w[0, :] = 10
    e = relax_ops.derive_delta_exp(deltas, w)
    assert e == 4  # 2^4 = 16 >= 10
    assert (1 << e) >= 10
    # max spread: p75 tracks the bulk, capped at 2^28
    w[0, :] = 1
    w[1, :] = 1 << 27
    e = relax_ops.derive_delta_exp(deltas, w)
    assert 1 <= e <= 28
    # weight exactly 1 -> smallest usable exponent, still eligible
    w = np.full((4, 8), INF, np.int32)
    w[0, :] = 1
    assert relax_ops.derive_delta_exp(deltas, w) == 1


def test_plan_delta_exp_sticky_across_rebuilds():
    """build_plan keeps the previous usable exponent so metric churn
    never flips the (kernel, delta_exp) jit-cache class."""
    from openr_tpu.ops.edgeplan import build_plan

    adj_dbs, prefix_dbs = topologies.grid(4, node_labels=False)
    states, _ = topologies.build_states(adj_dbs, prefix_dbs)
    plan = build_plan(states[AREA])
    assert plan.delta_exp > 0
    churn = _Churn(adj_dbs, states, AREA)
    churn.set_metric("node-0-0", "node-0-1", 100000)
    plan2 = build_plan(states[AREA], prev=plan)
    assert plan2.delta_exp == plan.delta_exp


# -- solver-level parity helpers -------------------------------------------


def _trio(me, states, ps, **tpu_kw):
    cpu = SpfSolver(me)
    sync = TpuSpfSolver(me, spf_kernel="sync", **tpu_kw)
    buck = TpuSpfSolver(me, spf_kernel="bucketed", **tpu_kw)

    def solve(ctx):
        cpu_db = cpu.build_route_db(me, states, ps)
        s_db = sync.build_route_db(me, states, ps)
        b_db = buck.build_route_db(me, states, ps)
        assert_rib_equal(cpu_db, b_db, f"{ctx}: bucketed vs oracle")
        assert_rib_equal(cpu_db, s_db, f"{ctx}: sync vs oracle")
        # bit-identical promise: both kernels produce the same RIB
        assert b_db.unicast_routes == s_db.unicast_routes, ctx
        assert b_db.mpls_routes == s_db.mpls_routes, ctx
        return buck.last_device_stats

    return solve, buck


def _random_churn(solve, churn, seed, rounds=6):
    rng = np.random.default_rng(seed)
    metrics = (1, 3, 50, 100000)
    edges = churn.edges()
    down = None
    for i in range(rounds):
        if down is not None and rng.integers(2) == 0:
            u, v, su, sv = down
            churn.link_up(u, v, su, sv)
            ctx = f"round{i + 1}: up {u}<->{v}"
            down = None
        elif down is None and rng.integers(4) == 0:
            u, v = edges[rng.integers(len(edges))]
            down = (u, v, churn.dbs[u], churn.dbs[v])
            churn.link_down(u, v)
            ctx = f"round{i + 1}: down {u}<->{v}"
        else:
            u, v = edges[rng.integers(len(edges))]
            m = int(metrics[rng.integers(len(metrics))])
            churn.set_metric(u, v, m)
            ctx = f"round{i + 1}: metric {u}<->{v}={m}"
        solve(ctx)


# -- full path --------------------------------------------------------------


@pytest.mark.parametrize("gen,me", FABRICS, ids=FABRIC_IDS)
def test_full_path_churn_parity(gen, me):
    from openr_tpu.ops.edgeplan import build_plan

    adj_dbs, prefix_dbs = gen()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    # the eligibility ladder is part of the contract: plans with live
    # shift classes (grid4) derive a usable Δ and engage bucketed;
    # all-residual plans (mesh5, this fat_tree) derive 0 and the solver
    # falls back to sync automatically — exactness either way
    expect = (
        "bucketed" if build_plan(states[AREA]).delta_exp > 0 else "sync"
    )
    solve, buck = _trio(me, states, ps)
    st = solve("cold")
    assert st.get("spf_kernel") == expect, (expect, st)
    if expect == "bucketed":
        assert int(st.get("bucket_epochs") or 0) > 0, st
    else:
        assert int(st.get("bucket_epochs") or 0) == 0, st
    assert int(st.get("rounds") or 0) > 0, st
    _random_churn(solve, _Churn(adj_dbs, states, AREA), seed=13)


def test_full_path_uniform_and_max_spread_metrics():
    """Δ-quantization boundaries: uniform metrics put every edge in one
    light bucket (ladder does all the work); max-spread metrics push the
    flapped edges heavy (handoff relax does). Both must stay exact."""
    adj_dbs, prefix_dbs = topologies.grid(4, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    solve, _ = _trio("node-1-1", states, ps)
    solve("uniform")
    churn = _Churn(adj_dbs, states, AREA)
    # max spread: a few edges near MAX_METRIC, the rest at 1
    churn.set_metric("node-0-0", "node-0-1", 100_000_000)
    churn.set_metric("node-2-2", "node-3-2", 100_000_000)
    churn.set_metric("node-1-0", "node-1-1", 1)
    solve("max-spread")


def test_ineligible_plan_falls_back_to_sync():
    """A 2-node fabric has residual-only edges (no shift classes with
    finite weights survive padding on every topology) — or at minimum a
    plan may derive delta_exp=0; either way the solver must resolve the
    dispatch to the sync kernel and still be exact. Forced here via the
    knob ladder: spf_kernel=sync never reports bucketed stats."""
    adj_dbs, prefix_dbs = topologies.grid(4, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    tpu = TpuSpfSolver("node-1-1", spf_kernel="sync")
    cpu_db = SpfSolver("node-1-1").build_route_db("node-1-1", states, ps)
    tpu_db = tpu.build_route_db("node-1-1", states, ps)
    assert_rib_equal(cpu_db, tpu_db, "forced sync")
    st = tpu.last_device_stats
    assert st.get("spf_kernel") == "sync", st
    assert int(st.get("bucket_epochs") or 0) == 0, st


def test_spf_kernel_knob_validation():
    with pytest.raises(ValueError):
        TpuSpfSolver("node-0", spf_kernel="quantum")
    from openr_tpu.config import Config, ConfigError, OpenrConfig

    cfg = OpenrConfig(node_name="n1")
    cfg.decision_config.spf_kernel = "quantum"
    with pytest.raises(ConfigError):
        Config(cfg)
    cfg.decision_config.spf_kernel = "sync"
    Config(cfg)


# -- incremental path -------------------------------------------------------


def test_incremental_path_churn_parity():
    """Warm seed-from-previous solves under the bucketed kernel: same
    trio discipline as test_incremental_spf, with the warm bucketed RIB
    additionally pinned to the warm sync RIB every round."""
    adj_dbs, prefix_dbs = topologies.grid(4, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-1-1"
    cpu = SpfSolver(me)
    sync_i = TpuSpfSolver(me, spf_kernel="sync", incremental_spf=True)
    buck_i = TpuSpfSolver(me, spf_kernel="bucketed", incremental_spf=True)

    engaged = 0

    def solve(ctx):
        nonlocal engaged
        cpu_db = cpu.build_route_db(me, states, ps)
        s_db = sync_i.build_route_db(me, states, ps)
        b_db = buck_i.build_route_db(me, states, ps)
        assert_rib_equal(cpu_db, b_db, f"{ctx}: warm bucketed vs oracle")
        assert b_db.unicast_routes == s_db.unicast_routes, ctx
        st = buck_i.last_device_stats
        if st.get("incremental") and not st.get("fell_back"):
            engaged += 1

    solve("cold")
    churn = _Churn(adj_dbs, states, AREA)
    rng = np.random.default_rng(29)
    edges = [e for e in churn.edges() if me not in e]
    for i in range(6):
        u, v = edges[rng.integers(len(edges))]
        m = int((1, 7, 40, 90000)[rng.integers(4)])
        churn.set_metric(u, v, m)
        solve(f"round{i + 1}: {u}<->{v}={m}")
    # metric-only churn away from the vantage must take the warm lane
    assert engaged >= 3, engaged


# -- a residual split over several rows a destination ------------------------

# tests/test_edgeplan.py's graphs whose residual is narrower than its
# widest destination whatever a row costs, and a vantage in each
SPLIT_VANTAGE = {"fabric": "pod000-rsw00", "wan": "r01-acc0000"}


SPLIT_PATHS = {
    "full": dict(incremental_spf=False),
    "incr": dict(incremental_spf=True),
    # rows sharded over 'graph': a destination's rows may lie on two
    # shards, whose candidates the plane's pmin combines
    "multichip": dict(multichip_n_cap_threshold=4, multichip_batch=4),
    "multichip-incr": dict(
        incremental_spf=True, multichip_n_cap_threshold=4, multichip_batch=4
    ),
}


@pytest.mark.parametrize("path", sorted(SPLIT_PATHS))
@pytest.mark.parametrize("graph", sorted(SPLIT_VANTAGE))
def test_split_mirror_parity(graph, path):
    """An increase, a decrease and a restore on an edge into a
    destination that spans several residual rows: sync and bucketed, on
    the full and on the incremental path of either tier, against the CPU
    oracle and each other."""
    me = SPLIT_VANTAGE[graph]
    adj_dbs, prefix_dbs = SPLIT_GRAPHS[graph]()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    incremental = "incr" in path
    solve, buck = _trio(me, states, ps, **SPLIT_PATHS[path])
    solve("cold")
    assert bool(buck.last_timing.get("multichip")) == ("multichip" in path)
    plan = buck._area_dev[AREA].plan
    assert plan.occupancy()["residual_split_rows"] > 0
    # the destination with most rows, and the source of an edge in its
    # first row that is not the vantage's own
    root = plan.node_index[me]
    v, mine = max(
        ((v, r) for v, r in _rows_of(plan).items() if v != root),
        key=lambda kv: len(kv[1]),
    )
    assert len(mine) > 1
    u = next(int(x) for x in plan.res_nbr[mine[0]] if x not in (-1, root))
    a, b = plan.node_names[u], plan.node_names[v]
    churn = _Churn(adj_dbs, states, AREA)
    base = next(
        x.metric for x in churn.dbs[a].adjacencies if x.other_node_name == b
    )
    warm = 0
    for ctx, metric in (
        ("increase", 3 * base + 2), ("decrease", base + 1),
        ("restore", base),
    ):
        churn.set_metric(a, b, metric)
        st = solve(f"{ctx}: {a} <-> {b} = {metric}")
        warm += bool(st.get("incremental") and not st.get("fell_back"))
    assert buck._area_dev[AREA].plan is plan  # the delta path, no rebuild
    if incremental:
        assert warm >= 2, warm


# -- multichip path ---------------------------------------------------------


@pytest.mark.parametrize("gen,me", FABRICS, ids=FABRIC_IDS)
def test_multichip_path_churn_parity(gen, me):
    from openr_tpu.ops.edgeplan import build_plan

    adj_dbs, prefix_dbs = gen()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    eligible = build_plan(states[AREA]).delta_exp > 0
    solve, buck = _trio(
        me, states, ps,
        multichip_n_cap_threshold=4, multichip_batch=4,
    )
    st = solve("cold")
    if eligible:
        assert st.get("spf_kernel") == "bucketed", st
        # one pmin per bucket EPOCH: halo count == epoch count
        assert st.get("halo_exchanges") == st.get("bucket_epochs"), st
    else:
        assert st.get("spf_kernel") == "sync", st
        # sync in the multichip tier: one pmin per relaxation round
        assert st.get("halo_exchanges") == st.get("rounds"), st
    assert int(st.get("halo_exchanges") or 0) > 0, st
    tm = buck.last_timing
    assert tm.get("multichip"), tm
    _random_churn(solve, _Churn(adj_dbs, states, AREA), seed=31, rounds=4)


def test_multichip_halo_per_epoch_beats_sync_per_round():
    """The round-proportional traffic claim at test scale: under sync
    the halo count equals the relaxation rounds; under bucketed it
    equals the bucket epochs, which must be strictly fewer."""
    adj_dbs, prefix_dbs = topologies.grid(4, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    kw = dict(multichip_n_cap_threshold=4, multichip_batch=4)
    sync = TpuSpfSolver("node-1-1", spf_kernel="sync", **kw)
    buck = TpuSpfSolver("node-1-1", spf_kernel="bucketed", **kw)
    sync.build_route_db("node-1-1", states, ps)
    buck.build_route_db("node-1-1", states, ps)
    s_st, b_st = sync.last_device_stats, buck.last_device_stats
    assert s_st.get("halo_exchanges") == s_st.get("rounds") > 0, s_st
    assert 0 < b_st["halo_exchanges"] < s_st["halo_exchanges"], (
        s_st, b_st,
    )


# -- what-if path ------------------------------------------------------------


@pytest.mark.parametrize("gen,me", FABRICS, ids=FABRIC_IDS)
def test_whatif_path_sweep_parity(gen, me):
    """The N-1 sweep's verdict rows and returned distance planes must be
    identical under both kernels (the sweep oracle differential lives in
    test_whatif; here the two device kernels are pinned to each other
    bit-for-bit)."""
    from openr_tpu.decision.whatif import WhatIfEngine

    adj_dbs, prefix_dbs = gen()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)

    jobs = {}
    for kern in ("sync", "bucketed"):
        tpu = TpuSpfSolver(me, spf_kernel=kern)
        assert tpu.build_route_db(me, states, ps) is not None
        eng = WhatIfEngine(tpu)
        job = eng.plan_sweep(states, ps, order=1, return_dist=True)
        out = job.run()
        jobs[kern] = (job, out)
    (s_job, s_out), (b_job, b_out) = jobs["sync"], jobs["bucketed"]
    assert s_out["rows"] == b_out["rows"]
    assert s_out["scenarios"] == b_out["scenarios"] > 0
    assert len(s_job.dist_planes) == len(b_job.dist_planes)
    for sp, bp in zip(s_job.dist_planes, b_job.dist_planes):
        np.testing.assert_array_equal(sp, bp)
    # the bucketed sweep actually took the bucketed executable
    assert b_job.rounds > 0


# -- observability ----------------------------------------------------------


def test_rounds_flow_to_stats_and_timing():
    from openr_tpu.runtime.counters import counters

    adj_dbs, prefix_dbs = topologies.grid(4, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    tpu = TpuSpfSolver("node-1-1", spf_kernel="bucketed")
    tpu.build_route_db("node-1-1", states, ps)
    tm = tpu.last_timing
    assert tm["spf_kernel"] == "bucketed", tm
    assert tm["rounds"] > 0, tm
    assert tm["bucket_epochs"] > 0, tm
    stats = counters.get_statistics("decision.device")
    assert "decision.device.rounds" in stats, stats
    assert "decision.device.bucket_epochs" in stats, stats
