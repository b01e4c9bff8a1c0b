"""Incremental device SSSP (ISSUE 7) — parity + fallback drills.

The incremental path (ops/incremental.py, tpu_solver.pipeline_for)
seeds each solve from the previous device-resident distance plane,
re-anchors the subtree behind any metric increase, and re-relaxes only
the affected cone. Its one promise is EXACT parity with a cold full
solve — same int32 fixpoint, same ECMP/LFA/UCMP planes — so every test
here compares three solvers on every churn step:

  cpu   the SpfSolver oracle (reference semantics)
  full  TpuSpfSolver with incremental_spf=False (cold path)
  incr  TpuSpfSolver with incremental_spf=True  (warm path)

and additionally asserts the warm RIB is identical to the cold RIB.
Fallback ladders (in-kernel cone fraction, host gates: zero-weight
edges, dirty-set overflow) are driven explicitly and checked against
the decision.solver.incr.* counter split.
"""

import functools

import numpy as np
import pytest

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.runtime.counters import counters
from openr_tpu.types import Adjacency, AdjacencyDatabase
from tests.test_tpu_solver import assert_rib_equal

ME = "node-2-2"


def _cnt(key):
    return int(counters.get_counter(key) or 0)


def _grid():
    adj_dbs, prefix_dbs = topologies.grid(5, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    return adj_dbs, states, ps


def _rebuild(db, adjs, area="0"):
    return AdjacencyDatabase(
        this_node_name=db.this_node_name,
        adjacencies=tuple(adjs),
        node_label=db.node_label,
        area=area,
    )


class _Churn:
    """Symmetric churn driver over a live LinkState: metric changes and
    link down/up applied to BOTH directions of an edge, through the
    real update path (changelog -> device scatter)."""

    def __init__(self, adj_dbs, states, area="0"):
        self.area = area
        self.states = states
        self.dbs = {db.this_node_name: db for db in adj_dbs}

    def _put(self, db):
        self.dbs[db.this_node_name] = db
        self.states[self.area].update_adjacency_database(db)

    def set_metric(self, u, v, metric):
        for a_name, b_name in ((u, v), (v, u)):
            db = self.dbs[a_name]
            adjs = [
                Adjacency(**{**a.__dict__, "metric": metric})
                if a.other_node_name == b_name else a
                for a in db.adjacencies
            ]
            self._put(_rebuild(db, adjs, self.area))

    def link_down(self, u, v):
        for a_name, b_name in ((u, v), (v, u)):
            db = self.dbs[a_name]
            adjs = [
                a for a in db.adjacencies if a.other_node_name != b_name
            ]
            self._put(_rebuild(db, adjs, self.area))

    def link_up(self, u, v, saved_u, saved_v):
        self._put(saved_u)
        self._put(saved_v)

    def edges(self):
        out = []
        for name, db in sorted(self.dbs.items()):
            for a in db.adjacencies:
                if name < a.other_node_name:
                    out.append((name, a.other_node_name))
        return out


def _trio(states, ps, **incr_kw):
    cpu = SpfSolver(ME)
    full = TpuSpfSolver(ME, incremental_spf=False)
    incr = TpuSpfSolver(ME, incremental_spf=True, **incr_kw)

    def solve(ctx):
        cpu_db = cpu.build_route_db(ME, states, ps)
        full_db = full.build_route_db(ME, states, ps)
        incr_db = incr.build_route_db(ME, states, ps)
        assert_rib_equal(cpu_db, incr_db, f"{ctx}: warm vs oracle")
        assert_rib_equal(cpu_db, full_db, f"{ctx}: cold vs oracle")
        # bit-identical promise: warm output == cold output exactly
        assert incr_db.unicast_routes == full_db.unicast_routes, ctx
        assert incr_db.mpls_routes == full_db.mpls_routes, ctx
        return incr.last_device_stats

    return solve, incr


def test_randomized_churn_property_parity():
    """Randomized metric inc/dec + link down/up sequence: the warm path
    must match the oracle AND the cold device path exactly on every
    step, whichever lane (incremental or fallback) each step takes."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solve, incr = _trio(states, ps)
    solve("round0")  # first solve: full (no previous plane)

    rng = np.random.default_rng(7)
    metrics = (1, 3, 50, 100000)
    edges = churn.edges()
    engaged = 0
    down = None  # at most one link down at a time
    for i in range(10):
        if down is not None and rng.integers(3) == 0:
            u, v, su, sv = down
            churn.link_up(u, v, su, sv)
            ctx = f"round{i + 1}: up {u}<->{v}"
            down = None
        elif down is None and rng.integers(4) == 0:
            while True:
                u, v = edges[rng.integers(len(edges))]
                # never isolate the vantage: keep ME's links intact so
                # the lane stays on the incremental-eligible shape
                if ME not in (u, v):
                    break
            down = (u, v, churn.dbs[u], churn.dbs[v])
            churn.link_down(u, v)
            ctx = f"round{i + 1}: down {u}<->{v}"
        else:
            u, v = edges[rng.integers(len(edges))]
            m = int(metrics[rng.integers(len(metrics))])
            churn.set_metric(u, v, m)
            ctx = f"round{i + 1}: metric {u}<->{v}={m}"
        st = solve(ctx)
        if st.get("incremental"):
            engaged += 1
    # the sequence must actually exercise the warm path, not fall back
    # on every round (root-link churn legitimately falls back)
    assert engaged >= 5, engaged


def test_metric_increase_reanchors_subtree():
    """Deterministic metric-increase drill: raising a victim node's
    link metrics invalidates the subtree hanging off its parent edges
    (cone > 0) and still reproduces the cold solve exactly."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solve, incr = _trio(states, ps)
    solve("cold")

    victim = adj_dbs[1].this_node_name
    nbrs = [a.other_node_name for a in churn.dbs[victim].adjacencies]
    for nb in nbrs:
        churn.set_metric(victim, nb, 50)  # 1 -> 50: pure increase
    st = solve("increase-50")
    assert st.get("incremental") is True, st
    assert not st.get("fell_back"), st
    # the victim's parent edge is in the flapped set, so its subtree
    # re-anchors: a non-empty cone, then exact re-relaxation
    assert st.get("cone", 0) > 0, st
    for nb in nbrs:
        churn.set_metric(victim, nb, 100000)  # 50 -> 100000
    st = solve("increase-100000")
    assert st.get("incremental") is True, st
    assert st.get("cone", 0) > 0, st
    # decrease back down: prev plane is a pure over-estimate, no cone
    for nb in nbrs:
        churn.set_metric(victim, nb, 2)
    st = solve("decrease-2")
    assert st.get("incremental") is True, st


def test_cone_fraction_fallback_boundary():
    """incremental_cone_frac=0.0 keeps the incremental kernel but makes
    ANY non-empty cone exceed the limit: the kernel must select the
    cold seed plane in-device (fell_back), count a full fallback (not
    an incremental solve), and still produce the exact RIB."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solve, incr = _trio(states, ps, incremental_cone_frac=0.0)
    solve("cold")

    victim = adj_dbs[1].this_node_name
    s0, f0 = (_cnt("decision.solver.incr.solves"),
              _cnt("decision.solver.incr.full_fallbacks"))
    for a in churn.dbs[victim].adjacencies:
        churn.set_metric(victim, a.other_node_name, 60)  # increase
        break
    st = solve("frac0-increase")
    assert st.get("incremental") is True, st
    assert st.get("cone", 0) > 0, st
    assert st.get("fell_back") is True, st
    assert _cnt("decision.solver.incr.full_fallbacks") > f0
    assert _cnt("decision.solver.incr.solves") == s0


def test_zero_weight_edge_gates_to_full():
    """A zero-metric link makes equal-distance parent cycles possible,
    defeating subtree invalidation — the plan's sticky has_zero_w flag
    must force the host full-solve fallback (with the counter split
    showing it) while parity holds."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solve, incr = _trio(states, ps)
    solve("cold")
    churn.set_metric("node-0-0", "node-0-1", 0)
    s0, f0 = (_cnt("decision.solver.incr.solves"),
              _cnt("decision.solver.incr.full_fallbacks"))
    st = solve("zero-weight")
    assert not st.get("incremental"), st
    assert _cnt("decision.solver.incr.full_fallbacks") > f0
    assert _cnt("decision.solver.incr.solves") == s0
    # the gate is sticky: later non-zero churn still solves full
    churn.set_metric("node-0-0", "node-0-1", 5)
    st = solve("after-zero")
    assert not st.get("incremental"), st


def test_dirty_overflow_gates_to_full(monkeypatch):
    """A churn batch larger than the biggest dirty bucket must take the
    host full-solve fallback instead of compiling an unbounded-cap
    incremental executable."""
    from openr_tpu.decision import tpu_solver as ts

    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solve, incr = _trio(states, ps)
    solve("cold")
    monkeypatch.setattr(ts, "_DIRTY_BUCKETS", (1,))
    victim = adj_dbs[1].this_node_name
    for a in churn.dbs[victim].adjacencies:
        churn.set_metric(victim, a.other_node_name, 7)
    f0 = _cnt("decision.solver.incr.full_fallbacks")
    st = solve("overflow")
    assert not st.get("incremental"), st
    assert _cnt("decision.solver.incr.full_fallbacks") > f0
    # with real buckets restored the next delta re-engages
    monkeypatch.setattr(ts, "_DIRTY_BUCKETS", (64, 256, 1024, 4096))
    churn.set_metric(victim, a.other_node_name, 9)
    st = solve("re-engage")
    assert st.get("incremental") is True, st


def test_incr_namespace_counters_isolated():
    """The incremental factories compile under the xla_cache "incr"
    namespace: their hit/miss/eviction counters exist separately and a
    steady churn evicts nothing."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solve, incr = _trio(states, ps)
    solve("cold")
    main0 = _cnt("xla_cache.factory_misses")
    hits0 = _cnt("xla_cache.incr_factory_hits")
    for i in range(3):
        churn.set_metric("node-0-0", "node-0-1", 10 + i)
        st = solve(f"r{i}")
        assert st.get("incremental") is True, st
    assert _cnt("xla_cache.incr_factory_hits") > hits0
    assert _cnt("xla_cache.incr_executable_evictions") == 0
    # warm churn compiles nothing new in the main (full-solve) namespace
    assert _cnt("xla_cache.factory_misses") == main0


def test_consolidate_and_drain_journal_units():
    """drain_dirty consolidation (last-new / first-old per slot) and the
    drain-journal merge used to bridge a vantage's previous plane over
    any number of syncs it slept through."""
    from collections import deque
    from types import SimpleNamespace

    from openr_tpu.decision.tpu_solver import _merge_drain_log
    from openr_tpu.ops.edgeplan import _consolidate

    idx, val, old = _consolidate(
        [(0, 1, 5, 1), (0, 1, 7, 5), (2, 3, 4, 9)], 10
    )
    assert idx.tolist() == [1, 23]
    assert val.tolist() == [7, 4]  # last new wins
    assert old.tolist() == [1, 9]  # first old wins

    ad = SimpleNamespace(
        drain_epoch=3,
        drain_log=deque([(2, {5: 1}, {}), (3, {5: 9, 7: 2}, {1: 4})]),
    )
    merged = _merge_drain_log(ad, 1)
    assert merged == ({5: 1, 7: 2}, {1: 4})  # first old per slot
    assert _merge_drain_log(ad, 3) == ({}, {})
    # gap: epoch 1's entry already rotated out of the journal
    assert _merge_drain_log(ad, 0) is None
    # reset marker (rebuild / residual-shape change) poisons the window
    ad.drain_log = deque([(2, None, None), (3, {5: 9}, {})])
    assert _merge_drain_log(ad, 1) is None


def test_incremental_solve_exact_on_link_down_up():
    """Deterministic link down -> up round trip away from the vantage:
    both transitions take the warm path and match the cold solve."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solve, incr = _trio(states, ps)
    solve("cold")
    u, v = "node-1-1", "node-1-2"
    su, sv = churn.dbs[u], churn.dbs[v]
    churn.link_down(u, v)
    st = solve("down")
    assert st.get("incremental") is True, st
    churn.link_up(u, v, su, sv)
    st = solve("up")
    assert st.get("incremental") is True, st


# -- a destination spread over several residual rows (ops/edgeplan.py) ------


def _hub_graph():
    """node-00 (the vantage) reaches the hub node-09 through node-01 at
    1 + 1 and through node-02..05 at 10 + 10; node-10 and node-11 hang
    behind the hub. Every edge is residual (no index offset has eight
    edges), and at width 2 the hub's in-edges fill three rows: [01, 02],
    [03, 04], [05, 10]. In node-01's lane the one tight parent of the hub
    sits in its FIRST row and the later rows hold none."""
    from openr_tpu.models.topologies import _adj, _mk_dbs
    from openr_tpu.types import PrefixForwardingAlgorithm

    links = [("node-00", "node-01", 1), ("node-01", "node-09", 1),
             ("node-09", "node-10", 1), ("node-10", "node-11", 1)]
    for far in ("node-02", "node-03", "node-04", "node-05"):
        links += [("node-00", far, 10), (far, "node-09", 10)]
    nodes: dict = {}
    for a, b, metric in links:
        nodes.setdefault(a, []).append(_adj(a, b, metric))
        nodes.setdefault(b, []).append(_adj(b, a, metric))
    nodes = dict(sorted(nodes.items()))
    adj_dbs, prefix_dbs = _mk_dbs(
        nodes, "0", PrefixForwardingAlgorithm.SP_ECMP, False
    )
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    return adj_dbs, states, ps


@pytest.mark.parametrize("tier", ["single", "multichip"])
def test_increase_behind_a_first_row_parent_invalidates_the_cone(
    tier, monkeypatch
):
    """The parent plane combines a split destination's rows by max. With
    a plain set the hub's last row, which finds no tight parent, writes -1
    over the first row's find, the increased edge seeds no cone, and the
    hub and what hangs behind it keep their old, too short distances
    (checked by hand against `.set` in both tiers: the hub at metric 2 where 20 is right)."""
    from openr_tpu.ops import edgeplan

    monkeypatch.setattr(edgeplan, "_residual_width", lambda degrees: 2)
    me = "node-00"
    adj_dbs, states, ps = _hub_graph()
    churn = _Churn(adj_dbs, states)
    kw = {} if tier == "single" else dict(
        multichip_n_cap_threshold=4, multichip_batch=4
    )
    cpu = SpfSolver(me)
    incr = TpuSpfSolver(me, incremental_spf=True, spf_kernel="sync", **kw)

    def solve(ctx):
        assert_rib_equal(
            cpu.build_route_db(me, states, ps),
            incr.build_route_db(me, states, ps), ctx,
        )
        return incr.last_device_stats

    solve("cold")
    plan = incr._area_dev["0"].plan
    hub, via = plan.node_index["node-09"], plan.node_index["node-01"]
    rows = np.flatnonzero(plan.res_rows == hub)
    assert plan.shift_edges == 0 and len(rows) == 3
    assert plan.res_nbr[rows[0], 0] == via
    assert via not in plan.res_nbr[rows[1:]]
    if tier == "multichip":
        assert incr.last_timing.get("multichip")

    churn.set_metric("node-01", "node-09", 50)
    st = solve("increase on the edge whose parent sits in the first row")
    assert st.get("incremental") and not st.get("fell_back"), st
    # the hub and the two behind it, in node-01's lane at the least
    assert st.get("cone") >= 3, st
    churn.set_metric("node-01", "node-09", 4)
    st = solve("decrease")
    assert st.get("incremental"), st
    churn.set_metric("node-01", "node-09", 1)
    st = solve("restore")
    assert st.get("incremental"), st


# -- the parent forest itself (ops/incremental._parent_plane) ---------------

_INF = 1 << 29


def _forest_case(lanes: int, width: int, seed: int):
    """A random mirror of 256 nodes — two shift classes and a residual ELL
    `width` wide — with what the parent plane has to step over: hubs whose
    in-edges span several rows, rows in no order, pad rows that carry
    plausible neighbours and weights, pad slots (neighbour -1) in the
    middle of a row with a finite weight, masked (`INF`) weights on real
    slots, nodes nothing reaches and lanes with no source. `prev` is the
    fixpoint of the relaxation over it from one source a lane."""
    rng = np.random.default_rng(seed)
    n_cap, s_cap = 256, 2
    deltas = np.array([1, 37], np.int32)
    shift_w = np.where(
        rng.random((s_cap, n_cap)) < 0.3,
        rng.integers(1, 20, (s_cap, n_cap)), _INF,
    ).astype(np.int32)
    islands = rng.choice(n_cap, 12, replace=False)  # no in-edge at all
    for k in range(s_cap):
        shift_w[k, (islands - deltas[k]) % n_cap] = _INF
    rows, nbrs, ws = [], [], []
    hubs = set(rng.choice(n_cap, 6, replace=False)) - set(islands)
    for v in range(n_cap):
        if v in islands:
            continue
        deg = 2 * width + 3 if v in hubs else int(rng.integers(0, 5))
        src = rng.integers(0, n_cap, deg)
        w = np.where(rng.random(deg) < 0.15, _INF, rng.integers(1, 20, deg))
        hole = rng.random(deg) < 0.1  # a pad slot where an edge was
        src, w = np.where(hole, -1, src), np.where(hole, 3, w)
        for at in range(0, deg, width):
            pad = width - len(src[at:at + width])
            rows.append(v)
            nbrs.append(np.r_[src[at:at + width], np.full(pad, -1)])
            ws.append(np.r_[w[at:at + width], np.full(pad, _INF)])
    r_cap = 1 << int(np.ceil(np.log2(len(rows) + 5)))
    for _ in range(r_cap - len(rows)):  # pad rows: row -1, slots look real
        rows.append(-1)
        nbrs.append(rng.integers(0, n_cap, width))
        ws.append(rng.integers(1, 20, width))
    order = rng.permutation(r_cap)
    res_rows = np.array(rows, np.int32)[order]
    res_nbr = np.array(nbrs, np.int32)[order]
    res_w = np.array(ws, np.int32)[order]

    sources = rng.choice(n_cap, min(lanes, 52), replace=False)
    prev = np.full((lanes, n_cap), _INF, np.int64)
    prev[np.arange(len(sources)), sources] = 0
    live = (res_rows >= 0)[:, None] & (res_nbr >= 0) & (res_w < _INF)
    while True:
        new = prev.copy()
        for k in range(s_cap):
            cand = np.roll(prev + shift_w[k][None], deltas[k], axis=1)
            new = np.minimum(new, cand)
        cand = np.where(live[None], prev[:, res_nbr] + res_w[None], _INF)
        np.minimum.at(
            new, (slice(None), np.where(res_rows >= 0, res_rows, 0)),
            np.where((res_rows >= 0)[None], cand.min(axis=2), _INF),
        )
        new = np.minimum(new, _INF)
        if (new == prev).all():
            break
        prev = new
    return deltas, shift_w, res_rows, res_nbr, res_w, prev.astype(np.int32)


def _forest_reference(deltas, shift_w, res_rows, res_nbr, res_w, prev):
    """The forest, node by node: the first shift class with a tight edge
    into v; else, of every residual row of v, the first tight slot in
    slot order, and the largest of those finds where v spans several rows
    (any tight edge serves; the device combines the rows by max); else -1."""
    lanes, n_cap = prev.shape
    par = np.full((lanes, n_cap), -1, np.int32)

    def tight(d, u, w, v):
        return prev[d, u] < _INF and w < _INF and prev[d, u] + w == prev[d, v]

    for d in range(lanes):
        for v in range(n_cap):
            for k, dk in enumerate(deltas):
                u = (v - dk) % n_cap
                if tight(d, u, shift_w[k, u], v):
                    par[d, v] = u
                    break
            if par[d, v] >= 0:
                continue
            for r in np.flatnonzero(res_rows == v):
                for u, w in zip(res_nbr[r], res_w[r]):
                    if u >= 0 and tight(d, u, w, v):
                        par[d, v] = max(par[d, v], u)
                        break
    return par


@pytest.mark.parametrize("width", [2, 8, 64])
@pytest.mark.parametrize("lanes", [4, 8, 64])
def test_parent_forest_is_the_first_tight_slot(lanes, width):
    """`_parent_plane` against the plain reference, at the lanes of an
    access router, a rack switch and an aggregation router and at widths
    below, at and above the deployments' 8."""
    import jax

    from openr_tpu.ops.incremental import _parent_plane

    case = _forest_case(lanes, width, seed=1000 * lanes + width)
    deltas, shift_w, res_rows, res_nbr, res_w, prev = case
    want = _forest_reference(*case)
    got = np.asarray(jax.jit(
        lambda *a: _parent_plane(
            *a, s_cap=2, has_res=True, n_cap=prev.shape[1], d_cap=lanes
        )
    )(deltas, shift_w, res_rows, res_nbr, res_w, prev))
    np.testing.assert_array_equal(got, want)
    # the case holds what it says: parents from both halves, split
    # destinations among them, and nodes with none
    reached = prev < _INF
    assert (want[reached] >= 0).sum() > lanes and (want[~reached] < 0).all()
    split = np.flatnonzero(np.bincount(res_rows[res_rows >= 0]) > 1)
    assert len(split) and (want[:, split] >= 0).any()


def test_parent_key_holds_the_widest_shapes():
    """The packed key `slot * n_cap + neighbour` at the widest mirror the
    repo builds, 256 slots a row over 2^20 nodes: the last slot's key is
    2^28 - 1, under the sentinel, and gives its neighbour back. A shape
    whose keys would pass the sentinel is refused when traced."""
    import jax

    from openr_tpu.ops.incremental import _parent_plane

    n_cap, k_cap = 1 << 20, 256
    prev = np.full((1, n_cap), _INF, np.int32)
    prev[0, [n_cap - 1, 5, 9]] = [7, 10, 8]
    res_rows = np.array([5, 9, -1, -1], np.int32)
    res_nbr = np.full((4, k_cap), -1, np.int32)
    res_w = np.full((4, k_cap), _INF, np.int32)
    res_nbr[0, -1], res_w[0, -1] = n_cap - 1, 3  # tight, in the last slot
    res_nbr[1, 0], res_w[1, 0] = n_cap - 1, 1  # tight, in the first
    res_nbr[1, -1], res_w[1, -1] = 5, 4  # not tight
    args = (np.array([1], np.int32), np.full((1, n_cap), _INF, np.int32),
            res_rows, res_nbr, res_w, prev)

    def plane(*a):
        return _parent_plane(
            *a, s_cap=1, has_res=True, n_cap=n_cap, d_cap=1
        )

    par = np.asarray(jax.jit(plane)(*args))
    assert par[0, 5] == n_cap - 1 and par[0, 9] == n_cap - 1
    assert (np.delete(par[0], [5, 9]) == -1).all()
    wide = jax.ShapeDtypeStruct((4, 4096), np.int32)
    with pytest.raises(AssertionError):
        jax.eval_shape(plane, *args[:3], wide, wide, args[5])


# -- how often the two fixpoint loops test for change (ISSUE 39) ------------

_N, _CHAIN, _ROOT = 16, 14, 15  # n_cap, the chain 0 - 1 - ... - 13, the vantage


def _chain_weights(**links):
    """Directed weights of the chain at unit metrics, both directions of
    link `a_b` at the metric given."""
    w = {}
    for i in range(_CHAIN - 1):
        w[i, i + 1] = w[i + 1, i] = 1
    for name, metric in links.items():
        a, b = map(int, name[1:].split("_"))
        w[a, b] = w[b, a] = metric
    return w


# event -> (old links, new links, cone_limit, the cone it invalidates)
_CHAIN_EVENTS = {
    "restore": (dict(l6_7=_INF), {}, 1000, range(0)),
    "leaf": ({}, dict(l12_13=5), 1000, range(13, 14)),
    "spread": ({}, dict(l2_3=5), 1000, range(3, 14)),  # depth 10
    "fallback": ({}, dict(l2_3=5), 3, range(3, 14)),
    "decrease": (dict(l2_3=5), {}, 1000, range(0)),
}


def _chain_mirror(has_res: bool, w: dict):
    """The chain as the device holds it: two shift classes (+1, -1), or
    one residual row a destination, two slots wide, beside a shift class
    with no edge (offset 5: the cone's closure follows parents, not
    weights, and no node's parent lies 5 behind it). Returns (mirror arrays, flat slot of each directed edge
    in the plane that carries it)."""
    slot = {}
    if has_res:
        deltas = np.array([5], np.int32)
        shift_w = np.full((1, _N), _INF, np.int32)
        res_rows = np.where(np.arange(_N) < _CHAIN, np.arange(_N), -1)
        res_nbr = np.full((_N, 2), -1, np.int32)
        res_w = np.full((_N, 2), _INF, np.int32)
        for v in range(_CHAIN):
            for c, u in enumerate((v - 1, v + 1)):
                if (u, v) in w:
                    res_nbr[v, c], res_w[v, c] = u, w[u, v]
                    slot[u, v] = v * 2 + c
    else:
        deltas = np.array([1, _N - 1], np.int32)
        shift_w = np.full((2, _N), _INF, np.int32)
        res_rows = np.full(1, -1, np.int32)
        res_nbr = np.full((1, 1), -1, np.int32)
        res_w = np.full((1, 1), _INF, np.int32)
        for (u, v), metric in w.items():
            k = 0 if v == u + 1 else 1
            shift_w[k, u] = metric
            slot[u, v] = k * _N + u
    return (deltas, shift_w, res_rows.astype(np.int32), res_nbr, res_w), slot


def _chain_fixpoint(w: dict, dist0: np.ndarray):
    """Jacobi rounds over the chain from `dist0` [N]: the fixpoint and
    the number of rounds in which a distance moved."""
    dist, moved = dist0.astype(np.int64), 0
    while True:
        new = dist.copy()
        for (u, v), metric in w.items():
            new[v] = min(new[v], dist[u] + metric)
        new = np.minimum(new, _INF)
        if (new == dist).all():
            return dist.astype(np.int32), moved
        dist, moved = new, moved + 1


def _chain_cold():
    cold = np.full(_N, _INF, np.int32)
    cold[0] = 0  # the vantage's one live link lands on node 0
    return cold


@functools.lru_cache(maxsize=None)
def _chain_kernels(has_res: bool, quantum: int):
    """(incremental, cold) executables of the chain's shape class."""
    import jax

    from openr_tpu.decision.tpu_solver import _plan_sssp
    from openr_tpu.ops import relax as relax_ops
    from openr_tpu.ops.incremental import jit_incremental_sssp

    shape = dict(
        s_cap=1 if has_res else 2, has_res=has_res, n_cap=_N, d_cap=2,
        max_trips=relax_ops.max_trips(_N),
    )
    return (
        jit_incremental_sssp(**shape, quantum=quantum),
        jax.jit(functools.partial(_plan_sssp, **shape)),
    )


@pytest.mark.parametrize("event", sorted(_CHAIN_EVENTS))
@pytest.mark.parametrize("has_res", [True, False], ids=["res", "shift"])
@pytest.mark.parametrize("quantum", [1, 2, 8])
def test_loops_stop_when_converged_at_any_quantum(quantum, has_res, event):
    """The planes do not depend on how often the loops test for change:
    `dist`, `cone` and `fell_back` are the cold solve's at every quantum,
    with and without a residual. What depends on it is counted: the cone
    loop makes no pass where no edge grew, one at a leaf and depth + 1
    behind an edge that heads a subtree (in whole trips of the quantum);
    the relaxation makes k + 1 rounds at quantum 1, k the rounds in which
    a distance moved, and whole trips of 8 at 8."""
    old_links, new_links, cone_limit, cone_nodes = _CHAIN_EVENTS[event]
    w_old, w_new = _chain_weights(**old_links), _chain_weights(**new_links)
    mirror, slot = _chain_mirror(has_res, w_new)
    dirty = sorted(e for e in w_new if w_new[e] != w_old[e])

    def dirty_buffers(edges, plane):
        # pads index one past the plane they would write: dropped
        idx = np.full(64, plane.size, np.int32)
        old = np.zeros(64, np.int32)
        idx[:len(edges)] = [slot[e] for e in edges]
        old[:len(edges)] = [w_old[e] for e in edges]
        return idx, old

    s_dirty = dirty_buffers([] if has_res else dirty, mirror[1])
    r_dirty = dirty_buffers(dirty if has_res else [], mirror[4])
    # lane 0 leaves by the vantage's live link, lane 1 is a pad lane
    seeds_nbr = np.array([0, 0], np.int32)
    seeds_w = np.array([1, _INF], np.int32)
    prev = np.full((2, _N), _INF, np.int32)
    prev[0], _ = _chain_fixpoint(w_old, _chain_cold())
    want, _ = _chain_fixpoint(w_new, _chain_cold())

    incr, cold = _chain_kernels(has_res, quantum)
    dist, trips, cone, fell_back, rounds, cone_passes = map(np.asarray, incr(
        *mirror, np.int32(_ROOT), seeds_nbr, seeds_w, prev,
        *s_dirty, *r_dirty, np.int32(cone_limit),
    ))
    cold_dist, _, _ = cold(*mirror, np.int32(_ROOT), seeds_nbr, seeds_w)
    np.testing.assert_array_equal(dist, np.asarray(cold_dist))
    np.testing.assert_array_equal(dist[0], want)
    assert (dist[1] == _INF).all()
    assert cone == len(cone_nodes)
    assert fell_back == (len(cone_nodes) > cone_limit)
    # the cone's closure: none without a seed, else depth + 1 passes
    # rounded up to whole trips
    depth = len(cone_nodes) - 1
    assert cone_passes == (
        quantum * (-(-depth // quantum) + 1) if cone_nodes else 0
    )
    # the re-relaxation, from the seed the kernel must have chosen
    dist0 = prev[0].copy()
    dist0[list(cone_nodes)] = _INF
    dist0[0] = 0
    _, moved = _chain_fixpoint(w_new, _chain_cold() if fell_back else dist0)
    assert rounds == quantum * (-(-moved // quantum) + 1)
    assert trips * quantum == rounds


@pytest.mark.parametrize("tier", ["single", "multichip"])
def test_cone_passes_reach_the_host(tier):
    """The cone loop's executed passes ride the scalar tail to
    `last_device_stats`, `last_timing`, the `tpu.device_wait` span and
    the counters `decision.tpu.cone_passes` / `.cone_skips`. On one chip
    over a residual the loop tests after every pass: depth + 1 passes
    behind an increase, none where no edge grew. The multichip twin
    keeps trips of 8 and starts its loop every time."""
    me = "node-00"
    adj_dbs, states, ps = _hub_graph()
    churn = _Churn(adj_dbs, states)
    kw = {} if tier == "single" else dict(
        multichip_n_cap_threshold=4, multichip_batch=4
    )
    cpu = SpfSolver(me)
    incr = TpuSpfSolver(me, incremental_spf=True, spf_kernel="sync", **kw)

    def solve(ctx):
        assert_rib_equal(
            cpu.build_route_db(me, states, ps),
            incr.build_route_db(me, states, ps), ctx,
        )
        return incr.last_device_stats

    st = solve("cold")
    assert "cone_passes" not in st and incr.last_timing["cone_passes"] == 0
    assert bool(incr.last_timing.get("multichip")) == (tier == "multichip")
    want = {  # metric of node-01 <-> node-09 -> passes on one chip
        # the hub, node-10 behind it, node-11 behind that: depth 2
        50: 3,
        4: 0,
        1: 0,
    }
    for metric, passes in want.items():
        if tier == "multichip":
            # a trip that spreads the cone and one that finds it spread,
            # or the one trip that finds nothing to spread
            passes = 16 if passes else 8
        p0, s0 = (_cnt("decision.tpu.cone_passes"),
                  _cnt("decision.tpu.cone_skips"))
        churn.set_metric("node-01", "node-09", metric)
        st = solve(f"node-01 <-> node-09 = {metric}")
        assert st.get("incremental") and not st.get("fell_back"), st
        assert st["cone_passes"] == passes, st
        assert incr.last_timing["cone_passes"] == passes
        wait = next(
            attrs for name, _, _, _, attrs in incr.last_timing["spans"]
            if name == "tpu.device_wait"
        )
        assert wait["cone_passes"] == passes and wait["rounds"] == st["rounds"]
        assert _cnt("decision.tpu.cone_passes") - p0 == passes
        assert _cnt("decision.tpu.cone_skips") - s0 == (passes == 0)
        if tier == "single":
            # every edge is residual: a trip is one relaxation, so the
            # loop stops one round after the last that moved a distance
            assert st["trips"] == st["rounds"] < 8, st
        else:
            assert st["rounds"] % 8 == 0, st
