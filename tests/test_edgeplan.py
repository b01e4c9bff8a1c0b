"""Unit tests for the shift-decomposed device mirror (ops/edgeplan.py):
full-build decomposition, changelog delta application vs fresh rebuild,
and the natural node ordering."""

from dataclasses import replace

import numpy as np
import pytest

from openr_tpu.decision.link_state import LinkState
from openr_tpu.models import topologies
from openr_tpu.ops.edgeplan import (
    INF32E,
    build_plan,
    natural_key,
    sync_plan,
)
from openr_tpu.types import Adjacency, AdjacencyDatabase


def dense_w(plan):
    """Reconstruct the effective directed weight matrix from a plan —
    min over all slots that map u->v (the semantics the relax computes)."""
    n = plan.n_cap
    w = np.full((n, n), int(INF32E), np.int64)
    for k in range(plan.s_cap):
        d = int(plan.deltas[k])
        for u in range(n):
            v = u + d
            if 0 <= v < n and plan.shift_w[k, u] < INF32E:
                w[u, v] = min(w[u, v], int(plan.shift_w[k, u]))
    for row in range(plan.res_rows.shape[0]):
        v = int(plan.res_rows[row])
        if v < 0:
            continue
        for c in range(plan.res_nbr.shape[1]):
            u = int(plan.res_nbr[row, c])
            if u >= 0 and plan.res_w[row, c] < INF32E:
                w[u, v] = min(w[u, v], int(plan.res_w[row, c]))
    return w


def build_ls(adj_dbs, area="0"):
    ls = LinkState(area)
    for db in adj_dbs:
        ls.update_adjacency_database(db)
    return ls


def update_metrics(ls, adj_dbs, node_i, metric):
    db = adj_dbs[node_i]
    new = AdjacencyDatabase(
        this_node_name=db.this_node_name,
        adjacencies=tuple(
            Adjacency(**{**a.__dict__, "metric": metric})
            for a in db.adjacencies
        ),
        node_label=db.node_label,
        area=db.area,
    )
    return ls.update_adjacency_database(new)


class TestBuild:
    def test_grid_is_pure_shifts(self):
        adj, _ = topologies.grid(8)
        ls = build_ls(adj)
        plan = build_plan(ls)
        assert plan.k_res == 0
        # 4 shift classes: +-1 (cols) and +-8 (rows)
        live = {int(d) for k, d in enumerate(plan.deltas)
                if (plan.shift_w[k] < INF32E).any()}
        assert live == {1, -1, 8, -8}

    def test_fabric_residual_is_row_compact(self):
        # pods large enough that intra-pod deltas clear the class floor
        adj, _ = topologies.fabric(pods=12, planes=2, ssws_per_plane=3,
                                   rsws_per_pod=6)
        ls = build_ls(adj)
        plan = build_plan(ls)
        rows = int((plan.res_rows >= 0).sum())
        # residual rows stay far below node count (spine tier only)
        assert 0 < rows < plan.n_nodes // 2

    def test_natural_order(self):
        names = ["node-10-2", "node-2-3", "node-2-10"]
        assert sorted(names, key=natural_key) == [
            "node-2-3", "node-2-10", "node-10-2"
        ]


class TestDeltaSync:
    def test_metric_flap_matches_fresh_build(self):
        adj, _ = topologies.grid(6)
        ls = build_ls(adj)
        plan = build_plan(ls)
        update_metrics(ls, adj, 7, 5)
        update_metrics(ls, adj, 12, 9)
        synced = sync_plan(ls, plan)
        assert synced is plan  # delta path, no rebuild
        fresh = build_plan(ls)
        assert np.array_equal(dense_w(synced), dense_w(fresh))
        # dirty entries queued for the device scatter
        assert synced.dirty_shift or synced.dirty_res

    def test_link_down_and_up(self):
        adj, _ = topologies.ring(6)
        ls = build_ls(adj)
        plan = build_plan(ls)
        # drop node-2 <-> node-3 by removing the adjacency from node-2
        db = adj[2]
        keep = tuple(
            a for a in db.adjacencies if a.other_node_name != "node-3"
        )
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="node-2", adjacencies=keep,
                node_label=db.node_label, area="0",
            )
        )
        synced = sync_plan(ls, plan)
        assert synced is plan
        assert np.array_equal(dense_w(synced), dense_w(build_plan(ls)))
        # restore
        ls.update_adjacency_database(db)
        synced = sync_plan(ls, plan)
        assert np.array_equal(dense_w(synced), dense_w(build_plan(ls)))

    def test_node_overload_drains_transit(self):
        adj, _ = topologies.grid(4)
        ls = build_ls(adj)
        plan = build_plan(ls)
        db = adj[5]
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=db.this_node_name,
                adjacencies=db.adjacencies,
                node_label=db.node_label,
                area="0",
                is_overloaded=True,
            )
        )
        synced = sync_plan(ls, plan)
        assert synced is plan
        fresh = build_plan(ls)
        assert np.array_equal(dense_w(synced), dense_w(fresh))
        # all out-edges of the drained node are INF
        u = plan.node_index[db.this_node_name]
        assert (dense_w(synced)[u] >= INF32E).all()

    def test_node_add_triggers_rebuild(self):
        adj, _ = topologies.ring(4)
        ls = build_ls(adj)
        plan = build_plan(ls)
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="node-9",
                adjacencies=(),
                node_label=0,
                area="0",
            )
        )
        synced = sync_plan(ls, plan)
        assert synced is not plan  # rebuilt
        assert "node-9" in synced.node_index

    def test_changelog_overflow_forces_rebuild(self):
        adj, _ = topologies.ring(4)
        ls = build_ls(adj)
        plan = build_plan(ls)
        for i in range(5000):  # exceed the bounded changelog
            update_metrics(ls, adj, i % 4, 2 + i % 7)
        assert ls.events_since(plan.synced_generation) is None
        synced = sync_plan(ls, plan)
        assert synced is not plan
        assert np.array_equal(dense_w(synced), dense_w(build_plan(ls)))


# -- the row-split residual ELL ---------------------------------------------

# graphs whose residual splits whatever a row costs (ops/edgeplan._ROW_COST
# from 0 to 32): a fabric whose spine switches have 40 residual in-edges
# (width 4), a small WAN with RTT metrics and 14 at its widest (width 2)
SPLIT_GRAPHS = {
    "fabric": lambda: topologies.fabric(
        pods=40, planes=2, ssws_per_plane=2, rsws_per_pod=4
    ),
    "wan": lambda: topologies.wan_rtt(
        regions=4, cores=2, aggs=6, access=52, seed=3
    ),
}


def _split(graph):
    adj, _ = SPLIT_GRAPHS[graph]()
    ls = build_ls(adj)
    return adj, ls, build_plan(ls)


def _rows_of(plan):
    """destination -> its rows, in row order."""
    rows = {}
    for row, v in enumerate(plan.res_rows.tolist()):
        if v >= 0:
            rows.setdefault(v, []).append(row)
    return rows


def _link_state_w(adj, plan):
    """The LinkState's directed weights as dense_w lays them out."""
    n = plan.n_cap
    w = np.full((n, n), int(INF32E), np.int64)
    for db in adj:
        u = plan.node_index[db.this_node_name]
        for a in db.adjacencies:
            v = plan.node_index[a.other_node_name]
            w[u, v] = min(w[u, v], a.metric)
    return w


def _widest(plan):
    """(destination, its rows) of the destination with most rows."""
    return max(_rows_of(plan).items(), key=lambda kv: len(kv[1]))


def _db_of(adj, name):
    return next(db for db in adj if db.this_node_name == name)


def _without(db, other):
    return replace(db, adjacencies=tuple(
        a for a in db.adjacencies if a.other_node_name != other
    ))


def _flap_metrics(adj, ls, plan, v, nbr):
    names = [db.this_node_name for db in adj]
    update_metrics(ls, adj, names.index(plan.node_names[v]), 7)
    update_metrics(ls, adj, names.index(plan.node_names[nbr]), 11)


def _down_then_up(adj, ls, plan, v, nbr):
    a, b = plan.node_names[v], plan.node_names[nbr]
    ls.update_adjacency_database(_without(_db_of(adj, a), b))
    ls.update_adjacency_database(_without(_db_of(adj, b), a))
    synced = sync_plan(ls, plan)
    assert synced is plan
    assert np.array_equal(dense_w(synced), dense_w(build_plan(ls)))
    ls.update_adjacency_database(_db_of(adj, a))
    ls.update_adjacency_database(_db_of(adj, b))


def _overload(adj, ls, plan, v, nbr):
    for node in (v, nbr):
        db = _db_of(adj, plan.node_names[node])
        ls.update_adjacency_database(replace(db, is_overloaded=True))


@pytest.mark.parametrize("graph", sorted(SPLIT_GRAPHS))
class TestRowSplit:
    def test_split_plan_holds_the_link_states_weights(self, graph):
        adj, _, plan = _split(graph)
        stats = plan.occupancy()
        rows = _rows_of(plan)
        widest = max(
            int((plan.res_nbr[r] >= 0).sum()) for r in rows.values()
        )
        # narrower than its widest destination: some destination is split
        assert stats["residual_k_cap"] < widest
        assert stats["residual_split_rows"] > 0
        assert plan.k_res == stats["residual_k_cap"]
        assert np.array_equal(dense_w(plan), _link_state_w(adj, plan))

    def test_rows_are_consecutive_and_fill_adds_up(self, graph):
        _, _, plan = _split(graph)
        k_cap = plan.res_nbr.shape[1]
        rows = _rows_of(plan)
        assert sum(len(r) for r in rows.values()) == plan._res_nrows
        assert len(rows) == len(plan._res_row_of)
        edges = 0
        for v, mine in rows.items():
            assert mine == list(range(mine[0], mine[-1] + 1)), v
            assert plan._res_row_of[v] == mine[-1]
            fill = plan._res_fill[mine]
            held = (plan.res_nbr[mine] >= 0).sum(axis=1)
            assert np.array_equal(fill, held), v
            # every row but the destination's last is full
            assert (fill[:-1] == k_cap).all() and 0 < fill[-1] <= k_cap, v
            edges += int(fill.sum())
        assert edges == plan.res_edges

    def test_occupancy_counts_rows_and_split_rows(self, graph):
        _, _, plan = _split(graph)
        stats = plan.occupancy()
        used = plan.res_rows[plan.res_rows >= 0]
        assert stats["residual_rows"] == len(used) == plan._res_nrows
        assert stats["residual_split_rows"] == len(used) - len(set(used.tolist()))
        assert stats["residual_r_cap"] == plan.res_rows.shape[0] >= len(used)

    @pytest.mark.parametrize(
        "churn", [_flap_metrics, _down_then_up, _overload],
        ids=["metric-flap", "down-up", "overload"],
    )
    def test_churn_on_a_split_destination_matches_fresh_build(
        self, graph, churn
    ):
        adj, ls, plan = _split(graph)
        v, mine = _widest(plan)
        assert len(mine) > 1
        # a neighbour whose edge into v sits in v's FIRST row
        nbr = int(plan.res_nbr[mine[0], 0])
        churn(adj, ls, plan, v, nbr)
        synced = sync_plan(ls, plan)
        assert synced is plan  # delta path on the split rows, no rebuild
        assert plan.dirty_res
        assert np.array_equal(dense_w(synced), dense_w(build_plan(ls)))

    def test_rebuild_keeps_width_and_row_cap(self, graph, monkeypatch):
        from openr_tpu.ops import edgeplan

        _, ls, plan = _split(graph)
        k_cap = plan.res_nbr.shape[1]
        # whatever the builder would choose now, churn keeps the class
        monkeypatch.setattr(
            edgeplan, "_residual_width", lambda degrees: 2 * k_cap
        )
        again = build_plan(ls, prev=plan)
        assert again.res_nbr.shape == plan.res_nbr.shape
        assert np.array_equal(dense_w(again), dense_w(plan))
        fresh = build_plan(ls)
        assert fresh.res_nbr.shape[1] == 2 * k_cap
        assert np.array_equal(dense_w(fresh), dense_w(plan))


def _new_link(ls, adj_of, a, b, metric=3):
    """Add the link a - b to both databases (a LinkState 'added' event)."""
    for me, other in ((a, b), (b, a)):
        db = adj_of[me]
        new = Adjacency(
            other_node_name=other, if_name=f"if-{me}-{other}",
            other_if_name=f"if-{other}-{me}", metric=metric, weight=1,
        )
        adj_of[me] = replace(db, adjacencies=db.adjacencies + (new,))
        ls.update_adjacency_database(adj_of[me])


class TestAddLinkOpensRows:
    def test_full_row_opens_the_next_free_row_then_rebuilds(self):
        adj, ls, plan = _split("fabric")
        adj_of = {db.this_node_name: db for db in adj}
        r_cap, k_cap = plan.res_nbr.shape
        v, mine = _widest(plan)
        assert plan._res_fill[mine[-1]] == k_cap  # its last row is full
        hub = plan.node_names[v]
        peers = {a.other_node_name for a in adj_of[hub].adjacencies}
        strangers = [
            n for n in plan.node_names if n != hub and n not in peers
        ]
        opened = 0
        for other in strangers:
            rows_before = plan._res_nrows
            last_before = plan._res_row_of[v]
            _new_link(ls, adj_of, other, hub)
            synced = sync_plan(ls, plan)
            if synced is not plan:
                break
            assert not plan.needs_rebuild
            assert np.array_equal(dense_w(plan), dense_w(build_plan(ls)))
            if plan._res_row_of[v] != last_before:
                # v's last row was full: the next free row is v's now
                opened += 1
                assert plan._res_row_of[v] == rows_before
                assert plan.res_rows[rows_before] == v
                assert plan._res_fill[rows_before] == 1
            assert plan._res_nrows <= r_cap
        else:
            pytest.fail("the free rows never ran out")
        assert opened >= 1
        # rebuilt only because no row was free; same width, more rows
        assert plan.needs_rebuild and plan._res_nrows == r_cap
        assert synced.res_nbr.shape == (2 * r_cap, k_cap)
        assert np.array_equal(dense_w(synced), dense_w(build_plan(ls)))
        assert synced.occupancy()["residual_rows"] > r_cap


class TestResidualWidth:
    @pytest.mark.parametrize("row_cost, degrees, width", [
        # one row a destination already: today's layout stays
        (0.0, [2, 2, 2, 2], 2),
        (16.0, [4] * 8, 4),
        # a tie in the model goes to the wider (fewer rows): eight
        # destinations of 8 gather 64 slots at widths 2, 4 and 8
        (0.0, [8] * 8, 8),
        # the same row cap with fewer slots is cheaper whatever a row costs
        (16.0, [3, 4, 4, 2], 2),
        # 60 destinations of 2 and one of 64: split to the narrowest, or,
        # where rows are dear, to the narrowest that fits 64 rows
        (0.0, [2] * 60 + [64], 2),
        (4.0, [2] * 60 + [64], 2),
        (1e9, [2] * 60 + [64], 16),
        (4.0, [], 2),
    ])
    def test_width_follows_the_degrees(
        self, monkeypatch, row_cost, degrees, width
    ):
        from openr_tpu.ops import edgeplan

        monkeypatch.setattr(edgeplan, "_ROW_COST", row_cost)
        got = edgeplan._residual_width(np.array(degrees, np.int32))
        assert got == width
