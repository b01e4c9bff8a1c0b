"""Prefix events served from the device's prefix rows (benchmark
configuration fabric10k_pfxchurn): one prefix advertised or withdrawn per
event while the links stay up.

A small three-tier fabric (4 pods of 4 fabric and 6 rack switches, 4 planes
of 2 spine switches: 48 switches, 3 prefixes each: 144 prefixes in 256
rows). The TPU solver against the CPU oracle, route for route and update
for update, over seeded sequences of advertise / withdraw / re-advertise
with changed `path_preference` and `distance`, alone and mixed with link
events in one epoch; the life of a row (`PrefixMatrix.apply_changes`): a
fresh prefix, the last advertiser withdrawn, a freed row taken again by
another prefix, a second advertiser, growth across a `p_cap` bucket and
past a crib's columns, the vantage's own prefix, a prefix of a node a down
link has cut off; that a prefix-only epoch makes no relaxation round, no
whole-matrix build and no key-index build; that the lazy table's length
and diff stay O(changed); and the same through the Decision actor.

The prefix-only program looks at the rows the dispatcher hands it and at
no other (ISSUE 42): every such dispatch is replayed through the all-rows
row stages on the same arguments (`tests/test_compact_rows.Recorder`: the
five resident arrays and `delta_buf` word for word); the epochs it cannot
serve take the incremental or the full solve and end equal to the oracle;
and two counters say how often it served and how many rows.

A link event's row stages run over candidate rows too (ISSUE 44): the
incremental solve finds on the device the rows its moved node columns can
reach, adds the rows the host knows were written (the touch log's, a
drain's repack's), and looks at those alone where they fit a delta pull
and nothing every row shares moved; each such dispatch is replayed through
the all-rows program as well, every wide reason takes the all-rows branch
and ends equal to the oracle, and the counters say which happened.
"""

import random
from dataclasses import replace

import numpy as np
import pytest

from openr_tpu.config import DecisionConfig
from openr_tpu.decision.columnar_rib import LazyUnicastRoutes, crib_rows
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.runtime.counters import counters
from openr_tpu.serde import serialize
from openr_tpu.types import (
    PrefixDatabase,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
    PrefixMetrics,
    PrefixType,
    Value,
    adj_key,
    prefix_key,
)
from openr_tpu.decision import tpu_solver as ts
from openr_tpu.ops import csr
from tests.conftest import run_async
from tests.test_compact_rows import Recorder
from tests.test_decision import DecisionHarness
from tests.test_incremental_spf import _Churn, _rebuild
from tests.test_tpu_solver import assert_rib_equal

FABRIC = {"pods": 4, "planes": 4, "ssws_per_plane": 2, "rsws_per_pod": 6}
PER_NODE = 3
ME = "pod000-rsw00"
AREA = "0"
SEEDS = [1, 2, 3]


def counter(key: str) -> float:
    return counters.get_counter(key) or 0


def entry_of(prefix: str, **metrics) -> PrefixEntry:
    return PrefixEntry(
        prefix=prefix, type=PrefixType.LOOPBACK,
        metrics=PrefixMetrics(**metrics),
    )


class World:
    """The LSDB, both solvers over it, and their last tables: every
    `solve` compares the tables and the updates that lead to them."""

    def __init__(self, per_node: int = PER_NODE, lfa: bool = True,
                 enable_v4: bool = True, **tpu_kw):
        tpu_kw.setdefault("incremental_spf", True)  # Decision's default
        self.adj_dbs, prefix_dbs = topologies.fabric(
            **FABRIC, prefixes_per_node=per_node
        )
        self.states, self.ps = topologies.build_states(
            self.adj_dbs, prefix_dbs
        )
        self.churn = _Churn(self.adj_dbs, self.states)
        self.cpu = SpfSolver(ME, enable_lfa=lfa, enable_v4=enable_v4)
        self.tpu = TpuSpfSolver(
            ME, enable_lfa=lfa, enable_v4=enable_v4, **tpu_kw
        )
        # who advertises what, as the state holds it
        self.held = {
            (db.this_node_name, e.prefix): e
            for db in prefix_dbs for e in db.prefix_entries
        }
        self.fresh = 0
        self.want = self.got = None
        self.solve("the first table")

    def advertise(self, node: str, entry: PrefixEntry) -> None:
        self.held[(node, entry.prefix)] = entry
        self.ps.update_prefix_database(
            PrefixDatabase(node, (entry,), AREA)
        )

    def withdraw(self, node: str, prefix: str) -> None:
        del self.held[(node, prefix)]
        self.ps.update_prefix_database(PrefixDatabase(
            node, (PrefixEntry(prefix=prefix),), AREA, delete_prefix=True
        ))

    def fresh_prefix(self) -> str:
        self.fresh += 1
        return f"fd00:c::{self.fresh:x}/128"

    def solve(self, ctx: str) -> dict:
        """-> the TPU solver's device stats of this epoch."""
        want = self.cpu.build_route_db(ME, self.states, self.ps)
        got = self.tpu.build_route_db(ME, self.states, self.ps)
        assert_rib_equal(want, got, ctx)
        assert len(got.unicast_routes) == len(want.unicast_routes), ctx
        if self.want is not None:
            w = self.want.calculate_update(want)
            g = self.got.calculate_update(got)
            sent = dict(g.unicast_routes_to_update)
            for prefix, route in w.unicast_routes_to_update.items():
                assert sent.pop(prefix) == route, (ctx, prefix)
            # beyond the oracle's, only a route whose advertisement
            # changed, sent again as it stands (ColumnarRib.touch_rows)
            for prefix, route in sent.items():
                assert route == self.want.unicast_routes[prefix], ctx
                assert prefix in self.ps.changes_since(self.gen), ctx
            assert sorted(g.unicast_routes_to_delete) == sorted(
                w.unicast_routes_to_delete
            ), ctx
        self.gen = self.ps.generation
        self.want, self.got = want, got
        return self.tpu.last_device_stats


def rsws(world: World, but=()) -> list:
    return sorted(
        n for n in world.churn.dbs if "-rsw" in n and n not in but
    )


# -- seeded sequences against the oracle -------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_random_prefix_events_match_the_oracle(seed):
    """Advertise, withdraw, re-advertise with other metrics, a second
    advertiser and its withdrawal: one to three of them an epoch, every
    epoch prefix-only on the device and right against the oracle."""
    rng = random.Random(seed)
    w = World()
    rebuilds = counter("decision.tpu.prefix_matrix_rebuilds")
    only = counter("decision.tpu.prefix_only_epochs")
    nodes = sorted(w.churn.dbs)
    epochs = 0
    for step in range(24):
        for _ in range(rng.randint(1, 3)):
            kind = rng.choice(
                ["withdraw", "fresh", "back", "metrics", "second"]
            )
            mine = sorted(w.held)
            if kind == "withdraw" and mine:
                w.withdraw(*rng.choice(mine))
            elif kind == "fresh":
                w.advertise(rng.choice(nodes), entry_of(w.fresh_prefix()))
            elif kind == "back" and mine:
                # withdrawn and advertised again before the solver looks
                node, prefix = rng.choice(mine)
                entry = w.held[(node, prefix)]
                w.withdraw(node, prefix)
                w.advertise(node, entry)
            elif kind == "metrics" and mine:
                node, prefix = rng.choice(mine)
                w.advertise(node, entry_of(
                    prefix,
                    path_preference=rng.choice([900, 1000, 1100]),
                    distance=rng.randint(0, 3),
                ))
            elif kind == "second" and mine:
                # another switch advertises a prefix that has one: two
                # advertisers fill the row's two cells, a third would not
                # fit (covered below)
                node, prefix = rng.choice(mine)
                holders = [n for n, p in w.held if p == prefix]
                other = rng.choice(nodes)
                if len(holders) == 1 and other not in holders:
                    w.advertise(other, entry_of(
                        prefix, distance=rng.randint(0, 2),
                    ))
        stats = w.solve(f"seed {seed} step {step}")
        assert stats.get("prefix_only"), (seed, step)
        assert stats["rounds"] == 0 and stats["trips"] == 0
        assert w.tpu.last_timing["prefix_only"] is True
        assert w.tpu.last_timing["rounds"] == 0
        epochs += 1
    assert counter("decision.tpu.prefix_matrix_rebuilds") == rebuilds
    assert counter("decision.tpu.prefix_only_epochs") == only + epochs


@pytest.mark.parametrize("seed", SEEDS)
def test_prefix_events_mixed_with_link_events_match_the_oracle(seed):
    """A prefix event and a link event in one epoch: the rows are
    scattered before an ordinary (incremental) solve."""
    rng = random.Random(seed)
    w = World(incremental_spf=True)
    rebuilds = counter("decision.tpu.prefix_matrix_rebuilds")
    p_cap = w.tpu._area_dev[AREA].matrix.ann_node.shape[0]
    edges = [e for e in w.churn.edges() if ME not in e]
    for step in range(8):
        u, v = rng.choice(edges)
        saved = w.churn.dbs[u], w.churn.dbs[v]
        node, prefix = rng.choice(sorted(w.held))
        w.withdraw(node, prefix)
        w.advertise(rng.choice(rsws(w)), entry_of(w.fresh_prefix()))
        w.churn.link_down(u, v)
        stats = w.solve(f"seed {seed} step {step}: down and prefixes")
        assert not stats.get("prefix_only")
        assert stats["rounds"] > 0
        # the two rows the host scattered and the rows the moved node
        # columns can reach, and no other (ISSUE 44)
        assert 2 <= stats["rows_looked"] < p_cap and "wide" not in stats
        w.churn.link_up(u, v, *saved)
        w.advertise(node, entry_of(prefix, distance=step % 3))
        stats = w.solve(f"seed {seed} step {step}: up and prefix back")
        assert not stats.get("prefix_only")
        assert 1 <= stats["rows_looked"] < p_cap and "wide" not in stats
        # and a prefix event alone right after: the plane stands again
        w.advertise(node, entry_of(prefix))
        stats = w.solve(f"seed {seed} step {step}: prefix alone")
        assert stats.get("prefix_only") and stats["rounds"] == 0
    assert counter("decision.tpu.prefix_matrix_rebuilds") == rebuilds


# -- the life of a row -------------------------------------------------------


def test_a_freed_row_keeps_its_name_until_another_prefix_takes_it():
    w = World()
    ad = w.tpu._area_dev[AREA]
    matrix = ad.matrix
    node, prefix = "pod002-rsw03", next(
        p for n, p in sorted(w.held) if n == "pod002-rsw03"
    )
    row = matrix.row_index()[prefix]
    n_rows = len(matrix.prefix_list)
    n_prefixes = matrix.n_prefixes
    old = w.got  # holds a view in which the row is a route

    w.withdraw(node, prefix)
    stats = w.solve("the last advertiser withdrawn")
    assert stats["changed_rows"] == 1 and stats.get("prefix_only")
    assert prefix not in w.got.unicast_routes
    assert ad.matrix is matrix and matrix.prefix_list[row] == prefix
    assert row in matrix.free and matrix.n_prefixes == n_prefixes - 1
    assert not matrix.ann_valid[row].any()
    assert counter("decision.tpu.prefix_rows_free") == 256 - n_prefixes + 1
    # the earlier table still reads its route, name and all
    assert old.unicast_routes[prefix].prefix == prefix

    # a fresh prefix while the earlier table lives: not that row
    first = w.fresh_prefix()
    w.advertise("pod001-rsw01", entry_of(first))
    w.solve("a fresh prefix, the freed row still read")
    assert matrix.row_index()[first] == n_rows
    assert matrix.prefix_list[row] == prefix and row in matrix.free

    # the prefix comes back: its own row
    entry = entry_of(prefix, distance=2)
    w.advertise(node, entry)
    w.solve("advertised again")
    assert matrix.row_index()[prefix] == row and row not in matrix.free
    assert w.got.unicast_routes[prefix].best_prefix_entry == entry

    # withdrawn again, and once no table reads the row, another takes it
    w.withdraw(node, prefix)
    w.solve("withdrawn again")
    del old
    second = w.fresh_prefix()
    w.advertise("pod003-rsw05", entry_of(second))
    stats = w.solve("a freed row taken by another prefix")
    assert stats.get("prefix_only") and stats["changed_rows"] == 1
    assert matrix.row_index()[second] == row
    assert matrix.prefix_list[row] == second
    assert prefix not in matrix.row_index()
    assert second in w.got.unicast_routes
    assert prefix not in w.got.unicast_routes
    assert ad.matrix is matrix


def test_the_vantage_s_own_prefix_has_no_route_either_way():
    w = World()
    mine = sorted(p for n, p in w.held if n == ME)
    routes = len(w.got.unicast_routes)
    w.withdraw(ME, mine[0])
    stats = w.solve("the vantage's own prefix withdrawn")
    # its row's columns move (no advertiser left to select); no route did
    assert stats.get("prefix_only") and stats["changed_rows"] == 1
    assert len(w.got.unicast_routes) == routes
    w.advertise(ME, entry_of(mine[0]))
    w.advertise(ME, entry_of(w.fresh_prefix()))
    stats = w.solve("and advertised, with one more")
    # both rows' columns move (each selects its advertiser) and neither
    # is a route
    assert stats.get("prefix_only") and stats["changed_rows"] == 2
    assert len(w.got.unicast_routes) == routes


def test_a_prefix_event_on_a_node_a_down_link_has_cut_off():
    w = World(incremental_spf=True)
    node = "pod003-rsw04"
    saved = {}
    for u, v in [e for e in w.churn.edges() if node in e]:
        saved[(u, v)] = w.churn.dbs[u], w.churn.dbs[v]
        w.churn.link_down(u, v)
    w.solve("the rack switch cut off")
    assert not any(
        n == node and p in w.got.unicast_routes for n, p in w.held
    )
    prefix = w.fresh_prefix()
    w.advertise(node, entry_of(prefix))
    gone = next(p for n, p in sorted(w.held) if n == node and p != prefix)
    w.withdraw(node, gone)
    stats = w.solve("it advertises and withdraws behind the cut")
    assert stats.get("prefix_only") and stats["changed_rows"] == 0
    for (u, v), dbs in saved.items():
        w.churn.link_up(u, v, *dbs)
    w.solve("and is back")
    assert prefix in w.got.unicast_routes
    assert gone not in w.got.unicast_routes


def test_growth_across_a_p_cap_bucket_and_a_third_advertiser():
    w = World()
    ad = w.tpu._area_dev[AREA]
    assert ad.matrix.ann_node.shape == (256, 2)
    rebuilds = counter("decision.tpu.prefix_matrix_rebuilds")
    nodes = rsws(w, but=(ME,))
    # up to the bucket's last row: rows of the matrix that is there
    room = 256 - len(ad.matrix.prefix_list)
    for k in range(room):
        w.advertise(nodes[k % len(nodes)], entry_of(w.fresh_prefix()))
        if k % 37 == 0:
            w.solve(f"fresh prefix {k}")
    w.solve("the bucket full")
    assert counter("decision.tpu.prefix_matrix_rebuilds") == rebuilds
    assert ad.matrix.n_prefixes == 256 and not ad.matrix.free
    # one more: the next power of two, built anew
    w.advertise(nodes[0], entry_of(w.fresh_prefix()))
    stats = w.solve("past the bucket")
    assert counter("decision.tpu.prefix_matrix_rebuilds") == rebuilds + 1
    assert ad.matrix.ann_node.shape == (512, 2)
    assert stats["full_pull"] and not stats.get("prefix_only")
    # and from there row by row again
    w.withdraw(nodes[0], next(p for n, p in sorted(w.held) if n == nodes[0]))
    assert w.solve("a withdraw in the new bucket").get("prefix_only")
    assert counter("decision.tpu.prefix_matrix_rebuilds") == rebuilds + 1
    # a third advertiser does not fit two cells: a_cap grows the same way
    prefix = next(p for n, p in sorted(w.held) if n == nodes[1])
    w.advertise(nodes[2], entry_of(prefix))
    assert w.solve("a second advertiser").get("prefix_only")
    w.advertise(nodes[3], entry_of(prefix))
    w.solve("a third advertiser")
    assert counter("decision.tpu.prefix_matrix_rebuilds") == rebuilds + 2
    # (256 prefixes again: a matrix built anew takes the bucket they need)
    assert ad.matrix.ann_node.shape == (256, 4)


def test_rows_past_a_crib_s_columns_start_a_new_crib_not_a_new_matrix():
    w = World()
    ad = w.tpu._area_dev[AREA]
    matrix = ad.matrix
    vs = w.tpu._vstates[(AREA, ME)]
    crib = vs.crib
    assert crib.p_n == crib_rows(matrix) == 144 + 64
    rebuilds = counter("decision.tpu.prefix_matrix_rebuilds")
    nodes = rsws(w, but=(ME,))
    for k in range(crib.p_n - 144):
        w.advertise(nodes[k % len(nodes)], entry_of(w.fresh_prefix()))
    assert w.solve("up to the columns' last row").get("prefix_only")
    assert vs.crib is crib
    w.advertise(nodes[0], entry_of(w.fresh_prefix()))
    stats = w.solve("one row past them")
    assert vs.crib is not crib and vs.crib.p_n > crib.p_n
    assert stats["full_pull"]
    assert ad.matrix is matrix
    assert counter("decision.tpu.prefix_matrix_rebuilds") == rebuilds


def test_a_matrix_two_solvers_hold_is_not_changed_in_place():
    """The PrefixState memo hands one matrix to every solver over the
    state: neither may then move its rows under the other."""
    w = World()
    other = TpuSpfSolver(ME, enable_lfa=True)
    other.build_route_db(ME, w.states, w.ps)
    shared = w.tpu._area_dev[AREA].matrix
    assert other._area_dev[AREA].matrix is shared and shared.holders == 2
    names = list(shared.prefix_list)
    node, prefix = sorted(w.held)[5]
    w.withdraw(node, prefix)
    w.advertise(node, entry_of(w.fresh_prefix()))
    w.solve("a change under a shared matrix")
    assert w.tpu._area_dev[AREA].matrix is not shared
    assert shared.prefix_list == names and not shared.free
    assert_rib_equal(
        w.want, other.build_route_db(ME, w.states, w.ps), "the other solver"
    )


# -- the candidate rows (ISSUE 42) -------------------------------------------


@pytest.mark.parametrize("v4", [True, False], ids=["v4", "block_v4"])
@pytest.mark.parametrize("lfa", [True, False], ids=["lfa", "no_lfa"])
def test_candidate_rows_equal_the_all_rows_stages(monkeypatch, lfa, v4):
    """Every dispatch of the prefix-only program against the all-rows row
    stages on its own arguments: the five resident arrays and `delta_buf`
    word for word (the Recorder's check), the tables and the updates
    against the oracle's (`World.solve`), over the life of a row and over
    more rows in one epoch than a bucket holds."""
    w = World(lfa=lfa, enable_v4=v4)
    rec = Recorder(monkeypatch, w.tpu)
    matrix = w.tpu._area_dev[AREA].matrix
    nodes = rsws(w, but=(ME,))
    # advertise (rows never used), an IPv4 prefix among them; withdraw
    w.advertise(nodes[0], entry_of(w.fresh_prefix()))
    w.advertise(nodes[1], entry_of("10.42.1.0/24"))
    w.solve("two fresh prefixes")
    node, prefix = next(k for k in sorted(w.held) if k[0] == nodes[2])
    row = matrix.row_index()[prefix]
    w.withdraw(node, prefix)
    w.solve("a withdraw")
    # the freed row taken by another prefix, once no table reads it
    w.want = w.got = None
    w.advertise(nodes[3], entry_of("10.42.2.0/24"))
    w.solve("a freed row taken")
    assert matrix.row_index()["10.42.2.0/24"] == row
    # a second advertiser, preferred; then changed, and the first one
    # withdrawn and back inside one epoch
    other, shared = next(k for k in sorted(w.held) if k[0] == nodes[4])
    w.advertise(nodes[5], entry_of(shared, path_preference=1100))
    w.solve("a second advertiser, preferred")
    w.advertise(nodes[5], entry_of(shared, distance=3))
    w.withdraw(other, shared)
    w.advertise(other, entry_of(shared, distance=1))
    w.solve("changed, withdrawn and back inside one epoch")
    # several rows an epoch: up to the first bucket, and past it
    for count in (63, 64, 65):
        for k in range(count):
            n, p = sorted(w.held)[2 * k]
            w.advertise(n, entry_of(
                p, distance=count % 3 + 1, path_preference=1000 + count,
            ))
        assert w.solve(f"{count} rows in one epoch").get("prefix_only")
    assert w.tpu._area_dev[AREA].matrix is matrix
    caps = [v.rows_only for v, *_ in rec.epochs]
    assert all(caps) and caps[-3:] == [64, 64, 256], caps
    assert all(
        v.lfa == lfa and v.block_v4 == (not v4) for v, *_ in rec.epochs
    )
    assert any(count for _, _, count, _ in rec.epochs)
    # a third advertiser does not fit the row's two cells: a new matrix
    # and the full solve, and candidate rows again after it
    w.advertise(nodes[6], entry_of(shared))
    stats = w.solve("a third advertiser")
    assert stats["full_pull"] and not stats.get("prefix_only")
    assert not rec.epochs[-1][0].rows_only
    w.withdraw(nodes[6], shared)
    assert w.solve("and gone again").get("prefix_only")
    assert rec.epochs[-1][0].rows_only == 64
    # link events (ISSUE 44): the incremental solve's row stages over the
    # rows its moved node columns can reach, each dispatch replayed
    # through the all-rows program like the ones above — random links
    # down and up, a prefix event in the same epoch, a metric, a drained
    # switch and its give-back
    first = len(rec.epochs)
    p_cap = w.tpu._area_dev[AREA].matrix.ann_node.shape[0]
    rng = random.Random(44)
    edges = [e for e in w.churn.edges() if ME not in e]
    for step in range(4):
        u, v = rng.choice(edges)
        saved = w.churn.dbs[u], w.churn.dbs[v]
        w.churn.link_down(u, v)
        if step % 2:
            w.advertise(rng.choice(nodes), entry_of(w.fresh_prefix()))
        w.solve(f"link {u} - {v} down")
        w.churn.link_up(u, v, *saved)
        if step % 2:
            n, p = rng.choice(sorted(w.held))
            w.advertise(n, entry_of(p, distance=2))
        w.solve(f"link {u} - {v} up")
    u, v = rng.choice(edges)
    w.churn.set_metric(u, v, 5)
    w.solve(f"metric {u} - {v}")
    fsw = "pod002-fsw01"
    w.churn._put(replace(w.churn.dbs[fsw], is_overloaded=True))
    drained = w.solve(f"{fsw} drained")
    w.churn._put(replace(w.churn.dbs[fsw], is_overloaded=False))
    w.solve(f"{fsw} given back")
    link = rec.epochs[first:]
    assert len(link) == 11 and all(v.narrow for v, *_ in link)
    # every one of them narrow: fewer rows than all, never none at the
    # drain (its flagged rows are the host's, the rack switches' behind it
    # the device's)
    looked = rec.looked[first:]
    assert all(0 <= n < p_cap for n in looked), looked
    assert drained["rows_looked"] == looked[-2] >= PER_NODE * (1 + 6)
    assert any(count for _, _, count, _ in link)


def _foreign(w: World, node: str) -> None:
    """Another vantage's solve: the area is synced (rows scattered, the
    matrix's log written) and ME's resident outputs do not move."""
    assert_rib_equal(
        SpfSolver(node, enable_lfa=True).build_route_db(
            node, w.states, w.ps
        ),
        w.tpu.build_route_db(node, w.states, w.ps), f"from {node}",
    )


def _abandon(w: World) -> None:
    """A dispatch whose prepare never runs (the host work between
    dispatch and collection raised): the crib has dropped what it cached
    of the changed rows, the resident outputs stand."""
    fast = w.tpu._partition_prefixes(w.ps, w.states)[0]
    w.tpu._dispatch_one(w.tpu._prep_vantage(
        ME, AREA, w.states[AREA], w.ps, fast[AREA]
    ))


# case -> what serves it, the prefix-only program declining: the
# incremental solve over its own candidate rows ("narrow": since ISSUE 44
# the host's rows reach it too), the incremental solve over every row
# ("wide", where the host's rows are not known), or the full solve
DECLINES = {
    "overload_snapshot_changed": "narrow",
    "matrix_rebuilt": "full",
    "touch_log_does_not_reach_back": "full",
    "more_rows_than_the_largest_bucket": "narrow",
    "vantage_not_valid": "full",
    "a_link_event_in_the_same_epoch": "narrow",
    "an_abandoned_prepare": "wide",
}


@pytest.mark.parametrize("case", sorted(DECLINES))
def test_an_epoch_the_candidate_rows_cannot_serve(monkeypatch, case):
    """No prefix-only epoch: each takes the incremental solve (with
    nothing dirty where no weight changed) or the full solve, ends equal
    to the oracle, and leaves the next prefix-only epoch to the candidate
    rows again. The incremental solve looks at the candidate rows where
    the host can name its share of them (a drain's repack, a link event
    beside the prefix event, more rows than a bucket) and at every row
    where it cannot (an abandoned prepare)."""
    if case == "touch_log_does_not_reach_back":
        monkeypatch.setattr(csr, "_TOUCH_LOG", 2)
    w = World()
    ad = w.tpu._area_dev[AREA]
    vs = w.tpu._vstates[(AREA, ME)]
    nodes = rsws(w, but=(ME,))
    w.advertise(nodes[0], entry_of(w.fresh_prefix()))
    assert w.solve("a candidate epoch first").get("prefix_only")
    puts = ad.mbuf_puts
    assert vs.rows_stamp == (puts, ad.matrix.touch_seq)
    node, prefix = next(k for k in sorted(w.held) if k[0] == nodes[1])
    w.withdraw(node, prefix)
    if case == "overload_snapshot_changed":
        # a drained switch's advertisements carry the drain in their
        # flags: the packed matrix goes up whole
        w.churn._put(replace(w.churn.dbs[nodes[2]], is_overloaded=True))
    elif case == "matrix_rebuilt":
        _, shared = next(k for k in sorted(w.held) if k[0] == nodes[3])
        w.advertise(nodes[4], entry_of(shared))
        w.advertise(nodes[5], entry_of(shared))
    elif case == "touch_log_does_not_reach_back":
        for k in range(3):
            _foreign(w, nodes[6])
            w.advertise(nodes[k], entry_of(w.fresh_prefix()))
    elif case == "more_rows_than_the_largest_bucket":
        monkeypatch.setattr(ts, "_DIRTY_BUCKETS", (2, 4))
        _foreign(w, nodes[6])
        for k in range(4):
            w.advertise(nodes[k], entry_of(w.fresh_prefix()))
    elif case == "vantage_not_valid":
        vs.valid = False  # as a reset of the planes leaves it
    elif case == "a_link_event_in_the_same_epoch":
        u, v = next(e for e in w.churn.edges() if ME not in e)
        w.churn.link_down(u, v)
    elif case == "an_abandoned_prepare":
        _abandon(w)
        assert vs.crib.matrix_seq == ad.matrix.touch_seq
        assert vs.rows_stamp[1] < ad.matrix.touch_seq
        w.advertise(nodes[3], entry_of(w.fresh_prefix()))
    served = counter("decision.tpu.candidate_epochs")
    rows = counter("decision.tpu.candidate_rows")
    wide = counter("decision.tpu.wide_epochs")
    only = counter("decision.tpu.prefix_only_epochs")
    p_cap = ad.matrix.ann_node.shape[0]
    stats = w.solve(case)
    assert not stats.get("prefix_only"), stats
    assert counter("decision.tpu.prefix_only_epochs") == only
    narrow = DECLINES[case] == "narrow"
    assert counter("decision.tpu.candidate_epochs") == served + narrow
    assert counter("decision.tpu.wide_epochs") == wide + (
        DECLINES[case] == "wide"
    )
    if DECLINES[case] == "full":
        assert stats["full_pull"] and not stats.get("incremental")
        assert "rows_looked" not in stats
    else:
        assert stats.get("incremental") and not stats["fell_back"]
        assert not stats["full_pull"]
    if narrow:
        assert 1 <= stats["rows_looked"] < p_cap and "wide" not in stats
        assert counter("decision.tpu.candidate_rows") == (
            rows + stats["rows_looked"]
        )
    if case == "overload_snapshot_changed":
        # the put's rows are known: the drained switch's own (their flags
        # cells changed) beside the withdrawn one
        assert ad.mbuf_puts == puts + 1
        assert list(ad.put_log)[-1][0] == puts + 1
        assert len(list(ad.put_log)[-1][1]) == PER_NODE
        assert stats["rows_looked"] >= PER_NODE + 1
    if case == "more_rows_than_the_largest_bucket":
        # nothing dirty: the solve converges at once, and no column moved:
        # the host's five rows are all there is to look at
        assert stats["cone"] == 0 and stats["changed_rows"] == 5
        assert stats["rows_looked"] == 5
        monkeypatch.undo()
    if case == "an_abandoned_prepare":
        assert stats["wide"] == "rows_unknown"
        assert stats["rows_looked"] == p_cap
    # the stamp follows whichever program computed the resident outputs
    ad, vs = w.tpu._area_dev[AREA], w.tpu._vstates[(AREA, ME)]
    assert vs.rows_stamp == (ad.mbuf_puts, ad.matrix.touch_seq)
    w.advertise(node, entry_of(prefix, distance=2))
    assert w.solve("a prefix alone, after").get("prefix_only")
    assert counter("decision.tpu.candidate_epochs") == served + narrow + 1
    assert counter("decision.tpu.prefix_only_epochs") == only + 1


# case -> the reason the epoch's span and counter give
WIDE = {
    "a_metric_on_the_vantage_s_own_link": "root_w",
    "the_root_s_own_column_moved": "device",
    "candidates_past_the_budget": "device",
    "more_columns_moved_than_the_mask_compares": "device",
    "host_rows_past_the_budget": "host_rows",
    "a_put_of_rows_not_kept": "rows_unknown",
    "an_abandoned_prepare_then_a_link_event": "rows_unknown",
    "outputs_not_of_the_resident_plane": "plane",
}
BUDGETS = {
    "candidates_past_the_budget": 16, "host_rows_past_the_budget": 16,
    "a_put_of_rows_not_kept": 4,
    # a budget no other test has: its executables are this test's alone,
    # traced under the _MOVED_CAP it patches
    "more_columns_moved_than_the_mask_compares": 48,
}


@pytest.mark.parametrize("case", sorted(WIDE))
def test_every_wide_reason_looks_at_every_row(monkeypatch, case):
    """What every row shares moved (the root's link weights, the root's
    own column of the plane, outputs that are not the resident plane's),
    or the rows are too many (the device's candidates, the host's) or not
    known (a put whose rows were not kept, an abandoned prepare): the
    incremental solve takes its all-rows branch, says why, ends equal to
    the oracle, and the next link event is narrow again (one column
    moves: under every cap here). (`want_full`,
    the device's own too, never reaches an incremental dispatch from the solver:
    tests/test_compact_rows.py hands it to the executable.)"""
    if case in BUDGETS:
        monkeypatch.setattr(ts, "_DELTA_BUDGET", BUDGETS[case])
    if case == "more_columns_moved_than_the_mask_compares":
        monkeypatch.setattr(ts, "_MOVED_CAP", 2)
    w = World()
    rec = Recorder(monkeypatch, w.tpu)
    ad = w.tpu._area_dev[AREA]
    vs = w.tpu._vstates[(AREA, ME)]
    p_cap = ad.matrix.ann_node.shape[0]
    nodes = rsws(w, but=(ME,))
    far = next(
        e for e in w.churn.edges() if "pod003-rsw02" in e and "fsw" in e[0]
    )
    w.churn.set_metric(*far, 2)
    stats = w.solve("a narrow link event first")
    assert stats["rows_looked"] == PER_NODE and "wide" not in stats
    w.churn.set_metric(*far, 1)
    fsw = "pod002-fsw01"
    if case == "a_metric_on_the_vantage_s_own_link":
        # root_sig holds (the same links are up), root_w moved
        w.churn.set_metric(ME, "pod000-fsw00", 3)
    elif case == "the_root_s_own_column_moved":
        # the neighbour's way back to the vantage: one direction alone,
        # so the vantage's own weights stand
        db = w.churn.dbs["pod000-fsw00"]
        w.churn._put(_rebuild(db, [
            replace(a, metric=4) if a.other_node_name == ME else a
            for a in db.adjacencies
        ], AREA))
    elif case in (
        "candidates_past_the_budget",
        "more_columns_moved_than_the_mask_compares",
    ):
        # six rack switches behind it and its own three flagged rows: 21
        # candidates, past a budget of 16; six moved columns, past a cap
        # of 2 though 21 rows fit a budget of 48
        w.churn._put(replace(w.churn.dbs[fsw], is_overloaded=True))
    elif case == "a_put_of_rows_not_kept":
        # two switches' six changed cells, past a budget of four
        for node in (fsw, "pod001-fsw02"):
            w.churn._put(replace(w.churn.dbs[node], is_overloaded=True))
    elif case == "host_rows_past_the_budget":
        for node, prefix in sorted(w.held)[:17]:
            w.advertise(node, entry_of(prefix, distance=2))
    elif case == "an_abandoned_prepare_then_a_link_event":
        w.advertise(nodes[0], entry_of(w.fresh_prefix()))
        _abandon(w)
    elif case == "outputs_not_of_the_resident_plane":
        # as a dispatch that emits no plane (a fused group's) leaves it
        vs.shared_stamp = (vs.shared_stamp[0] - 1, vs.shared_stamp[1])
    before = {
        key: counter(f"decision.tpu.{key}") for key in (
            "candidate_epochs", "wide_epochs",
            f"wide_epochs.{WIDE[case]}",
        )
    }
    stats = w.solve(case)
    assert stats.get("incremental") and not stats["fell_back"], stats
    assert stats["wide"] == WIDE[case] and stats["rows_looked"] == p_cap
    assert rec.epochs[-1][0].narrow and rec.looked[-1] == p_cap
    gained = {
        key: counter(f"decision.tpu.{key}") - was
        for key, was in before.items()
    }
    assert gained == {
        "candidate_epochs": 0, "wide_epochs": 1,
        f"wide_epochs.{WIDE[case]}": 1,
    }
    if case == "a_put_of_rows_not_kept":
        assert list(ad.put_log)[-1] == (ad.mbuf_puts, None)
    # whichever branch computed them, the stamps are the resident
    # outputs': the next link event is narrow
    w.churn.set_metric(*far, 2)
    stats = w.solve("a link event after")
    assert stats["rows_looked"] == PER_NODE and "wide" not in stats


def test_the_counters_say_how_often_and_how_many_rows():
    w = World()
    nodes = rsws(w, but=(ME,))
    before = {
        key: counter(f"decision.tpu.{key}") for key in (
            "candidate_epochs", "candidate_rows", "prefix_only_epochs",
            "prefix_rows_changed", "epochs", "wide_epochs",
            "wide_epochs.root_w",
        )
    }
    handed = []
    for step, count in enumerate((1, 3, 0, 2, 70)):
        for node, prefix in sorted(w.held)[:count]:
            w.advertise(node, entry_of(prefix, path_preference=900 + step))
        stats = w.solve(f"{count} rows")
        assert stats.get("prefix_only")
        attrs = {
            name: a for name, _, _, _, a in w.tpu.last_timing["spans"]
        }["tpu.device_wait"]
        assert attrs["prefix_only"] is True and attrs["rounds"] == 0
        handed.append((attrs["cand_rows"], attrs["cand_cap"]))
    assert handed == [(1, 64), (3, 64), (0, 64), (2, 64), (70, 256)]
    # a link event (ISSUE 44): the candidate rows' counters move by the
    # rows its row stages looked at, the prefix-only one does not; and a
    # metric on one of the vantage's own links goes wide, with its reason
    u, v = next(e for e in w.churn.edges() if ME not in e)
    w.churn.link_down(u, v)
    stats = w.solve("a link down")
    assert not stats.get("prefix_only")
    looked = stats["rows_looked"]
    attrs = {
        name: a for name, _, _, _, a in w.tpu.last_timing["spans"]
    }["tpu.device_wait"]
    assert attrs["rows_looked"] == looked and "wide" not in attrs
    assert 0 < looked < w.tpu._area_dev[AREA].matrix.ann_node.shape[0]
    mine = next(e for e in w.churn.edges() if ME in e)
    w.churn.set_metric(*mine, 3)
    stats = w.solve("a metric on the vantage's own link")
    attrs = {
        name: a for name, _, _, _, a in w.tpu.last_timing["spans"]
    }["tpu.device_wait"]
    assert stats["wide"] == attrs["wide"] == "root_w"
    gained = {
        key: counter(f"decision.tpu.{key}") - was
        for key, was in before.items()
    }
    assert gained == {
        "candidate_epochs": 6, "candidate_rows": 76 + looked,
        "prefix_only_epochs": 5, "prefix_rows_changed": 76, "epochs": 7,
        "wide_epochs": 1, "wide_epochs.root_w": 1,
    }
    # each addition is a stamped sample too: a window's gain is readable
    for key, least, times in (
        ("candidate_epochs", 6, 6), ("candidate_rows", 76 + looked, 6),
        ("wide_epochs", 1, 1),
    ):
        stat = counters.get_statistics(
            f"decision.tpu.{key}", windows=(3600.0,)
        )[f"decision.tpu.{key}"]["3600"]
        assert stat["sum"] >= least and stat["count"] >= times


# -- what a prefix-only epoch does not do ------------------------------------


def test_a_prefix_only_epoch_moves_one_row_and_builds_nothing():
    w = World()
    ad = w.tpu._area_dev[AREA]
    node, prefix = "pod001-rsw02", next(
        p for n, p in sorted(w.held) if n == "pod001-rsw02"
    )
    # warm: the index is built by the first diff, the programs installed
    w.withdraw(node, prefix)
    w.solve("warm-up withdraw")
    w.advertise(node, entry_of(prefix))
    w.solve("warm-up advertise")
    before = {
        key: counter(key) for key in (
            "decision.crib.key_index_builds",
            "decision.tpu.prefix_matrix_rebuilds",
            "decision.solver.full.solves",
            "decision.solver.incr.solves",
            "decision.tpu.prefix_rows_changed",
            "decision.tpu.prefix_only_epochs",
            "decision.rib.entries_built",
        )
    }
    mbuf = ad.d_mbuf
    uploaded = []
    put = w.tpu._put_counted
    w.tpu._put_counted = lambda arr, *a: (
        uploaded.append(arr.nbytes), put(arr, *a)
    )[1]
    for step in range(6):
        w.withdraw(node, prefix)
        got = w.tpu.build_route_db(ME, w.states, w.ps)
        assert isinstance(got.unicast_routes, LazyUnicastRoutes)
        assert len(got.unicast_routes) == 47 * PER_NODE - 1
        assert not got.unicast_routes.base
        update = w.got.calculate_update(got)
        assert update.unicast_routes_to_delete == [prefix]
        assert len(update.unicast_routes_to_update) == 0
        w.got = got
        w.advertise(node, entry_of(prefix, distance=step))
        got = w.tpu.build_route_db(ME, w.states, w.ps)
        assert len(got.unicast_routes) == 47 * PER_NODE
        update = w.got.calculate_update(got)
        assert list(update.unicast_routes_to_update) == [prefix]
        assert not update.unicast_routes_to_delete
        w.got = got
        tm = w.tpu.last_timing
        assert tm["prefix_only"] and tm["rounds"] == 0
        assert tm["areas"][AREA]["kernel"].startswith("pipeline_rows[")
        assert tm["areas"][AREA]["exec_ms"] > 0
        span = {name: attrs for name, _, _, _, attrs in tm["spans"]}
        assert span["tpu.sync.prefix"]["rows_changed"] == 1
        assert span["tpu.sync.prefix"]["rebuilt"] is False
        assert 0 < span["tpu.sync.prefix"]["bytes"] < 16384
        assert span["tpu.device_wait"]["prefix_only"] is True
    after = {key: counter(key) for key in before}
    # no full matrix put: what went up is the root tables' few words
    assert max(uploaded, default=0) < 1024
    assert ad.d_mbuf is not mbuf  # scattered into, in place (donated)
    for key in ("decision.crib.key_index_builds",
                "decision.tpu.prefix_matrix_rebuilds",
                "decision.solver.full.solves",
                "decision.solver.incr.solves"):
        assert after[key] == before[key], key
    assert after["decision.tpu.prefix_rows_changed"] == (
        before["decision.tpu.prefix_rows_changed"] + 12
    )
    assert after["decision.tpu.prefix_only_epochs"] == (
        before["decision.tpu.prefix_only_epochs"] + 12
    )
    # one entry an advertised route, built for the diff; none for the rest
    assert after["decision.rib.entries_built"] - before[
        "decision.rib.entries_built"
    ] <= 12
    # the stat of the counter's name stamps each addition
    stat = counters.get_statistics(
        "decision.tpu.prefix_rows_changed", windows=(3600.0,)
    )["decision.tpu.prefix_rows_changed"]["3600"]
    assert stat["sum"] >= 12 and stat["count"] >= 12


def test_a_changed_advertisement_is_an_update_though_no_column_moves():
    """`distance` of a prefix with one advertiser changes nothing the
    device computes; the route's `best_prefix_entry` is the advertisement,
    so the oracle sends the route and so must the lazy table's diff."""
    w = World()
    node, prefix = sorted(w.held)[40]
    w.advertise(node, entry_of(prefix, distance=7))
    stats = w.solve("a distance of one advertiser")
    assert stats.get("prefix_only") and stats["changed_rows"] == 0
    assert w.got.unicast_routes[prefix].best_prefix_entry.metrics.distance == 7
    # and with the table forced into entries first (ctrl, policy)
    dict(w.got.unicast_routes.items())
    w.advertise(node, entry_of(prefix, distance=8))
    w.solve("again, over a materialized table")
    assert w.got.unicast_routes[prefix].best_prefix_entry.metrics.distance == 8


def test_the_partition_follows_the_changed_prefixes():
    w = World()
    fast, slow, ksp2, _ = w.tpu._partition_prefixes(w.ps, w.states)
    held = fast[AREA]
    assert isinstance(held, dict) and len(held) == 48 * PER_NODE
    walked = []
    classify = TpuSpfSolver._classify
    w.tpu._classify = lambda prefix, *a: (
        walked.append(prefix), classify(prefix, *a)
    )[1]
    node, prefix = sorted(w.held)[9]
    w.withdraw(node, prefix)
    fresh = w.fresh_prefix()
    w.advertise(node, entry_of(fresh))
    again = w.tpu._partition_prefixes(w.ps, w.states)
    assert again[0][AREA] is held  # the container that was there
    assert walked == [fresh] or sorted(walked) == sorted([fresh])
    assert prefix not in held and fresh in held
    assert len(held) == 48 * PER_NODE
    # a reader behind the log walks all of them once more
    w.advertise(node, entry_of(prefix))
    w.ps._changes.clear()
    del walked[:]
    w.tpu._partition_prefixes(w.ps, w.states)
    assert len(walked) == 48 * PER_NODE + 1
    w.solve("after the walk")


def test_prefix_state_remembers_what_changed():
    w = World()
    g = w.ps.generation
    assert w.ps.changes_since(g) == set()
    node, prefix = sorted(w.held)[3]
    w.withdraw(node, prefix)
    w.advertise(node, entry_of("fd00:c::99/128"))
    assert w.ps.changes_since(g) == {prefix, "fd00:c::99/128"}
    assert w.ps.changes_since(g + 1) == {"fd00:c::99/128"}
    assert w.ps.changes_since(g - 200) is None  # the load's generations
    assert w.ps.changes_since(g + 5) is None  # not one of this state's


# -- through the Decision actor ----------------------------------------------


def _kv(node: str, entry: PrefixEntry, version: int, gone: bool = False):
    db = PrefixDatabase(node, (entry,), AREA, delete_prefix=gone)
    return prefix_key(node, AREA, entry.prefix), Value(
        version=version, originator_id=node, value=serialize(db)
    )


@pytest.mark.parametrize("dispatch", ["inline", "async"])
@run_async
async def test_decision_sends_a_prefix_only_epoch_to_the_device(dispatch):
    adj_dbs, prefix_dbs = topologies.fabric(**FABRIC, prefixes_per_node=2)
    config = DecisionConfig(
        debounce_min_ms=5, debounce_max_ms=20, enable_lfa=True,
        async_dispatch=dispatch == "async",
    )
    async with DecisionHarness(ME, backend="tpu", config=config) as h:
        h.publish(*(
            (adj_key(db.this_node_name), Value(
                version=1, originator_id=db.this_node_name,
                value=serialize(db),
            )) for db in adj_dbs
        ))
        h.publish(*(
            _kv(db.this_node_name, db.prefix_entries[0], 1)
            for db in prefix_dbs
        ))
        h.synced()
        first = await h.next_route_update()
        assert len(first.unicast_routes_to_update) == 47 * 2
        decision = h.decision
        oracle = SpfSolver(ME, enable_lfa=True)
        node = "pod002-rsw01"
        entry = next(
            db.prefix_entries[0] for db in prefix_dbs
            if db.this_node_name == node
        )
        fresh = entry_of("fd00:c::7/128")
        only = counter("decision.tpu.prefix_only_epochs")
        failovers = counter("decision.solver.failovers")
        builds = []
        for version, (e, gone) in enumerate([
            (entry, True), (entry, False), (fresh, False), (fresh, True),
            (replace(entry, metrics=PrefixMetrics(distance=4)), False),
        ], start=2):
            built = counter("decision.crib.key_index_builds")
            h.publish(_kv(node, e, version, gone))
            update = await h.next_route_update()
            builds.append(counter("decision.crib.key_index_builds") - built)
            if gone:
                assert update.unicast_routes_to_delete == [e.prefix]
                assert not len(update.unicast_routes_to_update)
            else:
                assert not update.unicast_routes_to_delete
                got = dict(update.unicast_routes_to_update)
                assert list(got) == [e.prefix]
                assert got[e.prefix].best_prefix_entry == e
                assert len(got[e.prefix].nexthops) == 4
            routes = decision.route_db.unicast_routes
            assert isinstance(routes, LazyUnicastRoutes) and not routes.base
            assert not routes.overrides and not routes.deleted
            tm = decision.solver.last_timing
            assert tm["prefix_only"] and tm["rounds"] == 0
            assert_rib_equal(
                oracle.build_route_db(
                    ME, decision.area_link_states, decision.prefix_state
                ), decision.route_db, f"version {version}",
            )
        assert counter("decision.tpu.prefix_only_epochs") == only + 5
        # the matrix's index once, at the first change; none after
        assert builds[0] <= 1 and not any(builds[1:])
        assert counter("decision.solver.failovers") == failovers


@run_async
async def test_the_per_prefix_path_keeps_a_lazy_table_lazy():
    """What the device's rows do not hold (here a static route's prefix)
    still takes `_incremental_db`; it copies the lazy table as one."""
    from openr_tpu.decision.rib import (
        DecisionRouteUpdate, NextHop, RibUnicastEntry,
    )

    adj_dbs, prefix_dbs = topologies.fabric(**FABRIC, prefixes_per_node=2)
    config = DecisionConfig(debounce_min_ms=5, debounce_max_ms=20)
    async with DecisionHarness(ME, backend="tpu", config=config) as h:
        h.publish(*(
            (adj_key(db.this_node_name), Value(
                version=1, originator_id=db.this_node_name,
                value=serialize(db),
            )) for db in adj_dbs
        ))
        h.publish(*(
            _kv(db.this_node_name, db.prefix_entries[0], 1)
            for db in prefix_dbs
        ))
        h.synced()
        await h.next_route_update()
        built = counter("decision.rib.entries_built")
        static = RibUnicastEntry(
            prefix="fd00:5::/64", nexthops=frozenset({NextHop(
                address="fe80::1", if_name="eth0",
            )}),
        )
        h.static_q.push(DecisionRouteUpdate(
            unicast_routes_to_update={static.prefix: static}
        ))
        update = await h.next_route_update()
        assert list(update.unicast_routes_to_update) == [static.prefix]
        routes = h.decision.route_db.unicast_routes
        assert isinstance(routes, LazyUnicastRoutes)
        # a route the host computed is counted where the build's own
        # host routes are (what `no_host_computed_route` reads)
        assert list(routes.base) == [static.prefix]
        assert routes[static.prefix] == static
        assert len(routes) == 47 * 2 + 1
        assert counter("decision.rib.entries_built") == built


def _ksp2_entry(prefix: str) -> PrefixEntry:
    return PrefixEntry(
        prefix=prefix, type=PrefixType.LOOPBACK,
        forwarding_type=PrefixForwardingType.SR_MPLS,
        forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    )


def test_the_solver_serves_a_prefix_epoch_only_with_no_host_route():
    """An epoch the solver serves runs every host route of the table
    again (`dispatch_route_db`), so one KSP2 prefix anywhere, a prefix of
    two areas, a static route or an area the oracle solves keeps the
    epoch with Decision's per-prefix path; the rule is the partition's."""
    w = World()
    node, prefix = sorted(w.held)[5]
    w.advertise(node, entry_of(prefix, distance=3))
    assert w.tpu.serves_prefix_epoch(w.states, w.ps, [prefix])
    w.solve("a fast prefix changed")
    # a KSP2 prefix elsewhere: not the changed one
    other = rsws(w, but=(ME, node))[0]
    w.advertise(other, _ksp2_entry("fd00:d::1/128"))
    w.advertise(node, entry_of(prefix, distance=5))
    assert not w.tpu.serves_prefix_epoch(w.states, w.ps, [prefix])
    assert list(w.tpu._partition[3]) == ["fd00:d::1/128"]
    w.solve("a KSP2 prefix in the table")
    w.withdraw(other, "fd00:d::1/128")
    assert w.tpu.serves_prefix_epoch(w.states, w.ps, ["fd00:d::1/128"])
    w.solve("the KSP2 prefix gone")
    # a second area's announcement of it makes the prefix a slow one
    w.ps.update_prefix_database(
        PrefixDatabase(node, (entry_of(prefix),), "1")
    )
    assert not w.tpu.serves_prefix_epoch(w.states, w.ps, [prefix])
    w.ps.update_prefix_database(PrefixDatabase(
        node, (PrefixEntry(prefix=prefix),), "1", delete_prefix=True
    ))
    assert w.tpu.serves_prefix_epoch(w.states, w.ps, [prefix])
    w.tpu.update_static_unicast_routes({prefix: w.want.unicast_routes[prefix]}, [])
    assert not w.tpu.serves_prefix_epoch(w.states, w.ps, [prefix])
    w.tpu.update_static_unicast_routes({}, [prefix])
    # an area under the oracle's size has no device rows
    w.tpu.small_graph_nodes = 10_000
    assert not w.tpu.serves_prefix_epoch(w.states, w.ps, [prefix])


@run_async
async def test_a_prefix_epoch_beside_a_ksp2_prefix_recomputes_one_route():
    """With a KSP2 prefix in the table a fast prefix's change is not the
    device's: Decision's per-prefix path asks the oracle for the changed
    prefix alone (not for every host route, and no area is dispatched),
    the route lies in the lazy table's `base`, and the next link event's
    solve gives the oracle's table again."""
    adj_dbs, prefix_dbs = topologies.fabric(
        **FABRIC, prefixes_per_node=2, node_labels=True
    )
    config = DecisionConfig(
        debounce_min_ms=5, debounce_max_ms=20, enable_lfa=True,
    )
    async with DecisionHarness(ME, backend="tpu", config=config) as h:
        h.publish(*(
            (adj_key(db.this_node_name), Value(
                version=1, originator_id=db.this_node_name,
                value=serialize(db),
            )) for db in adj_dbs
        ))
        h.publish(*(
            _kv(db.this_node_name, db.prefix_entries[0], 1)
            for db in prefix_dbs
        ), _kv("pod003-rsw02", _ksp2_entry("fd00:d::1/128"), 1))
        h.synced()
        first = await h.next_route_update()
        assert len(first.unicast_routes_to_update) == 47 * 2 + 1
        decision = h.decision
        oracle = SpfSolver(ME, enable_lfa=True)
        asked = []
        one = decision.solver.cpu.create_route_for_prefix
        decision.solver.cpu.create_route_for_prefix = lambda *a: (
            asked.append(a[-1]), one(*a)
        )[1]
        node = "pod002-rsw01"
        entry = next(
            db.prefix_entries[0] for db in prefix_dbs
            if db.this_node_name == node
        )
        only = counter("decision.tpu.prefix_only_epochs")
        epochs = counter("decision.tpu.epochs")
        changed = replace(entry, metrics=PrefixMetrics(distance=4))
        h.publish(_kv(node, changed, 2))
        update = await h.next_route_update()
        got = dict(update.unicast_routes_to_update)
        assert list(got) == [entry.prefix] and asked == [entry.prefix]
        assert got[entry.prefix].best_prefix_entry == changed
        assert counter("decision.tpu.prefix_only_epochs") == only
        assert counter("decision.tpu.epochs") == epochs
        routes = decision.route_db.unicast_routes
        assert isinstance(routes, LazyUnicastRoutes)
        assert set(routes.base) == {entry.prefix, "fd00:d::1/128"}
        # and no earlier solve's breakdown stands for this epoch
        assert not decision.solver.last_timing
        assert_rib_equal(
            oracle.build_route_db(
                ME, decision.area_link_states, decision.prefix_state
            ), decision.route_db, "after the per-prefix path",
        )
        # a withdraw on the same path deletes the one route
        h.publish(_kv(node, changed, 3, gone=True))
        update = await h.next_route_update()
        assert update.unicast_routes_to_delete == [entry.prefix]
        assert entry.prefix not in decision.route_db.unicast_routes
        # a link event: the device's rows catch up with both changes
        db = next(d for d in adj_dbs if d.this_node_name == ME)
        up, *rest = db.adjacencies
        h.publish((adj_key(ME), Value(
            version=2, originator_id=ME, value=serialize(replace(
                db, adjacencies=(replace(up, metric=up.metric + 2), *rest),
            )),
        )))
        await h.next_route_update()
        assert counter("decision.tpu.epochs") == epochs + 1
        assert set(decision.route_db.unicast_routes.base) == {
            "fd00:d::1/128"
        }
        assert_rib_equal(
            oracle.build_route_db(
                ME, decision.area_link_states, decision.prefix_state
            ), decision.route_db, "after the link event",
        )


def test_a_row_is_quiet_only_when_no_view_reads_it():
    from openr_tpu.decision.columnar_rib import row_quiet

    w = World()
    matrix = w.tpu._area_dev[AREA].matrix
    row = matrix.row_index()[sorted(w.held)[20][1]]
    assert not row_quiet(matrix, row)  # the table holds a route there
    assert row_quiet(matrix, len(matrix.prefix_list))  # never used
    mine = matrix.row_index()[next(p for n, p in sorted(w.held) if n == ME)]
    assert row_quiet(matrix, mine)  # the vantage's own: no route
    assert np.count_nonzero(matrix.ann_valid[:, 0]) == 144
