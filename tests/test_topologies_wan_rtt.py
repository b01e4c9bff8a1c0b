"""topologies.wan_rtt: a WAN whose metrics are measured round-trip times
and whose links follow the geography (benchmark configuration wan50k).
What the generator promises, at sizes a test can hold."""

import math
from collections import Counter

import pytest

from openr_tpu.link_monitor.link_monitor import get_rtt_metric
from openr_tpu.models import topologies

# (regions, cores, aggs, access)
SIZES = [(4, 2, 6, 52), (6, 2, 4, 34), (9, 4, 8, 20), (1, 3, 5, 12),
         (2, 2, 3, 7)]


def _links(adj_dbs) -> Counter:
    return Counter(
        (db.this_node_name, adj.other_node_name)
        for db in adj_dbs for adj in db.adjacencies
    )


def _tier(name: str) -> str:
    return name.split("-")[1].rstrip("0123456789")


@pytest.mark.parametrize("size", SIZES)
def test_node_count_names_and_one_advertiser_a_prefix(size):
    regions, cores, aggs, access = size
    adj_dbs, prefix_dbs = topologies.wan_rtt(*size, seed=3)
    names = [db.this_node_name for db in adj_dbs]
    assert len(names) == regions * (cores + aggs + access)
    assert len(set(names)) == len(names)
    per_region = Counter((n.split("-")[0], _tier(n)) for n in names)
    assert set(per_region.values()) == {cores, aggs, access}
    assert "r00-core0" in names and "r00-agg00" in names
    assert "r00-acc0000" in names
    assert {db.area for db in adj_dbs} == {"0"}
    owners = Counter(
        e.prefix for db in prefix_dbs for e in db.prefix_entries
    )
    assert len(owners) == len(names) and set(owners.values()) == {1}
    assert {db.this_node_name for db in prefix_dbs} == set(names)


@pytest.mark.parametrize("size", SIZES)
def test_connected_symmetric_and_no_parallel_links(size):
    adj_dbs, _ = topologies.wan_rtt(*size, seed=5)
    links = _links(adj_dbs)
    assert set(links.values()) == {1}, "two links between one pair"
    assert all((b, a) in links for a, b in links), "a one-way link"
    assert all(a != b for a, b in links)
    metric = {
        (db.this_node_name, adj.other_node_name): adj.metric
        for db in adj_dbs for adj in db.adjacencies
    }
    assert all(metric[a, b] == metric[b, a] for a, b in metric)
    nbrs: dict[str, list] = {}
    for a, b in links:
        nbrs.setdefault(a, []).append(b)
    seen, todo = {adj_dbs[0].this_node_name}, [adj_dbs[0].this_node_name]
    while todo:
        for b in nbrs[todo.pop()]:
            if b not in seen:
                seen.add(b)
                todo.append(b)
    assert len(seen) == len(adj_dbs)


@pytest.mark.parametrize("seed", [1, 7, 2**31 + 5])
def test_metric_is_get_rtt_metric_of_the_stated_distance(seed):
    pos: dict = {}
    adj_dbs, _ = topologies.wan_rtt(5, 2, 6, 30, seed=seed, positions=pos)
    assert len(pos) == len(adj_dbs)
    seen = set()
    for db in adj_dbs:
        for adj in db.adjacencies:
            km = math.dist(pos[db.this_node_name], pos[adj.other_node_name])
            want = get_rtt_metric(int(10 * 1.4 * km + 100))
            assert adj.metric == want >= 1
            seen.add(adj.metric)
    # an RTT spread, not one weight class: metro links of a few units,
    # long-haul links of tens to hundreds
    assert min(seen) <= 3 and max(seen) >= 50 and len(seen) >= 12


def test_the_stated_examples_of_the_rtt_rule():
    def metric(km):
        return get_rtt_metric(int(10 * 1.4 * km + 100))

    assert (metric(5), metric(80), metric(3000)) == (1, 12, 421)


@pytest.mark.parametrize("caps", [(6, 12, 20), (40, 48, 64), (7, 10, 14)])
def test_no_router_over_its_port_caps(caps):
    core_agg, agg_access, ports = caps
    size = (4, 2, 6, 26)
    adj_dbs, _ = topologies.wan_rtt(
        *size, seed=11, core_agg_ports=core_agg,
        agg_access_ports=agg_access, router_ports=ports,
    )
    for db in adj_dbs:
        tiers = Counter(_tier(a.other_node_name) for a in db.adjacencies)
        assert len(db.adjacencies) <= ports
        mine = _tier(db.this_node_name)
        if mine == "core":
            assert tiers["agg"] <= core_agg
        elif mine == "agg":
            assert tiers["acc"] <= agg_access
            assert tiers["core"] == 2 and tiers["agg"] == 2
        else:
            assert dict(tiers) == {"agg": 2}  # dual-homed
    if caps == (7, 10, 14):
        # a cap that cannot be kept is an error, not a router over it:
        # 6 x 2 uplinks over 2 cores of 5 ports
        with pytest.raises(ValueError):
            topologies.wan_rtt(*size, seed=11, core_agg_ports=5)


def test_degrees_follow_the_geography_not_the_index():
    adj_dbs, _ = topologies.wan_rtt(6, 4, 16, 120, seed=7)
    degree = Counter(len(db.adjacencies) for db in adj_dbs)
    access = [db for db in adj_dbs if _tier(db.this_node_name) == "acc"]
    assert all(len(db.adjacencies) == 2 for db in access)
    assert max(degree) >= 20 and len(degree) >= 10  # a heavy tail
    # index-affine would mean few distinct index offsets carry the edges
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    offsets = Counter(
        index[a.other_node_name] - index[db.this_node_name]
        for db in adj_dbs for a in db.adjacencies
    )
    assert offsets.most_common(1)[0][1] < 0.02 * sum(offsets.values())


def test_region_graph_has_two_links_a_pair_and_no_bridge():
    regions = 9
    adj_dbs, _ = topologies.wan_rtt(regions, 4, 6, 10, seed=13)
    between = Counter()
    for db in adj_dbs:
        for adj in db.adjacencies:
            a = db.this_node_name.split("-")[0]
            b = adj.other_node_name.split("-")[0]
            if a < b:
                assert _tier(db.this_node_name) == "core"
                assert _tier(adj.other_node_name) == "core"
                between[a, b] += 1
    assert set(between.values()) == {2}
    assert len(between) >= regions * 5 // 2
    pairs = {
        (int(a[1:]), int(b[1:])) for a, b in between
    }
    assert topologies._bridge_side(regions, pairs) is None
    # and the two links of a pair share no router
    ends = {}
    for db in adj_dbs:
        for adj in db.adjacencies:
            a, b = db.this_node_name, adj.other_node_name
            if a.split("-")[0] < b.split("-")[0]:
                ends.setdefault((a.split("-")[0], b.split("-")[0]), []).append(
                    (a, b)
                )
    for (first, second) in ends.values():
        assert first[0] != second[0] and first[1] != second[1]


@pytest.mark.parametrize("seed", [7, 8])
def test_the_same_lsdb_for_the_same_seed(seed):
    one = topologies.wan_rtt(4, 2, 6, 30, seed=seed)
    two = topologies.wan_rtt(4, 2, 6, 30, seed=seed)
    assert one == two
    other = topologies.wan_rtt(4, 2, 6, 30, seed=seed + 100)
    assert other[0] != one[0]
