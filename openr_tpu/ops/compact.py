"""Device-side route filter shared by the compaction paths.

`route_ok_device` is the jnp mirror of the host predicate
`columnar_rib.route_ok_rows`: it decides, per prefix row, whether the
solver's packed outputs describe a programmable route. The monolithic
pipeline (`tpu_solver._make_pipeline`) uses it to compact the cold
full-RIB pull down to ok rows on device; the sharded fabric kernel
(`parallel/sharding.py`) returns it alongside the unpacked masks so
the host skips its own O(P*A) filter pass. The two predicates MUST
stay in lockstep — the property test in tests/test_columnar_rib.py
pins columnar == eager materialization, which transitively pins this.
"""

from __future__ import annotations

import jax.numpy as jnp

from openr_tpu.ops.edgeplan import INF32E


def route_ok_device(metric, s3, nh_mask, ann_node, min_nh, v4_blocked,
                    root):
    """bool [P]: row is a real route from `root`'s vantage.

    metric  int32 [P]      best path metric
    s3      bool  [P, A]   selected announcer slots
    nh_mask bool  [P, D]   chosen next-hop links
    ann_node int32 [P, A]  announcing node per slot
    min_nh  int32 [P, A]   per-announcement minimum-nexthop requirement
    v4_blocked bool [P]    v4 prefixes suppressed by address config
    root    int32 scalar   vantage node index
    """
    ok = s3.any(axis=1) & (metric < INF32E)
    ok &= ~v4_blocked
    # drop self-announced prefixes (we originated them)
    ok &= ~(s3 & (ann_node == root)).any(axis=1)
    eff_min = jnp.max(jnp.where(s3, min_nh, -1), axis=1)
    nhc = nh_mask.sum(axis=1)
    return ok & (eff_min <= nhc) & (nhc > 0)
