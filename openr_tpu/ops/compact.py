"""What leaves the device: the route filter, the column diff and the
two compactions every pipeline variant shares.

`route_ok_device` is the jnp mirror of the host predicate
`columnar_rib.route_ok_rows`: it decides, per prefix row, whether the
solver's packed outputs describe a programmable route. The monolithic
pipeline (`tpu_solver._make_pipeline`) uses it to compact the cold
full-RIB pull down to ok rows on device; the sharded fabric kernel
(`parallel/sharding.py`) returns it alongside the unpacked masks so
the host skips its own O(P*A) filter pass. The two predicates MUST
stay in lockstep — the property test in tests/test_columnar_rib.py
pins columnar == eager materialization, which transitively pins this.

`column_diff` compares an epoch's published columns with the PREVIOUS
epoch's device-resident planes and `compact_changed_rows` gathers the
rows that differ to the front of the delta payload, so an epoch's
download is proportional to churn, not to the prefix capacity
(DeltaPath, arXiv 1808.06893, frames convergence as one
incrementally-maintained dataflow; these stages are the part of that
dataflow that decides what leaves the device). Both are traced under
the pipeline closure of every variant, so every variant's changed set
is the same function of the same columns.

`first_true_rows` / `true_rows` are the two compactions' index finders:
what `jnp.nonzero(mask, size=...)` returns, by block counts and a search
(the changed rows, a budget's worth of a long mask) or a running count
and one scatter (the cold pull's ok rows, all of them) instead of scans
and a scatter-add over every row.

Delta payload layout (int32 throughout, b = the variant's budget):

    [0]          count   total changed rows (may exceed b -> the host
                         reads the device-compacted full pull)
    [1]          trips
    [2 : 2+b]    changed row indices (pad slots carry p_cap)
    ... b        metric
    ... b*wa     s3 words
    ... b*wd     nh words
    ... 2b       lfa slot + metric        (lfa pipelines only)
    ... 2        unreachable, saturated   (sentinels enabled)
    ... 1        rows looked at           (narrow pipelines)
    ... 3        cone passes, cone, fell_back  (incremental pipelines)
    [-1]         rounds
"""

from __future__ import annotations

import math

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


def column_diff(metric, s3w, nhw, lfa_slot, lfa_metric,
                prev_metric, prev_s3w, prev_nhw,
                prev_lfa_slot, prev_lfa_metric, lfa: bool):
    """bool [P]: rows whose published columns differ from the previous
    epoch's device-resident planes. The route-ok bit is a pure function
    of (metric, s3, nh) given a fixed matrix/root, so comparing the
    packed columns alone is complete — ok cannot flip on an unchanged
    row."""
    changed = (
        (metric != prev_metric)
        | jnp.any(s3w != prev_s3w, axis=1)
        | jnp.any(nhw != prev_nhw, axis=1)
    )
    if lfa:
        changed |= (lfa_slot != prev_lfa_slot) | (
            lfa_metric != prev_lfa_metric
        )
    return changed


def _in_blocks(mask):
    """(blocks, ends) of a bool vector: its rows as int32
    [blocks, width], and the count of true rows up to each block's end.
    With `_count_within` the mask's running count in two levels, which
    both compactions below are made of. A `jnp.cumsum` over the whole
    mask says the same and is what `jnp.nonzero` does; it is the slow
    part of it on the chip, and the TPU compiler takes 5 s over one of
    524,288 rows and 40 s over one inside a `cond`. The block width
    follows from the mask's length: a lane tile, or the whole of a
    shorter mask."""
    p = mask.shape[0]
    w = math.gcd(p, 128)
    blocks = mask.reshape(p // w, w).astype(jnp.int32)
    return blocks, jnp.cumsum(blocks.sum(axis=1))


def _count_within(blocks):
    """int32, the shape of `blocks`: the count of true rows inside each
    block up to and with each row — a product with a triangle of ones
    (exact: 0/1 terms, sums <= 128, accumulated in f32), because the TPU
    compiler takes 11 s over a `cumsum` along [4096, 128] and 0.1 s over
    this."""
    w = blocks.shape[1]
    upto = jnp.arange(w)[:, None] <= jnp.arange(w)[None, :]
    return jnp.dot(
        blocks.astype(jnp.bfloat16), upto.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


def rows_any(cells, p: int, a: int):
    """bool [p]: whether any of a row's `a` cells is set, for the flat
    bool vector `cells` [p * a] of `p` rows — `cells.reshape(p, a)
    .any(axis=1)` for every input, without that reshape: a plane whose
    minor dimension is 2 is laid out a lane tile (128) wide on the TPU,
    64 times its size (1.6 GB planned and 3.7 ms of an epoch at 524,288
    rows of 2, where the compiler reshapes the whole packed buffer before
    it slices). The cells stay a lane tile wide instead and each row's
    are pooled by a product with a 0/1 matrix (exact, like
    `_count_within`'s). Rows wider than a tile, or not dividing one, take
    the plain form: it is laid out well there."""
    w = math.gcd(p * a, 128)
    if w % a:
        return cells.reshape(p, a).any(axis=1)
    pool = jnp.arange(w)[:, None] // a == jnp.arange(w // a)[None, :]
    held = jnp.dot(
        cells.reshape(p * a // w, w).astype(jnp.bfloat16),
        pool.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
    )
    return (held > 0).reshape(p)


def first_true_rows(mask, size: int, fill: int):
    """int32 [size]: the indices of the first `size` true rows of the
    bool vector `mask`, ascending, pad slots carrying `fill` — equal to
    `jnp.nonzero(mask, size=size, fill_value=fill)[0]` for every mask,
    without its scans and scatter-add over every row (jax lowers a sized
    nonzero to cumsum + bincount + cumsum over the mask's whole length:
    at 524,288 rows that was most of an epoch's device time, to find 32
    indices). For a `size` well below the mask's length: the rows count
    in blocks — one reduce over the mask — and only the `size` blocks
    that hold an output slot's row are looked into. Slot k's block is
    the first whose running count passes k; its row is the
    (k - rows before the block)-th true of that block. Work is one pass
    over the mask plus O(size x (blocks + block width)); nothing is
    scattered."""
    blocks, ends = _in_blocks(mask)
    nb, w = blocks.shape
    slots = jnp.arange(size, dtype=jnp.int32)
    passed = ends[None, :] <= slots[:, None]  # [size, blocks]
    blk = passed.sum(axis=1, dtype=jnp.int32)
    before = jnp.where(passed, ends[None, :], 0).max(axis=1)
    within = _count_within(blocks[jnp.minimum(blk, nb - 1)])
    row = (within <= (slots - before)[:, None]).sum(axis=1, dtype=jnp.int32)
    # a slot past the last true row found no block: pad
    return jnp.where(blk < nb, blk * w + row, fill).astype(jnp.int32)


def true_rows(mask, fill: int):
    """int32 [P]: the indices of every true row of the bool vector
    `mask` [P], ascending, gathered to the front, pad slots carrying
    `fill` — equal to `jnp.nonzero(mask, size=P, fill_value=fill)[0]`
    for every mask. The cold pull's compaction, where as many slots as
    rows go out: each true row's running count is its slot, and one
    scatter puts it there (false rows aim past the end and drop). A sort
    of `where(mask, row, fill)` is 4 x faster on the chip at 524,288
    rows (0.58 against 2.47 ms; `jnp.nonzero` 4.64) and takes 14 s to
    compile there against 0.5: this runs where a whole table is pulled
    and rebuilt on the host, so the compile is what counts."""
    p = mask.shape[0]
    blocks, ends = _in_blocks(mask)
    before = (ends - blocks.sum(axis=1))[:, None]
    slot = jnp.where(mask, (_count_within(blocks) + before).reshape(p) - 1, p)
    return jnp.full((p,), fill, jnp.int32).at[slot].set(
        jnp.arange(p, dtype=jnp.int32), mode="drop"
    )


def compact_changed_rows(changed, trips, metric, s3w, nhw,
                         lfa_slot, lfa_metric, budget: int, p_cap: int,
                         lfa: bool):
    """(count, parts): head of the changed-rows payload — count, trips,
    then the changed rows' indices and packed columns gathered to the
    front (pad index slots carry p_cap; their gathered values are
    clipped reads the host masks off). The host re-derives route-ok
    from the columns while it unpacks them
    (columnar_rib.route_ok_rows)."""
    count = changed.sum().astype(jnp.int32)
    cidx = first_true_rows(changed, budget, p_cap)
    safe = jnp.clip(cidx, 0, p_cap - 1)
    parts = [
        count[None],
        trips[None].astype(jnp.int32),
        cidx,
        metric[safe],
        s3w[safe].ravel(),
        nhw[safe].ravel(),
    ]
    if lfa:
        parts += [lfa_slot[safe], lfa_metric[safe]]
    return count, parts
