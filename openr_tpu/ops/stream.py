"""Streaming churn-epoch device stages: on-device column diff +
changed-rows compaction.

The streaming pipeline (a `tpu_solver.PipelineVariant` with `stream`
set, jit-cache namespace "stream") fuses one churn epoch into a single dispatch: the
incremental bucketed relax (ops/relax.py + ops/incremental.py), the
best-route selection / LFA tail, and the column diff against the
PREVIOUS epoch's device-resident published planes — so the download per
epoch is a compacted changed-rows payload proportional to churn, not to
the prefix capacity. DeltaPath (arXiv 1808.06893) frames convergence as
one incrementally-maintained dataflow; these stages are the part of
that dataflow that decides what leaves the device.

`first_true_rows` / `true_rows` are the two compactions' index finders:
what `jnp.nonzero(mask, size=...)` returns, by block counts and a search
(the changed rows, a budget's worth of a long mask) or a running count
and one scatter (the cold pull's ok rows, all of them) instead of scans
and a scatter-add over every row.

`column_diff` / `compact_changed_rows` are traced under the pipeline
closure and are shared by the classic delta path (fixed budget, no ok
bit — the host re-derives route-ok while unpacking) and the streaming
path (bucketed budget from STREAM_BUDGETS, device ok bit riding the
payload so the host apply is unpack-free). One implementation, so the
two paths' changed sets are bit-identical by construction — the parity
property test pins device diff == fast_unicast_column_diff through
this sharing.

Streaming payload layout (int32 throughout, b = stream budget):

    [0]          count   total changed rows (may exceed b -> host
                         falls back to the device-compacted full pull)
    [1]          trips
    [2 : 2+b]    changed row indices (pad slots carry p_cap)
    ... b        metric
    ... b*wa     s3 words
    ... b*wd     nh words
    ... b        route-ok bit (STREAMING ONLY — absent on the classic
                 delta path, which recomputes ok host-side)
    ... 2b       lfa slot + metric        (lfa pipelines only)
    ... 2        unreachable, saturated   (sentinels enabled)
    ... 2        cone, fell_back          (incremental pipelines)
    [-1]         rounds
"""

from __future__ import annotations

import math

import jax.numpy as jnp

# changed-rows download budgets for the streaming epoch payload. The
# solver tracks each vantage's recent changed-row count and picks the
# smallest bucket that held the last epoch (growing on overflow), so a
# quiet mesh downloads the 64-row floor and a flap storm settles into
# the bucket its churn rate needs. Quantized so budget churn can't
# thrash the "stream" jit-cache namespace (the budget is part of the
# executable's capacity signature).
STREAM_BUDGETS = (64, 256, 1024, 4096)


def stream_budget(n: int):
    """Smallest streaming budget bucket holding `n` changed rows, or
    None past the top bucket (the caller falls back to the full pull
    and the classic delta budget)."""
    for b in STREAM_BUDGETS:
        if n <= b:
            return b
    return None


def stream_payload_len(budget: int, wa: int, wd: int, lfa: bool,
                       sentinels: bool) -> int:
    """int32 element count of the streaming delta payload for a budget
    — the host-side mirror of the layout above. bytes_downloaded for a
    within-budget epoch is exactly 4x this, independent of p_cap."""
    n = 2 + budget * (3 + wa + wd)  # count, trips, idx/metric/ok, words
    if lfa:
        n += 2 * budget
    if sentinels:
        n += 2
    n += 2  # cone, fell_back — the streaming epoch is always incremental
    n += 1  # rounds
    return n


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


def compact_changed_rows(changed, trips, metric, s3w, nhw, ok,
                         lfa_slot, lfa_metric, budget: int, p_cap: int,
                         lfa: bool):
    """(count, parts): head of the changed-rows payload — count, trips,
    then the changed rows' indices and packed columns gathered to the
    front (pad index slots carry p_cap; their gathered values are
    clipped reads the host masks off). `ok` is the device route-ok
    vector on the streaming path and None on the classic delta path,
    which keeps the classic payload layout byte-stable."""
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
    if ok is not None:
        parts.append(ok[safe].astype(jnp.int32))
    if lfa:
        parts += [lfa_slot[safe], lfa_metric[safe]]
    return count, parts
