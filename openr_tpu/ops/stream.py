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
    cidx = jnp.nonzero(changed, size=budget, fill_value=p_cap)[0]
    safe = jnp.clip(cidx, 0, p_cap - 1).astype(jnp.int32)
    parts = [
        count[None],
        trips[None].astype(jnp.int32),
        cidx.astype(jnp.int32),
        metric[safe],
        s3w[safe].ravel(),
        nhw[safe].ravel(),
    ]
    if ok is not None:
        parts.append(ok[safe].astype(jnp.int32))
    if lfa:
        parts += [lfa_slot[safe], lfa_metric[safe]]
    return count, parts
