"""Incremental device SSSP: seed-from-previous, cone-bounded
re-relaxation (DeltaPath / Bounded-Dijkstra style) on the resident
shift-decomposed mirror.

The full solve relaxes a cold all-INF plane to fixpoint. Relaxation
over non-negative int32 weights is monotone-decreasing and its fixpoint
(with the root-neighbor seeds pinned to 0) is *unique*: starting from
ANY pointwise over-estimate of the true distances it converges to
exactly the cold-solve plane, bit for bit (int32 arithmetic is exact).
That gives the incremental recipe:

  decreases  — the previous plane is already an over-estimate of the
               new distances; just re-relax. The cone that changed is
               small, so the while_loop hits fixpoint in a few trips.
  increases  — the previous plane UNDER-estimates exactly on the
               affected cone; those rows must be re-anchored to INF
               first. A node's distance can only have increased if its
               parent chain (a shortest path under the OLD weights)
               crosses an increased edge, so the affected cone is the
               union of parent-tree subtrees hanging off the head of
               each increased dirty edge. We rebuild the parent plane
               on device from the OLD weights (reconstructed from the
               dirty tuples' pre-write values), seed the subtree roots,
               and propagate descendants to fixpoint.

Zero-weight edges break the subtree argument (equal-distance parent
cycles never reach the increased edge); the host gates incremental off
via EdgePlan.has_zero_w, so every weight seen here is >= 1 and parent
chains strictly decrease the previous distance — a proper forest.

Cone fallback is decided ON DEVICE: when the affected cone exceeds
cone_limit the warm seed is swapped for the cold all-INF seed inside
the same dispatch, degrading to a bit-identical full solve with no
extra host round-trip. Over-invalidation is always safe (INF is an
over-estimate), so every approximation here errs toward correctness.

INF discipline matches the full solver: INF32E = 2^29, weights
<= 2^28, `dist + w` overflow-free in int32. Dirty pad entries use
out-of-range flat indices and are dropped by `mode="drop"` scatters /
validity masks on gathers.
"""

from __future__ import annotations

from openr_tpu.ops import relax as relax_ops

INF_E = 1 << 29  # matches edgeplan.INF32E / tpu_solver.INF_E


def _old_planes(shift_w, res_w, s_dirty_idx, s_dirty_old,
                r_dirty_idx, r_dirty_old, has_res):
    """Reconstruct the previous weight planes from the new resident
    planes + the dirty tuples' pre-write values. Pad entries carry
    out-of-range flat indices and drop."""
    import jax.numpy as jnp

    old_shift = (
        shift_w.ravel()
        .at[s_dirty_idx].set(s_dirty_old, mode="drop")
        .reshape(shift_w.shape)
    )
    if has_res:
        old_res = (
            res_w.ravel()
            .at[r_dirty_idx].set(r_dirty_old, mode="drop")
            .reshape(res_w.shape)
        )
    else:
        old_res = res_w
    return old_shift, old_res


def residual_parents(par, prev_dist, res_rows, res_nbr, rwm_old, n_cap):
    """The residual half of the parent forest: for every destination
    still without a parent in `par` [D, N], its first tight residual
    slot's neighbour — first in slot order within a row, the largest
    find among the rows a split destination spans. The multichip twin
    (parallel/sharding.py) calls this too, replicated after its pmax.

    One gather-and-min pass shaped like the relaxation's
    (ops/relax.py): a slot's key `k * n_cap + nbr` orders the slots of
    a row as they lie and carries the neighbour in its low bits, so the
    row's minimum over its tight slots IS the first tight slot, found
    for what a relaxation's pass costs at any count of lanes."""
    import jax.numpy as jnp

    k_cap = res_nbr.shape[1]
    sent = jnp.iinfo(jnp.int32).max
    # n_cap is a power of two (the low bits hold the neighbour) and the
    # widest key stays under the sentinel: static shapes, no fallback
    assert n_cap & (n_cap - 1) == 0 and k_cap * n_cap <= sent, (
        k_cap, n_cap,
    )
    nbr_c = jnp.clip(res_nbr, 0, n_cap - 1)
    rows_c = jnp.clip(res_rows, 0, n_cap - 1)
    row_valid = res_rows >= 0
    # pad scatter target n_cap drops — a clipped pad row would
    # collide with node 0's real residual row otherwise
    rows_s = jnp.where(row_valid, res_rows, n_cap)
    slot = jnp.arange(k_cap, dtype=jnp.int32)[None, :]
    key = jnp.where(
        (res_nbr >= 0) & (rwm_old < INF_E), slot * n_cap + res_nbr, sent
    )  # [R, K], the same for every lane
    prev_n = prev_dist[:, nbr_c]  # [D, R, K]
    tgt = prev_dist[:, rows_c]  # [D, R]
    tight = (prev_n < INF_E) & (prev_n + rwm_old[None] == tgt[:, :, None])
    best = jnp.where(tight, key[None], sent).min(axis=2)  # [D, R]
    has = best < sent
    pick = best & (n_cap - 1)
    cur = par[:, rows_c]
    new = jnp.where((cur < 0) & has & row_valid[None], pick, cur)
    # max, not set: a destination may span several rows, and one
    # that found no tight parent (-1) must not erase another's.
    # Either row's find is a tight-edge parent; a shift-class
    # parent (cur >= 0) is the same in all of them and is kept
    return par.at[:, rows_s].max(new, mode="drop")


def _parent_plane(deltas, swm_old, res_rows, res_nbr, rwm_old,
                  prev_dist, s_cap, has_res, n_cap, d_cap):
    """Per-lane parent forest [D, N] under the OLD (root-masked)
    weights: par[d, v] = some u with prev[d,u] + w_old(u,v) ==
    prev[d,v], or -1 (seeds and unreachable nodes). Any tight-edge
    parent works for the invalidation argument — the par chain is one
    concrete old shortest path. Guards: prev[u] < INF and w < INF keep
    INF+0 / 0+INF arithmetic from minting spurious tight edges."""
    import jax
    import jax.numpy as jnp

    par = jnp.full((d_cap, n_cap), -1, jnp.int32)
    src = jnp.arange(n_cap, dtype=jnp.int32)

    def cls(k, par):
        dk = deltas[k]
        wk = swm_old[k]
        cand = prev_dist + wk[None, :]
        tgt = jnp.roll(prev_dist, -dk, axis=1)  # tgt[:, u] = prev[:, v]
        hit = (prev_dist < INF_E) & (wk < INF_E)[None, :] & (cand == tgt)
        hit_v = jnp.roll(hit, dk, axis=1)  # hit at child position v
        src_v = jnp.roll(src, dk)[None, :]  # src_v[v] = u
        return jnp.where((par < 0) & hit_v, src_v, par)

    par = jax.lax.fori_loop(0, s_cap, cls, par)

    if has_res:
        par = residual_parents(
            par, prev_dist, res_rows, res_nbr, rwm_old, n_cap
        )
    return par


def incremental_sssp(deltas, shift_w, res_rows, res_nbr, res_w, root,
                     seeds_nbr, seeds_w, prev_dist,
                     s_dirty_idx, s_dirty_old,
                     r_dirty_idx, r_dirty_old, cone_limit,
                     s_cap: int, has_res: bool, n_cap: int, d_cap: int,
                     max_trips: int, kernel: str = "sync",
                     delta_exp: int = 0, quantum: int | None = None):
    """Incremental counterpart of tpu_solver._plan_sssp. Same resident
    inputs plus: prev_dist [D, N] (the last solve's per-slot plane),
    consolidated dirty tuples (flat index into the raveled shift /
    residual weight planes + each slot's PRE-drain value; pads are
    out-of-range indices), and cone_limit (dynamic int32 scalar —
    affected-cone budget in node-lanes). `kernel` selects the final
    re-relaxation's implementation (ops/relax.py sync rounds or
    bucketed Δ-stepping) — either way the fixpoint is unique, so the
    output stays bit-identical to the cold solve. `quantum` is the
    passes a trip of the cone's closure and of the sync re-relaxation
    (relax_ops.sync_quantum(has_res) unless a test says otherwise):
    how often they test for change, never what they converge to.
    Returns (dist [D, N], trips, cone, fell_back, rounds, cone_passes)
    — cone_passes the closure's executed passes: 0 where no edge grew
    (a restore, a decrease: the loop does not start), depth + 1 at
    quantum 1."""
    import jax
    import jax.numpy as jnp

    if quantum is None:
        quantum = relax_ops.sync_quantum(has_res)
    bound = max_trips * relax_ops.UNROLL // quantum

    # root-masked weight planes, new and old
    swm_new = shift_w.at[:, root].set(INF_E)
    old_shift, old_res = _old_planes(
        shift_w, res_w, s_dirty_idx, s_dirty_old,
        r_dirty_idx, r_dirty_old, has_res,
    )
    swm_old = old_shift.at[:, root].set(INF_E)
    if has_res:
        rwm_new = jnp.where(res_nbr == root, INF_E, res_w)
        rwm_old = jnp.where(res_nbr == root, INF_E, old_res)
        nbr_c = jnp.clip(res_nbr, 0, n_cap - 1)
        rows_c = jnp.clip(res_rows, 0, n_cap - 1)
        rows_s = jnp.where(res_rows >= 0, res_rows, n_cap)
    else:
        rwm_old = res_w

    with jax.named_scope("seed.parent"):
        par = _parent_plane(
            deltas, swm_old, res_rows, res_nbr, rwm_old, prev_dist,
            s_cap, has_res, n_cap, d_cap,
        )

    with jax.named_scope("seed.cone"):
        # --- classify increased dirty edges + seed the affected cone ---
        aff = jnp.zeros((d_cap, n_cap), jnp.int32)

        ok_s = (s_dirty_idx >= 0) & (s_dirty_idx < s_cap * n_cap)
        sic = jnp.clip(s_dirty_idx, 0, s_cap * n_cap - 1)
        k_j = sic // n_cap
        u_j = sic % n_cap
        # compare ROOT-MASKED values: root-column edges are INF to both
        # solves, so their churn is invisible and must not seed anything
        new_m = swm_new.ravel()[sic]
        old_m = jnp.where(u_j == root, INF_E, s_dirty_old)
        inc_s = ok_s & (new_m > old_m)
        # class-k edge u -> v with v = (u + deltas[k]) % n (roll semantics)
        v_j = (u_j + deltas[k_j]) % n_cap
        pv = par[:, jnp.clip(v_j, 0, n_cap - 1)]  # [D, Sd]
        seed_s = (inc_s[None, :] & (pv == u_j[None, :])).astype(jnp.int32)
        v_sc = jnp.where(ok_s, v_j, n_cap)
        aff = aff.at[:, v_sc].max(seed_s, mode="drop")

        if has_res:
            kr = res_nbr.shape[1]
            lim = res_rows.shape[0] * kr
            ok_r = (r_dirty_idx >= 0) & (r_dirty_idx < lim)
            ric = jnp.clip(r_dirty_idx, 0, lim - 1)
            row_j = ric // kr
            c_j = ric % kr
            ru = res_nbr[row_j, c_j]  # source neighbor
            rv = res_rows[row_j]  # destination node
            new_mr = rwm_new[row_j, c_j]
            old_mr = jnp.where(ru == root, INF_E, r_dirty_old)
            inc_r = ok_r & (new_mr > old_mr) & (ru >= 0) & (rv >= 0)
            pv_r = par[:, jnp.clip(rv, 0, n_cap - 1)]
            seed_r = (inc_r[None, :] & (pv_r == ru[None, :])).astype(
                jnp.int32
            )
            rv_sc = jnp.where(ok_r & (rv >= 0), rv, n_cap)
            aff = aff.at[:, rv_sc].max(seed_r, mode="drop")

        # --- propagate aff to tree descendants (one step = one level) ---
        nodes = jnp.arange(n_cap, dtype=jnp.int32)

        def aff_step(acc):
            def cls(k, a):
                dk = deltas[k]
                childpar = jnp.roll(par, -dk, axis=1)  # par of v at pos u
                is_child = childpar == nodes[None, :]
                contrib = jnp.roll(jnp.where(is_child, a, 0), dk, axis=1)
                return jnp.maximum(a, contrib)

            acc = jax.lax.fori_loop(0, s_cap, cls, acc)
            if has_res:
                is_child = (
                    par[:, rows_c][:, :, None] == res_nbr[None]
                ) & (res_nbr >= 0)[None]  # [D, R, K]
                acc_n = acc[:, nbr_c]  # [D, R, K]
                contrib = jnp.where(is_child, acc_n, 0).max(axis=2)
                acc = acc.at[:, rows_s].max(contrib, mode="drop")
            return acc

        # an empty cone has no descendants: the loop opens only where
        # an increased edge seeded one
        aff, _, cone_passes = relax_ops.run_sync(
            aff_step, aff, bound, quantum, start=jnp.any(aff > 0),
            scope="seed.cone",
        )

        cone = aff.sum().astype(jnp.int32)
        fell_back = cone > cone_limit

    # --- seed: warm (re-anchored prev) or cold (full-solve dist0) ---
    valid = seeds_w < INF_E
    seed_idx = jnp.clip(seeds_nbr, 0, n_cap - 1)
    pin = jnp.where(valid, 0, INF_E).astype(jnp.int32)
    lanes = jnp.arange(d_cap)
    warm = jnp.where(aff > 0, INF_E, prev_dist)
    warm = warm.at[lanes, seed_idx].min(pin)
    cold = jnp.full((d_cap, n_cap), INF_E, jnp.int32)
    cold = cold.at[lanes, seed_idx].min(pin)
    dist0 = jnp.where(fell_back, cold, warm)

    # --- relax to fixpoint under the NEW weights (the shared kernel
    # bodies in ops/relax.py; fixpoint uniqueness gives bit-identical
    # output whichever implementation runs)
    residual = (rows_c, nbr_c, rwm_new) if has_res else None
    relax = relax_ops.make_relax(
        deltas, s_cap, lambda k: swm_new[k], residual=residual
    )
    if kernel == "bucketed":
        dist, trips, rounds = relax_ops.run_bucketed(
            relax, dist0, deltas, swm_new, lambda k: swm_new[k],
            n_cap, s_cap, delta_exp,
        )
    else:
        dist, trips, rounds = relax_ops.run_sync(
            relax, dist0, bound, quantum
        )
    return dist, trips, cone, fell_back, rounds, cone_passes


def jit_incremental_sssp(s_cap: int, has_res: bool, n_cap: int,
                         d_cap: int, max_trips: int,
                         kernel: str = "sync", delta_exp: int = 0,
                         quantum: int | None = None):
    """Standalone jitted wrapper for unit tests; production composes
    incremental_sssp into the solver pipeline tail instead."""
    import jax
    from functools import partial

    return jax.jit(partial(
        incremental_sssp,
        s_cap=s_cap, has_res=has_res, n_cap=n_cap, d_cap=d_cap,
        max_trips=max_trips, kernel=kernel, delta_exp=delta_exp,
        quantum=quantum,
    ))
