"""Batched what-if sweeps over the resident shift-decomposed mirror.

The live solver already keeps each area's graph on device (deltas /
shift_w / residual ELL, decision/tpu_solver.py). A what-if scenario —
a failed link, a drained node, a metric change — is a handful of
directed-edge weight overrides on top of that mirror. This module vmaps
the delta-stepping SSSP over a BATCH of such sparse overlays: the graph
arrays ride in once per dispatch as shared operands (no re-upload), each
lane scatters its own overrides into a private copy on device, and the
per-scenario verdicts (unreachable pairs, max metric stretch, partition
flag) reduce on device so the host pulls O(batch) ints, not O(batch*N)
planes.

Lane 0 of every batch is the identity overlay: the baseline distance
plane every other lane is judged against. That keeps the whole sweep —
baseline included — in ONE device dispatch, and follows Bounded
Dijkstra (arXiv:1903.00436) in spirit: each perturbed solve is measured
as a stretch against the baseline plane computed in the same launch.

The TE half (`te_step`) is the differentiable variant per "Fast Traffic
Engineering by Gradient Descent" (arXiv:2209.10380): the same
relaxation in float32 with a softmin (-tau*logsumexp) combine, so
per-demand path costs are differentiable in the link-weight vector and
`jax.grad` of the total cost yields per-link traffic fractions (the
classic shortest-path sensitivity identity).

Executables here live in their own `whatif` bounded-cache namespace so
interactive sweeps can never evict the live solver's compiled
pipelines (ops/xla_cache.py).
"""

from __future__ import annotations

import numpy as np

from openr_tpu.ops import relax as relax_ops
from openr_tpu.ops.edgeplan import INF32E
from openr_tpu.ops.xla_cache import bounded_jit_cache, instrument_jit

INF_E = int(INF32E)

# fused relaxations per while_loop trip — owned by ops/relax.py so sweep
# trip counts stay comparable with the solver's last_trips
_UNROLL = relax_ops.UNROLL

# "unreachable" in the float TE surrogate: finite so logsumexp grads
# never see inf-inf (which poisons reverse-mode with NaNs), huge enough
# that exp(-_BIG_F/tau) underflows to exactly 0 for any sane tau
_BIG_F = np.float32(1.0e9)


def sweep_max_trips(n_cap: int) -> int:
    """Worst-case while_loop trips for a sweep SSSP — same bound as the
    live pipeline (a failure can only lengthen paths, never beyond the
    n-node chain the pipeline already bounds)."""
    return relax_ops.max_trips(n_cap)


def _make_sweep(b, r, es_cap, er_cap, n_cap, s_cap, r_cap, kr_cap,
                has_res, max_trips, return_dist, kernel="sync",
                delta_exp=0):
    import jax
    import jax.numpy as jnp

    def kernel(deltas, shift_w, res_rows, res_nbr, res_w, roots,
               sh_idx, sh_val, rs_idx, rs_val):
        if has_res:
            nbr_c = jnp.clip(res_nbr, 0, n_cap - 1)
            rows_c = jnp.clip(res_rows, 0, n_cap - 1)

        def one(si, sv, ri, rv):
            # per-lane weight planes: the shared resident mirror with
            # this scenario's overrides scattered in. Pad entries carry
            # an out-of-range index and drop on scatter, so every lane
            # ships the same fixed-size overlay regardless of how many
            # edges its scenario touches.
            sw = (
                shift_w.reshape(-1)
                .at[si].set(sv, mode="drop")
                .reshape(s_cap, n_cap)
            )
            if has_res:
                rw = (
                    res_w.reshape(-1)
                    .at[ri].set(rv, mode="drop")
                    .reshape(r_cap, kr_cap)
                )

            residual = (rows_c, nbr_c, rw) if has_res else None
            relax = relax_ops.make_relax(
                deltas, s_cap, lambda k: sw[k], residual=residual
            )

            dist0 = jnp.full((r, n_cap), INF_E, jnp.int32)
            dist0 = dist0.at[
                jnp.arange(r), jnp.clip(roots, 0, n_cap - 1)
            ].set(0)

            if kernel == "bucketed":
                dist, trips, rounds = relax_ops.run_bucketed(
                    relax, dist0, deltas, sw, lambda k: sw[k],
                    n_cap, s_cap, delta_exp,
                )
            else:
                dist, trips, rounds = relax_ops.run_sync(
                    relax, dist0, max_trips
                )
            return dist, trips, rounds

        dist_all, trips_all, rounds_all = jax.vmap(one)(
            sh_idx, sh_val, rs_idx, rs_val
        )
        # lane 0 is the identity overlay: the baseline every other lane
        # is judged against. `valid` masks pad columns and nodes the
        # baseline itself cannot reach — a failure is only charged for
        # pairs it newly disconnects.
        base = dist_all[0]  # [R, N]
        valid = base < INF_E
        unreachable = (valid[None] & (dist_all >= INF_E)).sum(axis=(1, 2))
        reach = valid[None] & (dist_all < INF_E)
        stretch = jnp.where(reach, dist_all - base[None], 0).max(axis=(1, 2))
        changed = (valid[None] & (dist_all != base[None])).sum(axis=(1, 2))
        # rounds rides LAST so whatif.collect's fixed [:4] / [4] parses
        # stay valid whether or not the dist plane is pulled
        if return_dist:
            return (unreachable, stretch, changed, trips_all.max(),
                    dist_all, rounds_all.max())
        return (unreachable, stretch, changed, trips_all.max(),
                rounds_all.max())

    return kernel


@bounded_jit_cache(namespace="whatif")
def sweep_batch(b, r, es_cap, er_cap, n_cap, s_cap, r_cap, kr_cap,
                has_res, max_trips, return_dist, kernel="sync",
                delta_exp=0):
    """-> (kernel name, instrumented executable) for a sweep of `b`
    scenario lanes x `r` vantage roots over an [n_cap] mirror. Each lane
    carries es_cap shift-slot and er_cap residual-slot overrides (flat
    indices into the raveled planes, same addressing as drain_dirty)."""
    import jax

    kern = _make_sweep(
        b, r, es_cap, er_cap, n_cap, s_cap, r_cap, kr_cap,
        has_res, max_trips, return_dist, kernel, delta_exp,
    )
    name = (
        f"sweep[b={b},r={r},n={n_cap},s={s_cap}"
        + (",res" if has_res else "")
        + (",dist" if return_dist else "")
        + (f",bk{delta_exp}" if kernel == "bucketed" else "")
        + "]"
    )
    aot_key = repr((
        "sweep", b, r, es_cap, er_cap, n_cap, s_cap, r_cap, kr_cap,
        has_res, max_trips, return_dist, kernel, delta_exp,
    ))
    return name, instrument_jit(name, jax.jit(kern), aot_key=aot_key)


# -- differentiable TE (softmin surrogate, arXiv:2209.10380) ---------------


def _make_te(n_links, n_srcs, n_dem, es_cap, er_cap, n_cap, s_cap,
             r_cap, kr_cap, has_res, trips):
    import jax
    import jax.numpy as jnp

    def fn(theta, deltas, res_rows, res_nbr,
           sh_idx, sh_link, rs_idx, rs_link,
           srcs, dem_row, dem_dst, dem_vol, tau, tau_util):
        def softmin2(a, b):
            return -tau * jnp.logaddexp(-a / tau, -b / tau)

        if has_res:
            nbr_c = jnp.clip(res_nbr, 0, n_cap - 1)
            rows_c = jnp.clip(res_rows, 0, n_cap - 1)
            pad_row = (res_rows < 0)[:, None]

        def total_cost(th):
            # float planes: _BIG_F everywhere a directed edge is absent
            # or administratively down, theta[link] at every live slot —
            # so one scalar per link drives both directions
            swf = (
                jnp.full((s_cap * n_cap,), _BIG_F, jnp.float32)
                .at[sh_idx].set(th[sh_link], mode="drop")
                .reshape(s_cap, n_cap)
            )
            if has_res:
                rwf = (
                    jnp.full((r_cap * kr_cap,), _BIG_F, jnp.float32)
                    .at[rs_idx].set(th[rs_link], mode="drop")
                    .reshape(r_cap, kr_cap)
                )
                rwf = jnp.where(pad_row, _BIG_F, rwf)

            def one_src(s):
                d0 = (
                    jnp.full((n_cap,), _BIG_F, jnp.float32)
                    .at[jnp.clip(s, 0, n_cap - 1)].set(0.0)
                )

                def trip(d, _):
                    def cls(acc, kx):
                        delta, w = kx
                        return softmin2(acc, jnp.roll(d + w, delta)), None
                    acc, _ = jax.lax.scan(cls, d, (deltas, swf))
                    if has_res:
                        nd = d[nbr_c]  # [rows, K]
                        # softmin within a row, hard min across rows
                        # (and against the shift classes): a node that
                        # spans several rows takes the best of them
                        cand = -tau * jax.nn.logsumexp(
                            -(nd + rwf) / tau, axis=1
                        )
                        acc = acc.at[rows_c].min(cand)
                    return jnp.minimum(acc, d), None

                d, _ = jax.lax.scan(trip, d0, None, length=trips)
                return d

            dists = jax.vmap(one_src)(srcs)  # [S, N]
            cost = dists[dem_row, dem_dst]  # [D]
            return (dem_vol * cost).sum()

        # shortest-path sensitivity: d(total_cost)/d(theta_l) is the
        # (softmin-weighted) demand volume crossing link l — the link's
        # predicted utilization under this weight vector
        util = jax.grad(total_cost)(theta)

        def loss_fn(th):
            u = jax.grad(total_cost)(th)
            return tau_util * jax.nn.logsumexp(u / tau_util)

        loss, grad = jax.value_and_grad(loss_fn)(theta)
        return loss, grad, util, total_cost(theta)

    return fn


@bounded_jit_cache(namespace="whatif")
def te_step(n_links, n_srcs, n_dem, es_cap, er_cap, n_cap, s_cap,
            r_cap, kr_cap, has_res, trips):
    """-> (name, executable) computing one gradient-descent step of the
    softmin TE surrogate: (soft-max-utilization loss, its gradient in
    the per-link weight vector, per-link utilization, total path cost).
    `trips` is static — reverse-mode AD needs the relaxation as a fixed
    scan, so callers bound it by the measured baseline trip count."""
    import jax

    fn = _make_te(
        n_links, n_srcs, n_dem, es_cap, er_cap, n_cap, s_cap,
        r_cap, kr_cap, has_res, trips,
    )
    name = (
        f"te_step[l={n_links},s={n_srcs},d={n_dem},n={n_cap},t={trips}"
        + (",res" if has_res else "")
        + "]"
    )
    aot_key = repr((
        "te", n_links, n_srcs, n_dem, es_cap, er_cap, n_cap, s_cap,
        r_cap, kr_cap, has_res, trips,
    ))
    return name, instrument_jit(name, jax.jit(fn), aot_key=aot_key)
