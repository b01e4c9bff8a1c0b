"""Multi-chip sharding of the route-computation pipeline.

The reference is single-process C++ with no device parallelism; the scale
axis it offers is per-area partitioning (SURVEY §5 long-context analogue).
Here the TPU-native scale story is explicit (SURVEY §2 parallelism
checklist), over the shift-decomposed mirror (ops/edgeplan.py):

  - **batch axis ("dp")**: independent SSSP vantages — whole-fabric RIB
    computation (every node's routes; the any-vantage ctrl API) shards
    roots across devices; zero communication.
  - **graph axis ("tp"/"cp")**: the node dimension of the WEIGHT arrays
    (the memory that scales with LSDB size: shift_w [S, N], residual
    ELL) is sharded across devices. Each relaxation computes the partial
    candidate field contributed by the LOCAL source columns, then
    combines with jax.lax.pmin over the 'graph' axis — the halo exchange
    of this domain. The frontier (dist [D, N]) stays replicated, so a
    relax is: local shifts over a locally-weighted full-width field +
    one pmin collective. This is what lets a 1M+-node LSDB's weight
    state exceed a single chip's HBM while collectives ride ICI.

Both axes compose in one jax.sharding.Mesh('batch', 'graph') via
shard_map. Iteration count is a diameter bound measured on device by the
single-chip pipeline (trips are part of its output), not a blind
n_nodes bound — every shard runs the same fixed trip count, keeping the
mesh in lockstep with no host round-trips.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from openr_tpu.ops import incremental as incremental_ops
from openr_tpu.ops import relax as relax_ops
from openr_tpu.ops.edgeplan import INF32E
from openr_tpu.ops.xla_cache import bounded_jit_cache, instrument_jit, retrace

INF_E = int(INF32E)
_UNROLL = relax_ops.UNROLL


def make_mesh(n_devices: Optional[int] = None, batch: Optional[int] = None):
    """Factor devices into a ('batch', 'graph') mesh. Prefers a wider
    batch axis (root fan-out is embarrassingly parallel; graph sharding
    pays a pmin per relaxation step)."""
    import jax

    devs = jax.devices()
    n = n_devices or len(devs)
    devs = devs[:n]
    if batch is None:
        graph = 1
        # give the graph axis a factor of 2 when we have >= 4 devices so
        # both kinds of sharding are exercised
        if n >= 4 and n % 2 == 0:
            graph = 2
        batch = n // graph
    else:
        graph = n // batch
    assert batch * graph == n, (batch, graph, n)
    from jax.sharding import Mesh

    return Mesh(np.array(devs).reshape(batch, graph), ("batch", "graph"))


# bounded (not lru_cache): superseded fabric capacity buckets release
# their executables' HBM, and the namespace shows up in the cache-class
# census and retrace attribution (xla_cache.fabric_* / retraces.fabric)
@bounded_jit_cache(namespace="fabric")
def _sharded_fabric_fn(mesh, n_cap: int, s_cap: int, r_cap: int,
                       kr_cap: int, has_res: bool, d_cap: int,
                       p_cap: int, a_cap: int, n_trips: int,
                       lfa: bool = False, rt_cap: int = 0):
    """(kernel name, instrumented executable) for the shard_mapped
    whole-fabric pipeline: for each root (sharded over 'batch'),
    batched-seed SSSP with graph-axis-sharded weights, then best-route
    selection. Returns (dist[R, N], metric[R, P], nh_mask[R, P, D])."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    graph_size = mesh.shape["graph"]
    shard_cols = n_cap // graph_size

    def local_fn(
        deltas,      # [S]            replicated
        shift_w,     # [S, N/g]       node columns sharded over 'graph'
        res_rows,    # [R/g]          residual rows sharded
        res_nbr,     # [R/g, K]
        res_w,       # [R/g, K]
        roots,       # [Rt/b]         roots sharded over 'batch'
        root_nbr,    # [Rt/b, D]
        root_w,      # [Rt/b, D]
        ann_node,    # [P, A]         announcer matrix replicated
        ann_flags,
        path_pref,
        source_pref,
        dist_adv,
        min_nh,      # [P, A]
        v4_blocked,  # [P]
    ):
        my_col0 = jax.lax.axis_index("graph") * shard_cols

        def one_root(root, seeds_nbr, seeds_w):
            # mask root as transit within my local source columns (no
            # column matches when the root lives in another shard)
            local_root = root - my_col0
            col_iota = jnp.arange(shard_cols)
            sw = jnp.where(
                col_iota[None, :] == local_root, INF_E, shift_w
            )
            rw = jnp.where(res_nbr == root, INF_E, res_w)
            valid = seeds_w < INF_E
            seed_idx = jnp.clip(seeds_nbr, 0, n_cap - 1)
            dist0 = jnp.full((d_cap, n_cap), INF_E, jnp.int32)
            dist0 = dist0.at[jnp.arange(d_cap), seed_idx].min(
                jnp.where(valid, 0, INF_E).astype(jnp.int32)
            )

            nbr_c = jnp.clip(res_nbr, 0, n_cap - 1)
            rows_c = jnp.clip(res_rows, 0, n_cap - 1)

            # local sources' contribution over the full-width field
            # (ops/relax.py owns the relaxation body); the pmin combine
            # is the per-relaxation halo exchange
            def w_of(k):
                return jax.lax.dynamic_update_slice(
                    jnp.full((n_cap,), INF_E, jnp.int32), sw[k],
                    (my_col0,),
                )

            relax = relax_ops.make_relax(
                deltas, s_cap, w_of,
                residual=(rows_c, nbr_c, rw) if has_res else None,
                combine=lambda pc: jax.lax.pmin(pc, "graph"),
            )

            def body(i, dist):
                for _ in range(_UNROLL):
                    dist = relax(dist)
                return dist

            dist_d = jax.lax.fori_loop(0, n_trips, body, dist0)
            # convergence verdict: one extra relaxation must be a no-op.
            # Under-iteration (n_trips below the true diameter bound) is
            # thereby detected instead of silently returning too-large
            # distances for distant roots.
            converged = jnp.all(relax(dist_d) == dist_d)
            via = seeds_w[:, None] + dist_d
            dist = jnp.minimum(via.min(axis=0), INF_E).at[root].set(0)

            ann_valid = (ann_flags & 1).astype(bool)
            ann_over = (ann_flags & 2).astype(bool)
            idx = jnp.clip(ann_node, 0, n_cap - 1)
            ann_dist = dist[idx]
            reach = ann_valid & (ann_dist < INF_E)
            neg = -(2**31)
            pp = jnp.where(reach, path_pref, neg)
            s = reach & (pp == pp.max(axis=1, keepdims=True))
            sp = jnp.where(s, source_pref, neg)
            s = s & (sp == sp.max(axis=1, keepdims=True))
            da = jnp.where(s, dist_adv, INF_E)
            s2 = s & (da == da.min(axis=1, keepdims=True))
            nd = s2 & ~ann_over
            s3 = jnp.where(nd.any(axis=1, keepdims=True), nd, s2)
            igp = jnp.where(s3, ann_dist, INF_E)
            metric = igp.min(axis=1)
            s4 = s3 & (igp == metric[:, None])
            on_sp = (via == dist[None, :]).T
            nh_mask = jnp.any(s4[:, :, None] & on_sp[idx], axis=1)
            if lfa:
                # rfc5286 alternates, same predicate as the single-chip
                # pipeline (tpu_solver._make_pipeline): neighbor slot d
                # backs up prefix p iff its own distance to the selected
                # announcers beats detouring back through this root
                d_root = dist_d[:, root]
                ann_nd = dist_d.T[idx]  # [P, A, D]
                nbr_pd = jnp.where(
                    s3[:, :, None], ann_nd, INF_E
                ).min(axis=1)
                link_up = seeds_w < INF_E
                ok_lfa = (
                    link_up[None, :]
                    & ~nh_mask
                    & (nbr_pd < INF_E)
                    & (nbr_pd < d_root[None, :] + metric[:, None])
                )
                alt = jnp.where(
                    ok_lfa, seeds_w[None, :] + nbr_pd, jnp.int32(1 << 30)
                )
                has_lfa = ok_lfa.any(axis=1)
                lfa_slot = jnp.where(
                    has_lfa,
                    jnp.argmin(alt, axis=1).astype(jnp.int32),
                    -1,
                )
                lfa_metric = jnp.where(has_lfa, alt.min(axis=1), 0)
            else:
                lfa_slot = jnp.full((p_cap,), -1, jnp.int32)
                lfa_metric = jnp.zeros((p_cap,), jnp.int32)
            # route-level ok on device (shared with the single-chip
            # compaction) so the host skips its own O(P*A) filter pass
            from openr_tpu.ops.compact import route_ok_device

            ok = route_ok_device(
                metric, s3, nh_mask, ann_node, min_nh, v4_blocked, root
            )
            return (
                dist, metric, s3, nh_mask, lfa_slot, lfa_metric, ok,
                converged,
            )

        return jax.vmap(one_root)(roots, root_nbr, root_w)

    from jax import shard_map

    jitted = jax.jit(
        shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(
                P(),                 # deltas
                P(None, "graph"),    # shift_w columns
                P("graph"),          # res_rows
                P("graph", None),    # res_nbr
                P("graph", None),    # res_w
                P("batch"),          # roots
                P("batch", None),    # root_nbr
                P("batch", None),    # root_w
                P(), P(), P(), P(), P(),
                P(),                 # min_nh
                P(),                 # v4_blocked
            ),
            out_specs=(
                P("batch", None),
                P("batch", None),
                P("batch", None, None),
                P("batch", None, None),
                P("batch", None),
                P("batch", None),
                P("batch", None),    # ok
                P("batch"),
            ),
            check_vma=False,
        )
    )
    mesh_tag = f"{mesh.shape['batch']}x{mesh.shape['graph']}"
    # rt_cap (the padded root-batch extent) is part of the executable's
    # identity: instrument_jit pins ONE compiled aval set per instance,
    # so the factory key must carry every dispatched-shape degree of
    # freedom (a plain jax.jit would have silently retraced instead)
    name = (
        f"fabric[mesh={mesh_tag},n={n_cap},rt={rt_cap},p={p_cap}"
        f",t={n_trips}" + (",lfa" if lfa else "") + "]"
    )
    aot_key = repr((
        "fabric", mesh_tag, n_cap, s_cap, r_cap, kr_cap, has_res,
        d_cap, p_cap, a_cap, n_trips, lfa, rt_cap,
    ))
    return name, instrument_jit(name, jitted, aot_key=aot_key)


class Unconverged(AssertionError):
    """The fixed trip bound was below the graph's diameter bound."""


def plan_shardings(mesh, n_cap: int, r_cap: int, d_cap: int) -> dict:
    """NamedSharding layout for the production multichip tier
    (decision/tpu_solver.py): the GSPMD twin of `_sharded_fabric_fn`'s
    shard_map specs. Weight state — the memory that scales with LSDB
    size — shards its node/residual axes across 'graph'; the per-link
    root tables shard across 'batch' (vantage fan-out); small planes
    (deltas, prefix matrix, previous outputs) replicate. An axis whose
    extent doesn't divide the mesh axis falls back to replicated for
    that array: correctness never depends on the placement, only HBM
    footprint does, and the caller pads the axes it wants sharded.

    Returns a dict of jax.sharding.NamedSharding keyed by role:
    ``replicated``, ``shift_w`` [S, N], ``res_rows`` [R], ``res_2d``
    [R, K], ``root_vec`` [D], ``dist`` [D, N]."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    b = mesh.shape["batch"]
    g = mesh.shape["graph"]
    rep = NamedSharding(mesh, P())

    def sh(spec, ok):
        return NamedSharding(mesh, spec) if ok else rep

    return {
        "replicated": rep,
        "shift_w": sh(P(None, "graph"), n_cap % g == 0),
        "res_rows": sh(P("graph"), r_cap % g == 0),
        "res_2d": sh(P("graph", None), r_cap % g == 0),
        "root_vec": sh(P("batch"), d_cap % b == 0),
        # the resident distance plane shards its vantage lanes over
        # 'batch' but keeps the node axis full-width: the mc SSSP
        # kernels roll along that axis, and a roll on a sharded axis is
        # exactly the op the GSPMD partitioner cannot be trusted with
        # (see make_mc_sssp) — each device owns whole lanes instead
        "dist": sh(P("batch", None), d_cap % b == 0),
    }


def make_mc_sssp(mesh, s_cap: int, has_res: bool, n_cap: int,
                 d_cap: int, max_trips: int,
                 kernel: str = "sync", delta_exp: int = 0):
    """shard_mapped twin of tpu_solver._plan_sssp for the production
    multichip capacity tier: batched SSSP from the root's out-neighbor
    seeds with shift_w's node columns sharded over 'graph' and the
    vantage lanes sharded over 'batch'.

    Why not plain GSPMD over the existing kernel: the relaxation's
    `jnp.roll(dist + w, deltas[k], axis=1)` has a TRACED shift amount,
    and XLA's partitioner miscompiles a dynamic roll along a sharded
    axis (observed on CPU GSPMD: outputs multiplied by the orthogonal
    mesh-axis size — an unreduced partial-sum artifact). shard_map
    sidesteps the partitioner entirely: each device rolls a locally
    FULL-WIDTH field seeded with only its own weight columns
    (dynamic_update_slice into an INF plane, exactly like
    _sharded_fabric_fn), and one lax.pmin over 'graph' per relaxation
    is the halo exchange. The residual ELL tail is small and irregular,
    so every 'graph' member computes it identically on replicated
    inputs — pmin of identical candidates is a no-op, and the
    divergence bookkeeping a row-sharded residual would need (partial
    scatter-mins per member) never arises.

    Convergence stays data-dependent (while_loop, not the fabric
    kernel's fixed trip bound): members of one 'graph' group always
    agree on the post-pmin plane, so they take the same trip count and
    their collectives stay matched; 'batch' groups share no collectives
    and may exit at different trip counts — legal, their replica groups
    are disjoint. Requires n_cap % graph == 0 and d_cap % batch == 0
    (the solver pads both).

    With ``kernel="bucketed"`` the round loop swaps for ops/relax.py's
    Δ-stepping epochs: each shard ladders its own most-light-populous
    LOCAL classes collective-free (shards may pick different classes —
    local acceleration only), then the epoch handoff relaxation's FULL
    combined plane takes ONE lax.pmin over 'graph'. The halo exchange
    moves from per-relaxation to per-EPOCH — the round-proportional
    1M-scale traffic reduction. Epoch exit still certifies the global
    fixpoint: the post-pmin plane equalling the (group-uniform) epoch
    input forces every shard's partial candidates to be dominated, so
    the union — the full relaxation — is too.

    Returns a callable (deltas, shift_w, res_rows, res_nbr, res_w,
    root, root_nbr, root_w) -> (dist [D, N] sharded P('batch', None),
    trips [batch] per-group trip counts (bucket epochs under the
    bucketed kernel), rounds [batch] executed relaxation passes).
    Compose it inside a jit — it is not jitted here."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    g = mesh.shape["graph"]
    b = mesh.shape["batch"]
    assert n_cap % g == 0 and d_cap % b == 0, (n_cap, d_cap, mesh.shape)
    shard_cols = n_cap // g

    def local_fn(deltas, shift_w, res_rows, res_nbr, res_w, root,
                 root_nbr, root_w):
        my_col0 = jax.lax.axis_index("graph") * shard_cols
        col_iota = jnp.arange(shard_cols)
        # mask root as transit within my local source columns
        sw = jnp.where(
            col_iota[None, :] == (root - my_col0), INF_E, shift_w
        )
        if has_res:
            rw = jnp.where(res_nbr == root, INF_E, res_w)
            nbr_c = jnp.clip(res_nbr, 0, n_cap - 1)
            rows_c = jnp.clip(res_rows, 0, n_cap - 1)
        d_loc = d_cap // b
        valid = root_w < INF_E
        seed_idx = jnp.clip(root_nbr, 0, n_cap - 1)
        dist0 = jnp.full((d_loc, n_cap), INF_E, jnp.int32)
        dist0 = dist0.at[jnp.arange(d_loc), seed_idx].min(
            jnp.where(valid, 0, INF_E).astype(jnp.int32)
        )

        def w_of(k):
            return jax.lax.dynamic_update_slice(
                jnp.full((n_cap,), INF_E, jnp.int32), sw[k],
                (my_col0,),
            )

        residual = (rows_c, nbr_c, rw) if has_res else None
        if kernel == "bucketed":
            # collective-free ladder per shard; ONE pmin per bucket
            # epoch on the full combined plane re-unifies the group
            relax_local = relax_ops.make_relax(
                deltas, s_cap, w_of, residual=residual
            )
            dist, trips, rounds = relax_ops.run_bucketed(
                relax_local, dist0, deltas, sw, w_of,
                n_cap, s_cap, delta_exp,
                plane_combine=lambda d: jax.lax.pmin(d, "graph"),
            )
        else:
            relax = relax_ops.make_relax(
                deltas, s_cap, w_of, residual=residual,
                combine=lambda pc: jax.lax.pmin(pc, "graph"),
            )
            dist, trips, rounds = relax_ops.run_sync(
                relax, dist0, max_trips
            )
        return dist, trips[None], rounds[None]

    from jax import shard_map

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(),                 # deltas
            P(None, "graph"),    # shift_w columns
            P(), P(), P(),       # residual ELL replicated at use
            P(),                 # root scalar
            P("batch"),          # root_nbr (vantage lanes)
            P("batch"),          # root_w
        ),
        out_specs=(P("batch", None), P("batch"), P("batch")),
        check_vma=False,
    )


def make_mc_incremental_sssp(mesh, s_cap: int, has_res: bool,
                             n_cap: int, d_cap: int, max_trips: int,
                             kernel: str = "sync", delta_exp: int = 0):
    """shard_mapped twin of ops/incremental.incremental_sssp for the
    multichip tier. Same layout contract as make_mc_sssp (shift
    columns over 'graph', vantage lanes over 'batch', residual
    replicated at use), plus the warm plane prev_dist enters sharded
    P('batch', None) — each device re-relaxes only its own lanes.

    Parity notes (the invariants that make this bit-identical where it
    must be, and deliberately looser where it may be):
    - The distance fixpoint is unique, so dist matches the single-chip
      incremental AND cold solves bit-for-bit regardless of anything
      below.
    - The parent plane is assembled from per-shard tight-edge finds
      combined with one lax.pmax over 'graph' (largest source index
      wins across shards) — a deterministic, group-uniform choice, but
      not necessarily the same parent the single-chip kernel picks.
      Any tight parent is valid for subtree invalidation; only the
      cone SIZE can differ, and over-invalidation is safe.
    - The dirty-slot gather (new weight at a global flat index) reads
      the owning shard's columns and resolves with a pmin over 'graph'
      (absent shards contribute INF) — group-uniform by construction.
    - cone is psum'd over 'batch' so fell_back (warm vs cold seed) is
      one GLOBAL decision, exactly like the single-chip kernel; every
      'graph' group member then seeds identically and the relaxation
      while_loop stays in lockstep within each group.

    Returns a callable (...incremental_sssp args...) ->
    (dist [D, N] P('batch', None), trips [batch], cone [1],
    fell_back [1], rounds [batch], cone_passes [batch]: the passes the
    cone's closure ran, whole trips of 8 here). The final re-relaxation
    consumes ops/relax.py like make_mc_sssp — under the bucketed kernel
    its halo exchange likewise drops to one pmin per bucket epoch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    g = mesh.shape["graph"]
    b = mesh.shape["batch"]
    assert n_cap % g == 0 and d_cap % b == 0, (n_cap, d_cap, mesh.shape)
    shard_cols = n_cap // g
    d_loc = d_cap // b

    def local_fn(deltas, shift_w, res_rows, res_nbr, res_w, root,
                 root_nbr, root_w, prev_dist,
                 s_dirty_idx, s_dirty_old, r_dirty_idx, r_dirty_old,
                 cone_limit):
        my_col0 = jax.lax.axis_index("graph") * shard_cols
        col_iota = jnp.arange(shard_cols)
        local_root = root - my_col0
        swm_new = jnp.where(
            col_iota[None, :] == local_root, INF_E, shift_w
        )
        # reconstruct the OLD local plane: dirty tuples carry GLOBAL
        # flat indices into [S, N]; translate to this shard's columns,
        # everything foreign drops
        ok_s = (s_dirty_idx >= 0) & (s_dirty_idx < s_cap * n_cap)
        sic = jnp.clip(s_dirty_idx, 0, s_cap * n_cap - 1)
        k_j = sic // n_cap
        u_j = sic % n_cap
        u_loc = u_j - my_col0
        owned = ok_s & (u_loc >= 0) & (u_loc < shard_cols)
        lflat = jnp.where(
            owned,
            k_j * shard_cols + jnp.clip(u_loc, 0, shard_cols - 1),
            s_cap * shard_cols,
        )
        old_local = (
            shift_w.ravel()
            .at[lflat].set(s_dirty_old, mode="drop")
            .reshape(shift_w.shape)
        )
        swm_old = jnp.where(
            col_iota[None, :] == local_root, INF_E, old_local
        )
        if has_res:
            old_res = (
                res_w.ravel()
                .at[r_dirty_idx].set(r_dirty_old, mode="drop")
                .reshape(res_w.shape)
            )
            rwm_new = jnp.where(res_nbr == root, INF_E, res_w)
            rwm_old = jnp.where(res_nbr == root, INF_E, old_res)
            nbr_c = jnp.clip(res_nbr, 0, n_cap - 1)
            rows_c = jnp.clip(res_rows, 0, n_cap - 1)
            rows_s = jnp.where(res_rows >= 0, res_rows, n_cap)

        with jax.named_scope("seed.parent"):
            # --- parent plane under the OLD weights (cf. ops/incremental
            # _parent_plane): per-shard tight-edge finds over local
            # columns, then one pmax('graph') combine ---
            src = jnp.arange(n_cap, dtype=jnp.int32)
            par = jnp.full((d_loc, n_cap), -1, jnp.int32)

            def pcls(k, par):
                dk = deltas[k]
                w_full = jax.lax.dynamic_update_slice(
                    jnp.full((n_cap,), INF_E, jnp.int32), swm_old[k],
                    (my_col0,),
                )
                cand = prev_dist + w_full[None, :]
                tgt = jnp.roll(prev_dist, -dk, axis=1)
                hit = (
                    (prev_dist < INF_E) & (w_full < INF_E)[None, :]
                    & (cand == tgt)
                )
                hit_v = jnp.roll(hit, dk, axis=1)
                src_v = jnp.roll(src, dk)[None, :]
                return jnp.where((par < 0) & hit_v, src_v, par)

            par = jax.lax.fori_loop(0, s_cap, pcls, par)
            par = jax.lax.pmax(par, "graph")
            if has_res:
                # replicated after the pmax: the single-chip find
                par = incremental_ops.residual_parents(
                    par, prev_dist, res_rows, res_nbr, rwm_old, n_cap
                )

        with jax.named_scope("seed.cone"):
            # --- classify increased dirty edges + seed the cone ---
            aff = jnp.zeros((d_loc, n_cap), jnp.int32)
            new_loc = jnp.where(
                owned,
                swm_new.ravel()[
                    jnp.clip(lflat, 0, s_cap * shard_cols - 1)
                ],
                INF_E,
            )
            new_m = jax.lax.pmin(new_loc, "graph")
            old_m = jnp.where(u_j == root, INF_E, s_dirty_old)
            inc_s = ok_s & (new_m > old_m)
            v_j = (u_j + deltas[k_j]) % n_cap
            pv = par[:, jnp.clip(v_j, 0, n_cap - 1)]
            seed_s = (inc_s[None, :] & (pv == u_j[None, :])).astype(
                jnp.int32
            )
            v_sc = jnp.where(ok_s, v_j, n_cap)
            aff = aff.at[:, v_sc].max(seed_s, mode="drop")

            if has_res:
                kr = res_nbr.shape[1]
                lim = res_rows.shape[0] * kr
                ok_r = (r_dirty_idx >= 0) & (r_dirty_idx < lim)
                ric = jnp.clip(r_dirty_idx, 0, lim - 1)
                row_j = ric // kr
                c_j = ric % kr
                ru = res_nbr[row_j, c_j]
                rv = res_rows[row_j]
                new_mr = rwm_new[row_j, c_j]
                old_mr = jnp.where(ru == root, INF_E, r_dirty_old)
                inc_r = ok_r & (new_mr > old_mr) & (ru >= 0) & (rv >= 0)
                pv_r = par[:, jnp.clip(rv, 0, n_cap - 1)]
                seed_r = (inc_r[None, :] & (pv_r == ru[None, :])).astype(
                    jnp.int32
                )
                rv_sc = jnp.where(ok_r & (rv >= 0), rv, n_cap)
                aff = aff.at[:, rv_sc].max(seed_r, mode="drop")

            # --- propagate aff to tree descendants (par is group-uniform
            # and the residual is replicated, so no collectives here) ---
            nodes = jnp.arange(n_cap, dtype=jnp.int32)

            def aff_step(acc):
                def cls(k, a):
                    dk = deltas[k]
                    childpar = jnp.roll(par, -dk, axis=1)
                    is_child = childpar == nodes[None, :]
                    contrib = jnp.roll(
                        jnp.where(is_child, a, 0), dk, axis=1
                    )
                    return jnp.maximum(a, contrib)

                acc = jax.lax.fori_loop(0, s_cap, cls, acc)
                if has_res:
                    is_child = (
                        par[:, rows_c][:, :, None] == res_nbr[None]
                    ) & (res_nbr >= 0)[None]
                    acc_n = acc[:, nbr_c]
                    contrib = jnp.where(is_child, acc_n, 0).max(axis=2)
                    acc = acc.at[:, rows_s].max(contrib, mode="drop")
                return acc

            def aff_body(state):
                acc, _, t = state
                new = acc
                for _ in range(_UNROLL):
                    new = aff_step(new)
                return new, jnp.any(new != acc), t + 1

            def aff_cond(state):
                return state[1] & (state[2] < max_trips)

            aff, _, cone_trips = jax.lax.while_loop(
                aff_cond, aff_body, (aff, jnp.bool_(True), jnp.int32(0))
            )

        # one global warm-vs-cold decision: sum lane-partial cones over
        # 'batch' ('graph' members already agree)
        cone = jax.lax.psum(aff.sum().astype(jnp.int32), "batch")
        fell_back = cone > cone_limit

        valid = root_w < INF_E
        seed_idx = jnp.clip(root_nbr, 0, n_cap - 1)
        pin = jnp.where(valid, 0, INF_E).astype(jnp.int32)
        lanes = jnp.arange(d_loc)
        warm = jnp.where(aff > 0, INF_E, prev_dist)
        warm = warm.at[lanes, seed_idx].min(pin)
        cold = jnp.full((d_loc, n_cap), INF_E, jnp.int32)
        cold = cold.at[lanes, seed_idx].min(pin)
        dist0 = jnp.where(fell_back, cold, warm)

        def w_of(k):
            return jax.lax.dynamic_update_slice(
                jnp.full((n_cap,), INF_E, jnp.int32), swm_new[k],
                (my_col0,),
            )

        residual = (rows_c, nbr_c, rwm_new) if has_res else None
        if kernel == "bucketed":
            relax_local = relax_ops.make_relax(
                deltas, s_cap, w_of, residual=residual
            )
            dist, trips, rounds = relax_ops.run_bucketed(
                relax_local, dist0, deltas, swm_new, w_of,
                n_cap, s_cap, delta_exp,
                plane_combine=lambda d: jax.lax.pmin(d, "graph"),
            )
        else:
            relax = relax_ops.make_relax(
                deltas, s_cap, w_of, residual=residual,
                combine=lambda pc: jax.lax.pmin(pc, "graph"),
            )
            dist, trips, rounds = relax_ops.run_sync(
                relax, dist0, max_trips
            )
        return (dist, trips[None], cone[None], fell_back[None], rounds[None],
                (cone_trips * _UNROLL)[None])

    from jax import shard_map

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(),                 # deltas
            P(None, "graph"),    # shift_w columns
            P(), P(), P(),       # residual ELL replicated at use
            P(),                 # root scalar
            P("batch"),          # root_nbr
            P("batch"),          # root_w
            P("batch", None),    # prev_dist (lanes stay home)
            P(), P(), P(), P(),  # dirty tuples replicated
            P(),                 # cone_limit
        ),
        out_specs=(
            P("batch", None), P("batch"), P(), P(), P("batch"), P("batch"),
        ),
        check_vma=False,
    )


def pad_to(arr: np.ndarray, size: int, fill, axis: int = 0) -> np.ndarray:
    if arr.shape[axis] == size:
        return arr
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, size - arr.shape[axis])
    return np.pad(arr, pad, constant_values=fill)


def sharded_fabric_step(mesh, plan, matrix, roots, out_nbr, out_w,
                        n_trips: int, check_convergence: bool = True,
                        lfa: bool = False, block_v4: bool = False,
                        with_ok: bool = False):
    """Run the sharded whole-fabric pipeline.

    plan: ops.edgeplan.EdgePlan; matrix: ops.csr.PrefixMatrix;
    roots [Rt] int32 (padded to a multiple of the batch axis);
    out_nbr/out_w [Rt, D]: per-root out-edge tables; n_trips: diameter
    bound in unrolled trips (take it from the single-chip pipeline's
    measured trip count with 2x slack — one vantage's trip count bounds
    its eccentricity, and another root's can be up to ~2x that). The
    kernel emits a per-root convergence verdict (one extra relaxation
    must be a fixpoint no-op); with check_convergence the verdict is
    asserted host-side (raising Unconverged), so an insufficient bound
    fails loudly — TpuSpfSolver.build_fabric_route_dbs catches it and
    retries with a doubled bound.

    Returns (dist [Rt, N_cap], metric [Rt, P_cap], s3 [Rt, P_cap, A]
    selected-announcer masks, nh_mask [Rt, P_cap, D], lfa_slot
    [Rt, P_cap] (-1 = none; only meaningful with lfa=True), lfa_metric
    [Rt, P_cap]). With with_ok=True a seventh array is appended: the
    device-computed route-level ok mask [Rt, P_cap]
    (ops/compact.route_ok_device with v4 rows blocked per block_v4),
    which ColumnarRib.set_full_arrays consumes directly.
    """
    g = mesh.shape["graph"]
    # pad the node axis up to the graph-axis size so arbitrary capacity
    # classes work on any mesh factorization. Exact by construction:
    # shift deltas are signed differences (ops/edgeplan.py), so no real
    # edge ever wraps through the pad columns, and INF_E-filled pad
    # columns neither emit (dist + INF_E never beats a real candidate)
    # nor receive (real targets stay < plan.n_cap) finite distances.
    n_cap = ((plan.n_cap + g - 1) // g) * g
    shift_w = pad_to(plan.shift_w, n_cap, INF_E, axis=1)
    r_cap = ((plan.res_rows.shape[0] + g - 1) // g) * g
    res_rows = pad_to(plan.res_rows, r_cap, -1)
    res_nbr = pad_to(plan.res_nbr, r_cap, -1)
    res_w = pad_to(plan.res_w, r_cap, INF_E)
    kr_cap = res_nbr.shape[1]
    d_cap = out_nbr.shape[1]
    p_cap, a_cap = matrix.ann_node.shape
    has_res = plan.k_res > 0

    idxm = np.clip(matrix.ann_node, 0, None)
    flags = matrix.ann_valid.astype(np.int32) | (
        plan.node_overloaded[idxm].astype(np.int32) << 1
    )

    v4_blocked = (
        matrix.is_v4 if block_v4 else np.zeros(p_cap, bool)
    )

    name, fn = _sharded_fabric_fn(
        mesh, n_cap, plan.s_cap, r_cap, kr_cap, has_res, d_cap,
        p_cap, a_cap, n_trips, lfa, int(roots.shape[0]),
    )
    sig = (n_cap, r_cap, d_cap, p_cap, a_cap, n_trips, int(roots.shape[0]))
    with retrace.scope("fabric", name, sig):
        dist, metric, s3, nh_mask, lfa_slot, lfa_metric, ok, converged = fn(
            plan.deltas, shift_w, res_rows, res_nbr, res_w,
            roots.astype(np.int32), out_nbr.astype(np.int32),
            out_w.astype(np.int32),
            matrix.ann_node, flags, matrix.path_pref, matrix.source_pref,
            matrix.dist_adv,
            matrix.min_nexthop.astype(np.int32), v4_blocked,
        )
    if check_convergence:
        conv = np.asarray(converged)
        if not conv.all():
            raise Unconverged(
                f"sharded SSSP unconverged for roots "
                f"{np.asarray(roots)[~conv].tolist()}: raise n_trips ({n_trips})"
            )
    if with_ok:
        return dist, metric, s3, nh_mask, lfa_slot, lfa_metric, ok
    return dist, metric, s3, nh_mask, lfa_slot, lfa_metric
