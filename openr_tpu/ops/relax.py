"""Shared SSSP relaxation kernels — the single home of the round loop.

Every device solve in the tree (cold full, incremental re-relax, what-if
sweep lanes, and both multichip shard_map kernels) used to carry its own
copy of the same 8-unrolled synchronous round loop. This module owns
that loop — plus a bucketed Δ-stepping variant (arXiv 1604.02113 /
2105.06145) selected by ``decision_config.spf_kernel`` — so the
relaxation semantics exist exactly once and every path picks its
implementation through the same two entry points:

- ``run_sync``:   the classic synchronous rounds. One full relaxation
  per round, ``quantum`` rounds per while_loop trip and one no-change
  test a trip: every round where a round is dear (``sync_quantum``: a
  mirror with a residual), every ``UNROLL`` where it is a few rolls.
  In the multichip tier each relaxation carries one ``lax.pmin`` halo
  exchange — rounds are the unit of inter-chip traffic.
- ``run_bucketed``: bucketed Δ-stepping. Edges are classified light
  (weight <= Δ) or heavy at trace time from the resident shift planes;
  each *bucket epoch* first settles the light frontier with a
  rung-doubling ladder over the most-populous light shift classes
  (pointer-jumping: rung j relaxes 2^j-hop compositions of one class,
  so a light chain of length L settles in O(log L) passes instead of
  L rounds), then applies ONE full synchronous relaxation (all edges,
  heavy + residual) to hand settled mass across buckets. In the
  multichip tier the halo exchange moves to the epoch boundary — one
  ``pmin`` per bucket epoch instead of per relaxation — which is the
  round-proportional 1M-scale traffic win.

Exactness: relaxation over non-negative int32 weights is a monotone
min-plus fixpoint — from any pointwise over-estimate every candidate
ever produced is the length of a REAL path, so both kernels converge to
the same unique fixpoint bit-for-bit (the parity property
tests/test_relax.py enforces against the CPU oracle). The bucketed
epoch loop exits only when ladder + full relaxation leave the plane
unchanged, which certifies ``relax(dist) == dist`` — the exact fixpoint
— regardless of Δ, the ladder width, or early exits. Δ therefore only
steers *performance*, never results, and is quantized to a pow2
exponent (``derive_delta_exp``) so ``bounded_jit_cache`` capacity
classes stay warm under metric jitter.

INF discipline (ops/edgeplan.py): weights <= 2^28, INF_E = 2^29, so
``dist + w <= 2^30`` and the ladder's rung composition ``w + w`` peaks
at 2^30 before its clip back to INF_E — int32-exact everywhere with no
overflow masks.
"""

from __future__ import annotations

import numpy as np

# effectively-infinite metric, same discipline as ops/edgeplan.INF32E
INF_E = 1 << 29

# relaxations fused per while_loop trip where a relaxation is cheap
# beside the trip's no-change test, and the unit `max_trips` counts in.
# What stays comparable across the full / incremental / sweep /
# multichip paths (bench and last_timing report them side by side) is
# `rounds`, the executed relaxations; a trip is `UNROLL` of them in the
# sweep, KSP2 and multichip loops and `sync_quantum(has_res)` in the
# single-chip solves.
UNROLL = 8

# bucketed ladder shape: at most this many light shift classes ride the
# rung-doubling ladder (the most-populous ones win a top_k), and the
# rung doubles at most LADDER_DEPTH times per epoch (2^16 covers any
# light chain the capacity classes can hold; the ladder early-exits on
# the first no-change pass, which is lossless — rung-j stability
# implies every higher rung is stable too).
LADDER_WIDTH = 8
_LADDER_DEPTH_MAX = 16


def max_trips(n_cap: int) -> int:
    """Worst-case while_loop trips for a synchronous solve: the longest
    shortest path visits <= n_cap nodes, +2 trips of slack for the
    detect-no-change exit."""
    return max(2, -(-n_cap // UNROLL) + 2)


def sync_quantum(has_res: bool) -> int:
    """Applications a trip of the single-chip solves' fixpoint loops
    (the relaxation and seed.cone's closure), from what a pass costs.
    Over a residual a pass gathers and scatters the whole ELL (1-2.4 ms
    at the 10k-50k classes) and the test reads two [lanes, n_cap] planes
    (tens of µs): test after every pass, so the loop stops one pass
    after the last change. With shift classes alone a pass is a few
    rolls (~0.08 ms at n_cap 131072), the test is no longer free beside
    it, and trips of ``UNROLL`` amortise it."""
    return 1 if has_res else UNROLL


def fixpoint_bound(n_cap: int) -> int:
    """Round bound for any monotone fixpoint over an n_cap-node graph
    (one node settles per round in the worst case, +2 rounds of slack
    so the final no-change round is observable). ops/ucmp.py's DAG
    weight-spread walk shares this ledger instead of a private
    constant."""
    return n_cap + 2


def ladder_depth(n_cap: int) -> int:
    """Static rung-doubling bound: 2^depth >= n_cap covers the longest
    possible light chain; capped so the gathered rung planes stay
    small."""
    d = 1
    while (1 << d) < max(n_cap, 2):
        d += 1
    return max(4, min(d + 1, _LADDER_DEPTH_MAX))


def derive_delta_exp(deltas, shift_w) -> int:
    """One-shot host/numpy Δ derivation, riding the mirror build
    (ops/edgeplan.build_plan): Δ = 2^exp chosen as the pow2 ceiling of
    the ~p75 finite shift-class weight, so ~3/4 of the shift edges
    classify light and ride the ladder. Returns 0 when the plan has no
    usable shift classes — the eligibility signal callers use to fall
    back to the sync kernel (a ladder with no light classes would do
    one full relaxation per epoch: strictly worse than sync rounds).

    pow2 quantization keeps the (kernel, delta_exp) jit-cache classes
    warm: metric jitter that moves the percentile within a factor of
    two recompiles nothing."""
    d = np.asarray(deltas)
    if d.size == 0 or not bool(np.any(d != 0)):
        return 0
    w = np.asarray(shift_w)
    finite = w[w < INF_E]
    if finite.size == 0:
        return 0
    p75 = max(int(np.percentile(finite, 75)), 1)
    e = 1
    while (1 << e) < p75:
        e += 1
    return min(e, 28)


def relax_bytes(kernel: str, rounds: int, trips: int, s_cap: int,
                d_cap: int, n_cap: int, r_cap: int, kr_cap: int) -> int:
    """Bytes one solve's relaxation loop moves through device memory,
    as a model from its shapes and the rounds it ran. A full relaxation
    (``make_relax``): every shift class reads the distance plane and
    its weight row and reads and writes the accumulator; the residual
    gathers ``kr_cap`` neighbours (index, weight, a distance per lane)
    for each of ``r_cap`` rows and scatter-mins one value per lane into
    each row (row index, read, write); the closing minimum reads two
    planes and writes one. A ladder pass of the bucketed kernel: each
    laddered class reads and writes the plane and reads its rung's
    weight row, then the rungs double (two rows read, one written). A
    trip's no-change test reads two planes. The sync kernel runs
    ``rounds`` full relaxations in ``trips`` trips — ``run_sync``'s
    ``quantum`` relaxations each, so ``trips == rounds`` where the loop
    tests after every pass; the bucketed one
    ``trips`` epochs of one full relaxation each and ``rounds - trips``
    ladder passes. Pad classes and pad rows count: the device runs
    them. Over a measured loop time this gives an achieved rate to set
    beside the device's peak; it is not a count of what the compiler's
    fusions really move."""
    plane = 4 * d_cap * n_cap
    full = (
        s_cap * (3 * plane + 4 * n_cap)
        + r_cap * (kr_cap * (8 + 4 * d_cap) + 8 * d_cap + 4)
        + 3 * plane
    )
    if kernel != "bucketed":
        return rounds * full + trips * 2 * plane
    s_lad = min(s_cap, LADDER_WIDTH)
    ladder = s_lad * (2 * plane + 4 * n_cap + 12 * n_cap) + 2 * plane
    return trips * (full + 2 * plane) + (rounds - trips) * ladder


def make_relax(deltas, s_cap: int, w_of, residual=None, combine=None):
    """One exact synchronous relaxation step ``dist -> dist'`` over a
    shift-decomposed mirror (ops/edgeplan.py). ``dist`` is int32
    [rows, n_cap]; candidates are Jacobi (computed from the incoming
    plane, accumulated by min).

    - ``w_of(k)`` -> the class-k effective weight row [n_cap]
      (root-masked; multichip callers pad their local columns into an
      INF full-width row here). ``k`` may be traced.
    - ``residual``: optional ``(rows_c, nbr_c, rw)`` row-compact ELL
      tail, indices pre-clipped and weights root-masked by the caller.
    - ``combine``: optional hook applied to the combined candidate
      plane before the final min — the multichip sync path passes
      ``lax.pmin(. , 'graph')`` here (one halo per relaxation)."""
    import jax
    import jax.numpy as jnp

    def relax(dist):
        def cls(k, acc):
            return jnp.minimum(
                acc,
                jnp.roll(dist + w_of(k)[None, :], deltas[k], axis=1),
            )

        with jax.named_scope("relax.shift"):
            acc = jax.lax.fori_loop(
                0, s_cap, cls, jnp.full_like(dist, INF_E)
            )
        if residual is not None:
            with jax.named_scope("relax.residual"):
                rows_c, nbr_c, rw = residual
                cand = (dist[:, nbr_c] + rw[None]).min(axis=2)
                acc = acc.at[:, rows_c].min(cand)
        if combine is not None:
            acc = combine(acc)
        return jnp.minimum(acc, dist)

    return relax


def run_sync(relax, state0, bound: int, quantum: int = UNROLL, start=True,
             scope: str = "relax"):
    """Synchronous rounds to fixpoint: ``quantum`` applications of
    ``relax`` per trip, exiting on the first no-change trip or at
    ``bound`` trips (a caller holding ``max_trips(n_cap)`` trips of
    ``UNROLL`` passes ``max_trips * UNROLL // quantum``: the bound on
    applications stays). ``start`` is the carried "changed" flag the loop
    opens with, traced or not: a caller that can tell an input already at
    its fixpoint passes False there and the loop runs no trip. Generic
    over the plane type (int32 distance planes, the cone's 0/1 closure,
    boolean next-hop planes) — ``relax`` must be monotone so the
    no-change exit certifies the fixpoint. ``scope`` names the loop for
    the device trace's ``by_scope``.

    Returns ``(state, trips, rounds)`` with ``rounds = trips * quantum``
    (every executed relaxation counts, converged tail included)."""
    import jax
    import jax.numpy as jnp

    def body(s):
        cur, _, t = s
        new = cur
        for _ in range(quantum):
            new = relax(new)
        return new, jnp.any(new != cur), t + 1

    def cond(s):
        return s[1] & (s[2] < bound)

    with jax.named_scope(scope):
        state, _, trips = jax.lax.while_loop(
            cond, body, (state0, jnp.asarray(start, bool), jnp.int32(0))
        )
    return state, trips, trips * jnp.int32(quantum)


def run_bucketed(relax, dist0, deltas, score_w, w_of, n_cap: int,
                 s_cap: int, delta_exp: int, plane_combine=None):
    """Bucketed Δ-stepping to the exact fixpoint.

    Per bucket epoch:
      1. *light ladder*: the ``LADDER_WIDTH`` shift classes with the
         most light edges (weight <= Δ, counted from ``score_w`` at
         trace time — multichip shards count their resident columns,
         so shards may ladder different classes: local acceleration
         only, exactness never depends on the choice) run rung-doubling
         passes. Rung j of class k holds the 2^j-hop composition
         weights ``w_{j+1}[u] = w_j[u] + w_j[u + 2^j·δ_k]`` (clipped to
         INF_E; index arithmetic wraps mod the pow2 ``n_cap``, exact
         for real chains whose intermediate indices never wrap). A pass
         applies every laddered class's current rung Gauss-Seidel
         chained, then doubles in place; the ladder exits on the first
         no-change pass (lossless: rung-j stability implies rung-j+1
         candidates ``dist[u] + w_j[u] + w_j[u+d_j]`` are already
         dominated) or at ``ladder_depth(n_cap)``.
      2. *bucket handoff*: ONE full synchronous relaxation (all shift
         classes + residual ELL) moves settled mass across the
         light/heavy boundary. ``plane_combine`` (multichip:
         ``lax.pmin(., 'graph')``) runs here, on the full combined
         plane — the shards' ladder-divergent planes re-unify at every
         epoch boundary, so one halo exchange per EPOCH replaces one
         per relaxation.
    The epoch loop exits when an entire epoch changes nothing, which
    certifies ``relax(dist) == dist`` — the same unique fixpoint the
    sync kernel reaches (monotonicity: the ladder only ever applies
    real-path candidates).

    Returns ``(dist, epochs, rounds)`` — ``rounds`` counts executed
    relaxation passes (ladder passes + one handoff per epoch), the
    work metric ``decision.device.rounds`` reports."""
    import jax
    import jax.numpy as jnp

    s_lad = min(s_cap, LADDER_WIDTH)
    j_cap = ladder_depth(n_cap)
    epoch_bound = max_trips(n_cap) * UNROLL
    dq = jnp.int32(1 << max(delta_exp, 1))

    # trace-time light-class selection: most light edges wins a slot
    score = jnp.sum((score_w <= dq).astype(jnp.int32), axis=-1)
    _, lad_idx = jax.lax.top_k(score, s_lad)
    d_base = deltas[lad_idx]
    w_base = jax.vmap(w_of)(lad_idx)
    w_base = jnp.where(w_base <= dq, w_base, INF_E)

    def ladder(dist):
        def pass_once(di, w, d):
            def one(k, acc):
                return jnp.minimum(
                    acc, jnp.roll(acc + w[k][None, :], d[k], axis=1)
                )

            return jax.lax.fori_loop(0, s_lad, one, di)

        def lbody(st):
            di, w, d, j, _ = st
            new = pass_once(di, w, d)
            w2 = jnp.minimum(
                w + jax.vmap(lambda row, s: jnp.roll(row, -s))(w, d),
                INF_E,
            )
            return new, w2, d * 2, j + 1, jnp.any(new != di)

        def lcond(st):
            return st[4] & (st[3] < j_cap)

        with jax.named_scope("relax.ladder"):
            di, _, _, j, _ = jax.lax.while_loop(
                lcond, lbody,
                (dist, w_base, d_base, jnp.int32(0), jnp.bool_(True)),
            )
        return di, j

    def ebody(st):
        dist, _, epochs, rounds = st
        d1, j = ladder(dist)
        d2 = relax(d1)
        if plane_combine is not None:
            d2 = plane_combine(d2)
        return (
            d2,
            jnp.any(d2 != dist),
            epochs + 1,
            rounds + j + 1,
        )

    def econd(st):
        return st[1] & (st[2] < epoch_bound)

    with jax.named_scope("relax"):
        dist, _, epochs, rounds = jax.lax.while_loop(
            econd, ebody,
            (dist0, jnp.bool_(True), jnp.int32(0), jnp.int32(0)),
        )
    return dist, epochs, rounds
