"""The chip capture behind `ops/edgeplan._ROW_COST`: what one pass over
the residual ELL costs at each candidate width, on the two deployments
whose mirrors are all residual.

    chiprun -- python3 tools/residual_width.py            # both
    chiprun -- python3 tools/residual_width.py fabric10k  # one
    chiprun -- python3 tools/residual_width.py fabric10k:16  # from width 16 down
    JAX_PLATFORMS=cpu python3 tools/residual_width.py fabric-small  # rehearsal
    chiprun -- python3 tools/residual_width.py --lanes 4,8,16,32,64 wan50k
    chiprun -- python3 tools/residual_width.py --prefixes-per-node 1,8,32 fabric10k

For every power-of-two width from 2 to the widest destination's it
rebuilds the mirror at that width (by replacing the builder's own choice,
`edgeplan._residual_width`, for the length of the build) and reports

  `loop`   ms per pass of a jitted loop of relaxations over that mirror
           alone (`ops/relax.make_relax`, all lanes of the vantage), by
           the host's clock round `block_until_ready`; `kmajor` is the
           same pass with the ELL stored [K, R], `gather` the pass with
           no scatter into the plane;
  `scope`  ms per pass of `relax.residual`, and per epoch of `seed.cone`,
           `seed.parent` and the whole device program, from
           `profiler_stop()`'s `by_scope` over incremental events of the
           solver itself (a remote link's metric up, then back), whose
           tables are compared with the first width's; taken at the
           first width and at widths up to 8 (two compiles a width).
           `rounds` and `cone_passes` list what each event's two loops
           ran (the latter null on a tree that does not count them), so
           a scope's ms a pass can be read off the same line.

The last lines fit `loop` to `a * r_cap * (K + c)` by least squares: `c`
is `_ROW_COST`. Needs a TPU: a CPU's gather costs nothing like the chip's.

With `--lanes` the widths are left alone (the mirror is built as the
builder builds it) and the lanes of the distance plane vary instead: seen
from the deployment's widest router (an aggregation router of the WAN, a
fabric switch), the first n of its links hold a lane each, for every n of
the list. A line gives `loop` and `gather` as above and `parent`, ms a
call of `seed.parent` (`ops/incremental._parent_plane`) over the plane the
relaxation converges to from those links; the last line is the solver's
own capture from that router with all its links (`scope`, with the whole
`by_scope` an event).

With `--prefixes-per-node` (a fabric: the generator takes the count) the
mirror and the lanes are left alone and the prefix plane varies instead:
the same graph with each count of prefixes a switch, one line a count with
the rows the device carries (`prefix_rows`, `prefixes`) and the solver's
own capture from the usual vantage: `by_scope` an event (`candidates` is
the incremental solve's mask of the rows its moved node columns can reach,
beside `select`, `lfa`, `nexthop`, `unpack`: ISSUE 44), `row_stages`, the
sum of the scopes that work over the rows (ROW_SCOPES), beside
`device_ms_per_event`, and `rows_looked`, the rows each event's row stages
looked at (the candidates, or every row: null on a tree that looks at
every row always). Then, under `prefix_only`, the same solver's
capture of prefix events alone (one prefix of the far switch withdrawn,
then advertised back: the prefix-only program, which since PR 42 works
over the candidate rows it is handed): `by_scope` and the device's ms an
event again, with the rows handed and their bucket (null on a tree that
hands none) — flat in the count of rows where the program is.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import set_metric  # noqa: E402
from openr_tpu.models import topologies  # noqa: E402
from openr_tpu.ops import edgeplan  # noqa: E402
from openr_tpu.ops import relax as relax_ops  # noqa: E402

CONFIGS = {
    # generator, vantage, the wide vantage of --lanes; the -small ones
    # rehearse the script on a CPU
    "fabric-small": (
        lambda **kw: topologies.fabric(
            pods=12, planes=2, ssws_per_plane=3, rsws_per_pod=6, **kw
        ),
        "pod000-rsw00", "pod000-fsw00",
    ),
    "wan-small": (
        lambda: topologies.wan_rtt(
            regions=4, cores=2, aggs=6, access=52, seed=7
        ),
        "r01-acc0000", "r02-agg03",
    ),
    "fabric10k": (
        lambda **kw: topologies.fabric(
            pods=173, planes=8, ssws_per_plane=36, rsws_per_pod=48, **kw
        ),
        "pod000-rsw00", "pod000-fsw00",
    ),
    "wan50k": (
        lambda: topologies.wan_rtt(
            regions=50, cores=4, aggs=64, access=932, seed=7
        ),
        "r25-acc0000", "r25-agg20",
    ),
}
CONFIGS_ON_CHIP = ("fabric10k", "wan50k")
PASSES = 16
REPS = 5
EVENTS = 6
# the solver's own capture compiles two pipelines a width (49 s each at
# wan50k): taken at the width before the split and at the narrow ones
SCOPE_MAX_WIDTH = 8
# the device program's stages after the SSSP: each works over the prefix
# rows the epoch looks at (every row, or the candidates `candidates` finds)
ROW_SCOPES = (
    "candidates", "unpack", "select", "nexthop", "lfa", "pack", "diff",
    "compact",
)


@contextlib.contextmanager
def _forced_width(width: int):
    """Mirrors built inside are `width` wide, whatever the builder would
    choose."""
    chosen = edgeplan._residual_width
    edgeplan._residual_width = lambda degrees: width
    try:
        yield
    finally:
        edgeplan._residual_width = chosen


def _median_ms(fn, arg) -> float:
    """ms per call of a jitted function, by the host's clock round
    `block_until_ready`: the median of REPS calls after one that compiles."""
    fn(arg).block_until_ready()
    times = []
    for _ in range(REPS):
        t0 = time.monotonic()
        fn(arg).block_until_ready()
        times.append((time.monotonic() - t0) * 1e3)
    return float(np.median(times))


def _loop_ms(plan, root: int, d_cap: int, layout: str) -> float:
    """ms per pass of PASSES relaxations over the plan's residual."""
    import jax
    import jax.numpy as jnp

    n_cap = plan.n_cap
    inf = relax_ops.INF_E
    rows_c = jnp.clip(jnp.asarray(plan.res_rows), 0, n_cap - 1)
    nbr = jnp.asarray(plan.res_nbr)
    rw = jnp.where(nbr == root, inf, jnp.asarray(plan.res_w))
    nbr_c = jnp.clip(nbr, 0, n_cap - 1)
    deltas = jnp.asarray(plan.deltas)
    shift_w = jnp.asarray(plan.shift_w)
    if layout == "rk":
        relax = relax_ops.make_relax(
            deltas, plan.s_cap, lambda k: shift_w[k],
            residual=(rows_c, nbr_c, rw),
        )
    else:
        nbr_t, rw_t = nbr_c.T, rw.T  # [K, R]

        def relax(dist):
            cand = (dist[:, nbr_t] + rw_t[None]).min(axis=1)
            if layout == "gather":  # the rows' minimum stands in
                return jnp.minimum(dist, cand.min(axis=1, keepdims=True))
            acc = jnp.full_like(dist, inf).at[:, rows_c].min(cand)
            return jnp.minimum(acc, dist)

    @jax.jit
    def loop(dist):
        return jax.lax.fori_loop(0, PASSES, lambda _, d: relax(d), dist)

    dist0 = jnp.full((d_cap, n_cap), inf, jnp.int32)
    dist0 = dist0.at[:, root].set(0)
    return _median_ms(loop, dist0) / PASSES


def _parent_ms(plan, root: int, seeds: np.ndarray, d_cap: int) -> float:
    """ms per call of `seed.parent` at `d_cap` lanes, over the plane the
    relaxation converges to from `seeds` (one lane each, the rest of the
    lanes padding) in the graph without `root`."""
    import jax
    import jax.numpy as jnp

    from openr_tpu.ops.incremental import _parent_plane

    n_cap = plan.n_cap
    inf = relax_ops.INF_E
    has_res = plan.k_res > 0
    deltas = jnp.asarray(plan.deltas)
    swm = jnp.asarray(plan.shift_w).at[:, root].set(inf)
    res_rows = jnp.asarray(plan.res_rows)
    nbr = jnp.asarray(plan.res_nbr)
    rwm = jnp.where(nbr == root, inf, jnp.asarray(plan.res_w))
    relax = relax_ops.make_relax(
        deltas, plan.s_cap, lambda k: swm[k],
        residual=(
            jnp.clip(res_rows, 0, n_cap - 1), jnp.clip(nbr, 0, n_cap - 1),
            rwm,
        ) if has_res else None,
    )
    dist0 = jnp.full((d_cap, n_cap), inf, jnp.int32)
    dist0 = dist0.at[jnp.arange(len(seeds)), jnp.asarray(seeds)].set(0)
    prev, _, _ = jax.jit(
        lambda d: relax_ops.run_sync(relax, d, relax_ops.max_trips(n_cap))
    )(dist0)

    @jax.jit
    def parent(prev_dist):
        return _parent_plane(
            deltas, swm, res_rows, nbr, rwm, prev_dist,
            plan.s_cap, has_res, n_cap, d_cap,
        )

    return _median_ms(parent, prev)


def capture_lanes(name: str, lanes: list[int]) -> None:
    """The mirror as the builder builds it, from the wide vantage, at
    each count of lanes; then the solver itself at all of its links."""
    import jax

    gen, _, me = CONFIGS[name]
    adj_dbs, prefix_dbs = gen()
    states, _ = topologies.build_states(adj_dbs, prefix_dbs)
    plan = edgeplan.build_plan(states["0"])
    root = plan.node_index[me]
    nbr, _, links = plan.out_links(states["0"], me)
    r_cap, k_cap = plan.res_nbr.shape
    for d_cap in lanes:
        seeds = nbr[: min(d_cap, len(links))]
        print(json.dumps({
            "config": name, "vantage": me, "lanes": d_cap,
            "sources": len(seeds), "width": k_cap, "r_cap": r_cap,
            "device": jax.devices()[0].device_kind,
            "loop_ms_per_pass": _loop_ms(plan, root, d_cap, "rk"),
            "gather_ms_per_pass": _loop_ms(plan, root, d_cap, "gather"),
            "parent_ms_per_call": _parent_ms(plan, root, seeds, d_cap),
        }), flush=True)
    scope, _ = _scope_ms(name, adj_dbs, prefix_dbs, me, k_cap, None)
    print(json.dumps({
        "config": name, "vantage": me, "lanes": nbr.shape[0],
        "sources": len(links), "width": k_cap, **scope,
    }), flush=True)


def capture_prefixes(name: str, counts: list[int]) -> None:
    """The same graph, mirror and vantage at each count of prefixes a
    node: the solver's own capture, and the rows it worked over."""
    gen, me, _ = CONFIGS[name]
    for per_node in counts:
        adj_dbs, prefix_dbs = gen(prefixes_per_node=per_node)
        states, _ = topologies.build_states(adj_dbs, prefix_dbs)
        width = edgeplan.build_plan(states["0"]).res_nbr.shape[1]
        scope, _ = _scope_ms(
            name, adj_dbs, prefix_dbs, me, width, None, prefix_events=True
        )
        by_scope = scope["by_scope_ms_per_event"]
        print(json.dumps({
            "config": name, "vantage": me, "prefixes_per_node": per_node,
            "row_stages_ms_per_event": sum(
                by_scope.get(stage, 0) for stage in ROW_SCOPES
            ),
            **scope,
        }), flush=True)


def _per_event(by_scope: dict) -> dict:
    return {scope: ms / EVENTS for scope, ms in sorted(by_scope.items())}


def _prefix_only_ms(solver, me: str, states, ps, prefix_db) -> dict:
    """by_scope over EVENTS prefix-only solves of a warm solver: the
    first prefix of `prefix_db`'s switch withdrawn (even steps) or
    advertised back."""
    from openr_tpu.runtime import device_stats
    from openr_tpu.types import PrefixDatabase, PrefixEntry

    node, area = prefix_db.this_node_name, prefix_db.area
    entry = prefix_db.prefix_entries[0]
    handed, changed = [], []

    def solve(step: int) -> dict:
        gone = step % 2 == 0
        ps.update_prefix_database(PrefixDatabase(
            node, (PrefixEntry(prefix=entry.prefix) if gone else entry,),
            area, delete_prefix=gone,
        ))
        solver.build_route_db(me, states, ps)
        return solver.last_device_stats

    for step in range(2):  # compile the program and the rows' scatter
        solve(step)
    device_stats.profiler_start()
    for step in range(EVENTS):
        stats = solve(step)
        if not stats.get("prefix_only"):
            raise SystemExit(f"prefix event {step} was solved: {stats}")
        wait = {
            span[0]: span[4] for span in solver.last_timing["spans"]
        }["tpu.device_wait"]
        handed.append((wait.get("cand_rows"), wait.get("cand_cap")))
        changed.append(stats.get("changed_rows"))
    by_scope = device_stats.profiler_stop()["by_scope"] or {}
    return {
        "device_ms_per_event": sum(by_scope.values()) / EVENTS,
        "by_scope_ms_per_event": _per_event(by_scope),
        "kernel": stats.get("kernel"),
        "rows_handed": handed,
        "changed_rows": changed,
    }


def _scope_ms(name: str, adj_dbs, prefix_dbs, me: str, width: int, want,
              prefix_events: bool = False):
    """by_scope over EVENTS incremental solves at this width; the table
    after each event against `want` (the first width's). With
    `prefix_events`, the same solver's prefix-only solves after them
    (`_prefix_only_ms`), under the key `prefix_only`."""
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.runtime import device_stats

    adj_dbs = list(adj_dbs)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    # a link far from the vantage: the last node's first adjacency
    far = adj_dbs[-1].this_node_name
    peer = adj_dbs[-1].adjacencies[0].other_node_name
    base = adj_dbs[-1].adjacencies[0].metric
    solver = TpuSpfSolver(me, enable_lfa=True, incremental_spf=True)
    tables, rounds, cone_passes, cones, changed, looked = (
        [], [], [], [], [], []
    )

    def solve(step: int) -> dict:
        """The link's metric up (even steps) or back, then a solve."""
        if step >= 0:
            metric = base * 3 if step % 2 == 0 else base
            for db in set_metric(adj_dbs, index, far, peer, metric):
                states[db.area].update_adjacency_database(db)
        tables.append(solver.build_route_db(me, states, ps).unicast_routes)
        return solver.last_device_stats

    with _forced_width(width):
        solve(-1)  # the full solve
        for step in range(2):  # compile the incremental pipeline
            solve(step)
        device_stats.profiler_start()
        for step in range(EVENTS):
            stats = solve(step)
            if not stats.get("incremental"):
                raise SystemExit(f"event {step} did not solve warm: {stats}")
            rounds.append(solver.last_timing.get("rounds"))
            cone_passes.append(solver.last_timing.get("cone_passes"))
            cones.append((stats.get("cone"), bool(stats.get("fell_back"))))
            changed.append(stats.get("changed_rows"))
            looked.append(stats.get("rows_looked"))
        by_scope = device_stats.profiler_stop()["by_scope"] or {}
        prefix_only = {"prefix_only": _prefix_only_ms(
            solver, me, states, ps, prefix_dbs[-1]
        )} if prefix_events else {}
    same = want is None or all(a == b for a, b in zip(tables, want))
    passes = sum(rounds)
    return {
        **prefix_only,
        "relax.residual_ms_per_pass": by_scope.get("relax.residual", 0) / passes,
        "seed.cone_ms_per_event": by_scope.get("seed.cone", 0) / EVENTS,
        "seed.parent_ms_per_event": by_scope.get("seed.parent", 0) / EVENTS,
        "device_ms_per_event": sum(by_scope.values()) / EVENTS,
        "by_scope_ms_per_event": _per_event(by_scope),
        "rounds": rounds,
        "cone_passes": cone_passes,
        "cones": cones,
        "changed_rows": changed,
        "rows_looked": looked,
        "prefix_rows": stats.get("prefix_rows"),
        "prefixes": stats.get("prefixes"),
        "tables_equal_first_width": same,
    }, tables


def capture(name: str, max_width: int = 0) -> None:
    import jax

    gen, me, _ = CONFIGS[name]
    adj_dbs, prefix_dbs = gen()
    states, _ = topologies.build_states(adj_dbs, prefix_dbs)
    link_state = states["0"]
    # the width before the split: the widest destination's, pow2
    plan = edgeplan.build_plan(link_state)
    used = plan.res_rows >= 0
    degrees = np.bincount(plan.res_rows[used], weights=plan._res_fill[used])
    lines, want = [], None
    width = min(
        edgeplan._next_pow2(int(degrees.max()), 2), max_width or 1 << 30
    )
    while width >= 2:
        with _forced_width(width):
            plan = edgeplan.build_plan(link_state)
        root = plan.node_index[me]
        d_cap = plan.out_links(link_state, me)[0].shape[0]
        r_cap, k_cap = plan.res_nbr.shape
        line = {
            "config": name, "width": k_cap, "r_cap": r_cap,
            "rows": plan._res_nrows, "slots": r_cap * k_cap,
            "edges": plan.res_edges, "d_cap": d_cap,
            "device": jax.devices()[0].device_kind,
            "loop_ms_per_pass": _loop_ms(plan, root, d_cap, "rk"),
            "kmajor_ms_per_pass": _loop_ms(plan, root, d_cap, "kr"),
            "gather_ms_per_pass": _loop_ms(plan, root, d_cap, "gather"),
        }
        if want is None or width <= SCOPE_MAX_WIDTH:
            scope, tables = _scope_ms(
                name, adj_dbs, prefix_dbs, me, width, want
            )
            want = want or tables
            line.update(scope)
        print(json.dumps(line), flush=True)
        lines.append(line)
        width //= 2
    # loop = a * r_cap * (K + c)  ->  loop / r_cap = a * K + a * c
    ks = np.array([ln["width"] for ln in lines], float)
    for key in ("loop_ms_per_pass", "kmajor_ms_per_pass"):
        per_row = np.array([ln[key] / ln["r_cap"] for ln in lines])
        a, ac = np.polyfit(ks, per_row, 1)
        print(json.dumps({
            "config": name, "fit_of": key,
            "ns_per_slot": a * 1e6, "row_cost_in_slots": ac / a,
        }), flush=True)


def main(argv):
    import jax

    lanes, per_node = [], []
    if argv[:1] == ["--lanes"]:
        lanes, argv = [int(n) for n in argv[1].split(",")], argv[2:]
    elif argv[:1] == ["--prefixes-per-node"]:
        per_node, argv = [int(n) for n in argv[1].split(",")], argv[2:]
    # "fabric10k:16" starts at width 16 (a second call's way to go on)
    specs = [(spec + ":0").split(":")[:2] for spec in argv or CONFIGS_ON_CHIP]
    small = all(name.endswith("-small") for name, _ in specs)
    if jax.devices()[0].platform != "tpu" and not small:
        raise SystemExit("tools/residual_width.py measures a TPU; none here")
    for name, max_width in specs:
        if lanes:
            capture_lanes(name, lanes)
        elif per_node:
            capture_prefixes(name, per_node)
        else:
            capture(name, int(max_width))


if __name__ == "__main__":
    main(sys.argv[1:])
