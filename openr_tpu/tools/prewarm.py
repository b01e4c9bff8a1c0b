"""openr-tpu-prewarm — bake solver executables into the XLA cache.

The reference daemon cold-starts in milliseconds; ours pays XLA
compilation the first time each capacity class's jit programs run
(~80 s at the 131072-node class on TPU). Those executables are pure
functions of the padded capacity-class shapes, and ops/xla_cache.py
persists them — so this tool runs the solver once per requested class
against a synthetic topology at image-bake / maintenance time, and a
restarting daemon then loads everything from disk (measured: 80.7 s ->
10.4 s first-build at 100k; see docs/Operations.md).

Shapes are what matter, not the topology: a grid sized into the target
class produces the same (n_cap, s_cap, r_cap, ...) paddings the
production LSDB of that class hits, because capacities are pow2-rounded
(ops/edgeplan.py). Classes whose real deployment uses KSP2 or LFA
should prewarm those variants too — they are distinct programs.

Beyond the default full-solve executables, the solver keeps three more
jit-cache namespaces (ops/xla_cache.py bounded_jit_cache; the pipeline's
are chosen by tpu_solver.PipelineVariant.namespace): "incr"
(seed-from-previous incremental SSSP), "multichip" (the sharded
capacity tier), and "whatif" (interactive sweep batches). Each is a
distinct program set — a daemon that cold-starts straight into churn
pays the incr compile on its first flap unless it was baked. --incr /
--multichip / --whatif prewarm those namespaces too, and each bake
records a `prewarm[<namespace>:<nodes>]` entry (compile_ms) in the
kernel ledger so `breeze tpu kernels` shows what the bake paid per
workload class.

With --aot-cache-dir (or $OPENR_TPU_AOT_CACHE) every executable the
bake compiles is ALSO serialized into the persistent AOT cache
(ops/xla_cache.py, ISSUE 20): a restarting daemon's `aot_load` boot
phase then deserializes the finished executables instead of replaying
the XLA compile against the source cache — prewarm becomes an
install pass, not a compile pass.

Every bake compiles BOTH round-loop kernels (ops/relax.py): the
default bucketed Δ-stepping executables (the synthetic grid derives
the same pow2-quantized delta_exp capacity signature a production
grid of the class does) and the spf_kernel=sync variant, so the
restart an operator's first bisection step forces (docs/Operations.md)
loads from cache instead of paying a fresh compile.

Usage:
    openr-tpu-prewarm --nodes 1024 --nodes 100000 --lfa --ksp2
    openr-tpu-prewarm --nodes 50000 --cache-dir /var/cache/openr-xla
    openr-tpu-prewarm --nodes 4096 --incr --whatif --multichip --devices 8
"""

from __future__ import annotations

import argparse
import sys
import time


def _grid_side(nodes: int) -> int:
    """Smallest side with side*side >= nodes: rounding DOWN could land
    the synthetic graph in a lower pow2 capacity class than the real
    LSDB pads to (e.g. 66000 -> 256^2=65536 caps at 65536, but the
    production graph caps at 131072 — a different executable)."""
    import math

    return max(2, math.isqrt(max(nodes, 1) - 1) + 1)


def _record_prewarm(namespace: str, nodes: int, dt_s: float) -> None:
    """One kernel-ledger entry per (namespace, class) bake: the
    flight-recorder bundle and ctrl.tpu.kernels then attribute prewarm
    compile cost per workload class."""
    from openr_tpu.ops.xla_cache import ledger
    from openr_tpu.runtime.counters import counters
    from openr_tpu.runtime.perf_ledger import get_ledger

    ledger.record(f"prewarm[{namespace}:{nodes}]", dt_s * 1e3, {})
    counters.add_stat_value(
        f"xla_cache.prewarm.{namespace}.compile_ms", dt_s * 1e3
    )
    # perf observatory: per-(namespace, shape-class) bake wall-time —
    # boot traces attribute prewarm from this, and ROADMAP item 1
    # measures its cold-start win against it
    get_ledger().record(
        "prewarm",
        {"bake_ms": dt_s * 1e3},
        signature=f"n{nodes}",
        variant=namespace,
    )


def _grid_inputs(nodes: int):
    from openr_tpu.models import topologies

    side = _grid_side(nodes)
    adj_dbs, prefix_dbs = topologies.grid(side, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = adj_dbs[len(adj_dbs) // 2].this_node_name
    return side, adj_dbs, states, ps, me


def _flap_one(states, adj_dbs, metric: int = 55) -> None:
    """One node's adjacencies re-advertised at a new metric through the
    real update path — enough churn to engage the incremental lane."""
    from openr_tpu.types import Adjacency, AdjacencyDatabase

    area = next(iter(states))
    db = adj_dbs[1]
    states[area].update_adjacency_database(
        AdjacencyDatabase(
            this_node_name=db.this_node_name,
            adjacencies=tuple(
                Adjacency(**{**a.__dict__, "metric": metric})
                for a in db.adjacencies
            ),
            node_label=db.node_label,
            area=area,
        )
    )


def prewarm_incr(nodes: int, verbose: bool = True) -> float:
    """Bake the "incr" namespace: a cold solve seeds the resident
    distance plane, then a metric flap re-solves through the
    incremental pipeline — compiling the dirty-cap shape class the
    production churn path hits first."""
    from openr_tpu.decision.tpu_solver import TpuSpfSolver

    side, adj_dbs, states, ps, me = _grid_inputs(nodes)
    t0 = time.perf_counter()
    for kern, metric in (("bucketed", 55), ("sync", 56)):
        solver = TpuSpfSolver(me, incremental_spf=True, spf_kernel=kern)
        solver.build_route_db(me, states, ps)  # cold seed
        _flap_one(states, adj_dbs, metric=metric)
        solver.build_route_db(me, states, ps)  # incr-namespace compile
    dt = time.perf_counter() - t0
    _record_prewarm("incr", side * side, dt)
    if verbose:
        print(
            f"[prewarm] class {side}x{side} ({side * side} nodes)"
            f" +incr: {dt:.1f}s"
        )
    return dt


def prewarm_multichip(nodes: int, verbose: bool = True) -> float:
    """Bake the "multichip" namespace by forcing the capacity tier on
    for this class (threshold 1). Needs ≥2 visible devices — on a
    single-device host this is a no-op skip, not an error (use
    --devices N to fan out virtual CPU devices for the bake)."""
    import jax

    from openr_tpu.decision.tpu_solver import TpuSpfSolver

    if len(jax.devices()) < 2:
        if verbose:
            print(
                "[prewarm] multichip: <2 devices visible — skipped "
                "(--devices N forces virtual CPU devices)"
            )
        return 0.0
    side, adj_dbs, states, ps, me = _grid_inputs(nodes)
    t0 = time.perf_counter()
    for kern in ("bucketed", "sync"):
        solver = TpuSpfSolver(
            me, multichip_n_cap_threshold=1, spf_kernel=kern
        )
        solver.build_route_db(me, states, ps)
    dt = time.perf_counter() - t0
    _record_prewarm("multichip", side * side, dt)
    if verbose:
        print(
            f"[prewarm] class {side}x{side} ({side * side} nodes)"
            f" +multichip: {dt:.1f}s"
        )
    return dt


def prewarm_whatif(nodes: int, verbose: bool = True) -> float:
    """Bake the "whatif" namespace: one order-1 sweep over the class
    compiles the batched scenario executables an operator's first
    interactive sweep would otherwise stall on."""
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.decision.whatif import WhatIfEngine

    side, adj_dbs, states, ps, me = _grid_inputs(nodes)
    t0 = time.perf_counter()
    for kern in ("bucketed", "sync"):
        solver = TpuSpfSolver(me, spf_kernel=kern)
        solver.build_route_db(me, states, ps)
        WhatIfEngine(solver).sweep(states, ps, order=1, max_scenarios=8)
    dt = time.perf_counter() - t0
    _record_prewarm("whatif", side * side, dt)
    if verbose:
        print(
            f"[prewarm] class {side}x{side} ({side * side} nodes)"
            f" +whatif: {dt:.1f}s"
        )
    return dt


def prewarm_class(
    nodes: int, enable_lfa: bool, enable_ksp2: bool, verbose: bool = True
) -> float:
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.models import topologies
    from openr_tpu.types import (
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
        replace,
    )

    side = _grid_side(nodes)
    adj_dbs, prefix_dbs = topologies.grid(side, node_labels=False)
    if enable_ksp2:
        # a KSP2 sliver compiles the masked-batch programs for the class
        prefix_dbs = [
            replace(
                db,
                prefix_entries=tuple(
                    replace(
                        e,
                        forwarding_type=PrefixForwardingType.SR_MPLS,
                        forwarding_algorithm=(
                            PrefixForwardingAlgorithm.KSP2_ED_ECMP
                        ),
                    )
                    for e in db.prefix_entries
                ),
            )
            if i < 64
            else db
            for i, db in enumerate(prefix_dbs)
        ]
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = adj_dbs[len(adj_dbs) // 2].this_node_name
    t0 = time.perf_counter()
    for kern in ("bucketed", "sync"):
        solver = TpuSpfSolver(me, enable_lfa=enable_lfa, spf_kernel=kern)
        solver.build_route_db(me, states, ps)
    dt = time.perf_counter() - t0
    variant = "default"
    if enable_lfa:
        variant = "default+lfa"
    elif enable_ksp2:
        variant = "default+ksp2"
    _record_prewarm(variant, side * side, dt)
    if verbose:
        print(
            f"[prewarm] class {side}x{side} ({side * side} nodes)"
            f"{' +lfa' if enable_lfa else ''}"
            f"{' +ksp2' if enable_ksp2 else ''}: {dt:.1f}s"
        )
    return dt


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="openr-tpu-prewarm", description=__doc__.split("\n")[0]
    )
    p.add_argument(
        "--nodes", type=int, action="append", required=True,
        help="capacity class to prewarm (LSDB node count); repeatable",
    )
    p.add_argument(
        "--cache-dir", default=None,
        help="XLA cache directory (default: $OPENR_TPU_XLA_CACHE, then "
        "<checkout>/.jax_cache; $JAX_COMPILATION_CACHE_DIR beats all)",
    )
    p.add_argument(
        "--lfa", action="store_true",
        help="also compile the LFA backup-nexthop programs",
    )
    p.add_argument(
        "--ksp2", action="store_true",
        help="also compile the KSP2 masked-batch programs",
    )
    p.add_argument(
        "--incr", action="store_true",
        help="also bake the incremental-SSSP (incr) namespace",
    )
    p.add_argument(
        "--multichip", action="store_true",
        help="also bake the sharded capacity-tier (multichip) namespace"
        " (needs >=2 devices)",
    )
    p.add_argument(
        "--whatif", action="store_true",
        help="also bake the what-if sweep (whatif) namespace",
    )
    p.add_argument(
        "--aot-cache-dir", default="auto",
        help="persistent AOT executable-cache directory to bake "
        "serialized executables into (default 'auto' = "
        "<compile-cache root>/aot; 'off' disables; empty consults "
        "$OPENR_TPU_AOT_CACHE)",
    )
    p.add_argument(
        "--perf-ledger-dir", default=None,
        help="perf-ledger directory for bake-time records (default: "
        "$OPENR_TPU_PERF_LEDGER / ~/.cache/openr_tpu/perf)",
    )
    p.add_argument(
        "--devices", type=int, default=0,
        help="force N virtual CPU devices (XLA_FLAGS host platform "
        "device count) — for baking the multichip namespace off-TPU; "
        "must be set before jax first imports",
    )
    args = p.parse_args(argv)

    if args.devices > 0:
        import os as _os

        if "jax" in sys.modules:
            print(
                "[prewarm] --devices ignored: jax already imported",
                file=sys.stderr,
            )
        else:
            _os.environ["XLA_FLAGS"] = (
                _os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()

    from openr_tpu.ops.xla_cache import configure_aot, enable_compilation_cache
    from openr_tpu.runtime import perf_ledger

    perf_ledger.configure(
        args.perf_ledger_dir
        if args.perf_ledger_dir is not None
        else perf_ledger.default_dir()
    )
    cache = enable_compilation_cache(args.cache_dir)
    if cache is None:
        print("[prewarm] compilation cache DISABLED — nothing to bake",
              file=sys.stderr)
        return 1
    print(f"[prewarm] cache: {cache}")
    aot = configure_aot(args.aot_cache_dir)
    if aot.enabled:
        print(f"[prewarm] aot cache: {aot.dir}")
    else:
        print("[prewarm] aot cache disabled — executables not serialized")
    total = 0.0
    for n in args.nodes:
        total += prewarm_class(n, enable_lfa=False, enable_ksp2=False)
        if args.lfa:
            total += prewarm_class(n, enable_lfa=True, enable_ksp2=False)
        if args.ksp2:
            total += prewarm_class(n, enable_lfa=False, enable_ksp2=True)
        if args.incr:
            total += prewarm_incr(n)
        if args.multichip:
            total += prewarm_multichip(n)
        if args.whatif:
            total += prewarm_whatif(n)
    if aot.enabled:
        s = aot.summary()
        print(
            f"[prewarm] aot: {s['entries']} serialized entries on disk "
            f"({s['writes']} written this run, fp {s['fingerprint']})"
        )
    print(f"[prewarm] done in {total:.1f}s — restarts now load from cache")
    return 0


if __name__ == "__main__":
    sys.exit(main())
