#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts
on the chip.

One process, one TPU chip, the entry points a user's daemon goes through:
the real KvStore, Decision (solver_backend="tpu", default DecisionConfig
otherwise) and Fib actors, wired by the queues OpenrWrapper wires them
with, an in-memory FibService behind Fib. The lsdb100k deployment
(topologies.grid(316, node_labels=False): 99,856 nodes, 199,712 adj:/
prefix: keys, vantage node-158-158) is loaded the way a peer's full sync
arrives — KvStore.set_key_vals in chunks — and KVSTORE_SYNCED releases
Decision.

Phases (any failure: non-zero exit, no ok-true line):
  first_rib  Fib programs the whole table; EVERY programmed route must
             equal the CPU oracle's (SpfSolver.build_route_db on the same
             topologies.build_states input).
  churn      link-metric changes, each published as new versions of the
             two adj: keys it touches and awaited to the FIB ack; then
             the same whole-table comparison on the changed LSDB.
  no_hiding  nothing stood in for the device: no failover, no
             degradation, no small-graph delegation, no host-computed
             route, every kernel compiled, every resident array on the
             expected platform.

`--chips 4` runs ONLY the multichip tier and what it is compared with:
the same LSDB through the solver on the four-chip mesh, on one chip in
the same process, and the oracle.

Every line on stdout is one JSON object; the last is the contract's
`{"ok": true, "device": {...}}`. Without a TPU the script exits non-zero
before it builds anything. `--rehearse` (with JAX_PLATFORMS=cpu in the
environment) only relaxes that one check: it says so on an early line
and never prints the ok-true line.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import random
import sys
import time
from dataclasses import replace

AREA = "0"
LOAD_CHUNK_KEYS = 16384  # a peer's full sync arrives in chunks like this
CHURN_EPOCHS = 20
ACK_TIMEOUT_S = 900.0  # covers a cold ~90 s-per-variant compile


class SmokeFailure(Exception):
    pass


def emit(**obj) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=20260926)
    p.add_argument(
        "--grid", type=int, default=316,
        help="grid side; 316 is lsdb100k (smaller only to rehearse)",
    )
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = only the multichip tier and its comparisons",
    )
    p.add_argument(
        "--rehearse", action="store_true",
        help="allow a non-TPU platform; never prints the ok-true line",
    )
    return p.parse_args(argv)


# -- the deployment ----------------------------------------------------------


def build_lsdb(side: int):
    from openr_tpu.models import topologies

    adj_dbs, prefix_dbs = topologies.grid(side, node_labels=False)
    return adj_dbs, prefix_dbs, f"node-{side // 2}-{side // 2}"


def adj_kv(db, version: int):
    from openr_tpu.serde import serialize
    from openr_tpu.types import Value, adj_key

    return adj_key(db.this_node_name), Value(
        version=version, originator_id=db.this_node_name,
        value=serialize(db),
    )


def lsdb_key_vals(adj_dbs, prefix_dbs) -> dict:
    from openr_tpu.serde import serialize
    from openr_tpu.types import Value, prefix_key

    kvs = dict(adj_kv(db, 1) for db in adj_dbs)
    for db in prefix_dbs:
        for entry in db.prefix_entries:
            key = prefix_key(db.this_node_name, db.area, entry.prefix)
            kvs[key] = Value(
                version=1, originator_id=db.this_node_name,
                value=serialize(db),
            )
    return kvs


def oracle_routes(me: str, adj_dbs, prefix_dbs) -> dict:
    """The plain reference: the CPU oracle on states built directly from
    the generator's output, sharing nothing with the served path."""
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.models import topologies

    states, prefix_state = topologies.build_states(adj_dbs, prefix_dbs)
    db = SpfSolver(me).build_route_db(me, states, prefix_state)
    check(db is not None, "oracle: vantage not in the LSDB")
    return dict(db.unicast_routes)


def compare_tables(got: dict, want: dict, what: str) -> int:
    """Every route (prefix, metric, next-hop set — whole-entry equality,
    as the repo's own differential tests compare); -> routes compared."""
    check(
        got.keys() == want.keys(),
        f"{what}: {len(got)} routes vs the oracle's {len(want)}; "
        f"missing {sorted(want.keys() - got.keys())[:3]} "
        f"extra {sorted(got.keys() - want.keys())[:3]}",
    )
    for prefix, entry in want.items():
        if got[prefix] != entry:
            raise SmokeFailure(
                f"{what}: {prefix} differs:\n  got  {got[prefix]}\n"
                f"  want {entry}"
            )
    return len(want)


def churn_plan(side: int, n: int, seed: int) -> list[tuple]:
    """n (node_a, node_b, metric) changes on links of the vantage's row
    and column, the four arms taken in turn and each arm far-to-near:
    the straight line is then still the unique shortest path to the
    link's far end, so every change must move at least one route —
    every epoch has a FIB ack to wait for."""
    rng = random.Random(seed)
    c = side // 2
    name = lambda r, k: f"node-{r}-{k}"  # noqa: E731
    arms = [  # each near-to-far
        [(name(c, k), name(c, k + 1)) for k in range(c, side - 1)],
        [(name(c, k), name(c, k - 1)) for k in range(c, 0, -1)],
        [(name(k, c), name(k + 1, c)) for k in range(c, side - 1)],
        [(name(k, c), name(k - 1, c)) for k in range(c, 0, -1)],
    ]
    per_arm = -(-n // len(arms))
    check(
        all(len(links) >= per_arm for links in arms),
        f"grid {side} is too small for {n} churn events",
    )
    picks = [
        [links[i] for i in sorted(
            rng.sample(range(len(links)), per_arm), reverse=True
        )]
        for links in arms
    ]
    return [
        (*picks[i % len(arms)][i // len(arms)], rng.randint(3, 9))
        for i in range(n)
    ]


def set_metric(adj_dbs: list, index: dict, a: str, b: str, metric: int):
    """Both directions of link a-b to `metric`; -> the two changed dbs."""
    changed = []
    for me, other in ((a, b), (b, a)):
        i = index[me]
        db = adj_dbs[i]
        adj_dbs[i] = db = replace(db, adjacencies=tuple(
            replace(adj, metric=metric)
            if adj.other_node_name == other else adj
            for adj in db.adjacencies
        ))
        changed.append(db)
    return changed


# -- the served path ---------------------------------------------------------


class ServedStack:
    """KvStore -> Decision -> Fib with OpenrWrapper's queues. Spark and
    LinkMonitor are not on the publication -> FIB-ack path, and with them
    running the vantage's own adj: key would be self-originated and
    replace the injected one — so the stack is composed as the Decision
    tests compose it."""

    def __init__(self, me: str):
        from openr_tpu.config import DecisionConfig, FibConfig, KvstoreConfig
        from openr_tpu.decision.decision import Decision
        from openr_tpu.fib import Fib, MockFibService
        from openr_tpu.kvstore.kvstore import KvStore
        from openr_tpu.messaging import ReplicateQueue

        q = {
            n: ReplicateQueue(f"{me}.{n}") for n in (
                "peerUpdates", "kvRequests", "kvStoreUpdates",
                "kvStoreEvents", "staticRoutes", "routeUpdates",
                "fibRouteUpdates", "logSamples",
            )
        }
        self.queues = q
        self.kvstore = KvStore(
            me, KvstoreConfig(), [AREA],
            q["peerUpdates"].get_reader(), q["kvRequests"].get_reader(),
            q["kvStoreUpdates"], q["kvStoreEvents"],
        )
        self.decision = Decision(
            me, DecisionConfig(),
            q["kvStoreUpdates"].get_reader(), q["staticRoutes"].get_reader(),
            q["routeUpdates"], solver_backend="tpu",
            log_sample_queue=q["logSamples"],
        )
        self.fib_service = MockFibService()
        self.fib = Fib(
            me, FibConfig(route_delete_delay_ms=0), self.fib_service,
            q["routeUpdates"].get_reader(), q["fibRouteUpdates"],
            log_sample_queue=q["logSamples"],
        )
        self.fib.attach_kvstore(self.kvstore)
        self.acks = q["fibRouteUpdates"].get_reader("chip_smoke")

    async def start(self) -> None:
        for actor in (self.kvstore, self.decision, self.fib):
            await actor.start()

    async def stop(self) -> None:
        for queue in self.queues.values():
            queue.close()
        for actor in (self.fib, self.decision, self.kvstore):
            await actor.stop()

    async def load(self, key_vals: dict) -> None:
        items = list(key_vals.items())
        for i in range(0, len(items), LOAD_CHUNK_KEYS):
            await self.kvstore.set_key_vals(
                AREA, dict(items[i:i + LOAD_CHUNK_KEYS])
            )
            await asyncio.sleep(0)  # let Decision drain between chunks

    def release(self) -> None:
        """The initial (empty) peer event a standalone node's
        LinkMonitor sends: KvStore answers with KVSTORE_SYNCED."""
        from openr_tpu.types import AreaPeerEvent

        self.queues["peerUpdates"].push({AREA: AreaPeerEvent()})

    async def next_ack(self):
        """The next programmed-routes publication (the FIB ack)."""
        from openr_tpu.types import InitializationEvent

        async def get():
            while True:
                item = await self.acks.get()
                if not isinstance(item, InitializationEvent):
                    return item

        return await asyncio.wait_for(get(), ACK_TIMEOUT_S)


def epoch_evidence(decision) -> dict:
    """What the epoch that just acked ran on, read where the program
    itself records it."""
    from openr_tpu.decision.columnar_rib import LazyUnicastRoutes

    tm = decision.solver.last_timing
    areas = tm.get("areas") or {}
    routes = decision.route_db.unicast_routes
    return {
        "solver_kind": decision._solver_kind(True),
        "device_exec": bool(areas) and all(
            a.get("kernel") and a.get("exec_ms", 0) > 0
            for a in areas.values()
        ),
        "kernels": sorted({a.get("kernel") for a in areas.values()}),
        "host_routes": (
            len(routes.base) if isinstance(routes, LazyUnicastRoutes)
            else len(routes)
        ),
        "incremental": bool(tm.get("incremental")),
        "exec_ms": round(tm.get("exec_ms", 0.0), 3),
        "sync_ms": round(tm.get("sync_ms", 0.0), 3),
        "mat_ms": round(tm.get("mat_ms", 0.0), 3),
    }


def check_epochs(epochs: list[dict]) -> None:
    for i, e in enumerate(epochs):
        check(e["solver_kind"] != "failover-cpu", f"epoch {i}: CPU failover")
        check(e["device_exec"], f"epoch {i}: no device execution: {e}")
        check(
            e["host_routes"] == 0,
            f"epoch {i}: {e['host_routes']} routes computed on the host",
        )


def check_no_hiding(decision, epochs: list[dict], platform: str) -> dict:
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.ops.xla_cache import ledger
    from openr_tpu.runtime.counters import counters

    def counter(key: str) -> float:
        return counters.get_counter(key) or 0

    check(
        isinstance(decision.solver, TpuSpfSolver),
        f"solver is {type(decision.solver).__name__}",
    )
    for key in ("decision.solver.failovers", "decision.solver.degraded"):
        check(counter(key) == 0, f"{key} = {counter(key)}")
    check_epochs(epochs)
    kernels = ledger.snapshot()
    uncompiled = [
        k for k, e in kernels.items()
        if e["compile_ms"] is None and not e["aot_loaded"]
    ]
    check(not uncompiled, f"kernels neither compiled nor loaded: {uncompiled}")
    resident = list(decision.solver._device_arrays())
    check(resident, "the solver holds no resident device array")
    off = [
        str(d) for arr in resident for d in arr.devices()
        if d.platform != platform
    ]
    check(not off, f"resident arrays off the {platform}: {off[:4]}")
    return {
        "solver": type(decision.solver).__name__,
        "epochs": len(epochs),
        "failovers": counter("decision.solver.failovers"),
        "degraded": counter("decision.solver.degraded"),
        "kernels_in_ledger": len(kernels),
        "resident_arrays": len(resident),
        "resident_platform": platform,
    }


async def run_served(args, platform: str) -> None:
    t0 = time.perf_counter()
    adj_dbs, prefix_dbs, me = build_lsdb(args.grid)
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    key_vals = lsdb_key_vals(adj_dbs, prefix_dbs)
    emit(
        phase="lsdb", grid=args.grid, nodes=len(adj_dbs),
        keys=len(key_vals), vantage=me,
        seconds=round(time.perf_counter() - t0, 3),
    )

    stack = ServedStack(me)
    await stack.start()
    try:
        # -- first_rib --
        t0 = time.perf_counter()
        await stack.load(key_vals)
        t_loaded = time.perf_counter()
        stack.release()
        await stack.next_ack()
        t_acked = time.perf_counter()
        epochs = [epoch_evidence(stack.decision)]
        want = oracle_routes(me, adj_dbs, prefix_dbs)
        t_oracle = time.perf_counter()
        n = compare_tables(stack.fib_service.unicast, want, "first_rib")
        check(
            n == len(prefix_dbs) - 1,
            f"first_rib: {n} routes, expected every prefix but our own "
            f"({len(prefix_dbs) - 1})",
        )
        emit(
            phase="first_rib", routes_programmed=n, identical=True,
            load_s=round(t_loaded - t0, 3),
            release_to_ack_s=round(t_acked - t_loaded, 3),
            oracle_s=round(t_oracle - t_acked, 3),
            compare_s=round(time.perf_counter() - t_oracle, 3),
            epoch=epochs[0], platform=platform,
        )

        # -- churn --
        t0 = time.perf_counter()
        ack_ms = []
        for i, (a, b, metric) in enumerate(
            churn_plan(args.grid, CHURN_EPOCHS, args.seed)
        ):
            changed = set_metric(adj_dbs, index, a, b, metric)
            t_pub = time.perf_counter()
            await stack.kvstore.set_key_vals(
                AREA, dict(adj_kv(db, 2 + i) for db in changed)
            )
            ack = await stack.next_ack()
            ms = (time.perf_counter() - t_pub) * 1e3
            ack_ms.append(round(ms, 3))
            epochs.append(epoch_evidence(stack.decision))
            emit(
                phase="churn_epoch", i=i, link=[a, b], metric=metric,
                churn_to_ack_ms=round(ms, 3),
                routes_changed=len(ack.unicast_routes_to_update)
                + len(ack.unicast_routes_to_delete),
                epoch=epochs[-1], platform=platform,
            )
        t_churn = time.perf_counter()
        want = oracle_routes(me, adj_dbs, prefix_dbs)
        n = compare_tables(stack.fib_service.unicast, want, "churn")
        emit(
            phase="churn", epochs_acked=len(ack_ms), routes_compared=n,
            identical=True, churn_to_ack_ms=ack_ms,
            churn_s=round(t_churn - t0, 3),
            oracle_and_compare_s=round(time.perf_counter() - t_churn, 3),
            platform=platform,
        )

        # -- no_hiding --
        emit(
            phase="no_hiding",
            **check_no_hiding(stack.decision, epochs, platform),
        )
    finally:
        await stack.stop()


# -- the multichip tier (--chips 4) ------------------------------------------


def run_multichip(args, platform: str) -> None:
    import os
    import shutil

    import jax

    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.models import topologies
    from openr_tpu.ops.xla_cache import (
        cache_root,
        clear_all_jit_caches,
        configure_aot,
        ledger,
        retrace,
    )

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    adj_dbs, prefix_dbs, me = build_lsdb(args.grid)
    states, prefix_state = topologies.build_states(adj_dbs, prefix_dbs)
    # n_cap sits exactly AT the default threshold at lsdb100k, so halve
    # it (as the bench's lsdb100k_mc cell does); a rehearsal grid needs
    # the threshold under its own capacity class
    n_cap = 1 << max(len(adj_dbs) - 1, 1).bit_length()
    threshold = n_cap // 2

    def solve(aot_dir: str, **kw):
        configure_aot(aot_dir)
        # incremental_spf as DecisionConfig defaults it: the programs a
        # user's Decision would build (and the one-chip run has cached)
        solver = TpuSpfSolver(me, incremental_spf=True, **kw)
        t0 = time.perf_counter()
        db = solver.build_route_db(me, states, prefix_state)
        check(db is not None, "vantage not in the LSDB")
        routes = dict(db.unicast_routes)
        return solver, routes, round(time.perf_counter() - t0, 3)

    # a fixed path under the one cache root, emptied so that the first
    # solve must compile and serialize
    aot_dir = os.path.join(cache_root(), "aot_smoke")
    shutil.rmtree(aot_dir, ignore_errors=True)
    try:
        mc, mc_routes, mc_s = solve(
            aot_dir, multichip_n_cap_threshold=threshold
        )
        mesh_info = mc.last_timing.get("multichip")
        check(bool(mesh_info), "the multichip tier did not engage")
        resident = list(mc._device_arrays(mc=True))
        check(resident, "no multichip-resident array")
        spans = {
            d.id for arr in resident for d in arr.sharding.device_set
        }
        check(
            len(spans) == 4
            and all(len(arr.sharding.device_set) == 4 for arr in resident),
            f"resident planes span devices {sorted(spans)}, not four each",
        )
        partitioned = sum(
            not arr.sharding.is_fully_replicated for arr in resident
        )
        check(partitioned > 0, "every resident plane is fully replicated")
        in_use = [
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices
        ]
        if platform == "tpu":
            check(
                all(in_use) and all(b > 0 for b in in_use),
                f"bytes_in_use per device: {in_use}",
            )
        emit(
            phase="multichip", mesh=mesh_info, solve_s=mc_s,
            routes=len(mc_routes), resident_arrays=len(resident),
            partitioned_arrays=partitioned,
            devices_spanned=sorted(spans), bytes_in_use=in_use,
            compile_ms={  # before the AOT reload overwrites the entry
                k: e["compile_ms"] for k, e in ledger.snapshot().items()
            },
            platform=platform,
        )

        # AOT round trip of the mesh executable: a second solver
        # with every in-memory executable dropped must install the
        # serialized one onto the same four devices and agree
        cache = configure_aot(aot_dir)
        stored = cache.summary()["writes"]
        check(stored > 0, "the mesh executable was not serialized")
        clear_all_jit_caches()
        jax.clear_caches()
        retrace.reset()
        cache.reset_stats()
        _, warm_routes, warm_s = solve(
            aot_dir, multichip_n_cap_threshold=threshold
        )
        s = cache.summary()
        check(
            s["hits"] > 0 and s["load_errors"] == 0,
            f"AOT cache did not round-trip the mesh executable: {s}",
        )
        compare_tables(warm_routes, mc_routes, "multichip aot reload")
        emit(
            phase="multichip_aot", stored=stored, hits=s["hits"],
            misses=s["misses"], load_errors=s["load_errors"],
            solve_s=warm_s, platform=platform,
        )
    finally:
        configure_aot("off")
        shutil.rmtree(aot_dir, ignore_errors=True)

    one, one_routes, one_s = solve("off", multichip_n_cap_threshold=0)
    check(not one.last_timing.get("multichip"), "one-chip solve went multichip")
    emit(phase="one_chip", solve_s=one_s, routes=len(one_routes),
         platform=platform)
    compare_tables(mc_routes, one_routes, "multichip vs one chip")
    want = oracle_routes(me, adj_dbs, prefix_dbs)
    n = compare_tables(mc_routes, want, "multichip vs oracle")
    compare_tables(one_routes, want, "one chip vs oracle")
    emit(phase="multichip_compare", routes_compared=n, identical=True)


# -- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    dev = jax.devices()[0]  # raises where jax finds no backend at all
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if dev.platform != "tpu":
        if not args.rehearse:
            print(
                f"chip_smoke: needs a TPU, jax found {device}",
                file=sys.stderr,
            )
            return 2
        emit(rehearsal=True, device=device)

    import jaxlib
    import numpy as np

    from openr_tpu.ops.xla_cache import enable_compilation_cache, ledger
    from openr_tpu.runtime.counters import counters

    cache_dir = enable_compilation_cache()
    emit(
        jax=jax.__version__, jaxlib=jaxlib.__version__, device=device,
        compile_cache_dir=cache_dir, seed=args.seed,
    )
    try:
        check(cache_dir is not None, "no compile cache directory in use")
        # the fixed device round trip every recompute pays once: a pull
        # of 8 bytes (what ROADMAP C6's auto_small_graph_nodes hangs on)
        x = jax.device_put(np.zeros(2, np.int32))
        f = jax.jit(lambda a: a + 1)
        np.asarray(f(x))
        pulls = []
        for _ in range(20):
            t0 = time.perf_counter()
            np.asarray(f(x))
            pulls.append((time.perf_counter() - t0) * 1e3)
        emit(
            round_trip_ms_median=sorted(pulls)[len(pulls) // 2],
            round_trip_ms_min=min(pulls), platform=dev.platform,
        )

        t0 = time.perf_counter()
        if args.chips == 4:
            run_multichip(args, dev.platform)
        else:
            asyncio.run(run_served(args, dev.platform))
        stats = dev.memory_stats() or {}
        if dev.platform == "tpu":
            check(
                "peak_bytes_in_use" in stats,
                f"memory_stats() has no peak_bytes_in_use: {sorted(stats)}",
            )
        emit(
            phases_s=round(time.perf_counter() - t0, 3),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            compile_cache_hits=counters.get_counter("xla_cache.hits") or 0,
            compile_cache_misses=counters.get_counter("xla_cache.misses")
            or 0,
            compile_ms={
                k: e["compile_ms"] for k, e in ledger.snapshot().items()
            },
            platform=dev.platform,
        )
    except SmokeFailure as e:
        emit(failed=str(e))
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if dev.platform != "tpu":
        emit(rehearsal=True, passed=True, device=device)
        return 0
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
