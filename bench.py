"""Benchmark: full-RIB recompute across the five BASELINE.md configs —
TPU pipeline vs the CPU SpfSolver oracle (the reference publishes no
absolute numbers; the oracle re-expresses its per-root Dijkstra +
per-prefix loop, openr/decision/LinkState.cpp:836 + SpfSolver.cpp:460).

Prints exactly ONE JSON line on stdout:
  {"metric": "...", "value": N, "unit": "ms", "vs_baseline": N, ...}

value        = TPU full-RIB recompute wall time on the headline config
               (100k-node LSDB), median over runs, including host
               materialization and the device round trip
vs_baseline  = CPU-oracle time / TPU time on that config

The extra "configs" key carries per-config results and a device/host
breakdown:
  sync_ms    host mirror sync (changelog delta -> device scatter)
  exec_ms    device pipeline + the one result pull (the host<->device
             round trip included; its measured fixed part is reported
             as rig_rtt_ms)
  mat_ms     host route materialization (delta rows only, steady state)
Progress goes to stderr. Runs on whatever device jax picks (real TPU
under the driver; CPU elsewhere).
"""

from __future__ import annotations

import json
import statistics
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _flap(states, adj_dbs, victims, round_i, area="0"):
    """Apply a metric flap on each victim node's adjacencies — BOTH link
    directions (a fiber event costs both ways), through the real update
    path (changelog -> device scatter). Large metric so traffic actually
    reroutes and routes to/through the victims change."""
    from openr_tpu.types import AdjacencyDatabase, Adjacency

    # cache the name index per adj_dbs object — holding the reference
    # itself (not its id(), which the allocator reuses across configs)
    by_name = getattr(_flap, "_index", None)
    if by_name is None or _flap._index_src is not adj_dbs:
        by_name = {db.this_node_name: db for db in adj_dbs}
        _flap._index = by_name
        _flap._index_src = adj_dbs

    metric = 50 + (round_i % 5)
    touched = {}
    victim_names = set()
    for v in victims:
        db = adj_dbs[v]
        victim_names.add(db.this_node_name)
        touched[db.this_node_name] = tuple(
            Adjacency(**{**a.__dict__, "metric": metric})
            for a in db.adjacencies
        )
    for v in victims:
        for a in adj_dbs[v].adjacencies:
            nb = a.other_node_name
            if nb in victim_names:
                continue
            ndb = by_name[nb]
            base = touched.get(nb, ndb.adjacencies)
            touched[nb] = tuple(
                Adjacency(**{**x.__dict__, "metric": metric})
                if x.other_node_name in victim_names
                else x
                for x in base
            )
    for name, adjs in touched.items():
        src = by_name[name]
        states[area].update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=name,
                adjacencies=adjs,
                node_label=src.node_label,
                area=area,
            )
        )


def bench_config(name, gen, me, runs=5, flap_victims=0, cpu_baseline=True,
                 small_graph_nodes=0, tpu_kw=None, **solver_kw):
    """Run one config; returns a result dict. small_graph_nodes > 0
    exercises the "auto" backend's small-graph delegation (the solver
    routes the whole build to the CPU oracle below that node count);
    extra solver_kw (e.g. enable_lfa) go to BOTH backends, tpu_kw only
    to the device solver (multichip tier knobs have no CPU analogue)."""
    tpu_kw = dict(tpu_kw or {})
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.models import topologies

    t0 = time.perf_counter()
    adj_dbs, prefix_dbs = gen()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    area = next(iter(states))
    n_nodes = len(adj_dbs)
    n_links = len(states[area].all_links())
    log(
        f"[{name}] {n_nodes} nodes, {n_links} links "
        f"({time.perf_counter() - t0:.1f}s build)"
    )

    res = {"nodes": n_nodes, "links": n_links, "prefixes": len(prefix_dbs)}

    cpu_ms = None
    if cpu_baseline:
        cpu = SpfSolver(me, **solver_kw)
        t0 = time.perf_counter()
        cpu_db = cpu.build_route_db(me, states, ps)
        cpu_ms = (time.perf_counter() - t0) * 1e3
        res["cpu_ms"] = round(cpu_ms, 1)
        log(f"[{name}] cpu oracle: {cpu_ms:.1f} ms, {len(cpu_db.unicast_routes)} routes")

    tpu = TpuSpfSolver(me, small_graph_nodes=small_graph_nodes,
                   **tpu_kw, **solver_kw)
    t0 = time.perf_counter()
    tpu_db = tpu.build_route_db(me, states, ps)
    res["compile_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    log(f"[{name}] tpu first build (compile): {res['compile_ms']:.0f} ms; "
        f"plan: {tpu.last_device_stats}")
    if cpu_baseline:
        assert tpu_db.unicast_routes == cpu_db.unicast_routes, (
            f"[{name}] RIB mismatch vs oracle"
        )
        log(f"[{name}] parity vs CPU oracle OK")

    # cold full rebuild, jit warm: fresh solver state -> plan build + full
    # device pull + full host materialization (what a restarting daemon
    # pays once)
    tpu2 = TpuSpfSolver(me, small_graph_nodes=small_graph_nodes,
                    **tpu_kw, **solver_kw)
    t0 = time.perf_counter()
    cold_db = tpu2.build_route_db(me, states, ps)
    res["full_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    tm = getattr(tpu2, "last_timing", {})
    # last_timing also carries the per-area "areas" sub-dict (trace
    # folding); the breakdown only wants the scalar stage timings
    res["full_breakdown"] = {
        k: round(v, 1) for k, v in tm.items()
        if isinstance(v, (int, float))
    }
    # zero-copy program lane: device columns -> packed RouteColumnBatch
    # -> columnar dataplane sync, measured BEFORE anything forces lazy
    # entries. The decision.rib.entries_built counter standing still
    # across this lane is the proof that no per-route objects were
    # constructed on the program path (the columnar-spine headline)
    from openr_tpu.decision.column_delta import build_column_batch
    from openr_tpu.decision.columnar_rib import LazyUnicastRoutes
    from openr_tpu.runtime.counters import counters as _counters

    if isinstance(cold_db.unicast_routes, LazyUnicastRoutes):
        import asyncio as _asyncio

        from openr_tpu.platform.fib_handler import MemoryDataplane

        eb0 = int(_counters.get_counter("decision.rib.entries_built") or 0)
        t0 = time.perf_counter()
        batch = build_column_batch(cold_db.unicast_routes)
        if batch is not None:
            dp = MemoryDataplane()
            _asyncio.run(dp.sync_unicast_columns(batch))
            res["cold_program_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 1
            )
            res["cold_program_routes"] = len(dp.unicast)
            # 0 == the whole program path stayed in packed-array land
            res["cold_program_entries_built"] = (
                int(_counters.get_counter("decision.rib.entries_built") or 0)
                - eb0
            )
            del dp, batch
    # consumption boundary: force every lazy entry in one bulk pass —
    # what Fib's first full sync pays on top of full_ms. The columnar
    # rebuild moved eager per-entry construction out of full_ms into
    # this bounded, vectorized pass (ISSUE 1 target: >=2x under the
    # eager seed's mat_ms)
    t0 = time.perf_counter()
    n_cold = len(dict(cold_db.unicast_routes))
    res["cold_consume_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    # overlap efficiency: sum of per-area sync/exec/mat stage time vs
    # the pipeline's wall clock. >1.0 means the worker thread's
    # device-pull + column scatter genuinely ran under the main
    # thread's next-area sync / host-route work
    wall = tm.get("pipeline_wall_ms")
    stages = tm.get("pipeline_stages_ms")
    if wall and stages:
        res["overlap_efficiency"] = round(stages / wall, 2)
    log(f"[{name}] tpu cold full rebuild (warm jit): {res['full_ms']:.0f} ms "
        f"{res['full_breakdown']} "
        f"program({res.get('cold_program_routes')} routes): "
        f"{res.get('cold_program_ms')} ms "
        f"entries_built {res.get('cold_program_entries_built')} "
        f"consume({n_cold} routes): "
        f"{res['cold_consume_ms']:.0f} ms "
        f"overlap: {res.get('overlap_efficiency')}")
    del tpu2, cold_db

    # steady-state full recompute through real churn (changelog path)
    victims = list(range(1, (flap_victims or 1) + 1))
    from openr_tpu.runtime.counters import counters as _counters

    _XLA_KEYS = ("factory_hits", "factory_misses", "executable_evictions")
    xla0 = {
        k: int(_counters.get_counter(f"xla_cache.{k}") or 0)
        for k in _XLA_KEYS
    }
    retrace0 = sum(
        _counters.get_counters("xla_cache.retraces.").values()
    )
    from openr_tpu.runtime.latency_budget import latency_budget

    samples, phases, budget_rows = [], {}, []
    dispatch = getattr(tpu, "dispatch_route_db", None)
    for i in range(runs):
        _flap(states, adj_dbs, victims, i, area)
        t0 = time.perf_counter()
        # per-solve latency budget: drive the explicit dispatch/collect
        # split so the churn loop emits per-component columns (no
        # program/ack stage in this lane — the storm lane covers those)
        bud = latency_budget.begin(("churn", name, i))
        if dispatch is not None:
            pending = dispatch(me, states, ps)
            if bud is not None:
                bud.advance("host_sync")
            tpu.collect_route_db(pending)
            tm_i = getattr(tpu, "last_timing", {}) or {}
            if bud is not None:
                bud.advance_split(
                    {
                        "device_exec": tm_i.get("exec_ms"),
                        "payload_apply": tm_i.get("mat_ms"),
                    },
                    primary="collect_block",
                )
        else:
            tpu.build_route_db(me, states, ps)
            if bud is not None:
                bud.advance("device_exec")
        budget_rows.append(latency_budget.close(bud))
        samples.append((time.perf_counter() - t0) * 1e3)
        for k, v in getattr(tpu, "last_timing", {}).items():
            if isinstance(v, (int, float)):
                phases.setdefault(k, []).append(v)
    tpu_ms = statistics.median(samples)
    res["tpu_ms"] = round(tpu_ms, 1)
    # steady-state convergence latency distribution (same interpolation
    # as the runtime stat fabric, so BENCH and monitor.statistics agree)
    from openr_tpu.runtime.counters import _percentile

    sv = sorted(samples)
    res["convergence_ms"] = {
        "p50": round(_percentile(sv, 50.0), 1),
        "p99": round(_percentile(sv, 99.0), 1),
    }
    for k in ("sync_ms", "exec_ms", "mat_ms"):
        phases.setdefault(k, [])
    res["stage_percentiles"] = {}
    for k, vals in phases.items():
        # a phase absent from a run contributed 0 to it — backfill so
        # medians aren't computed over only the runs where it fired
        vals = vals + [0] * (runs - len(vals))
        res[k] = round(statistics.median(vals), 1)
        pv = sorted(vals)
        res["stage_percentiles"][k] = {
            "p50": round(_percentile(pv, 50.0), 1),
            "p99": round(_percentile(pv, 99.0), 1),
        }
    # uniform across fabric sizes: 0 when the delta pull had no changed
    # rows (or the config delegated to the CPU oracle), never null
    res["changed_rows"] = int(tpu.last_device_stats.get("changed_rows") or 0)
    # per-component latency-budget columns + conservation (ISSUE 17)
    res.update(_budget_summary(budget_rows))
    # peak HBM across devices at end of the churn loop — None on backends
    # (cpu) that don't expose memory_stats()
    from openr_tpu.runtime.device_stats import peak_hbm_mb

    peak_mb, backend = peak_hbm_mb()
    res["backend"] = backend
    if peak_mb is not None:
        res["peak_hbm_mb"] = round(peak_mb, 1)
    # device-only: chained dispatches, one blocking sync amortized —
    # what the chip does per solve, with the rig's fixed transfer RTT
    # (rig_rtt_ms) excluded
    dev_ms = tpu.device_compute_ms()
    if dev_ms is not None:
        res["device_ms"] = round(dev_ms, 1)
        # the exec_ms <-> device_ms gap: dispatch overhead + the one
        # result pull (rig RTT) — the quantity the async dispatch /
        # delta-resident sync work drives down. Per-solve bytes_uploaded
        # rides last_timing into the phase medians above.
        res["exec_overhead_ms"] = round(res["exec_ms"] - dev_ms, 1)
    if cpu_ms:
        res["speedup"] = round(cpu_ms / tpu_ms, 2)
        if dev_ms:
            res["device_speedup"] = round(cpu_ms / dev_ms, 2)
    # multichip capacity tier: whether the steady-state solves ran
    # through the sharded path, the mesh factorization they used, and
    # the per-shard completion timings (a straggler device is one
    # outlier entry in shard_ms)
    mc = getattr(tpu, "last_timing", {}).get("multichip")
    res["multichip_engaged"] = bool(mc)
    if mc:
        res["multichip"] = mc
    else:
        # the phase-median loop above folds last_timing's bool flags in
        # as 0s; an off tier reports only multichip_engaged=False
        res.pop("multichip", None)
    # executable-cache health over the churn loop (deltas vs the loop
    # start, so other configs/tests in the process don't pollute the
    # reading): a steady state that misses (recompiles) or evicts here
    # is a capacity-class leak
    res["xla_cache"] = {
        k: int(_counters.get_counter(f"xla_cache.{k}") or 0) - xla0[k]
        for k in _XLA_KEYS
    }
    # unexpected recompiles over the churn loop (retrace sentinel,
    # summed across namespaces). A warm steady state must report 0 —
    # the smoke test gates on it; any nonzero means a trace-level
    # cache-class fork that the factory key did not capture
    res["xla_cache"]["retraces"] = int(
        sum(_counters.get_counters("xla_cache.retraces.").values())
        - retrace0
    )
    # async dispatch queue depth gauge (0 unless a Decision actor with
    # async_dispatch ran in this process; reported so daemon-embedded
    # bench runs surface backlog)
    res["dispatch_queue_depth"] = int(
        _counters.get_counter("decision.dispatch.depth") or 0
    )
    # flight-recorder overhead (runtime/monitor.py FlightRecorder): the
    # always-on cost is one raw-counter ring append per monitor tick —
    # nothing hooks the solve path. Price a tick against the measured
    # churn iteration: even ticking once PER SOLVE (far above the 1 Hz
    # production cadence) must fit the ≤1% budget the smoke test pins.
    from openr_tpu.config import MonitorConfig
    from openr_tpu.runtime.monitor import FlightRecorder

    _recorder = FlightRecorder(me, MonitorConfig())
    _FR_TICKS = 200
    t0 = time.perf_counter()
    for _ in range(_FR_TICKS):
        _recorder.record_tick()
    fr_tick_ms = (time.perf_counter() - t0) * 1e3 / _FR_TICKS
    res["flightrec_tick_ms"] = round(fr_tick_ms, 4)
    res["flightrec_overhead_pct"] = round(
        100.0 * fr_tick_ms / max(tpu_ms, 1e-6), 3
    )
    log(f"[{name}] tpu recompute: {[f'{s:.0f}' for s in samples]} ms "
        f"(sync {res['sync_ms']} / exec {res['exec_ms']} / mat {res['mat_ms']} "
        f"/ device-only {res.get('device_ms')} "
        f"/ uploaded {res.get('bytes_uploaded')} B "
        f"/ xla {res['xla_cache']})")

    # incremental churn lane: same fabric, single-victim metric flaps
    # against a solver with the seed-from-previous path enabled, so each
    # config reports incr_device_ms / incr_changed_rows next to its
    # full-solve numbers. Skipped when the config delegated to the CPU
    # oracle (no device path to make incremental). The incr executable
    # cache deltas ride along: a steady flap sequence reuses ONE dirty
    # bucket, so incr_executable_evictions staying 0 is the health
    # signal the smoke test pins.
    if res.get("device_ms") is not None:
        _INCR_KEYS = (
            "incr_factory_hits", "incr_factory_misses",
            "incr_executable_evictions",
        )
        ix0 = {
            k: int(_counters.get_counter(f"xla_cache.{k}") or 0)
            for k in _INCR_KEYS
        }
        tpu_i = TpuSpfSolver(
            me, small_graph_nodes=small_graph_nodes,
            incremental_spf=True, **tpu_kw, **solver_kw,
        )
        tpu_i.build_route_db(me, states, ps)  # first solve: cold seed
        i_samples, engaged, cones, rows = [], 0, [], []
        for i in range(runs):
            _flap(states, adj_dbs, victims[:1], runs + i, area)
            t0 = time.perf_counter()
            tpu_i.build_route_db(me, states, ps)
            i_samples.append((time.perf_counter() - t0) * 1e3)
            st = tpu_i.last_device_stats
            if st.get("incremental") and not st.get("fell_back"):
                engaged += 1
            cones.append(int(st.get("cone") or 0))
            rows.append(int(st.get("changed_rows") or 0))
        res["incr_tpu_ms"] = round(statistics.median(i_samples), 1)
        res["incr_engaged"] = engaged
        res["incr_runs"] = runs
        res["incr_cone"] = max(cones) if cones else 0
        res["incr_changed_rows"] = max(rows) if rows else 0
        res["incr_xla_cache"] = {
            k: int(_counters.get_counter(f"xla_cache.{k}") or 0) - ix0[k]
            for k in _INCR_KEYS
        }
        i_dev = tpu_i.incr_device_compute_ms()
        if i_dev is not None:
            res["incr_device_ms"] = round(i_dev, 2)
        log(f"[{name}] tpu incremental churn: "
            f"{[f'{s:.0f}' for s in i_samples]} ms "
            f"(engaged {engaged}/{runs} / device-only "
            f"{res.get('incr_device_ms')} / cone {res['incr_cone']} "
            f"/ changed {res['incr_changed_rows']} "
            f"/ xla {res['incr_xla_cache']})")
        del tpu_i

    # kernel A/B lane: sync vs bucketed Δ-stepping (ops/relax.py) over
    # the SAME flap sequence (round indices match, so each lane sees
    # identical per-run graphs). Records device-only time, executed
    # relaxation rounds, bucket epochs, and the multichip halo-exchange
    # count — the round/halo delta is the bucketed kernel's whole claim.
    if res.get("device_ms") is not None:
        res["kernel_ab"] = {}
        for kern in ("sync", "bucketed"):
            tpu_k = TpuSpfSolver(
                me, small_graph_nodes=small_graph_nodes,
                spf_kernel=kern, **tpu_kw, **solver_kw,
            )
            tpu_k.build_route_db(me, states, ps)  # warm jit
            k_samples, k_rounds, k_epochs, k_halo, k_engaged = (
                [], [], [], [], 0
            )
            for i in range(runs):
                _flap(states, adj_dbs, victims, 2 * runs + i, area)
                t0 = time.perf_counter()
                tpu_k.build_route_db(me, states, ps)
                k_samples.append((time.perf_counter() - t0) * 1e3)
                tm_k = getattr(tpu_k, "last_timing", {})
                k_rounds.append(int(tm_k.get("rounds") or 0))
                k_epochs.append(int(tm_k.get("bucket_epochs") or 0))
                k_halo.append(int(tm_k.get("halo_exchanges") or 0))
                if tm_k.get("spf_kernel") == "bucketed":
                    k_engaged += 1
            lane = {
                "tpu_ms": round(statistics.median(k_samples), 1),
                "rounds": max(k_rounds) if k_rounds else 0,
                "bucket_epochs": max(k_epochs) if k_epochs else 0,
                "halo_exchanges": max(k_halo) if k_halo else 0,
                "engaged": k_engaged,
            }
            k_dev = tpu_k.device_compute_ms()
            if k_dev is not None:
                lane["device_ms"] = round(k_dev, 2)
            res["kernel_ab"][kern] = lane
            log(f"[{name}] kernel={kern}: device-only "
                f"{lane.get('device_ms')} ms / rounds {lane['rounds']} "
                f"/ epochs {lane['bucket_epochs']} "
                f"/ halo {lane['halo_exchanges']} "
                f"/ engaged {k_engaged}/{runs}")
            del tpu_k
        ab = res["kernel_ab"]
        ab["rounds_decreased"] = (
            0 < ab["bucketed"]["rounds"] < ab["sync"]["rounds"]
        )
        if ab["sync"]["halo_exchanges"]:
            ab["halo_decreased"] = (
                ab["bucketed"]["halo_exchanges"]
                < ab["sync"]["halo_exchanges"]
            )
    return res, tpu_ms, cpu_ms


def bench_whatif(name, gen, me) -> dict:
    """N-1 what-if sweep smoke (decision/whatif.py): one batched device
    dispatch sweeping every up link of the fabric. Tier-1/CPU-friendly —
    runs on whatever device jax picked, so the quick lane starts
    tracking sweep throughput (scenarios/s) and peak HBM during a sweep
    alongside the solve trajectory."""
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.decision.whatif import WhatIfEngine
    from openr_tpu.models import topologies
    from openr_tpu.runtime.counters import counters as _counters
    from openr_tpu.runtime.device_stats import peak_hbm_mb

    adj_dbs, prefix_dbs = gen()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    tpu = TpuSpfSolver(me)
    tpu.build_route_db(me, states, ps)  # resident mirror + warm jit
    eng = WhatIfEngine(tpu)
    eng.sweep(states, ps, order=1)  # warm the sweep executable
    d0 = int(_counters.get_counter("whatif.device.batched_dispatches") or 0)
    t0 = time.perf_counter()
    out = eng.sweep(states, ps, order=1)
    sweep_ms = (time.perf_counter() - t0) * 1e3
    res = {
        "scenarios": out["scenarios"],
        "sweep_ms": round(sweep_ms, 1),
        "scenarios_per_s": round(out["scenarios"] / (sweep_ms / 1e3), 1),
        "dispatches": int(
            _counters.get_counter("whatif.device.batched_dispatches") or 0
        ) - d0,
        "partitioned": out["partitioned"],
    }
    peak_mb, backend = peak_hbm_mb()
    res["backend"] = backend
    if peak_mb is not None:
        res["peak_hbm_mb"] = round(peak_mb, 1)
    log(f"[{name}] whatif N-1 sweep: {out['scenarios']} scenarios in "
        f"{sweep_ms:.0f} ms ({res['scenarios_per_s']}/s, "
        f"{res['dispatches']} dispatch) peak_hbm {res.get('peak_hbm_mb')}")
    return res


def _budget_summary(rows: list) -> dict:
    """Flatten closed latency-budget rows (runtime/latency_budget.py)
    into per-component bench columns: budget_<comp>_{p50,p99}_ms, the
    conservation check (unattributed vs e2e), and the p50->p99 tail
    attribution (ISSUE 17 acceptance: top-2 components cover >=80% of
    the gap under flapstorm)."""
    from openr_tpu.runtime.counters import _percentile
    from openr_tpu.runtime.latency_budget import (
        BUDGET_COMPONENTS,
        tail_attribution,
    )

    rows = [r for r in rows if r]
    if not rows:
        return {}
    per = {c: [] for c in BUDGET_COMPONENTS}
    e2e, unattr = [], []
    for r in rows:
        e2e.append(r["e2e_ms"])
        unattr.append(r["unattributed_ms"])
        for c in BUDGET_COMPONENTS:
            per[c].append(r["components"].get(c, 0.0))
    out = {}
    for c in BUDGET_COMPONENTS:
        pv = sorted(per[c])
        if not pv or pv[-1] <= 0.0:
            continue  # component never engaged in this lane
        out[f"budget_{c}_p50_ms"] = round(_percentile(pv, 50.0), 3)
        out[f"budget_{c}_p99_ms"] = round(_percentile(pv, 99.0), 3)
    ev, uv = sorted(e2e), sorted(unattr)
    out["budget_e2e_p50_ms"] = round(_percentile(ev, 50.0), 3)
    out["budget_e2e_p99_ms"] = round(_percentile(ev, 99.0), 3)
    out["budget_unattributed_p99_ms"] = round(_percentile(uv, 99.0), 3)
    # conservation: total unattributed residual as a fraction of total
    # e2e across the lane's epochs (gate: < 5%)
    out["budget_unattributed_frac"] = round(
        sum(unattr) / max(sum(e2e), 1e-9), 4
    )
    out["budget_epochs"] = len(rows)
    out["budget_tail"] = tail_attribution(per, e2e)
    return out


def bench_flapstorm(name, gen, me, events=100, rate_hz=100.0,
                    flap_victims=8, small_graph_nodes=0, **solver_kw):
    """Sustained flap-storm churn lane (ISSUE 16): paced single-victim
    metric flaps at rate_hz through an incremental_spf=True solver, each
    epoch's RIB delta programmed into the mock FibService —
    churn-to-FIB-ack is flap-apply -> programming ack, per-epoch
    download is last_timing's bytes_downloaded (one delta payload, not
    the table). The closing idle epoch (no flap) pins the standstill
    property: zero changed rows, download still exactly one delta
    payload."""
    import asyncio as _asyncio

    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.fib.fib_service import MockFibService
    from openr_tpu.models import topologies
    from openr_tpu.runtime.counters import _percentile

    t0 = time.perf_counter()
    adj_dbs, prefix_dbs = gen()
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    area = next(iter(states))
    log(f"[{name}] {len(adj_dbs)} nodes "
        f"({time.perf_counter() - t0:.1f}s build)")

    tpu = TpuSpfSolver(me, small_graph_nodes=small_graph_nodes,
                       incremental_spf=True, **solver_kw)
    db = tpu.build_route_db(me, states, ps)  # cold seed: full pull
    full_bytes = int(
        getattr(tpu, "last_timing", {}).get("bytes_downloaded") or 0
    )
    # warm the incremental executable before pacing starts — the
    # storm measures steady-state churn, not the one-time jit compile
    _flap(states, adj_dbs, [1], 7919, area)
    db = tpu.build_route_db(me, states, ps)
    from openr_tpu.runtime.counters import counters as _counters

    # post-boot retraces over the storm (summed across namespaces): a
    # warm steady state must report 0 — the smoke test gates on it
    retrace0 = sum(_counters.get_counters("xla_cache.retraces.").values())
    svc = MockFibService()
    victims = list(range(1, flap_victims + 1))
    interval = 1.0 / rate_hz

    from openr_tpu.decision.rib_digest import GENESIS, delta_digest, roll
    from openr_tpu.runtime.latency_budget import latency_budget
    from openr_tpu.runtime.overload import FlapDamper, OverloadController

    # overload soak instrumentation (ISSUE 19): the paced rotation runs
    # through a live controller + damper so the lane's headline proves
    # the steady-state property the smoke test gates on — bounded queue
    # depth, ZERO damping, zero shed. Damper tuned for the lane's pace:
    # an 8-victim rotation is steady churn, not a flap storm, and the
    # equilibrium figure of merit must sit well under suppress.
    octl = OverloadController(
        f"bench-{name}", queue_watermark=8,
        damper=FlapDamper(
            half_life_s=0.5, penalty=1.0, suppress_threshold=50.0,
            reuse_threshold=1.0, max_penalty=100.0,
        ),
    )

    async def _storm():
        nonlocal db
        acks, dl_bytes, rows = [], [], []
        budget_rows, dig_ms, depths = [], [], []
        rolling = GENESIS
        dispatch = getattr(tpu, "dispatch_route_db", None)
        start = time.perf_counter()
        for i in range(events):
            target = start + i * interval
            delay = target - time.perf_counter()
            if delay > 0:
                await _asyncio.sleep(delay)
            victim = victims[i % len(victims)]
            _flap(states, adj_dbs, [victim], i, area)
            t_ev = time.perf_counter()
            # dispatch-queue-depth proxy for this synchronous rig: how
            # many paced events are already due but not yet solved —
            # exactly what Decision's solve queue would hold. Capped at
            # the events that remain: pacing debt past the end of the
            # storm cannot queue anything
            backlog = max(0, min(events - 1, int((t_ev - start) / interval)) - i)
            octl.damper.record_change(area, f"adj:{victim}")
            octl.observe(queue_depth=backlog)
            octl.shed(backlog)
            depths.append(backlog)
            # per-event latency budget: the storm drives the explicit
            # dispatch/collect split so every churn-to-ack interval
            # decomposes into the canonical component taxonomy with the
            # conservation invariant enforced at close
            bud = latency_budget.begin(("storm", name, i))
            if dispatch is not None:
                pending = dispatch(me, states, ps)
                if bud is not None:
                    bud.advance("host_sync")
                new_db = tpu.collect_route_db(pending)
                tm_i = getattr(tpu, "last_timing", {}) or {}
                if bud is not None:
                    bud.advance_split(
                        {
                            "device_exec": tm_i.get("exec_ms"),
                            "payload_apply": tm_i.get("mat_ms"),
                        },
                        primary="collect_block",
                    )
            else:
                new_db = tpu.build_route_db(me, states, ps)
                if bud is not None:
                    bud.advance("device_exec")
            update = db.calculate_update(new_db)
            # force ONLY the changed rows (lazy column map) and program
            # them — the real Fib actor's incremental add/delete path
            changed = list(update.unicast_routes_to_update.values())
            if bud is not None:
                bud.advance("payload_apply")
            if changed:
                await svc.add_unicast_routes(0, changed)
            if update.unicast_routes_to_delete:
                await svc.delete_unicast_routes(
                    0, update.unicast_routes_to_delete
                )
            if bud is not None:
                bud.advance("program")
            budget_rows.append(
                latency_budget.close(bud, final_component="ack_rtt")
            )
            acks.append((time.perf_counter() - t_ev) * 1e3)
            # per-epoch RIB digest (ISSUE 18 replay recorder): the same
            # delta_digest the Decision actor stamps on every solve —
            # timed OUTSIDE the ack window so the headline churn-to-ack
            # keys stay comparable against pre-recorder baselines, with
            # the cost reported as its own columns (the ≤1% steady-state
            # overhead demonstration)
            t_dig = time.perf_counter()
            rolling = roll(rolling, delta_digest(update))
            dig_ms.append((time.perf_counter() - t_dig) * 1e3)
            db = new_db
            tm = getattr(tpu, "last_timing", {})
            dl_bytes.append(int(tm.get("bytes_downloaded") or 0))
            rows.append(int(tpu.last_device_stats.get("changed_rows") or 0))
        wall_s = time.perf_counter() - start
        return acks, dl_bytes, rows, wall_s, budget_rows, dig_ms, depths

    (acks, dl_bytes, rows, wall_s, budget_rows, dig_ms,
     depths) = _asyncio.run(_storm())
    # idle epoch: nothing changed since the last solve — the delta
    # payload still ships (count=0), so the download stands still at
    # exactly one payload
    tpu.build_route_db(me, states, ps)
    tm = getattr(tpu, "last_timing", {})
    idle_bytes = int(tm.get("bytes_downloaded") or 0)
    idle_rows = int(tpu.last_device_stats.get("changed_rows") or 0)

    sa, sb = sorted(acks), sorted(dl_bytes)
    res = {
        "nodes": len(adj_dbs),
        "events": events,
        "rate_hz": rate_hz,
        "achieved_rate_hz": round(events / wall_s, 1) if wall_s else None,
        "ack_p50_ms": round(_percentile(sa, 50.0), 2),
        "ack_p99_ms": round(_percentile(sa, 99.0), 2),
        "bytes_downloaded_per_epoch": int(_percentile(sb, 50.0)),
        "bytes_downloaded_max": max(dl_bytes) if dl_bytes else 0,
        "full_plane_bytes": full_bytes,
        "idle_bytes_downloaded": idle_bytes,
        "idle_changed_rows": idle_rows,
        "changed_rows_max": max(rows) if rows else 0,
        "fib_routes": len(svc.unicast),
        "retraces": int(
            sum(_counters.get_counters("xla_cache.retraces.").values())
            - retrace0
        ),
        # overload soak headline (ISSUE 19): under the steady paced
        # rotation these must read bounded-depth / zero-damped /
        # zero-shed — the smoke test and perf_diff gate hold the line
        "dispatch_queue_depth_p99": int(
            _percentile(sorted(depths), 99.0)
        ) if depths else 0,
        "dispatch_queue_depth_max": max(depths) if depths else 0,
        "damped_keys": octl.damper.damped_count(),
        "shed_epochs": octl.shed_epochs,
        "overload_state": octl.state,
    }
    if dig_ms:
        sd = sorted(dig_ms)
        res["rib_digest_p50_ms"] = round(_percentile(sd, 50.0), 3)
        res["rib_digest_p99_ms"] = round(_percentile(sd, 99.0), 3)
        # steady-state recorder overhead: digest time as a fraction of
        # the churn-to-ack interval it would ride inside in production
        res["rib_digest_overhead_pct"] = round(
            100.0 * sum(dig_ms) / max(sum(acks), 1e-9), 2
        )
    res.update(_budget_summary(budget_rows))
    log(f"[{name}] flapstorm: ack p50 {res['ack_p50_ms']} / p99 "
        f"{res['ack_p99_ms']} ms at {res['achieved_rate_hz']} ev/s "
        f"(asked {rate_hz}) / dl {res['bytes_downloaded_per_epoch']} B "
        f"per epoch (full {full_bytes} B) / idle {idle_bytes} B")
    if dig_ms:
        log(f"[{name}] rib digest: p50 {res['rib_digest_p50_ms']} / p99 "
            f"{res['rib_digest_p99_ms']} ms "
            f"({res['rib_digest_overhead_pct']}% of churn-to-ack)")
    tail = (res.get("budget_tail") or {}).get("ranked") or []
    log(f"[{name}] budget: e2e p99 {res.get('budget_e2e_p99_ms')} ms, "
        f"unattributed frac {res.get('budget_unattributed_frac')}, "
        f"tail owners "
        f"{[(t['component'], t['gap_ms']) for t in tail[:2]]}")
    log(f"[{name}] overload soak: state {res['overload_state']} / "
        f"queue depth p99 {res['dispatch_queue_depth_p99']} "
        f"(max {res['dispatch_queue_depth_max']}) / "
        f"damped {res['damped_keys']} / shed {res['shed_epochs']}")
    return res


def _ledger_record(name: str, res: dict) -> None:
    """Append one config's headline numbers to the perf ledger — no-op
    unless $OPENR_TPU_PERF_LEDGER points somewhere, so bare bench runs
    and tests stay disk-free. tools/perf_diff.py --ledger and the
    baseline_drift SLO read these back as stored baselines."""
    from openr_tpu.runtime import perf_ledger

    lg = perf_ledger.get_ledger()
    if not lg.enabled or not isinstance(res, dict):
        return
    sig = f"n{res['nodes']}" if res.get("nodes") else "bench"
    obs = {
        k: res[k]
        for k in ("compile_ms", "full_ms", "device_ms", "tpu_ms",
                  "exec_overhead_ms", "peak_hbm_mb", "cold_program_ms",
                  "incr_device_ms", "boot_first_rib_ms",
                  "boot_first_rib_ms_warmcache", "aot_hit_rate",
                  "ack_p50_ms", "ack_p99_ms",
                  "bytes_downloaded_per_epoch")
        if isinstance(res.get(k), (int, float))
    }
    # per-component budget baselines: perf_diff --ledger and the CI gate
    # diff the breakdown, so a regression names the component that moved
    obs.update(
        {
            k: v
            for k, v in res.items()
            if k.startswith("budget_") and isinstance(v, (int, float))
        }
    )
    if obs:
        lg.record(f"solve[{name}]", obs, signature=sig, variant="default")
    for variant, kr in (res.get("kernel_ab") or {}).items():
        vo = {
            k: v for k, v in (kr or {}).items()
            if isinstance(v, (int, float))
        }
        if vo:
            lg.record(f"solve[{name}]", vo, signature=sig, variant=variant)


def bench_boot() -> dict:
    """Cold-start lane (runtime/lifecycle.py): two full node stacks on a
    MockIoMesh; measures begin() -> first programmed RIB on boot-0. An
    in-process approximation of a daemon restart — the explicit setup
    phases (config load, device init) belong to main.py, but the
    pipeline phases (initial sync, first solve, first RIB delta, first
    FIB program) and the boot.first_rib_ms headline run the real path."""
    import asyncio
    import os

    from openr_tpu.kvstore.wrapper import wait_until
    from openr_tpu.runtime.lifecycle import boot_tracer
    from openr_tpu.runtime.openr_wrapper import OpenrWrapper
    from openr_tpu.spark import MockIoMesh

    async def _run() -> dict:
        boot_tracer.reset()
        boot_tracer.begin("boot-0")
        mesh = MockIoMesh()
        kv_ports: dict[str, int] = {}
        nodes = {
            n: OpenrWrapper(n, mesh.provider(n), kv_ports)
            for n in ("boot-0", "boot-1")
        }
        mesh.connect("boot-0", "if-01", "boot-1", "if-10")
        try:
            await nodes["boot-0"].start("if-01")
            await nodes["boot-1"].start("if-10")
            nodes["boot-0"].advertise_prefix("10.99.0.1/32")
            nodes["boot-1"].advertise_prefix("10.99.0.2/32")
            await wait_until(
                lambda: boot_tracer.report().get("complete"),
                timeout_s=30.0,
            )
        finally:
            for w in nodes.values():
                await w.stop()
        return boot_tracer.report()

    report = asyncio.run(_run())
    out_dir = os.environ.get("OPENR_TPU_BOOT_TRACE_OUT", "")
    if out_dir:
        from openr_tpu.runtime.tracing import tracer as _tracer

        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "boot_report.json"), "w") as f:
            json.dump(report, f, indent=1, sort_keys=True, default=str)
        with open(os.path.join(out_dir, "boot_trace.json"), "w") as f:
            f.write(_tracer.export_chrome_json(limit=64))
    res = {
        "boot_first_rib_ms": report.get("first_rib_ms"),
        "complete": bool(report.get("complete")),
        "phases": {
            p["name"]: p["duration_ms"] for p in report.get("phases", [])
        },
    }
    log(f"[boot] first_rib {res['boot_first_rib_ms']} ms "
        f"phases {sorted(res['phases'])}")
    res.update(bench_boot_aot())
    return res


def bench_boot_aot() -> dict:
    """Cold-vs-warm AOT-cache A/B on the boot lane (ISSUE 20): the same
    two-node stack as bench_boot but with the device solver forced on,
    run twice against one AOT cache directory. Run A compiles cold and
    serializes every executable; a simulated restart then drops ALL
    in-memory compiled state (bounded jit caches, jax's own caches, the
    retrace sentinel's compile census) and run B boots against the
    populated disk cache — its prewarm is deserialize-and-install, and
    the retrace sentinel proves zero true compiles (any would page as
    aot_warm_violation). Headlines: boot_first_rib_ms_warmcache +
    aot_hit_rate (gated >= 0.9 by tools/perf_diff.py)."""
    import asyncio
    import os
    import shutil

    from openr_tpu.config import DecisionConfig
    from openr_tpu.kvstore.wrapper import wait_until
    from openr_tpu.ops.xla_cache import (
        cache_root,
        clear_all_jit_caches,
        configure_aot,
        retrace,
    )
    from openr_tpu.runtime.lifecycle import boot_tracer
    from openr_tpu.runtime.openr_wrapper import OpenrWrapper
    from openr_tpu.spark import MockIoMesh

    # a fixed path: the directory is part of the cache key
    cache_dir = os.environ.get("OPENR_TPU_AOT_BENCH_DIR") or os.path.join(
        cache_root(), "aot_bench"
    )
    cleanup = "OPENR_TPU_AOT_BENCH_DIR" not in os.environ
    if cleanup:
        # run A must compile cold: drop what a killed run left behind
        shutil.rmtree(cache_dir, ignore_errors=True)
    aot = configure_aot(cache_dir)

    async def _one_boot() -> dict:
        boot_tracer.reset()
        boot_tracer.begin("boot-0")
        mesh = MockIoMesh()
        kv_ports: dict[str, int] = {}
        dcfg = DecisionConfig(debounce_min_ms=5, debounce_max_ms=25)
        nodes = {
            n: OpenrWrapper(
                n, mesh.provider(n), kv_ports,
                decision_config=dcfg, solver_backend="tpu",
            )
            for n in ("boot-0", "boot-1")
        }
        mesh.connect("boot-0", "if-01", "boot-1", "if-10")
        try:
            await nodes["boot-0"].start("if-01")
            await nodes["boot-1"].start("if-10")
            nodes["boot-0"].advertise_prefix("10.99.0.1/32")
            nodes["boot-1"].advertise_prefix("10.99.0.2/32")
            await wait_until(
                lambda: boot_tracer.report().get("complete"),
                timeout_s=60.0,
            )
        finally:
            for w in nodes.values():
                await w.stop()
        return boot_tracer.report()

    try:
        cold = asyncio.run(_one_boot())

        # simulated daemon restart: the disk cache survives, nothing
        # in-memory does — exactly what a real process restart drops
        import jax

        clear_all_jit_caches()
        jax.clear_caches()
        retrace.reset()
        aot.reset_stats()
        preload = aot.preload()

        warm = asyncio.run(_one_boot())
        summary = aot.summary()
        scoped = retrace.snapshot()
        res = {
            "boot_first_rib_ms_coldcache": cold.get("first_rib_ms"),
            "boot_first_rib_ms_warmcache": warm.get("first_rib_ms"),
            "aot_hit_rate": summary.get("hit_rate"),
            "aot_hits": summary.get("hits"),
            "aot_misses": summary.get("misses"),
            "aot_entries": summary.get("entries"),
            "aot_preloaded": preload.get("loaded"),
            "aot_warm_retraces": sum(
                (scoped.get("retraces") or {}).values()
            ),
        }
        log(
            f"[boot-aot] cold {res['boot_first_rib_ms_coldcache']} ms -> "
            f"warm {res['boot_first_rib_ms_warmcache']} ms "
            f"(hit_rate {res['aot_hit_rate']}, "
            f"{res['aot_entries']} entries)"
        )
        return res
    finally:
        configure_aot("off")
        if cleanup:
            shutil.rmtree(cache_dir, ignore_errors=True)


def _write_budget_out(configs) -> None:
    """Dump the per-lane latency-budget waterfall to
    $OPENR_TPU_BUDGET_OUT (CI uploads it as a failure artifact). The doc
    carries each lane's `budget_*` columns plus the ledger's own
    report() so a red bench lane is triageable offline — the waterfall
    names the component, not just the regressed total."""
    import os

    path = os.environ.get("OPENR_TPU_BUDGET_OUT")
    if not path:
        return
    from openr_tpu.runtime.latency_budget import latency_budget

    doc = {
        "lanes": {
            name: {
                k: v for k, v in res.items() if k.startswith("budget_")
            }
            for name, res in configs.items()
            if isinstance(res, dict)
            and any(k.startswith("budget_") for k in res)
        },
        "ledger": latency_budget.report(),
    }
    try:
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        log(f"budget waterfall: {path}")
    except OSError as exc:
        log(f"budget waterfall: write failed ({exc})")


def main() -> None:
    quick = "--quick" in sys.argv
    only = None
    for a in sys.argv[1:]:
        if a.startswith("--only="):
            only = a.split("=", 1)[1]

    import jax
    import numpy as np

    from openr_tpu.models import topologies
    from openr_tpu.ops.xla_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    # perf-baseline ledger: opt-in via env so bare runs stay disk-free
    import os as _env_os

    from openr_tpu.runtime import perf_ledger

    if _env_os.environ.get(perf_ledger.ENV_DIR):
        perf_ledger.configure(perf_ledger.default_dir())
        log(f"perf-ledger: {perf_ledger.get_ledger().path}")
    log(f"devices: {jax.devices()}  xla-cache: {cache_dir}")
    # measure the rig's fixed device round trip (a pull of 8 bytes):
    # everything below pays it once per recompute
    x = jax.device_put(np.zeros(2, np.int32))
    f = jax.jit(lambda a: a + 1)
    np.asarray(f(x))
    t0 = time.perf_counter()
    np.asarray(f(x))
    rtt_ms = (time.perf_counter() - t0) * 1e3
    log(f"rig fixed round-trip: {rtt_ms:.1f} ms")

    configs = {}
    headline = None

    def run(name, *args, **kw):
        if only and name != only:
            return None
        r, tpu_ms, cpu_ms = bench_config(name, *args, **kw)
        configs[name] = r
        _ledger_record(name, r)
        return r, tpu_ms, cpu_ms

    # 1: 4-node mesh — CPU parity baseline (example_openr.conf scale).
    # Runs with the "auto" backend's small-graph delegation: tiny graphs
    # solve on the CPU oracle (the device round trip alone is ~300x the
    # whole solve here).
    run("mesh4", lambda: topologies.full_mesh(4), "node-0", runs=3,
        small_graph_nodes=2816)

    # 2: 1k-node Terragraph-style mesh (street-lattice grid). Sits BELOW
    # auto_small_graph_nodes, so the auto backend delegates it to the
    # oracle — asserting auto is never slower than both backends at
    # this size.
    run("tg1k", lambda: topologies.grid(32, node_labels=False), "node-16-16",
        small_graph_nodes=2816)

    # N-1 what-if sweep throughput on the 1k-node mesh: ~2k hypothetical
    # topologies against the resident graph in one batched dispatch
    if only in (None, "whatif1k"):
        configs["whatif1k"] = bench_whatif(
            "whatif1k", lambda: topologies.grid(32, node_labels=False),
            "node-16-16",
        )

    # cold-start lane: boot-to-first-RIB through the full node stack
    # (skipped in --only runs that name another config)
    if only in (None, "boot"):
        configs["boot"] = bench_boot()
        _ledger_record("boot", configs["boot"])

    if quick:
        if not configs:
            sys.exit(f"--only={only} matched no config")
        _write_budget_out(configs)
        name = "tg1k" if "tg1k" in configs else next(iter(configs))
        out = configs[name]
        print(json.dumps({
            "metric": f"full_rib_recompute_{name}_ms",
            "value": out.get(
                "tpu_ms",
                out.get(
                    "sweep_ms",
                    out.get("boot_first_rib_ms", out.get("ack_p99_ms")),
                ),
            ),
            "unit": "ms",
            "vs_baseline": out.get("speedup", 1.0),
            "rig_rtt_ms": round(rtt_ms, 1),
            "boot_first_rib_ms": configs.get("boot", {}).get(
                "boot_first_rib_ms"
            ),
            "boot_first_rib_ms_warmcache": configs.get("boot", {}).get(
                "boot_first_rib_ms_warmcache"
            ),
            "aot_hit_rate": configs.get("boot", {}).get("aot_hit_rate"),
            "configs": configs,
        }))
        return

    # 3: 10k-node fat-tree fabric, ECMP + LFA backup next-hops (the CPU
    # oracle pays one extra Dijkstra per neighbor; the device derives
    # alternates from distance fields it already holds)
    run(
        "fabric10k",
        lambda: topologies.fabric(pods=96, planes=8, ssws_per_plane=36,
                                  rsws_per_pod=64),
        "pod000-rsw00",
        enable_lfa=True,
    )

    # 4: 50k-node WAN with a segment-routed KSP2 subset (every 768th
    # node's loopback is SR_MPLS + KSP2_ED_ECMP -> 64 destinations whose
    # per-destination second-pass SPFs batch on device, ops/ksp2.py)
    run(
        "wan50k",
        lambda: topologies.wan(regions=48, region_side=32, ksp2_every=768),
        "r00-n08-08",
    )

    # 5: 100k-node synthetic LSDB (grid, 400k directed adjacencies) +
    #    1k-link flap burst
    r5 = run(
        "lsdb100k",
        lambda: topologies.grid(316, node_labels=False),
        "node-158-158",
        runs=3,
        flap_victims=250,  # 250 nodes x ~4 links = ~1k directed flaps
    )
    if r5 is not None:
        headline = ("full_rib_recompute_100k_ms", r5[1], r5[2])

    # 5a: sustained flap storm at the 100k headline scale — the
    # incremental path's churn-to-FIB-ack distribution and per-epoch
    # download
    if only in (None, "flapstorm100k"):
        configs["flapstorm100k"] = bench_flapstorm(
            "flapstorm100k",
            lambda: topologies.grid(316, node_labels=False),
            "node-158-158", events=200, rate_hz=100.0,
        )
        _ledger_record("flapstorm100k", configs["flapstorm100k"])

    # 5b: the SAME 100k LSDB forced through the multichip capacity tier
    # (n_cap 131072 sits exactly AT the default threshold, so halving it
    # engages the sharded path) — the single-chip vs multichip device_ms
    # side-by-side is the tier's go/no-go number at this scale
    if len(jax.devices()) > 1:
        run(
            "lsdb100k_mc",
            lambda: topologies.grid(316, node_labels=False),
            "node-158-158",
            runs=3,
            flap_victims=250,
            tpu_kw={"multichip_n_cap_threshold": 65536},
        )

    # 6: 1M-node synthetic LSDB (grid 1000x1000, ~4M directed
    # adjacencies) through the production Decision path — the multichip
    # tier engages at the default threshold. Host topology construction
    # alone holds ~5M python objects, so the lane is memory-gated: on a
    # short box it reports a skip instead of an OOM kill. The CPU-oracle
    # parity assert (~minutes of host Dijkstra) is opt-in via
    # OPENR_TPU_BENCH_1M_ORACLE=1.
    import os as _os

    mem_gb = None
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    mem_gb = int(line.split()[1]) / 1e6
                    break
    except OSError:
        pass
    if only in (None, "lsdb1m") and (mem_gb is None or mem_gb >= 12.0):
        run(
            "lsdb1m",
            lambda: topologies.grid(1000, node_labels=False),
            "node-500-500",
            runs=1,
            flap_victims=100,
            cpu_baseline=_os.environ.get(
                "OPENR_TPU_BENCH_1M_ORACLE", ""
            ) == "1",
        )
    elif only in (None, "lsdb1m"):
        configs["lsdb1m"] = {
            "skipped": f"MemAvailable {mem_gb:.1f} GB < 12 GB"
        }
        log(f"[lsdb1m] skipped: MemAvailable {mem_gb:.1f} GB < 12 GB")

    if headline is None:
        last = next(
            (n for n in reversed(configs) if "tpu_ms" in configs[n]),
            None,
        )
        if last is None:
            sys.exit("no config produced a headline timing")
        headline = (
            f"full_rib_recompute_{last}_ms",
            configs[last]["tpu_ms"],
            configs[last].get("cpu_ms"),
        )
    metric, tpu_ms, cpu_ms = headline
    _write_budget_out(configs)
    dev = configs.get("lsdb100k", {}).get("device_ms")
    print(json.dumps({
        "metric": metric,
        "value": round(tpu_ms, 2),
        "unit": "ms",
        "vs_baseline": round((cpu_ms or tpu_ms) / tpu_ms, 2),
        "rig_rtt_ms": round(rtt_ms, 1),
        "device_ms_100k": dev,
        "incr_device_ms_100k": configs.get("lsdb100k", {}).get(
            "incr_device_ms"
        ),
        # bucketed Δ-stepping headlines: single-chip device-only time at
        # 100k under each kernel, and the 1M multichip halo-exchange
        # count (one pmin per bucket EPOCH under bucketed vs one per
        # relaxation round under sync)
        "device_ms_100k_bucketed": configs.get("lsdb100k", {}).get(
            "kernel_ab", {}
        ).get("bucketed", {}).get("device_ms"),
        "device_ms_100k_sync": configs.get("lsdb100k", {}).get(
            "kernel_ab", {}
        ).get("sync", {}).get("device_ms"),
        "mc_halo_exchanges_1m": configs.get("lsdb1m", {}).get(
            "kernel_ab", {}
        ).get("bucketed", {}).get("halo_exchanges"),
        "mc_halo_exchanges_1m_sync": configs.get("lsdb1m", {}).get(
            "kernel_ab", {}
        ).get("sync", {}).get("halo_exchanges"),
        # the 100k single-chip vs multichip side-by-side: the capacity
        # tier must beat the single-chip device_ms at this scale to be
        # worth its pmin halo exchange
        "device_ms_100k_single": dev,
        "device_ms_100k_multichip": configs.get("lsdb100k_mc", {}).get(
            "device_ms"
        ),
        "multichip_engaged_100k": configs.get("lsdb100k_mc", {}).get(
            "multichip_engaged"
        ),
        "multichip_engaged_1m": configs.get("lsdb1m", {}).get(
            "multichip_engaged"
        ),
        # columnar-spine headline: cold host materialization + the
        # zero-copy program/consume lanes at 100k and 1M (program must
        # report entries_built == 0 — no per-route objects on the path)
        "cold_mat_ms_100k": configs.get("lsdb100k", {}).get(
            "full_breakdown", {}
        ).get("mat_ms"),
        "cold_program_ms_100k": configs.get("lsdb100k", {}).get(
            "cold_program_ms"
        ),
        "cold_mat_ms_1m": configs.get("lsdb1m", {}).get(
            "full_breakdown", {}
        ).get("mat_ms"),
        "cold_program_ms_1m": configs.get("lsdb1m", {}).get(
            "cold_program_ms"
        ),
        "cold_consume_ms_1m": configs.get("lsdb1m", {}).get(
            "cold_consume_ms"
        ),
        "cold_program_entries_built_1m": configs.get("lsdb1m", {}).get(
            "cold_program_entries_built"
        ),
        # The e2e value above includes one mandatory device->host result
        # round trip; that RTT (rig_rtt_ms, measured with an 8-byte
        # pull) is a fixed floor independent of problem size.
        # device_ms_100k is the chip's amortized per-solve compute
        # (chained dispatches, no per-solve pull).
        # boot lifecycle headline (runtime/lifecycle.py): cold process
        # to first programmed RIB through the full node stack — ROADMAP
        # item 1's "under 2 s" gate reads this number
        "boot_first_rib_ms": configs.get("boot", {}).get(
            "boot_first_rib_ms"
        ),
        # AOT executable cache A/B (ISSUE 20): the same boot with the
        # device solver forced on, restarted against the populated
        # serialized-executable cache — warm must sit materially below
        # cold, with >= 0.9 of lookups served from disk
        "boot_first_rib_ms_warmcache": configs.get("boot", {}).get(
            "boot_first_rib_ms_warmcache"
        ),
        "aot_hit_rate": configs.get("boot", {}).get("aot_hit_rate"),
        # churn headline (ISSUE 16): flap-apply -> FIB ack p99 under a
        # sustained 100-events/s storm at 100k
        "churn_to_fib_ack_p99_ms_100k": configs.get(
            "flapstorm100k", {}
        ).get("ack_p99_ms"),
        "rtt_note": "e2e = device_ms + host sync/mat + rig_rtt_ms (the machine's fixed host<->device round trip)",
        "configs": configs,
    }))


if __name__ == "__main__":
    main()
