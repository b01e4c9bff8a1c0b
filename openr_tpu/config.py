"""Validated process configuration.

Role of the reference's openr/config/Config.{h,cpp} over the thrift-JSON
schema openr/if/OpenrConfig.thrift (DecisionConfig:171, LinkMonitorConfig:189,
SparkConfig:231, WatchdogConfig:260, areas + regex matchers Config.h:34-110).
Config is parsed from a JSON file, validated once at startup, and read-only
thereafter; runtime mutables (drain state, metric overrides) go through the
ctrl API + PersistentStore, not config reload.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Optional

from openr_tpu import serde


class ConfigError(ValueError):
    pass


@dataclass
class AreaConfig:
    """ref OpenrConfig.thrift AreaConfig + AreaConfiguration Config.h:112."""

    area_id: str = "0"
    neighbor_regexes: list[str] = field(default_factory=lambda: [".*"])
    # default: claim every interface — a single-area node with no
    # matchers configured must still form adjacencies (Spark area
    # negotiation consults these via Config.match_neighbor_area)
    include_interface_regexes: list[str] = field(
        default_factory=lambda: [".*"]
    )
    # named policy (OpenrConfig.policies) gating what this node
    # advertises INTO the area (ref AreaConfig.import_policy_name,
    # OpenrConfig.thrift:589 — applied per destination area at key
    # advertisement, addKvStoreKeyHelper)
    import_policy_name: str = ""
    exclude_interface_regexes: list[str] = field(default_factory=list)
    redistribute_interface_regexes: list[str] = field(default_factory=list)


@dataclass
class KvstoreConfig:
    """ref OpenrConfig.thrift KvstoreConfig + KvStoreParams."""

    key_ttl_ms: int = 300_000  # default ttl for self-originated keys
    ttl_decrement_ms: int = 1
    sync_interval_s: float = 60.0
    flood_rate_msgs_per_sec: float = 0.0  # 0 = unlimited
    flood_rate_burst_size: int = 0
    self_adjacency_timeout_warn_ms: int = 10_000
    enable_flood_optimization: bool = False  # DUAL SPT flooding
    # this node originates a flood-root SPT (ref flood_root_id /
    # is_flood_root): a few well-connected nodes per area should set it
    is_flood_root: bool = False
    max_parallel_initial_syncs: int = 32
    # TLS on the peer plane (flooding + full sync) using the
    # thrift_server certificates; peers are mutually authenticated and
    # identity-pinned to their node names (ref secure thrift between
    # stores)
    enable_secure_peers: bool = False
    # peer-plane bind address. Empty = fail-closed default: the global
    # listen_addr when the peer plane is TLS-secured, loopback
    # otherwise (an any-address PLAINTEXT peer plane would let any
    # on-path host inject LSDB state). Set explicitly to override.
    listen_addr: str = ""
    # LSDB divergence beacons (observatory): advertise a TTL'd per-area
    # digest key monitor:lsdb-digest:<node> every interval and compare
    # against every peer's beacon — two stores that silently disagree
    # flip the kvstore.divergence.* gauges within one interval
    enable_lsdb_digest: bool = True
    digest_interval_s: float = 15.0
    # flood-latency probes (opt-in): originate a timestamped synthetic
    # monitor:flood-probe:<node> key every interval; every RECEIVING
    # store measures propagation delay into kvstore.flood_rtt_ms, so a
    # single probing node maps the whole fleet's flood latency
    enable_flood_probes: bool = False
    flood_probe_interval_s: float = 5.0


@dataclass
class StepDetectorConfig:
    """ref OpenrConfig.thrift:223 StepDetectorConfig."""

    fast_window_size: int = 10
    slow_window_size: int = 60
    lower_threshold_pct: int = 2
    upper_threshold_pct: int = 5
    ads_threshold: int = 500  # absolute us threshold


@dataclass
class SparkConfig:
    """ref OpenrConfig.thrift SparkConfig:231."""

    neighbor_discovery_port: int = 6666
    hello_time_s: float = 20.0
    fastinit_hello_time_ms: int = 500
    keepalive_time_s: float = 2.0
    hold_time_s: float = 10.0
    graceful_restart_time_s: float = 30.0
    handshake_time_ms: int = 500
    step_detector_conf: StepDetectorConfig = field(default_factory=StepDetectorConfig)
    min_packets_per_sec: int = 50  # per-(iface,addr) rate limit (Spark.h:511)


@dataclass
class DecisionConfig:
    """ref OpenrConfig.thrift DecisionConfig:171 + TPU-backend extension."""

    debounce_min_ms: int = 10
    debounce_max_ms: int = 250
    enable_bgp_route_programming: bool = True
    save_rib_policy: bool = False
    # openr_tpu extension: route-computation backend. "cpu" is the oracle
    # (decision/spf_solver.py); "tpu" is the batched JAX solver
    # (decision/tpu_solver.py); "auto" prefers tpu when a device is present.
    solver_backend: str = "auto"
    # "auto" only: below this node count the device launch + result pull
    # costs more than the whole CPU solve, so auto delegates small
    # graphs to the oracle. The crossover hangs on the machine's fixed
    # host<->device round trip (chip_smoke.py prints it); the value has
    # not been re-derived on the present machine (ROADMAP C6).
    auto_small_graph_nodes: int = 2816
    # openr_tpu extension: compute rfc5286 loop-free-alternate backup
    # next hops for SP_ECMP/IP prefixes (RibUnicastEntry.lfa_nexthops)
    enable_lfa: bool = False
    # persistent XLA compilation cache directory so daemon restarts skip
    # recompilation (ops/xla_cache.py). "" = default resolution
    # ($JAX_COMPILATION_CACHE_DIR wins; else this, $OPENR_TPU_XLA_CACHE,
    # then <checkout>/.jax_cache); "off" disables.
    xla_cache_dir: str = ""
    # persistent AOT executable cache (ops/xla_cache.py, ISSUE 20):
    # serialized compiled executables keyed by kernel + capacity
    # signature + jax/backend fingerprint, preloaded during the
    # `aot_load` boot phase so prewarm deserializes instead of
    # compiling. "" = opt-in via $OPENR_TPU_AOT_CACHE (unset = off);
    # "auto" = <compile-cache root>/aot; "off" disables; anything else
    # is the cache directory itself.
    aot_cache_dir: str = ""
    # newest-N on-disk retention for .aotx entries (flight-recorder
    # pattern): oldest evicted past this count.
    aot_cache_keep: int = 64
    # speculative background bake (decision/tpu_solver.py): a daemon
    # fiber compiles the NEXT capacity class up (and its mesh variant)
    # whenever a vantage dispatches, so a churn-driven tier flip finds
    # its executable already baked — on disk and in memory.
    aot_speculate: bool = False
    # numerical-health sentinels (decision/tpu_solver.py): cheap
    # on-device reductions after each exec counting unreachable rows,
    # metric-overflow saturation, and bad UCMP weights; anomalies feed
    # counters + a LogSample + a span attribute. Kill-switch, default on.
    enable_numerical_sentinels: bool = True
    # capacity classes for static-shape padding (ops/csr.py)
    max_nodes_hint: int = 0  # 0 = grow on demand
    # mid-flight TPU->CPU solver failover (decision/decision.py): a
    # device/runtime error during build_route_db recomputes the round on
    # the CPU oracle and marks the node degraded; a backoff-timed canary
    # probe re-promotes the device backend once it answers again.
    enable_solver_failover: bool = True
    solver_probe_initial_backoff_s: float = 1.0
    solver_probe_max_backoff_s: float = 30.0
    # async device dispatch (decision/decision.py): route rebuilds run
    # on a dedicated supervised dispatch fiber instead of inline in the
    # Decision event loop — the actor stays responsive to LSDB events
    # while the device round trip is in flight, and bursts of topology
    # events coalesce into one solve. Default off; flip off to take the
    # dispatch fiber out of the picture when bisecting a regression
    # (docs/Operations.md).
    async_dispatch: bool = False
    # async only: after the first queued solve request, wait this long
    # and fold any further requests that arrive into the same solve
    # (0 = no extra wait; superseded requests still coalesce whenever
    # the fiber is busy solving).
    dispatch_coalesce_ms: int = 0
    # areas at or below this node capacity batch into the fused vmapped
    # dispatch (decision/tpu_solver.py); the what-if sweep batcher
    # (decision/whatif.py) sizes its scenario chunks off the same value.
    # Larger = fewer dispatches but bigger resident planes per launch.
    fuse_n_cap: int = 4096
    # incremental device SSSP (decision/tpu_solver.py +
    # ops/incremental.py): seed each single-area dispatch from the
    # previous resident distance plane and re-relax only the affected
    # cone of the drained dirty edges. Bit-identical to the full solve;
    # falls back automatically on first solve, topology-shape or
    # root-link churn, journal gaps, zero-weight edges, or when the
    # affected cone exceeds incremental_cone_frac of the fabric.
    incremental_spf: bool = True
    # full-solve fallback threshold: affected cone (in node-lanes, as a
    # fraction of d_cap * n_nodes) above which a warm re-relax stops
    # paying for its parent-plane overhead. Decided on device inside
    # the same dispatch. 0.0 forces every incremental dispatch to
    # degrade to the (bit-identical) cold seed — a bisection lever.
    incremental_cone_frac: float = 0.25
    # multichip capacity tier (decision/tpu_solver.py +
    # parallel/sharding.py): an area whose padded node capacity exceeds
    # this threshold — and with >1 visible device — solves through
    # NamedSharding-resident arrays over the ('batch','graph') mesh
    # instead of the single-chip pipeline, lifting the hard single-HBM
    # n_cap ceiling. Default is exactly one chip's ceiling so the tier
    # engages only when a single chip cannot hold the fabric; lower it
    # to force multichip earlier, 0 disables the tier entirely.
    multichip_n_cap_threshold: int = 131072
    # multichip mesh factorization: size of the 'batch' axis (vantage
    # rows); the 'graph' axis (weight columns) takes the rest of the
    # visible devices. 0 = auto (parallel/sharding.make_mesh — wide
    # batch, graph=2 from 4 devices up).
    multichip_batch: int = 0
    # SSSP relaxation kernel (ops/relax.py): "bucketed" settles light
    # edges with a Δ-stepping ladder per bucket epoch (one halo
    # exchange per EPOCH in the multichip tier) and falls back to
    # "sync" automatically on plans with no usable Δ; "sync" forces the
    # classic synchronous rounds everywhere — the first bisection step
    # when a device-solve result is under suspicion. Both kernels reach
    # the identical int32 fixpoint.
    spf_kernel: str = "bucketed"
    # opt-in jax.transfer_guard around the solver's exec hot path
    # (decision/tpu_solver.py): "log" logs implicit host<->device
    # transfers through jax; "disallow" turns each into a counted,
    # attributed finding (decision.solver.transfer_guard.findings +
    # a last_sentinels entry) and retries the dispatch unguarded so
    # routing still converges. "off" (default) stays out of the way —
    # the guard is a triage lever, not a production setting
    # (docs/Operations.md).
    transfer_guard: str = "off"
    # input black-box recorder (runtime/replay_log.py): always-on
    # bounded ring of every publication delta Decision consumes +
    # periodic LSDB snapshot anchors + the per-epoch RIB digest ledger,
    # exported as the flight-recorder `inputs` annex so any incident
    # bundle replays offline through tools/replay.py
    # (docs/Observability.md § Record & replay). replay_ring bounds the
    # event ring in EVENTS (a steady-state churn event is a few hundred
    # bytes: one serialized adj/prefix db + key strings);
    # replay_snapshot_every_epochs re-anchors the snapshot so the ring
    # only ever needs to span that many solve epochs' events — size the
    # pair so ring >= snapshot_every * typical events-per-epoch or the
    # recorder counts replay.ring_gaps and re-anchors early.
    replay_recorder: bool = True
    replay_ring: int = 8192
    replay_snapshot_every_epochs: int = 1024
    # --- overload control (runtime/overload.py) ---
    # process-wide overload state ladder ok -> backpressure -> brownout
    # -> shedding driving adaptive admission control on the dispatch
    # fiber, per-key flap damping at ingest, and the resource-pressure
    # brownout rungs (docs/Operations.md § Overload control). The
    # kill-switch disables the whole layer: no damping, no admission
    # gating, no ladder — the first bisection step for a suppression
    # regression.
    overload_control: bool = True
    # pending-solve queue depth at which the ladder reaches brownout;
    # 2x this is shedding (new requests fold into the held overflow
    # batch instead of growing the queue), half is backpressure.
    overload_queue_watermark: int = 8
    # ceiling for the adaptively widened dispatch coalescing window
    overload_coalesce_max_ms: int = 250
    # HBM pressure watermarks (fraction of bytes_limit, highest device):
    # at/above high enters brownout; must fall below clear to release.
    overload_hbm_high_frac: float = 0.9
    overload_hbm_clear_frac: float = 0.75
    # host-RSS watermarks in MB (0 = RSS does not drive the ladder)
    overload_rss_high_mb: float = 0.0
    overload_rss_clear_mb: float = 0.0
    # minimum time at a level before a downshift rung can release
    overload_dwell_s: float = 5.0
    # flap damping (RFC 2439 transplanted onto LSDB keys): each ingest
    # change adds `penalty` to the key's figure of merit, which decays
    # with `half_life_s`; a key crossing `suppress` stops perturbing
    # the LSDB (latest value held, re-ingested on release) until decay
    # brings it under `reuse`. damping=False disables only the damper,
    # leaving the ladder up (the runbook's bisection order). The
    # defaults target sustained storms only: with penalty 1 and a 10 s
    # half-life a key must sustain well over 2 changes/s to reach the
    # suppress threshold — ordinary reconvergence churn (a handful of
    # updates to one key in seconds) never trips it.
    overload_damping: bool = True
    overload_damping_half_life_s: float = 10.0
    overload_damping_penalty: float = 1.0
    overload_damping_suppress: float = 25.0
    overload_damping_reuse: float = 1.0
    overload_damping_max_penalty: float = 50.0
    # damper/ladder maintenance tick (decay sweep + release re-ingest)
    overload_tick_s: float = 1.0


@dataclass
class LinkMonitorConfig:
    """ref OpenrConfig.thrift LinkMonitorConfig:189."""

    linkflap_initial_backoff_ms: int = 60_000
    linkflap_max_backoff_ms: int = 300_000
    use_rtt_metric: bool = True
    # kernel interface discovery over rtnetlink events
    # (platform/iface_monitor.py) instead of static --interface flags;
    # selection via the reference's regex config
    # (ref LinkMonitorConfig include_interface_regexes:196)
    enable_netlink_interfaces: bool = False
    include_interface_regexes: list[str] = field(default_factory=list)
    exclude_interface_regexes: list[str] = field(default_factory=list)
    # interfaces whose addresses redistribute as LOOPBACK prefixes;
    # empty = all tracked interfaces (emulation-friendly default)
    redistribute_interface_regexes: list[str] = field(default_factory=list)


@dataclass
class FibConfig:
    fib_port: int = 60100
    enable_fib_ack: bool = True
    route_delete_delay_ms: int = 1000


@dataclass
class PlatformConfig:
    """Knobs for the platform agent's kernel-facing dataplane."""

    # batches at least this large go through the C++ bulk programmer
    # (native/netlink_bulk.cpp); smaller ones stay on the asyncio
    # netlink client, which interleaves with other platform work
    bulk_threshold: int = 64


@dataclass
class WatchdogConfig:
    """ref OpenrConfig.thrift WatchdogConfig:260."""

    interval_s: float = 20.0
    thread_timeout_s: float = 300.0
    max_memory_mb: int = 800
    # in-process fiber supervision (runtime/actor.py): crashed supervised
    # fibers restart with exponential backoff until the PER-ACTOR crash
    # budget is exhausted, then escalate to the watchdog crash handler
    # (role of systemd Restart=on-failure + StartLimitBurst for the
    # reference daemon). Applied to actors via Watchdog.watch_actor.
    supervisor_crash_budget: int = 3
    supervisor_backoff_initial_s: float = 0.05
    supervisor_backoff_max_s: float = 2.0


@dataclass
class MonitorConfig:
    max_event_log_entries: int = 100
    enable_event_log_submission: bool = True
    # convergence tracing (runtime/tracing.py): span per pipeline stage
    # kvstore -> decision -> fib -> platform; off = no spans recorded
    # and queue pushes carry no context (one comparison on the hot path)
    enable_tracing: bool = True
    # device-plane gauges (runtime/device_stats.py): per-device HBM
    # in-use/peak/allocs + live-array census, polled every metrics
    # interval. No-op where jax was never imported or the backend keeps
    # no memory accounting (CPU).
    enable_device_telemetry: bool = True
    # advertise this node's health card into KvStore as a TTL'd
    # monitor:health:<node> key so `breeze monitor fleet` reads every
    # node from any node
    enable_fleet_health: bool = True
    # OpenMetrics exposition (runtime/metrics_export.py): serve
    # GET /metrics from the Monitor's event base. None = disabled;
    # 0 = bind an ephemeral port (tests read it back from the exporter)
    metrics_port: Optional[int] = None
    metrics_listen_addr: str = "127.0.0.1"
    # --- SLO engine (docs/Observability.md § SLO engine) ---
    # declarative SLO table: name -> spec dict. Spec keys: kind
    # ("stat" | "counter_delta" | "gauge_duration" | "baseline_drift"),
    # source (counter / stat name), threshold, and optional per-SLO
    # fast_window_s / slow_window_s / burn_threshold overrides.
    # baseline_drift compares the live window quantile of `source`
    # against a perf-ledger baseline (threshold = max allowed ratio;
    # extra keys: baseline_kernel / baseline_metric / baseline_signature
    # / baseline_variant / quantile / min_count / warmup_s); it needs
    # perf_ledger_dir set, and never breaches without a stored
    # baseline. Each SLO runs a
    # multi-window burn-rate state machine in the Monitor metrics loop:
    # ok -> fast_burn when the fast window's breach fraction crosses
    # burn_threshold, -> sustained_burn when the slow window agrees,
    # back to ok with 2x hysteresis. Empty dict disables evaluation.
    slos: dict = field(
        default_factory=lambda: {
            "fleet_convergence_p99_ms": {
                "kind": "stat",
                "source": "fleet_convergence_ms",
                "threshold": 2000.0,
            },
            "convergence_p99_ms": {
                "kind": "stat",
                "source": "convergence_ms",
                "threshold": 1000.0,
            },
            "divergence_events": {
                "kind": "counter_delta",
                "source": "kvstore.divergence.events",
                "threshold": 0.0,
            },
            "solver_degraded_s": {
                "kind": "gauge_duration",
                "source": "decision.solver.degraded",
                "threshold": 5.0,
            },
            # sustained brownout: the overload ladder (runtime/
            # overload.py) is SUPPOSED to visit brownout under a storm
            # and come back — staying there past the threshold means
            # the downshift rungs are not releasing (docs/Operations.md
            # § Overload control)
            "overload_brownout_s": {
                "kind": "gauge_duration",
                "source": "overload.brownout",
                "threshold": 30.0,
            },
            # conservation drift of the latency-budget ledger: a growing
            # unattributed residual means the component taxonomy rotted
            # (a stage nobody stamps appeared) — page BEFORE the
            # per-component numbers mislead (docs/Observability.md
            # § Latency budget)
            "budget_unattributed_p99_ms": {
                "kind": "stat",
                "source": "budget.unattributed_ms",
                "threshold": 5.0,
            },
        }
    )
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 600.0
    # fraction of window samples in breach before the window burns
    slo_burn_threshold: float = 0.5
    # --- flight recorder (docs/Observability.md § Flight recorder) ---
    # always-on bounded ring of counter snapshots + anomaly events; on
    # trigger (SLO burn, sentinel anomaly, supervisor restart,
    # divergence, failover, or `breeze monitor dump`) the ring freezes
    # into a self-contained post-mortem bundle (JSON + Chrome trace)
    enable_flight_recorder: bool = True
    flight_recorder_dir: str = ""  # "" = <tempdir>/openr_tpu_flightrec
    flight_recorder_ring: int = 32
    # auto-trigger rate limit: a flapping trigger must not fill the disk
    flight_recorder_min_interval_s: float = 30.0
    # on-disk retention: after each bundle write, prune this node's
    # bundle directories down to the newest N (the in-memory deque was
    # always capped at 8; the DISK was unbounded before this). 0 keeps
    # everything — prunes count in monitor.flight_recorder.pruned and
    # `breeze monitor bundles` lists what's on disk.
    flight_recorder_keep: int = 16
    # --- perf-baseline ledger (docs/Observability.md § Perf baselines) ---
    # directory for the persistent perf ledger (runtime/perf_ledger.py):
    # rolling per-kernel timing baselines the `baseline_drift` SLO kind
    # compares live windows against. "" = disabled: no disk writes, no
    # baselines, drift SLOs never breach.
    perf_ledger_dir: str = ""
    # how often the live Monitor appends a solve observation to the
    # ledger (kernel "solve", signature/variant "live")
    perf_ledger_record_interval_s: float = 60.0


@dataclass
class RuntimeConfig:
    """Cross-cutting runtime/debug knobs (no reference analogue — the
    reference gets these invariants from its threading model)."""

    # thread-ownership sentinel (runtime/affinity.py): actors and the
    # device solver record their owning thread and raise
    # AffinityViolation on cross-thread access to guarded state. A
    # debug/CI knob — default off (the disabled cost is one bool read
    # per guarded site); CI test+chaos lanes enable it via the
    # OPENR_TPU_AFFINITY_CHECKS env var, which seeds the same switch.
    affinity_checks: bool = False


@dataclass
class FaultInjectionConfig:
    """Deterministic fault injection (runtime/faults.py). Schedules armed
    here apply from daemon startup; ctrl.fault.{inject,clear,list} and
    `breeze fault ...` arm/disarm at runtime. Each schedule dict takes
    the registry.arm() keywords: site (required), probability, every_nth,
    one_shot, window_s, max_fires, seed, delay_ms (latency fault: sleep
    instead of raise)."""

    enable_fault_injection: bool = False
    seed: int = 0
    schedules: list[dict] = field(default_factory=list)


@dataclass
class PrefixAllocationConfig:
    """ref OpenrConfig.thrift PrefixAllocationConfig."""

    loopback_interface: str = "lo"
    prefix_allocation_mode: str = "DYNAMIC_LEAF_NODE"  # or DYNAMIC_ROOT_NODE, STATIC
    seed_prefix: str = ""
    allocate_prefix_len: int = 128
    set_loopback_address: bool = False


@dataclass
class SegmentRoutingConfig:
    enable_segment_routing: bool = False
    sr_adj_label_type: str = "AUTO"  # AUTO | DISABLED
    sr_adj_label_range: tuple[int, int] = (50000, 59999)
    sr_node_label_range: tuple[int, int] = (101, 1100)
    # this node's static segment-routing node label, advertised in the
    # adjacency DB; 0 = none (KSP2/SR_MPLS label stacks require one)
    node_segment_label: int = 0


@dataclass
class ThriftServerConfig:
    """ref OpenrConfig.thrift thrift_server + the secure-server option
    (OpenrThriftCtrlServer SSL with acceptable peers)."""

    openr_ctrl_port: int = 2018
    listen_addr: str = "::1"
    enable_secure_thrift_server: bool = False
    x509_cert_path: str = ""
    x509_key_path: str = ""
    # CA bundle: the server VERIFIES CLIENT certs against it (mutual
    # TLS, the reference's acceptable-peers role) and clients verify the
    # server against it
    x509_ca_path: str = ""
    # comma-separated CNs the server accepts from client certs (ref's
    # acceptable-peers list); empty = any cert signed by the CA. CA
    # membership alone lets any node impersonate any other, so deployments
    # with per-role certs should set this.
    acceptable_peers: str = ""


def cert_peer_names(cert) -> set:
    """Names a peer certificate claims: subject CNs + SAN DNS entries.

    Host certs in an openr deployment identify the *node* (CN=node-name),
    not a DNS host, so identity checks compare against this set rather
    than using ssl's hostname matching."""
    names = set()
    if not cert:
        return names
    for rdn in cert.get("subject", ()):  # ((('commonName','x'),),...)
        for key, val in rdn:
            if key == "commonName":
                names.add(val)
    for typ, val in cert.get("subjectAltName", ()):
        if typ in ("DNS", "IP Address"):
            names.add(val)
    return names


def make_peer_verifier(acceptable_peers: str):
    """Server-side identity check for mutual TLS (role of the reference's
    acceptable-peers list on its secure thrift server): returns a callable
    fed the client's cert dict post-handshake, or None when no constraint
    is configured (any CA-signed cert accepted)."""
    allowed = {p.strip() for p in acceptable_peers.split(",") if p.strip()}
    if not allowed:
        return None

    def verify(cert) -> bool:
        return bool(cert_peer_names(cert) & allowed)

    return verify


def build_server_ssl_context(ts: ThriftServerConfig):
    """TLS context for the ctrl RPC server; requires cert+key, and
    enforces client certificates when a CA bundle is configured."""
    import ssl as _ssl

    if not (ts.x509_cert_path and ts.x509_key_path):
        raise ConfigError(
            "enable_secure_thrift_server requires x509_cert_path and "
            "x509_key_path"
        )
    if ts.acceptable_peers and not ts.x509_ca_path:
        # without a CA the server never requests client certs, so the
        # verifier would see no cert and reject every connection —
        # surface the misconfiguration at startup, not as a bricked
        # ctrl plane
        raise ConfigError(
            "acceptable_peers requires x509_ca_path (client certs are "
            "only requested when a CA bundle is configured)"
        )
    ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(ts.x509_cert_path, ts.x509_key_path)
    if ts.x509_ca_path:
        ctx.load_verify_locations(ts.x509_ca_path)
        ctx.verify_mode = _ssl.CERT_REQUIRED
    return ctx


def build_client_ssl_context(
    ca_path: str = "", cert_path: str = "", key_path: str = ""
):
    """TLS context for ctrl RPC clients (breeze, agents).

    A client certificate REQUIRES a CA bundle: authenticating ourselves
    to a server we refuse to verify hands the credential to any
    man-in-the-middle. cert without key treats the cert file as a
    combined PEM; key without cert is a mistake."""
    import ssl as _ssl

    if key_path and not cert_path:
        raise ConfigError("client TLS key given without a certificate")
    if cert_path and not ca_path:
        raise ConfigError(
            "client certificate requires a CA bundle to verify the "
            "server (mutual TLS against an unverified peer leaks the "
            "credential)"
        )
    ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_CLIENT)
    if ca_path:
        ctx.load_verify_locations(ca_path)
        # host certs are identified by node name, not DNS
        ctx.check_hostname = False
    else:
        ctx.check_hostname = False
        ctx.verify_mode = _ssl.CERT_NONE
    if cert_path:
        ctx.load_cert_chain(cert_path, key_path or None)
    return ctx


@dataclass
class OpenrConfig:
    """Top-level config (ref OpenrConfig.thrift:265-955)."""

    node_name: str = ""
    domain: str = "openr"
    areas: list[AreaConfig] = field(default_factory=lambda: [AreaConfig()])
    listen_addr: str = "::"
    openr_ctrl_port: int = 2018
    dryrun: bool = False
    enable_v4: bool = True
    enable_netlink_fib_handler: bool = False
    prefix_forwarding_type: int = 0
    prefix_forwarding_algorithm: int = 0
    enable_ordered_adj_publication: bool = False

    kvstore_config: KvstoreConfig = field(default_factory=KvstoreConfig)
    spark_config: SparkConfig = field(default_factory=SparkConfig)
    decision_config: DecisionConfig = field(default_factory=DecisionConfig)
    link_monitor_config: LinkMonitorConfig = field(default_factory=LinkMonitorConfig)
    fib_config: FibConfig = field(default_factory=FibConfig)
    platform_config: PlatformConfig = field(default_factory=PlatformConfig)
    watchdog_config: WatchdogConfig = field(default_factory=WatchdogConfig)
    monitor_config: MonitorConfig = field(default_factory=MonitorConfig)
    runtime_config: RuntimeConfig = field(default_factory=RuntimeConfig)
    fault_injection_config: FaultInjectionConfig = field(
        default_factory=FaultInjectionConfig
    )
    prefix_allocation_config: Optional[PrefixAllocationConfig] = None
    segment_routing_config: SegmentRoutingConfig = field(
        default_factory=SegmentRoutingConfig
    )
    thrift_server: ThriftServerConfig = field(default_factory=ThriftServerConfig)

    enable_watchdog: bool = True
    enable_prefix_allocation: bool = False
    persistent_store_path: str = ""
    originated_prefixes: list[dict] = field(default_factory=list)
    # origination policy (ref PolicyManager + config-sourced policies):
    # named policy definitions, and the one PrefixManager applies to
    # every prefix it advertises ("" = no policy)
    policies: dict = field(default_factory=dict)
    origination_policy: str = ""
    # plugin factories "pkg.module:factory" started after link-monitor
    # (ref Plugin.h extension points; openr_tpu/plugins)
    plugins: list[str] = field(default_factory=list)

    assume_drained: bool = False
    undrained_flag_path: str = ""


class AreaMatcher:
    """Compiled per-area regex sets for neighbor/interface matching
    (ref Config.h:34-110 compileRegexSet)."""

    def __init__(self, cfg: AreaConfig):
        self.area_id = cfg.area_id
        try:
            self._neighbor = [re.compile(p) for p in cfg.neighbor_regexes]
            self._include_if = [re.compile(p) for p in cfg.include_interface_regexes]
            self._exclude_if = [re.compile(p) for p in cfg.exclude_interface_regexes]
            self._redist_if = [re.compile(p) for p in cfg.redistribute_interface_regexes]
        except re.error as e:
            raise ConfigError(f"area {cfg.area_id}: bad regex: {e}") from e

    @staticmethod
    def _match(patterns: list[re.Pattern], s: str) -> bool:
        return any(p.fullmatch(s) for p in patterns)

    def should_discover_on_iface(self, if_name: str) -> bool:
        if self._match(self._exclude_if, if_name):
            return False
        return self._match(self._include_if, if_name)

    def should_peer_with_neighbor(self, node_name: str) -> bool:
        return self._match(self._neighbor, node_name)

    def should_redistribute_iface(self, if_name: str) -> bool:
        return self._match(self._redist_if, if_name)


class Config:
    """Validated wrapper (ref Config.h:34). Raises ConfigError on invalid."""

    def __init__(self, cfg: OpenrConfig):
        self.raw = cfg
        self._validate()
        self.areas: dict[str, AreaMatcher] = {
            a.area_id: AreaMatcher(a) for a in cfg.areas
        }

    # accessors mirroring the reference's isXEnabled() family ------------

    @property
    def node_name(self) -> str:
        return self.raw.node_name

    @property
    def domain(self) -> str:
        return self.raw.domain

    def area_ids(self) -> list[str]:
        return [a.area_id for a in self.raw.areas]

    def get_area_matcher(self, area_id: str) -> AreaMatcher:
        return self.areas[area_id]

    def match_neighbor_area(self, neighbor_node: str, if_name: str) -> Optional[str]:
        """First area whose matchers accept (iface, neighbor); None if no
        area claims it (ref Spark area negotiation)."""
        for area_id, m in self.areas.items():
            if m.should_discover_on_iface(if_name) and m.should_peer_with_neighbor(
                neighbor_node
            ):
                return area_id
        return None

    def is_segment_routing_enabled(self) -> bool:
        return self.raw.segment_routing_config.enable_segment_routing

    def is_ordered_adj_publication_enabled(self) -> bool:
        return self.raw.enable_ordered_adj_publication

    # validation ---------------------------------------------------------

    @staticmethod
    def _validate_key_component(value: str, what: str) -> None:
        # node/area ids embed into kvstore keys "prefix:<node>:[<area>]:<pfx>"
        # (types.py prefix_key); forbid the delimiter characters so key
        # encode/parse stay inverses
        if not value or any(c in value for c in " :[]"):
            raise ConfigError(
                f"{what} {value!r} must be non-empty and must not contain "
                "' ', ':', '[', ']'"
            )

    def _validate(self) -> None:
        cfg = self.raw
        if not cfg.node_name:
            raise ConfigError("node_name is required")
        self._validate_key_component(cfg.node_name, "node_name")
        if not cfg.areas:
            raise ConfigError("at least one area is required")
        ids = [a.area_id for a in cfg.areas]
        if len(ids) != len(set(ids)):
            raise ConfigError("duplicate area ids")
        for area_id in ids:
            self._validate_key_component(area_id, "area id")
        sc = cfg.spark_config
        if sc.hold_time_s < sc.keepalive_time_s:
            raise ConfigError("spark hold_time must be >= keepalive_time")
        if sc.keepalive_time_s <= 0 or sc.hello_time_s <= 0:
            raise ConfigError("spark timers must be positive")
        dc = cfg.decision_config
        if not (0 < dc.debounce_min_ms <= dc.debounce_max_ms):
            raise ConfigError(
                "decision debounce windows must satisfy 0 < min <= max"
            )
        if dc.solver_backend not in ("cpu", "tpu", "auto"):
            raise ConfigError(f"unknown solver_backend {dc.solver_backend!r}")
        if not (
            0 < dc.solver_probe_initial_backoff_s
            <= dc.solver_probe_max_backoff_s
        ):
            raise ConfigError(
                "decision solver probe backoff must satisfy 0 < initial <= max"
            )
        if dc.dispatch_coalesce_ms < 0:
            raise ConfigError("decision dispatch_coalesce_ms must be >= 0")
        if dc.fuse_n_cap < 1:
            raise ConfigError("decision fuse_n_cap must be >= 1")
        if not (0.0 <= dc.incremental_cone_frac <= 1.0):
            raise ConfigError(
                "decision incremental_cone_frac must be in [0, 1]"
            )
        if dc.multichip_n_cap_threshold < 0:
            raise ConfigError(
                "decision multichip_n_cap_threshold must be >= 0"
            )
        if dc.multichip_batch < 0:
            raise ConfigError("decision multichip_batch must be >= 0")
        if dc.spf_kernel not in ("sync", "bucketed"):
            raise ConfigError(f"unknown spf_kernel {dc.spf_kernel!r}")
        if dc.transfer_guard not in ("off", "log", "disallow"):
            raise ConfigError(
                f"unknown transfer_guard {dc.transfer_guard!r}"
            )
        if not isinstance(dc.replay_recorder, bool):
            raise ConfigError(
                f"decision replay_recorder must be a bool, got "
                f"{dc.replay_recorder!r}"
            )
        if dc.replay_ring < 1:
            raise ConfigError("decision replay_ring must be >= 1")
        if dc.replay_snapshot_every_epochs < 1:
            raise ConfigError(
                "decision replay_snapshot_every_epochs must be >= 1"
            )
        if dc.overload_queue_watermark < 1:
            raise ConfigError(
                "decision overload_queue_watermark must be >= 1"
            )
        if dc.overload_coalesce_max_ms < 1:
            raise ConfigError(
                "decision overload_coalesce_max_ms must be >= 1"
            )
        if not (
            0.0 < dc.overload_hbm_clear_frac
            <= dc.overload_hbm_high_frac <= 1.0
        ):
            raise ConfigError(
                "decision overload HBM watermarks must satisfy "
                "0 < clear <= high <= 1"
            )
        if dc.overload_rss_high_mb < 0 or dc.overload_rss_clear_mb < 0:
            raise ConfigError(
                "decision overload RSS watermarks must be >= 0"
            )
        if (
            dc.overload_rss_high_mb > 0
            and dc.overload_rss_clear_mb > dc.overload_rss_high_mb
        ):
            raise ConfigError(
                "decision overload_rss_clear_mb must not exceed "
                "overload_rss_high_mb"
            )
        if dc.overload_dwell_s < 0 or dc.overload_tick_s <= 0:
            raise ConfigError(
                "decision overload_dwell_s must be >= 0 and "
                "overload_tick_s positive"
            )
        if not (
            0.0
            < dc.overload_damping_reuse
            < dc.overload_damping_suppress
            <= dc.overload_damping_max_penalty
        ):
            raise ConfigError(
                "decision overload damping thresholds must satisfy "
                "0 < reuse < suppress <= max_penalty"
            )
        if (
            dc.overload_damping_half_life_s <= 0
            or dc.overload_damping_penalty <= 0
        ):
            raise ConfigError(
                "decision overload damping half-life and penalty must "
                "be positive"
            )
        pc = cfg.platform_config
        if pc.bulk_threshold < 1:
            raise ConfigError("platform bulk_threshold must be >= 1")
        wc = cfg.watchdog_config
        if wc.supervisor_crash_budget < 0:
            raise ConfigError("supervisor_crash_budget must be >= 0")
        if not (
            0 < wc.supervisor_backoff_initial_s <= wc.supervisor_backoff_max_s
        ):
            raise ConfigError(
                "supervisor backoff must satisfy 0 < initial <= max"
            )
        fi = cfg.fault_injection_config
        for i, sched in enumerate(fi.schedules):
            if not isinstance(sched, dict) or not sched.get("site"):
                raise ConfigError(
                    f"fault_injection_config.schedules[{i}] needs a 'site'"
                )
            p = float(sched.get("probability", 0.0))
            if not 0.0 <= p <= 1.0:
                raise ConfigError(
                    f"fault_injection_config.schedules[{i}]: probability "
                    f"{p} not in [0, 1]"
                )
        kc = cfg.kvstore_config
        if kc.key_ttl_ms <= 0 and kc.key_ttl_ms != -1:
            raise ConfigError("kvstore key_ttl_ms must be positive or -1 (infinite)")
        if kc.enable_lsdb_digest and kc.digest_interval_s <= 0:
            raise ConfigError("kvstore digest_interval_s must be positive")
        if kc.enable_flood_probes and kc.flood_probe_interval_s <= 0:
            raise ConfigError("kvstore flood_probe_interval_s must be positive")
        mc = cfg.monitor_config
        if mc.metrics_port is not None and not (0 <= mc.metrics_port <= 65535):
            raise ConfigError(
                f"monitor metrics_port {mc.metrics_port} not in [0, 65535]"
            )
        if not 0.0 < mc.slo_burn_threshold <= 1.0:
            raise ConfigError(
                f"monitor slo_burn_threshold {mc.slo_burn_threshold} "
                "not in (0, 1]"
            )
        if mc.slo_fast_window_s <= 0 or mc.slo_slow_window_s <= 0:
            raise ConfigError("monitor SLO windows must be positive")
        if mc.slo_fast_window_s > mc.slo_slow_window_s:
            raise ConfigError(
                "monitor slo_fast_window_s must not exceed slo_slow_window_s"
            )
        _SLO_KINDS = {"stat", "counter_delta", "gauge_duration", "baseline_drift"}
        for name, spec in (mc.slos or {}).items():
            if not isinstance(spec, dict):
                raise ConfigError(f"monitor slos[{name!r}] must be a dict")
            kind = spec.get("kind")
            if kind not in _SLO_KINDS:
                raise ConfigError(
                    f"monitor slos[{name!r}].kind {kind!r} not one of "
                    f"{sorted(_SLO_KINDS)}"
                )
            if not spec.get("source"):
                raise ConfigError(f"monitor slos[{name!r}] needs a 'source'")
            if "threshold" not in spec:
                raise ConfigError(f"monitor slos[{name!r}] needs a 'threshold'")
        if mc.flight_recorder_ring < 1:
            raise ConfigError("monitor flight_recorder_ring must be >= 1")
        if mc.flight_recorder_keep < 0:
            raise ConfigError(
                "monitor flight_recorder_keep must be >= 0 (0 = keep all)"
            )
        if mc.perf_ledger_record_interval_s <= 0:
            raise ConfigError(
                "monitor perf_ledger_record_interval_s must be positive"
            )
        sr = cfg.segment_routing_config
        if sr.enable_segment_routing:
            lo, hi = sr.sr_node_label_range
            if lo >= hi:
                raise ConfigError("bad node label range")
        ts = cfg.thrift_server
        if ts.enable_secure_thrift_server:
            # fail at LOAD time, not after half the actors started
            if not (ts.x509_cert_path and ts.x509_key_path):
                raise ConfigError(
                    "enable_secure_thrift_server requires x509_cert_path "
                    "and x509_key_path"
                )
            import os as _os

            for what, path in (
                ("x509_cert_path", ts.x509_cert_path),
                ("x509_key_path", ts.x509_key_path),
                ("x509_ca_path", ts.x509_ca_path),
            ):
                if path and not _os.path.isfile(path):
                    raise ConfigError(f"{what} {path!r} is not readable")
        if cfg.origination_policy and cfg.origination_policy not in cfg.policies:
            raise ConfigError(
                f"origination_policy {cfg.origination_policy!r} is not in "
                "policies"
            )
        for a in cfg.areas:
            if a.import_policy_name and a.import_policy_name not in cfg.policies:
                raise ConfigError(
                    f"area {a.area_id}: import_policy_name "
                    f"{a.import_policy_name!r} is not in policies"
                )
        self._validate_policies(cfg)

    @staticmethod
    def _validate_policies(cfg: OpenrConfig) -> None:
        """Strict policy validation at load time: the wire codec is
        forward-compatible (unknown keys are dropped), which for POLICY
        would turn a typo'd 'accept' into silent accept-all — so here
        every key is checked against the schema and cover prefixes are
        parsed, surfacing errors in dryrunConfig and at startup instead
        of at first advertisement."""
        if not cfg.policies:
            return
        import dataclasses

        from openr_tpu.policy import (
            Policy,
            PolicyAction,
            PolicyMatch,
            PolicyStatement,
        )

        def check_keys(value: dict, tp, where: str) -> None:
            known = {f.name for f in dataclasses.fields(tp)}
            for key in value:
                if key not in known:
                    raise ConfigError(
                        f"unknown key {key!r} in {where} "
                        f"(expected one of {sorted(known)})"
                    )

        for name, pol in cfg.policies.items():
            if not isinstance(pol, dict):
                continue  # already a Policy object
            check_keys(pol, Policy, f"policies[{name!r}]")
            for i, stmt in enumerate(pol.get("statements", ())):
                where = f"policies[{name!r}].statements[{i}]"
                check_keys(stmt, PolicyStatement, where)
                check_keys(stmt.get("match", {}), PolicyMatch, f"{where}.match")
                check_keys(
                    stmt.get("action", {}), PolicyAction, f"{where}.action"
                )
                try:
                    PolicyMatch(
                        prefixes=tuple(stmt.get("match", {}).get("prefixes", ()))
                    )
                except ValueError as e:
                    raise ConfigError(f"{where}.match.prefixes: {e}") from e

    # loading ------------------------------------------------------------

    @classmethod
    def from_file(cls, path: str) -> "Config":
        with open(path) as fh:
            return cls.from_json(fh.read())

    @classmethod
    def from_json(cls, text: str) -> "Config":
        try:
            plain = json.loads(text)
        except json.JSONDecodeError as e:
            raise ConfigError(f"invalid JSON: {e}") from e
        return cls(serde.from_plain(plain, OpenrConfig))

    def dump_json(self) -> str:
        return serde.dumps_json(self.raw, indent=2)
