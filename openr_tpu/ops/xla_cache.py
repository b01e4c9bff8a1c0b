"""Persistent XLA compilation cache.

The reference daemon cold-starts in milliseconds; our first solve at
100k nodes pays ~80 s of XLA compilation. The jit programs are pure
functions of capacity-class shapes, so their compiled executables are
reusable across process restarts: this module turns on jax's persistent
compilation cache so a restarting daemon (or a second bench run) loads
them from disk instead of recompiling.

Resolution order for the cache directory:
  1. $JAX_COMPILATION_CACHE_DIR — jax reads it itself; where it is set
     this module sets no directory in code, so whoever runs the program
     decides where compiled code is kept
  2. explicit `cache_dir` argument (daemon --xla-cache-dir / config)
  3. $OPENR_TPU_XLA_CACHE
  4. <checkout>/.jax_cache — a FIXED path (the path is part of jax's
     cache key, so a directory that moves never hits)
"0"/"off" in 2 or 3 disables. The AOT tier's "auto" home and the AOT
bench directory sit under the same root (`cache_root()`).

Safe to call any number of times; only the first call wins (jax reads
the setting at first compile).

Two cache tiers live here (ISSUE 20). jax's persistent compilation
cache above skips the XLA *backend* compile but still pays tracing,
lowering and executable re-construction per kernel — tens of seconds
across the solver's kernel set at the 100k class. The AOT executable
cache below (`AotExecutableCache` / the `aot` singleton) removes the
whole pass: `instrument_jit` serializes each freshly compiled
executable (jax.experimental.serialize_executable) to its own
fingerprinted file, and a warm restart deserializes-and-installs it —
zero compiles, zero traces — during the `aot_load` boot phase. A
`SpeculativeBaker` background fiber additionally compiles the NEXT
capacity class before churn forces a tier flip.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import sys
import threading
import time
from collections import OrderedDict, deque

log = logging.getLogger(__name__)

_DISABLE = ("0", "off", "none", "disabled")
_applied: str | None = None
_monitoring_hooked = False

# jax._src.monitoring event names -> our counter fabric keys. The cache
# hit/miss split is what tells an operator whether a slow cold start
# was a cache wipe or genuinely new shapes.
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "xla_cache.hits",
    "/jax/compilation_cache/cache_misses": "xla_cache.misses",
    "/jax/compilation_cache/compile_requests_use_cache": (
        "xla_cache.requests"
    ),
    "/jax/compilation_cache/tasks_using_cache": "xla_cache.tasks",
    "/jax/compilation_cache/task_disabled_cache": "xla_cache.disabled",
}


def _hook_cache_monitoring() -> bool:
    """Forward jax's compilation-cache monitoring events into the
    counter fabric (xla_cache.hits / xla_cache.misses / ...). Uses the
    private jax._src.monitoring listener registry — gated so a jax
    without it just skips the counters. Idempotent."""
    global _monitoring_hooked
    if _monitoring_hooked:
        return True
    try:
        from jax._src import monitoring
    # lint: allow(broad-except) private jax API; absence returns False
    except Exception:  # pragma: no cover - depends on jax internals
        return False

    from openr_tpu.runtime.counters import counters

    def _on_event(event: str, **kwargs) -> None:
        key = _EVENT_COUNTERS.get(event)
        if key is not None:
            counters.increment(key)

    try:
        monitoring.register_event_listener(_on_event)
    # lint: allow(broad-except) private jax API; absence returns False
    except Exception:  # pragma: no cover
        return False
    _monitoring_hooked = True
    return True


ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def cache_root() -> str:
    """The one directory compiled code is kept under:
    $JAX_COMPILATION_CACHE_DIR where set, else `.jax_cache` inside the
    checkout (git-ignored)."""
    return os.environ.get(ENV_JAX_CACHE_DIR) or os.path.join(
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
        ".jax_cache",
    )


def enable_compilation_cache(cache_dir: str | None = None) -> str | None:
    """Point jax at a persistent on-disk compilation cache; returns the
    directory in use, or None when disabled. Idempotent."""
    global _applied
    if _applied is not None:
        return _applied or None
    env = os.environ.get("OPENR_TPU_XLA_CACHE", "")
    d = cache_dir if cache_dir is not None else env
    if d.lower() in _DISABLE:
        _applied = ""
        return None
    from_env = os.environ.get(ENV_JAX_CACHE_DIR, "")
    d = from_env or d or cache_root()
    try:
        os.makedirs(d, exist_ok=True)
        import jax

        if not from_env:
            jax.config.update("jax_compilation_cache_dir", d)
        # the daemon's kernels are worth caching even when XLA compiles
        # them quickly — a restart replays dozens of them
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # lint: allow(broad-except) cache is best-effort; cold compile works
    except Exception as e:  # pragma: no cover - cache is best-effort
        log.warning("compilation cache unavailable (%s); compiling cold", e)
        _applied = ""
        return None
    _hook_cache_monitoring()
    _applied = d
    return d


# -- retrace sentinel -------------------------------------------------------
#
# The monitoring hook above answers "did the persistent cache hit?"; the
# sentinel below answers "did XLA compile when we believed the kernel
# was warm?". jax fires a backend-compile duration event once per fresh
# executable build and stays silent on executable-cache hits, so a
# compile observed while the solver is executing an already-warmed
# (namespace, kernel) pair is a RETRACE — the silent ~8s routing-stale
# stall ROADMAP item 1 chases. Mirrors the runtime/affinity.py design:
# cheap enough to leave on, attribution at the point of damage.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_NEVER = object()


def _sig_delta(prev: tuple, cur: tuple) -> str:
    if prev == cur:
        return (
            "signature unchanged — trace-level fork (closure capture, "
            "dtype/weak-type drift, or non-array argument churn)"
        )
    return f"{prev!r} -> {cur!r}"


class RetraceSentinel:
    """Attributes unexpected XLA compiles to their jit-cache namespace.

    The solver wraps each executable invocation in
    ``scope(namespace, kernel_name, capacity_signature)``. The FIRST
    compile observed for a (namespace, kernel) pair is warmup and is
    recorded; any LATER compile for the same pair is a retrace:
    `xla_cache.retraces.<namespace>` counts it, and a structured event
    carrying the offending signature delta is queued for the Decision
    actor to surface as a DEVICE_RETRACE LogSample (which trips the
    flight recorder through the Monitor's trigger table).

    Also keeps the per-namespace cache-class census (distinct capacity
    signatures per bounded_jit_cache namespace) that
    `xla_cache.classes.<namespace>` and ctrl.tpu.kernels report."""

    MAX_EVENTS = 32

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._hooked: bool | None = None  # None = not yet attempted
        # (namespace, kernel name) -> capacity signature at last compile
        self._compiled: dict[tuple, tuple] = {}
        # pairs installed warm from the AOT executable cache — no
        # compile event ever fired for them, so a later compile is not
        # a retrace but a WARM-CACHE VIOLATION (classified on the event)
        self._aot_installed: set[tuple] = set()
        # namespace label -> retrace count (counter fabric mirror)
        self._retraces: dict[str, int] = {}
        # namespace label -> {capacity signatures} (factory-miss census)
        self._classes: dict[str, set] = {}
        # pending LogSample payloads (drained by the Decision actor)
        self._events: deque = deque(maxlen=self.MAX_EVENTS)
        # retained ring for ctrl.tpu.kernels triage
        self._recent: deque = deque(maxlen=self.MAX_EVENTS)

    # -- jax hook ----------------------------------------------------------

    def _ensure_hooked(self) -> bool:
        if self._hooked is not None:
            return self._hooked
        with self._lock:
            if self._hooked is not None:
                return self._hooked
            try:
                from jax._src import monitoring

                monitoring.register_event_duration_secs_listener(
                    self._on_duration_event
                )
                self._hooked = True
            # lint: allow(broad-except) private jax API; sentinel darkens
            except Exception:  # pragma: no cover - jax internals moved
                self._hooked = False
            return self._hooked

    def _on_duration_event(self, event: str, duration, **kwargs) -> None:
        if event != _COMPILE_EVENT:
            return
        # compiles are synchronous within the dispatching call, so the
        # thread-local scope stack names the kernel being built
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return
        from openr_tpu.runtime.counters import counters

        # every in-scope compile is counted: a warm-cache boot asserts
        # this stays flat (zero true compiles for baked shape classes)
        counters.increment("xla_cache.scoped_compiles")
        namespace, name, sig = stack[-1]
        key = (namespace, name)
        with self._lock:
            prev = self._compiled.get(key, _NEVER)
            self._compiled[key] = sig
            aot_installed = key in self._aot_installed
        if prev is _NEVER:
            return  # warmup compile — expected
        self._record_retrace(namespace, name, prev, sig, aot_installed)

    def _record_retrace(
        self, namespace: str, name: str, prev: tuple, sig: tuple,
        aot_installed: bool = False,
    ) -> None:
        from openr_tpu.runtime.counters import counters

        label = namespace or "default"
        counters.increment(f"xla_cache.retraces.{label}")
        evt = {
            "namespace": label,
            "kernel": name,
            # classification (ISSUE 20): "retrace" = trace-level churn
            # after an in-process warmup compile; "aot_warm_violation"
            # = the kernel was installed from the warm AOT cache and
            # should NEVER compile again — the bug the sentinel guards
            "class": "aot_warm_violation" if aot_installed else "retrace",
            "signature": repr(sig),
            "signature_delta": _sig_delta(prev, sig),
            "ts": time.time(),
        }
        with self._lock:
            self._retraces[label] = self._retraces.get(label, 0) + 1
            self._events.append(evt)
            self._recent.append(dict(evt))
        log.warning(
            "%s after warmup: %s kernel %s (%s)",
            evt["class"], label, name, evt["signature_delta"],
        )

    # -- solver-facing API -------------------------------------------------

    @contextlib.contextmanager
    def scope(self, namespace: str, name: str, signature=()):
        """Mark the dynamic extent of one executable invocation; any
        compile firing inside it is attributed to (namespace, name)."""
        if not self._ensure_hooked():
            yield
            return
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append((namespace, name, tuple(signature)))
        try:
            yield
        finally:
            stack.pop()

    def current_scope(self) -> tuple | None:
        """(namespace, kernel, signature) of the innermost active scope
        on this thread, or None — lets the AOT install path label
        itself without replumbing every factory."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def note_aot_install(
        self, namespace: str, name: str, sig=()
    ) -> None:
        """An AOT-cache deserialize installed (namespace, kernel) warm
        WITHOUT a compile event ever firing: mark the pair compiled so
        any actual later compile classifies as a warm-cache violation
        (a DEVICE_RETRACE page), never as warmup."""
        key = (namespace, name)
        with self._lock:
            self._compiled.setdefault(key, tuple(sig))
            self._aot_installed.add(key)

    def note_class(self, namespace: str, sig: tuple) -> None:
        """Factory-miss census: one distinct capacity signature seen in
        `namespace` (called by bounded_jit_cache)."""
        from openr_tpu.runtime.counters import counters

        label = namespace or "default"
        with self._lock:
            classes = self._classes.setdefault(label, set())
            classes.add(sig)
            n = len(classes)
        counters.set_counter(f"xla_cache.classes.{label}", n)

    def forget(self, namespace: str) -> None:
        """A bucket eviction dropped executables in `namespace`; their
        re-compiles on regrowth are warmup, not retraces."""
        with self._lock:
            for key in [k for k in self._compiled if k[0] == namespace]:
                del self._compiled[key]
                self._aot_installed.discard(key)

    def drain_events(self) -> list[dict]:
        """Pending retrace events, consumed (Decision -> LogSample)."""
        with self._lock:
            out = list(self._events)
            self._events.clear()
        return out

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "retraces": dict(self._retraces),
                "classes": {
                    ns: len(sigs) for ns, sigs in self._classes.items()
                },
                "aot_installs": len(self._aot_installed),
                "recent": [dict(e) for e in self._recent],
            }

    def reset(self) -> None:
        """Test hook: drop warmup/census state (the jax listener cannot
        be unregistered; an empty scope stack makes it a no-op)."""
        with self._lock:
            self._compiled.clear()
            self._aot_installed.clear()
            self._retraces.clear()
            self._classes.clear()
            self._events.clear()
            self._recent.clear()


retrace = RetraceSentinel()


# -- bounded executable caches ----------------------------------------------
#
# The jit factories across the solver are keyed on capacity-class shapes.
# An unbounded lru_cache never drops an executable, so a long-lived
# daemon whose graph grew through several pow2 capacity buckets keeps
# every superseded bucket's compiled program (and its device constants)
# alive forever — exactly the slow-leak signature the HBM runbook
# chases. bounded_jit_cache evicts by CAPACITY BUCKET, not by raw key:
# flag variants of the same shape class (lfa / block_v4 / sentinels)
# live and die together, because a live bucket legitimately needs all
# of its variants while a dead (outgrown) bucket needs none.


# every bounded factory registers here so a simulated process restart
# (bench boot A/B, the chaos warm-restart drill) can drop ALL in-memory
# executables in one call and re-enter through the AOT load path
_BOUNDED_CACHES: list = []


def clear_all_jit_caches() -> int:
    """Drop every bounded factory's cached (wrapper, executable) state —
    the in-memory half of a process restart. On-disk AOT entries
    survive; the next dispatch re-installs through aot.load()."""
    for w in _BOUNDED_CACHES:
        w.cache_clear()
    return len(_BOUNDED_CACHES)


def bounded_jit_cache(max_buckets: int = 8, namespace: str = ""):
    """lru_cache replacement for shape-keyed jit factories, bounded to
    `max_buckets` distinct capacity signatures per factory. A key's
    capacity signature is its tuple of int (non-bool) components; bool
    flags select a variant WITHIN a bucket. On overflow the least-
    recently-used bucket is dropped whole, releasing every variant's
    executable, and `xla_cache.executable_evictions` counts the drops.

    `namespace` partitions workload classes: a namespaced factory keeps
    its own bucket table AND its own bucket budget, and reports through
    `xla_cache.<namespace>_factory_hits/_factory_misses/
    _executable_evictions`. The what-if sweep factories (ops/sweep.py)
    use namespace="whatif" so a burst of interactive sweep shapes
    churns only its own LRU and can never evict a live-solve
    executable — and the counter split shows which workload is
    compiling. The solver's one pipeline factory (tpu_solver
    _build_pipeline) is wrapped once per namespace its variants name
    (PipelineVariant.namespace): incremental solves use "incr" —
    dirty-set cap churn buckets under xla_cache.incr_* and cannot
    evict the full-solve or sweep executables — and the multichip
    capacity tier "multichip" for the
    same reason: a sharded executable can never evict a single-chip
    one or vice versa, so a fabric that oscillates around the tier
    threshold keeps both resident. The non-int mesh object in a
    multichip key is a within-bucket variant, exactly like a bool
    flag. A factory that takes a record takes it splatted, so its
    ints stay positional. The namespace is also
    folded into the bucket signature, so two namespaces can never
    alias a capacity bucket even if they were ever pointed at a
    shared table.

    Hashable positional keys only — same contract the lru_cache sites
    already honor. Exposes `cache_clear()` for tests."""

    prefix = f"xla_cache.{namespace}_" if namespace else "xla_cache."

    def decorate(fn):
        lock = threading.Lock()
        buckets: OrderedDict[tuple, dict] = OrderedDict()

        @functools.wraps(fn)
        def wrapper(*key):
            from openr_tpu.runtime.counters import counters

            sig = (namespace,) + tuple(
                k for k in key
                if isinstance(k, int) and not isinstance(k, bool)
            )
            with lock:
                group = buckets.get(sig)
                if group is not None and key in group:
                    buckets.move_to_end(sig)
                    counters.increment(prefix + "factory_hits")
                    return group[key]
            # compile outside the lock: factory bodies trace/compile and
            # may take seconds — a racing duplicate compile is benign
            counters.increment(prefix + "factory_misses")
            retrace.note_class(namespace, sig)
            value = fn(*key)
            evicted = False
            with lock:
                group = buckets.setdefault(sig, {})
                group.setdefault(key, value)
                buckets.move_to_end(sig)
                while len(buckets) > max_buckets:
                    _, dropped = buckets.popitem(last=False)
                    counters.increment(
                        prefix + "executable_evictions", len(dropped)
                    )
                    evicted = True
                value = group[key]
            if evicted:
                # dropped executables recompile as warmup on regrowth,
                # not as retraces
                retrace.forget(namespace)
            return value

        def cache_clear():
            with lock:
                buckets.clear()

        wrapper.cache_clear = cache_clear
        _BOUNDED_CACHES.append(wrapper)
        return wrapper

    return decorate


# -- kernel cost ledger -----------------------------------------------------
#
# The cache above answers "did we recompile?"; the ledger answers "what
# did the compiler think each kernel costs?". Per instrumented
# executable it keeps compile time plus XLA's own cost_analysis()
# (flops, bytes accessed) so ctrl.tpu.kernels can report estimated vs
# achieved throughput next to the solver's measured exec times.


def _extract_cost(compiled) -> dict:
    """Pull the headline numbers out of compiled.cost_analysis(), which
    is a flat dict on current jax and a [dict] on older releases; keys
    are XLA's spellings ("bytes accessed")."""
    try:
        ca = compiled.cost_analysis()
    # lint: allow(broad-except) cost analysis is optional telemetry
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    for src, dst in (
        ("flops", "flops"),
        ("bytes accessed", "bytes_accessed"),
        ("transcendentals", "transcendentals"),
    ):
        v = ca.get(src)
        if isinstance(v, (int, float)):
            out[dst] = float(v)
    return out


class KernelLedger:
    """Compile-cost bookkeeping per instrumented executable."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[str, dict] = {}

    def record(
        self, name: str, compile_ms: float | None, cost: dict,
        loaded: bool = False, load_ms: float | None = None,
    ) -> None:
        """`loaded` marks an executable installed from the persistent
        AOT cache (deserialize, no compile): compile_ms stays None and
        load_ms records what the install actually cost."""
        from openr_tpu.runtime.counters import counters

        with self._lock:
            self._entries[name] = {
                "name": name,
                "compile_ms": (
                    round(compile_ms, 3) if compile_ms is not None else None
                ),
                "aot_loaded": loaded,
                "load_ms": (
                    round(load_ms, 3) if load_ms is not None else None
                ),
                "calls": 0,
                **cost,
            }
        if compile_ms is not None:
            counters.add_stat_value("xla_cache.compile_ms", compile_ms)
            # perf observatory: compile times become per-kernel baselines
            # (no-op unless a perf-ledger dir is configured)
            from openr_tpu.runtime.perf_ledger import get_ledger

            get_ledger().record(
                name, {"compile_ms": compile_ms}, variant="compile"
            )
        counters.increment("xla_cache.kernels_recorded")

    def bump_calls(self, name: str) -> None:
        with self._lock:
            e = self._entries.get(name)
            if e is not None:
                e["calls"] += 1

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


ledger = KernelLedger()


# -- persistent AOT executable cache (ISSUE 20) ------------------------------
#
# jax's persistent compilation cache (enable_compilation_cache above)
# skips the XLA backend compile but still pays tracing + lowering +
# executable construction per kernel on every restart. This tier
# removes the whole pass: each freshly compiled executable is
# serialized (jax.experimental.serialize_executable) to its own file,
# keyed by (kernel name, full factory-arg signature) and stamped with
# the jax+jaxlib+backend+device fingerprint; a warm restart
# deserializes-and-installs it with ZERO compiles. Fallbacks are total:
# a stale fingerprint or a torn/corrupt file silently degrades to the
# compile path (counted, never raising into a solve), writes are
# atomic (tmp + os.replace, the perf-ledger idiom), and on-disk
# retention keeps the newest N entries (the flight-recorder idiom).

ENV_AOT_DIR = "OPENR_TPU_AOT_CACHE"
AOT_SUFFIX = ".aotx"
# closed counter vocabulary for the xla_cache.aot.<field> family
# (tools/lint/metric_names.py expands the placeholder over this)
AOT_COUNTER_FIELDS = (
    "hits", "misses", "load_errors", "stale_fingerprint", "writes",
    "write_errors", "evictions", "preloaded", "speculative_bakes",
    "speculative_errors",
)


def aot_fingerprint() -> str:
    """Toolchain + device identity a serialized executable is valid
    under. Deliberately eager on jax (unlike perf_ledger.fingerprint):
    it is only evaluated once the AOT cache is enabled, which implies a
    device-plane process. Device kind AND count are part of it — a
    sharded executable deserialized onto a different mesh is garbage."""
    try:
        import jax

        jaxlib = sys.modules.get("jaxlib")
        devs = jax.devices()
        kind = devs[0].device_kind.replace(" ", "_") if devs else "?"
        return (
            f"jax{getattr(jax, '__version__', '?')}"
            f"+jaxlib{getattr(jaxlib, '__version__', '?')}"
            f"+{jax.default_backend()}+{kind}x{len(devs)}"
        )
    # lint: allow(broad-except) identity probe is best-effort
    except Exception:  # pragma: no cover - no usable jax
        return "nojax"


class AotExecutableCache:
    """One directory of serialized compiled executables, one file per
    (kernel name, factory-arg signature). Disabled ("" dir) it is a
    total no-op — loads return None, stores return False — so tests
    and control-plane processes never touch disk."""

    SCHEMA = "openr-tpu-aot/1"

    def __init__(self, dir_path: str = "", keep: int = 64):
        self.dir = dir_path or ""
        self.keep = int(keep)
        self._lock = threading.Lock()
        self._fp: str | None = None
        # preload() parks deserialized executables here; load() claims
        # them by digest so boot pays deserialization once, in its own
        # attributed aot_load phase, not inside the first solve
        self._preloaded: dict[str, object] = {}
        self._stats = {f: 0 for f in AOT_COUNTER_FIELDS}

    @property
    def enabled(self) -> bool:
        return bool(self.dir)

    def fingerprint(self) -> str:
        if self._fp is None:
            self._fp = aot_fingerprint()
        return self._fp

    def _bump(self, field: str, n: int = 1) -> None:
        from openr_tpu.runtime.counters import counters

        with self._lock:
            self._stats[field] = self._stats.get(field, 0) + n
        counters.increment(f"xla_cache.aot.{field}", n)

    # -- keying ------------------------------------------------------------

    @staticmethod
    def _digest(name: str, key: str) -> str:
        import hashlib

        return hashlib.sha256(f"{name}|{key}".encode()).hexdigest()[:20]

    @staticmethod
    def _slug(name: str) -> str:
        safe = "".join(
            c if (c.isalnum() or c in "._=-") else "_" for c in name
        )
        return safe[:80] or "kernel"

    def _path(self, name: str, key: str) -> str:
        return os.path.join(
            self.dir, f"{self._slug(name)}-{self._digest(name, key)}{AOT_SUFFIX}"
        )

    # -- file format: one JSON header line + pickled serialize() triple ----

    @staticmethod
    def _read_file(path: str) -> tuple[dict, bytes]:
        """-> (header, blob); raises on a torn/corrupt entry (the
        caller counts + evicts). The header is newline-terminated JSON
        (json.dumps emits no raw newlines), the rest is the pickled
        (payload, in_tree, out_tree) triple."""
        import json

        with open(path, "rb") as f:
            raw = f.read()
        head, sep, blob = raw.partition(b"\n")
        header = json.loads(head.decode())
        if (
            not sep
            or not isinstance(header, dict)
            or header.get("schema") != AotExecutableCache.SCHEMA
            or not blob
        ):
            raise ValueError(f"malformed AOT cache entry {path}")
        return header, blob

    def _evict(self, path: str) -> None:
        with contextlib.suppress(OSError):
            os.remove(path)

    # -- store / load ------------------------------------------------------

    def store(
        self, name: str, key: str, compiled, compile_ms: float | None = None,
        source: str = "compile",
    ) -> bool:
        """Serialize one compiled executable to its keyed file. Atomic
        (tmp + os.replace) and best-effort: any failure is counted and
        swallowed — the in-memory executable keeps working."""
        if not self.enabled:
            return False
        import json
        import pickle

        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            # the devices this executable was compiled for: load must
            # hand exactly these back to deserialize_and_load, whose
            # default is EVERY device of the backend (a one-device
            # executable loaded as an N-device one fails at first call)
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
            blob = pickle.dumps(
                (payload, in_tree, out_tree),
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            header = json.dumps({
                "schema": self.SCHEMA,
                "kernel": name,
                "aot_key": key,
                "fingerprint": self.fingerprint(),
                "device_ids": device_ids,
                "created_ms": int(time.time() * 1000),
                "compile_ms": (
                    round(compile_ms, 3) if compile_ms is not None else None
                ),
                "source": source,
            }).encode()
            os.makedirs(self.dir, exist_ok=True)
            path = self._path(name, key)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(header + b"\n" + blob)
            os.replace(tmp, path)
        # lint: allow(broad-except) cache writes never fail a solve
        except Exception as e:
            self._bump("write_errors")
            log.warning("AOT cache write failed for %s (%s)", name, e)
            return False
        self._bump("writes")
        self._prune()
        return True

    def _load_file(self, path: str):
        """Deserialize one entry; returns the executable or None with
        the failure counted and the bad file evicted (corrupt entries
        must fall back to compile silently, never crash, and never be
        retried forever)."""
        import pickle

        try:
            header, blob = self._read_file(path)
        # lint: allow(broad-except) torn/corrupt entry -> compile path
        except Exception:
            self._bump("load_errors")
            log.warning(
                "corrupt AOT cache entry %s — evicted, will recompile",
                path,
            )
            self._evict(path)
            return None
        if header.get("fingerprint") != self.fingerprint():
            # a toolchain/backend/device-topology bump invalidates the
            # entry; evict so the next store rewrites it fresh
            self._bump("stale_fingerprint")
            self._evict(path)
            return None
        devices = self._execution_devices(header.get("device_ids"))
        if devices is None:
            # compiled for devices this process does not have (or an
            # entry from before device_ids was recorded): a counted
            # miss, never a load onto some other device set
            self._bump("stale_fingerprint")
            self._evict(path)
            return None
        try:
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            payload, in_tree, out_tree = pickle.loads(blob)
            return deserialize_and_load(
                payload, in_tree, out_tree,
                backend=devices[0].client, execution_devices=devices,
            )
        # lint: allow(broad-except) undeserializable entry -> compile
        except Exception as e:
            self._bump("load_errors")
            log.warning(
                "AOT deserialize failed for %s (%s) — evicted", path, e
            )
            self._evict(path)
            return None

    @staticmethod
    def _execution_devices(device_ids) -> list | None:
        """The recorded device ids resolved against this process's
        devices, in recorded order; None when any is absent."""
        import jax

        if not device_ids:
            return None
        by_id = {d.id: d for d in jax.devices()}
        if any(i not in by_id for i in device_ids):
            return None
        return [by_id[i] for i in device_ids]

    def load(self, name: str, key: str):
        """The warm path: claim a preloaded executable or deserialize
        the keyed file. Every call that cannot produce an executable —
        absent, stale or corrupt — counts one miss (aot_hit_rate =
        hits / (hits + misses))."""
        if not self.enabled:
            return None
        digest = self._digest(name, key)
        with self._lock:
            fn = self._preloaded.pop(digest, None)
        if fn is None:
            path = self._path(name, key)
            if os.path.exists(path):
                t0 = time.perf_counter()
                fn = self._load_file(path)
                if fn is not None:
                    from openr_tpu.runtime.counters import counters

                    counters.add_stat_value(
                        "xla_cache.aot.load_ms",
                        (time.perf_counter() - t0) * 1e3,
                    )
        if fn is None:
            self._bump("misses")
            return None
        self._bump("hits")
        return fn

    def preload(self) -> dict:
        """Eagerly deserialize every fingerprint-matching entry into
        memory — the `aot_load` boot phase (runtime/lifecycle.py).
        Returns the phase attribution dict; stale/corrupt entries are
        counted + evicted exactly as on the lazy path."""
        if not self.enabled:
            return {"enabled": False}
        loaded = skipped = 0
        nbytes = 0
        before = dict(self._stats)
        for path in sorted(self._entry_paths()):
            try:
                header, _ = self._read_file(path)
            # lint: allow(broad-except) corrupt entry -> counted evict
            except Exception:
                self._bump("load_errors")
                self._evict(path)
                continue
            digest = self._digest(
                str(header.get("kernel")), str(header.get("aot_key"))
            )
            with self._lock:
                have = digest in self._preloaded
            if have:
                skipped += 1
                continue
            fn = self._load_file(path)
            if fn is None:
                continue
            with self._lock:
                self._preloaded[digest] = fn
            loaded += 1
            nbytes += os.path.getsize(path) if os.path.exists(path) else 0
        if loaded:
            self._bump("preloaded", loaded)
        return {
            "enabled": True,
            "loaded": loaded,
            "skipped": skipped,
            "stale": self._stats["stale_fingerprint"]
            - before["stale_fingerprint"],
            "errors": self._stats["load_errors"] - before["load_errors"],
            "bytes": nbytes,
        }

    # -- retention / introspection -----------------------------------------

    def _entry_paths(self) -> list[str]:
        if not self.enabled or not os.path.isdir(self.dir):
            return []
        return [
            os.path.join(self.dir, f)
            for f in os.listdir(self.dir)
            if f.endswith(AOT_SUFFIX)
        ]

    def _prune(self) -> None:
        """Newest-N on-disk retention (the flight-recorder idiom): keep
        the `keep` most recently written entries, evict the rest."""
        paths = self._entry_paths()
        if len(paths) <= self.keep:
            return
        try:
            paths.sort(key=lambda p: os.path.getmtime(p), reverse=True)
        except OSError:
            return
        dropped = 0
        for path in paths[self.keep:]:
            self._evict(path)
            dropped += 1
        if dropped:
            self._bump("evictions", dropped)

    def entries(self) -> list[dict]:
        """On-disk listing for ctrl.tpu.aot / breeze tpu aot: kernel,
        signature, size, fingerprint (+staleness), age."""
        now = time.time()
        fp = self.fingerprint() if self.enabled else ""
        out = []
        for path in self._entry_paths():
            try:
                header, _ = self._read_file(path)
                size = os.path.getsize(path)
            # lint: allow(broad-except) listing skips torn entries
            except Exception:
                out.append({"file": os.path.basename(path), "corrupt": True})
                continue
            created = header.get("created_ms") or 0
            out.append({
                "file": os.path.basename(path),
                "kernel": header.get("kernel"),
                "signature": header.get("aot_key"),
                "size_bytes": size,
                "fingerprint": header.get("fingerprint"),
                "stale": header.get("fingerprint") != fp,
                "age_s": round(max(0.0, now - created / 1e3), 1),
                "compile_ms": header.get("compile_ms"),
                "source": header.get("source"),
            })
        out.sort(key=lambda e: e.get("age_s") or 0.0)
        return out

    def summary(self) -> dict:
        with self._lock:
            stats = dict(self._stats)
            pending = len(self._preloaded)
        lookups = stats["hits"] + stats["misses"]
        return {
            "enabled": self.enabled,
            "dir": self.dir,
            "keep": self.keep,
            "fingerprint": self.fingerprint() if self.enabled else None,
            "entries": len(self._entry_paths()),
            "preloaded_pending": pending,
            "hit_rate": (
                round(stats["hits"] / lookups, 4) if lookups else None
            ),
            **stats,
        }

    def reset_stats(self) -> None:
        """Test/bench hook: zero the in-memory stat mirror (the counter
        fabric keeps its own totals) and drop unclaimed preloads."""
        with self._lock:
            self._stats = {f: 0 for f in AOT_COUNTER_FIELDS}
            self._preloaded.clear()


# process singleton (the tracer/counters pattern); disabled by default
aot = AotExecutableCache("")

_AOT_DISABLE = _DISABLE
_AOT_AUTO = ("auto", "default")


def configure_aot(
    spec: str | None, keep: int | None = None
) -> AotExecutableCache:
    """Point the process AOT cache at a directory.

    `spec` resolution: None/"" consults $OPENR_TPU_AOT_CACHE (empty =
    stays disabled — the cache is opt-in, unlike the jax compilation
    cache); "auto" resolves <cache_root()>/aot; "off"/"0" disables;
    anything else is the directory. Repointing drops unclaimed
    preloads; an identical repoint is a cheap no-op."""
    global aot
    raw = spec if spec else os.environ.get(ENV_AOT_DIR, "")
    d = raw.strip()
    if d.lower() in _AOT_DISABLE or not d:
        d = ""
    elif d.lower() in _AOT_AUTO:
        d = os.path.join(cache_root(), "aot")
    if d != aot.dir or (keep is not None and keep != aot.keep):
        aot = AotExecutableCache(d, keep if keep is not None else aot.keep)
    return aot


def get_aot() -> AotExecutableCache:
    """Current process AOT cache (configure_aot may have swapped the
    module global; call sites that cache the object would miss it)."""
    return aot


# -- speculative background-compile fiber ------------------------------------


class SpeculativeBaker:
    """Single background thread that compiles executables BEFORE churn
    needs them (the next capacity class up, the multichip mesh shapes).
    Work items are deduplicated by label for the process lifetime — a
    tier the fabric oscillates around is baked once, not per solve.
    Failures are counted and logged at debug: a speculative miss costs
    nothing but the wasted compile."""

    def __init__(self):
        self._cv = threading.Condition()
        self._queue: deque = deque()
        self._seen: set[str] = set()
        self._pending = 0
        self._thread: threading.Thread | None = None

    def submit(self, label: str, thunk) -> bool:
        """Enqueue one bake; returns False when the label already ran
        (or is queued). The worker thread starts lazily on first use."""
        with self._cv:
            if label in self._seen:
                return False
            self._seen.add(label)
            self._queue.append((label, thunk))
            self._pending += 1
            if self._thread is None:
                # lint: allow(executor-escape) baker owns only its queue + the process AOT cache, both lock-guarded
                self._thread = threading.Thread(
                    target=self._run, name="aot-baker", daemon=True
                )
                self._thread.start()
            self._cv.notify_all()
        return True

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._queue:
                    self._cv.wait()
                label, thunk = self._queue.popleft()
            try:
                thunk()
                aot._bump("speculative_bakes")
                log.debug("speculative bake done: %s", label)
            # lint: allow(broad-except) a failed bake is a counted no-op
            except Exception:
                aot._bump("speculative_errors")
                log.debug("speculative bake failed: %s", label,
                          exc_info=True)
            with self._cv:
                self._pending -= 1
                self._cv.notify_all()

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until every queued bake finished (tests/bench); False
        on timeout."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._pending:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True

    def reset(self) -> None:
        """Test hook: drop queued (not in-flight) work + the dedup set."""
        with self._cv:
            self._pending -= len(self._queue)
            self._queue.clear()
            self._seen.clear()
            self._cv.notify_all()


baker = SpeculativeBaker()


def instrument_jit(name: str, jitted, aot_key: str | None = None):
    """Wrap a jitted callable so its first invocation AOT-compiles
    (lower().compile()), recording compile time + cost_analysis into
    the ledger, and every later invocation hits the compiled executable
    directly. Callers must keep argument shapes/dtypes fixed per
    instrumented instance — true for the solver's shape-keyed pipeline
    factories, whose lru key IS the shape class. A compile the
    backend's compiler refuses raises into the caller with the
    compiler's message: the plain jitted fn would only hit the same
    refusal later, under a less telling name.

    With `aot_key` (the canonical repr of EVERY factory argument — the
    kernel name alone under-keys: it omits r_cap/kr_cap/budget and the
    sentinel/block flags) the persistent executable cache engages:
    install first consults aot.load(name, aot_key) — a hit deserializes
    in milliseconds with no compile event, and the retrace sentinel is
    told so a later compile for the pair pages as a warm-cache
    violation — and a fresh compile is serialized back via aot.store.
    A loaded executable whose avals reject the first real call (an
    under-keyed or foreign entry) falls back to compiling, counted as
    a load error. `wrapper.prime(*avals)` installs without executing —
    jax.ShapeDtypeStruct args suffice — which is how the speculative
    baker bakes the next capacity class from abstract shapes."""

    state: dict = {"fn": None, "verify_loaded": False}
    lock = threading.Lock()

    def _mark_installed() -> None:
        scope = retrace.current_scope()
        if scope is not None:
            retrace.note_aot_install(scope[0], name, scope[2])
        else:
            retrace.note_aot_install("", name)

    def _compile(args, kwargs):
        t0 = time.perf_counter()
        fn = jitted.lower(*args, **kwargs).compile()
        compile_ms = (time.perf_counter() - t0) * 1e3
        ledger.record(name, compile_ms, _extract_cost(fn))
        if aot_key is not None:
            aot.store(name, aot_key, fn, compile_ms)
        return fn

    def _install(args, kwargs):
        """-> (fn, loaded_from_cache). Caller holds `lock`."""
        if aot_key is not None and aot.enabled:
            t0 = time.perf_counter()
            fn = aot.load(name, aot_key)
            if fn is not None:
                ledger.record(
                    name, None, _extract_cost(fn), loaded=True,
                    load_ms=(time.perf_counter() - t0) * 1e3,
                )
                _mark_installed()
                return fn, True
        return _compile(args, kwargs), False

    def _ensure(args, kwargs) -> bool:
        """Install once; True when this call did the install."""
        with lock:
            if state["fn"] is not None:
                return False
            fn, loaded = _install(args, kwargs)
            state["verify_loaded"] = loaded
            state["fn"] = fn
            return True

    def wrapper(*args, **kwargs):
        if state["fn"] is None:
            _ensure(args, kwargs)
        fn = state["fn"]
        ledger.bump_calls(name)
        if state["verify_loaded"]:
            # first call on a cache-loaded executable: a TypeError here
            # is the aval-mismatch rejection (raised before execution)
            # — fall back to a fresh compile, counted, never crashing
            state["verify_loaded"] = False
            try:
                return fn(*args, **kwargs)
            except TypeError as e:
                aot._bump("load_errors")
                log.warning(
                    "AOT-loaded executable %s rejected its first call "
                    "(%s); recompiling", name, e,
                )
                with lock:
                    fn = state["fn"] = _compile(args, kwargs)
                return fn(*args, **kwargs)
        return fn(*args, **kwargs)

    def prime(*args, **kwargs) -> bool:
        """Install (AOT-load or compile + persist) WITHOUT executing;
        `args` may be jax.ShapeDtypeStructs. Returns True when this
        call did the install. The speculative baker's entry point."""
        return state["fn"] is None and _ensure(args, kwargs)

    wrapper.prime = prime
    wrapper.kernel_name = name
    # the program itself: lower()-able and jittable without installing
    wrapper.jitted = jitted
    wrapper.is_installed = lambda: state["fn"] is not None
    return wrapper
