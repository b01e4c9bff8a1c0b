"""Persistent perf-baseline ledger (ISSUE 14).

Bench JSONs and the kernel cost ledger
(ops/xla_cache.KernelLedger) are write-only snapshots: nothing persists
per-kernel / per-stage baselines across runs, so a perf regression is
only caught by a human diffing bench JSONs. This module is the
measurement substrate: a small JSON store of timing observations keyed

  <kernel> | <capacity signature> | <variant> | <jax/XLA fingerprint>

 - kernel               what ran ("solve[lsdb100k]", "prewarm", a jit name)
 - capacity signature   the padded shape class ("n100489", "live")
 - variant              spf_kernel / namespace ("bucketed", "sync", "incr")
 - fingerprint          jax+jaxlib versions + backend — a toolchain bump
                        starts a fresh baseline instead of comparing
                        across compilers

Producers append observations (compile_ms, device_ms, rounds,
bucket_epochs, bytes_uploaded, peak_hbm_mb, ...): bench.py after each
config, tools/prewarm.py per bake, the live Monitor from its metrics
windows, and ops/xla_cache.KernelLedger per recorded compile. Consumers
read rolling quantile baselines: the ``baseline_drift`` SLO kind
(runtime/monitor.SloEngine) compares live window quantiles against the
stored quantile, and ``tools/perf_diff.py`` renders verdicts.

The store is OFF by default ("" dir — lookups return None, records
no-op) so tests and control-plane-only processes never touch disk;
``monitor_config.perf_ledger_dir`` / $OPENR_TPU_PERF_LEDGER /
``--perf-ledger-dir`` opt in. Writes are atomic (tmp + rename) and the
per-key observation window is bounded (rolling baseline, not an
ever-growing log).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from typing import Optional

from openr_tpu.runtime.counters import _percentile, counters

log = logging.getLogger(__name__)

ENV_DIR = "OPENR_TPU_PERF_LEDGER"
LEDGER_FILE = "perf_ledger.json"
# rolling window: enough history for a stable p95, bounded on disk
MAX_OBSERVATIONS = 64
_QUANTILES = ("p50", "p95", "p99")


def default_dir() -> str:
    """$OPENR_TPU_PERF_LEDGER, else the user cache — for the OFFLINE
    tools (prewarm, bench --perf-ledger) that want persistence without
    config plumbing. The daemon only persists via an explicit knob."""
    return os.environ.get(ENV_DIR, "") or os.path.join(
        os.path.expanduser("~"), ".cache", "openr_tpu", "perf"
    )


def fingerprint() -> str:
    """Toolchain identity a baseline is valid under. Passive on jax
    (device_stats._jax discipline): reads versions only if something
    already imported it, so a control-plane process stays light."""
    from openr_tpu.runtime import device_stats

    jax = device_stats._jax(allow_import=False)
    if jax is None:
        return "nojax"
    jaxlib = sys.modules.get("jaxlib")
    try:
        backend = jax.default_backend()
    # lint: allow(broad-except) backend probe is best-effort identity
    except Exception:
        backend = "unknown"
    return (
        f"jax{getattr(jax, '__version__', '?')}"
        f"+jaxlib{getattr(jaxlib, '__version__', '?')}"
        f"+{backend}"
    )


class PerfLedger:
    """One JSON file of keyed observation windows + quantile baselines."""

    def __init__(self, dir_path: str = ""):
        self.dir = dir_path or ""
        self._lock = threading.Lock()
        self._data: Optional[dict] = None  # lazy: {key: {"observations": []}}

    @property
    def enabled(self) -> bool:
        return bool(self.dir)

    @property
    def path(self) -> str:
        return os.path.join(self.dir, LEDGER_FILE) if self.dir else ""

    @staticmethod
    def key(
        kernel: str,
        signature: str = "",
        variant: str = "",
        fp: Optional[str] = None,
    ) -> str:
        return "|".join(
            (kernel, signature, variant, fp if fp is not None else fingerprint())
        )

    # -- storage -----------------------------------------------------------

    def _load(self) -> dict:
        """Caller holds the lock."""
        if self._data is not None:
            return self._data
        self._data = {}
        if not self.enabled:
            return self._data
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and isinstance(doc.get("entries"), dict):
                self._data = doc["entries"]
        except FileNotFoundError:
            pass
        except (OSError, ValueError):
            # a torn/corrupt ledger must not wedge the daemon: start
            # fresh and make the loss visible
            counters.increment("perf.ledger.load_errors")
            log.warning("perf ledger %s unreadable — starting fresh", self.path)
        return self._data

    def _save(self) -> None:
        """Caller holds the lock. Atomic: tmp + rename."""
        if not self.enabled:
            return
        try:
            os.makedirs(self.dir, exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(
                    {"schema": "openr-tpu-perf-ledger/1", "entries": self._data},
                    f,
                    indent=1,
                    sort_keys=True,
                )
            os.replace(tmp, self.path)
        except OSError:
            counters.increment("perf.ledger.write_errors")
            log.warning("perf ledger write failed", exc_info=True)

    # -- producers ---------------------------------------------------------

    def record(
        self,
        kernel: str,
        metrics: dict,
        signature: str = "",
        variant: str = "",
        fp: Optional[str] = None,
    ) -> None:
        """Append one observation (numeric fields only) to the key's
        rolling window. No-op while disabled."""
        if not self.enabled:
            return
        obs = {
            k: float(v)
            for k, v in (metrics or {}).items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        if not obs:
            return
        obs["ts_ms"] = int(time.time() * 1000)
        with self._lock:
            data = self._load()
            entry = data.setdefault(
                self.key(kernel, signature, variant, fp), {"observations": []}
            )
            entry["observations"] = (
                entry.get("observations", []) + [obs]
            )[-MAX_OBSERVATIONS:]
            self._save()
        counters.increment("perf.ledger.records")
        counters.set_counter("perf.ledger.keys", len(data))

    # -- consumers ---------------------------------------------------------

    def observations(
        self,
        kernel: str,
        signature: str = "",
        variant: str = "",
        fp: Optional[str] = None,
    ) -> list[dict]:
        with self._lock:
            entry = self._load().get(self.key(kernel, signature, variant, fp))
            return list(entry.get("observations", [])) if entry else []

    def baseline(
        self,
        kernel: str,
        metric: str,
        signature: str = "",
        variant: str = "",
        quantile: str = "p95",
        fp: Optional[str] = None,
    ) -> Optional[float]:
        """Rolling quantile of one metric over the key's stored window;
        None when the key (or the metric) has no history — the "no
        baseline never breaches" contract the drift SLO leans on."""
        vals = sorted(
            o[metric]
            for o in self.observations(kernel, signature, variant, fp)
            if isinstance(o.get(metric), (int, float))
        )
        if not vals:
            return None
        q = float(quantile.lstrip("p")) if quantile.startswith("p") else 50.0
        return _percentile(vals, q)

    def baselines(
        self,
        kernel: str,
        signature: str = "",
        variant: str = "",
        fp: Optional[str] = None,
    ) -> dict:
        """Per-metric quantile summary for one key (perf_diff, bundles)."""
        obs = self.observations(kernel, signature, variant, fp)
        metrics: dict[str, list] = {}
        for o in obs:
            for k, v in o.items():
                if k != "ts_ms" and isinstance(v, (int, float)):
                    metrics.setdefault(k, []).append(float(v))
        out = {}
        for k, vals in metrics.items():
            vals.sort()
            out[k] = {
                "count": len(vals),
                **{q: round(_percentile(vals, float(q[1:])), 3)
                   for q in _QUANTILES},
            }
        return out

    def prewarm_summary(self) -> dict:
        """Attribution for the boot tracer's `prewarm` phase: what the
        offline bake (tools/prewarm.py) paid per namespace, read back
        from the ledger instead of re-paying it at daemon start."""
        total_ms, namespaces = 0.0, {}
        with self._lock:
            data = self._load()
        for key, entry in data.items():
            kernel, _, variant, _ = (key.split("|") + [""] * 4)[:4]
            if kernel != "prewarm":
                continue
            obs = entry.get("observations") or []
            if not obs:
                continue
            last = obs[-1].get("bake_ms")
            if isinstance(last, (int, float)):
                namespaces[variant] = round(
                    namespaces.get(variant, 0.0) + last, 1
                )
                total_ms += last
        return {"baked_ms": round(total_ms, 1), "namespaces": namespaces}

    def snapshot(self) -> dict:
        """Bundle/report payload: every key's count + quantiles (no raw
        observation dump — bundles stay bounded)."""
        with self._lock:
            data = {k: dict(v) for k, v in self._load().items()}
        out = {}
        for key, entry in data.items():
            obs = entry.get("observations") or []
            metrics: dict[str, list] = {}
            for o in obs:
                for k, v in o.items():
                    if k != "ts_ms" and isinstance(v, (int, float)):
                        metrics.setdefault(k, []).append(float(v))
            out[key] = {
                "count": len(obs),
                "metrics": {
                    k: {
                        q: round(_percentile(sorted(vals), float(q[1:])), 3)
                        for q in _QUANTILES
                    }
                    for k, vals in metrics.items()
                },
            }
        return {
            "dir": self.dir,
            "fingerprint": fingerprint(),
            "keys": out,
        }


# -- process singleton (the tracer/counters pattern) -------------------------

_ledger = PerfLedger("")


def configure(dir_path: str) -> PerfLedger:
    """Point the process ledger at a directory ("" disables). Idempotent
    for a repeated identical dir; repointing drops the cached data."""
    global _ledger
    if dir_path != _ledger.dir:
        _ledger = PerfLedger(dir_path)
    return _ledger


def get_ledger() -> PerfLedger:
    return _ledger
