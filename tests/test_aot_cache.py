"""ISSUE 20 persistent AOT executable cache tests (ops/xla_cache.py).

Unit tests pin the disk tier's contract — serialize/deserialize
round-trip parity (bit-identical to a fresh compile), stale-fingerprint
eviction, corrupt-entry recovery, the atomic writer + newest-N
retention, preload claiming, and the aval-mismatch fallback — then the
solver-level tests prove the headline behavior: a warm restart rebuilds
the RIB with ZERO in-scope XLA compiles (the retrace sentinel's
scoped-compile census is the proof), and the speculative baker compiles
the next capacity class in the background so a tier flip lands on an
installed executable.

The disk cache is a process global (the tracer/counters pattern): every
test runs under the `aot_dir` fixture, which points the singleton at a
tmp dir and restores the disabled default afterwards.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import (
    TpuSpfSolver,
    _next_shape_key,
    _pipeline_avals,
)
from openr_tpu.models import topologies
from openr_tpu.ops.xla_cache import (
    AOT_COUNTER_FIELDS,
    AOT_SUFFIX,
    AotExecutableCache,
    baker,
    clear_all_jit_caches,
    configure_aot,
    get_aot,
    instrument_jit,
    retrace,
)
from openr_tpu.runtime.counters import counters
from tests.test_tpu_solver import assert_rib_equal


def _counter(key: str) -> float:
    return counters.get_counter(key) or 0


@pytest.fixture
def aot_dir(tmp_path):
    """Point the process AOT cache at a tmp dir; restore the disabled
    default (and quiesce the baker) afterwards."""
    cache = configure_aot(str(tmp_path / "aot"))
    cache.reset_stats()
    baker.reset()
    retrace.reset()
    yield cache
    baker.drain(30)
    baker.reset()
    configure_aot("off")
    retrace.reset()


def _grid_states(side: int):
    adj_dbs, pfx = topologies.grid(side, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, pfx)
    # an interior (degree-4) vantage: its shape class is what
    # _next_shape_key projects the next grid size onto
    me = f"node-{side // 2}-{side // 2}"
    assert any(d.this_node_name == me for d in adj_dbs)
    return states, ps, me


# -- disk-tier unit --------------------------------------------------------


class TestAotCacheUnit:
    def test_round_trip_is_bit_identical(self, aot_dir):
        """A deserialized executable computes exactly what the freshly
        compiled one did, and the hit/miss ledger attributes both
        installs correctly."""
        x = jnp.arange(64, dtype=jnp.int32)

        w_cold = instrument_jit(
            "rt-kern", jax.jit(lambda v: (v * 7 + 3) % 11), aot_key="rt"
        )
        cold = np.asarray(w_cold(x))
        s = aot_dir.summary()
        # cold install consulted the (empty) cache, then serialized
        assert s["misses"] == 1 and s["writes"] == 1 and s["hits"] == 0
        assert s["entries"] == 1

        # simulated restart: a fresh wrapper + fresh jit object; only
        # the disk entry survives
        w_warm = instrument_jit(
            "rt-kern", jax.jit(lambda v: (v * 7 + 3) % 11), aot_key="rt"
        )
        warm = np.asarray(w_warm(x))
        np.testing.assert_array_equal(cold, warm)
        s = aot_dir.summary()
        assert s["hits"] == 1 and s["misses"] == 1
        assert s["hit_rate"] == 0.5
        # the sentinel was told: an install is NOT a compile
        assert retrace.snapshot()["aot_installs"] == 1
        assert retrace.drain_events() == []

    def test_stale_fingerprint_evicted_and_recompiled(self, aot_dir):
        x = jnp.arange(8, dtype=jnp.int32)
        w = instrument_jit("stale-kern", jax.jit(lambda v: v + 1),
                           aot_key="sk")
        w(x)
        [path] = aot_dir._entry_paths()
        header, blob = AotExecutableCache._read_file(path)
        header["fingerprint"] = "jax0.0.0+jaxlib0.0.0+tpu+fakex8"
        with open(path, "wb") as f:
            f.write(json.dumps(header).encode() + b"\n" + blob)

        assert aot_dir.load("stale-kern", "sk") is None
        s = aot_dir.summary()
        assert s["stale_fingerprint"] == 1
        assert s["entries"] == 0  # evicted so the next store rewrites
        # the wrapper path silently falls back to compile — and re-bakes
        w2 = instrument_jit("stale-kern", jax.jit(lambda v: v + 1),
                            aot_key="sk")
        np.testing.assert_array_equal(
            np.asarray(w2(x)), np.arange(1, 9, dtype=np.int32)
        )
        assert aot_dir.summary()["writes"] == 2

    def test_corrupt_entry_recovery(self, aot_dir):
        """Torn/truncated files fall back to compile: counted, evicted,
        never raising into a solve."""
        x = jnp.arange(8, dtype=jnp.int32)
        w = instrument_jit("corrupt-kern", jax.jit(lambda v: v * 3),
                           aot_key="ck")
        w(x)
        [path] = aot_dir._entry_paths()
        raw = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(raw[: len(raw) // 2])  # torn mid-blob

        errors0 = aot_dir.summary()["load_errors"]
        w2 = instrument_jit("corrupt-kern", jax.jit(lambda v: v * 3),
                            aot_key="ck")
        np.testing.assert_array_equal(
            np.asarray(w2(x)), np.arange(8, dtype=np.int32) * 3
        )
        s = aot_dir.summary()
        assert s["load_errors"] >= errors0 + 1
        # no-header garbage is equally survivable: preload counts and
        # evicts it instead of aborting the aot_load boot phase
        junk = os.path.join(aot_dir.dir, f"junk{AOT_SUFFIX}")
        with open(junk, "wb") as f:
            f.write(b"\x00\x01\x02 not a cache entry")
        errors1 = aot_dir.summary()["load_errors"]
        pre = aot_dir.preload()
        assert pre["errors"] >= 1
        assert aot_dir.summary()["load_errors"] >= errors1 + 1
        assert not os.path.exists(junk)

    def test_atomic_writer_and_newest_n_retention(self, tmp_path):
        cache = configure_aot(str(tmp_path / "keepdir"), keep=3)
        try:
            compiled = jax.jit(lambda v: v * 2).lower(
                jnp.arange(4, dtype=jnp.int32)
            ).compile()
            for i in range(6):
                assert cache.store(f"k{i}", f"key{i}", compiled, 1.0)
                time.sleep(0.02)  # distinct mtimes for the prune order
            # newest 3 kept, no .tmp residue from the atomic writer
            assert cache.summary()["entries"] == 3
            assert not any(
                f.endswith(".tmp") for f in os.listdir(cache.dir)
            )
            assert cache.summary()["evictions"] == 3
            assert {e["kernel"] for e in cache.entries()} == {
                "k3", "k4", "k5"
            }
        finally:
            configure_aot("off")

    def test_preload_claims_into_lazy_load(self, aot_dir):
        x = jnp.arange(16, dtype=jnp.int32)
        w = instrument_jit("pre-kern", jax.jit(lambda v: v - 5),
                           aot_key="pk")
        expect = np.asarray(w(x))
        aot_dir.reset_stats()

        pre = aot_dir.preload()
        assert pre == {
            "enabled": True, "loaded": 1, "skipped": 0, "stale": 0,
            "errors": 0, "bytes": pre["bytes"],
        }
        assert pre["bytes"] > 0
        assert aot_dir.summary()["preloaded_pending"] == 1
        # the wrapper's install claims the parked executable — a hit
        # with zero disk reads in the solve path
        w2 = instrument_jit("pre-kern", jax.jit(lambda v: v - 5),
                            aot_key="pk")
        np.testing.assert_array_equal(np.asarray(w2(x)), expect)
        s = aot_dir.summary()
        assert s["hits"] == 1 and s["preloaded_pending"] == 0

    def test_loaded_executable_rejecting_call_recompiles(self, aot_dir):
        """An under-keyed/foreign entry whose avals reject the first
        real call degrades to a fresh compile — counted, correct."""
        w8 = instrument_jit("aval-kern", jax.jit(lambda v: v + 2),
                            aot_key="shared")
        w8(jnp.arange(8, dtype=jnp.int32))  # bakes an (8,) executable

        w16 = instrument_jit("aval-kern", jax.jit(lambda v: v + 2),
                             aot_key="shared")
        out = np.asarray(w16(jnp.arange(16, dtype=jnp.int32)))
        np.testing.assert_array_equal(
            out, np.arange(16, dtype=np.int32) + 2
        )
        s = aot_dir.summary()
        assert s["hits"] == 1  # the load itself succeeded...
        assert s["load_errors"] == 1  # ...but its first call rejected

    @pytest.mark.parametrize("n_dev", [1, 4], ids=["device0", "mesh4"])
    def test_loads_onto_the_devices_it_was_compiled_for(
        self, aot_dir, n_dev
    ):
        """An executable compiled for device 0 (or a 4-device mesh) of
        the 8 virtual devices is loaded onto exactly those devices and
        CALLED — deserialize_and_load's default of every backend device
        loads fine and only fails at the first call."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()[:n_dev]
        assert len(jax.devices()) > n_dev
        sharding = NamedSharding(Mesh(np.array(devs), ("x",)), P("x"))
        x = jax.device_put(jnp.arange(64, dtype=jnp.int32), sharding)
        fn = jax.jit(lambda v: (v * 5 + 1).sum() + v)
        compiled = fn.lower(x).compile()
        assert aot_dir.store("dev-kern", f"d{n_dev}", compiled, 1.0)
        [entry] = aot_dir._entry_paths()
        header, _ = AotExecutableCache._read_file(entry)
        assert header["device_ids"] == [d.id for d in devs]

        loaded = aot_dir.load("dev-kern", f"d{n_dev}")
        assert loaded is not None
        out = loaded(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(fn(x)))
        assert {d.id for d in out.sharding.device_set} == {
            d.id for d in devs
        }

    def test_entry_for_absent_devices_is_a_counted_miss(self, aot_dir):
        compiled = jax.jit(lambda v: v + 1).lower(
            jnp.arange(4, dtype=jnp.int32)
        ).compile()
        assert aot_dir.store("gone-kern", "gk", compiled, 1.0)
        [path] = aot_dir._entry_paths()
        header, blob = AotExecutableCache._read_file(path)
        header["device_ids"] = [len(jax.devices()) + 7]
        with open(path, "wb") as f:
            f.write(json.dumps(header).encode() + b"\n" + blob)
        assert aot_dir.load("gone-kern", "gk") is None
        s = aot_dir.summary()
        assert s["misses"] == 1 and s["hits"] == 0 and s["entries"] == 0

    def test_disabled_cache_is_total_noop(self):
        cache = configure_aot("off")
        compiled = jax.jit(lambda v: v).lower(
            jnp.arange(4, dtype=jnp.int32)
        ).compile()
        assert cache.enabled is False
        assert cache.store("k", "key", compiled) is False
        assert cache.load("k", "key") is None
        assert cache.preload() == {"enabled": False}
        assert all(cache.summary()[f] == 0 for f in AOT_COUNTER_FIELDS)

    def test_configure_resolution(self, tmp_path, monkeypatch):
        try:
            # empty spec consults the env var; empty env = stays off
            monkeypatch.delenv("OPENR_TPU_AOT_CACHE", raising=False)
            assert configure_aot("").enabled is False
            monkeypatch.setenv(
                "OPENR_TPU_AOT_CACHE", str(tmp_path / "envdir")
            )
            assert configure_aot("").dir == str(tmp_path / "envdir")
            # disable words beat the env var
            assert configure_aot("off").enabled is False
            assert configure_aot("0").enabled is False
            # auto sits under the one cache root, wherever that is
            monkeypatch.setenv(
                "JAX_COMPILATION_CACHE_DIR", str(tmp_path / "root")
            )
            auto = configure_aot("auto")
            assert auto.dir == str(tmp_path / "root" / "aot")
            # keep re-point preserves the knob
            keep = configure_aot(str(tmp_path / "kd"), keep=7)
            assert keep.keep == 7
            assert get_aot() is keep
        finally:
            configure_aot("off")


# -- where compiled code is kept -------------------------------------------


@pytest.mark.parametrize(
    "jax_env, arg, ours_env, expect",
    [
        # placed from outside: jax's own variable beats everything and
        # the program sets no directory in code
        ("{tmp}/outside", "{tmp}/arg", "{tmp}/ours", "{tmp}/outside"),
        ("", "{tmp}/arg", "{tmp}/ours", "{tmp}/arg"),
        ("", None, "{tmp}/ours", "{tmp}/ours"),
        # nothing set: one fixed path inside the checkout
        ("", None, "", "{tmp}/checkout/.jax_cache"),
        ("{tmp}/outside", None, "off", None),
    ],
    ids=["jax-env-wins", "explicit", "ours-env", "in-checkout", "off"],
)
def test_compile_cache_placement(
    fresh_xla_cache_state, tmp_path, monkeypatch, jax_env, arg, ours_env,
    expect,
):
    xc = fresh_xla_cache_state

    def sub(v):
        return v.format(tmp=tmp_path) if v else v

    monkeypatch.setenv("OPENR_TPU_XLA_CACHE", sub(ours_env))
    if jax_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", sub(jax_env))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(
            xc, "cache_root",
            lambda: str(tmp_path / "checkout" / ".jax_cache"),
        )
    before = jax.config.jax_compilation_cache_dir
    got = xc.enable_compilation_cache(sub(arg))
    assert got == sub(expect)
    if jax_env or expect is None:
        assert jax.config.jax_compilation_cache_dir == before
    else:
        assert jax.config.jax_compilation_cache_dir == sub(expect)
    if expect is not None:
        assert os.path.isdir(sub(expect))


def test_cache_root_is_fixed_inside_the_checkout(monkeypatch):
    import openr_tpu.ops.xla_cache as xc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert xc.cache_root() == os.path.join(repo, ".jax_cache")
    assert xc.cache_root() == xc.cache_root()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert xc.cache_root() == "/some/dir"


# -- speculative baker -----------------------------------------------------


class TestSpeculativeBaker:
    def test_dedups_by_label_and_counts(self, aot_dir):
        ran: list[int] = []
        assert baker.submit("lbl-a", lambda: ran.append(1)) is True
        assert baker.submit("lbl-a", lambda: ran.append(2)) is False
        assert baker.drain(30)
        assert ran == [1]
        assert aot_dir.summary()["speculative_bakes"] == 1

    def test_bake_errors_counted_not_raised(self, aot_dir):
        def boom() -> None:
            raise RuntimeError("synthetic bake failure")

        assert baker.submit("lbl-boom", boom) is True
        assert baker.drain(30)
        assert aot_dir.summary()["speculative_errors"] == 1

    def test_next_shape_key_doubles_node_proportional_caps(self):
        key = (16, 4, 8, 4, True, 4, 16, 1)
        assert _next_shape_key(key) == (32, 4, 16, 4, True, 4, 32, 1)
        # a residual-free class holds r_cap
        key = (16, 4, 8, 4, False, 4, 16, 1)
        assert _next_shape_key(key) == (32, 4, 8, 4, False, 4, 32, 1)

    def test_pipeline_avals_cover_the_15_arg_closure(self):
        key = (16, 4, 8, 4, True, 4, 16, 1)
        avals = _pipeline_avals(key)
        assert len(avals) == 15
        assert avals[0].shape == (4,)  # deltas [S]
        assert avals[1].shape == (4, 16)  # shift_w [S, N]
        assert avals[5].shape == (6 * 16 * 1,)  # packed matrix buffer
        assert avals[6].shape == ()  # root scalar
        assert avals[9].shape == ()  # want_full scalar
        assert avals[10].shape == (16,)  # prev metric [P]


# -- solver-level: warm restart + tier flip --------------------------------


class TestSolverWarmRestart:
    def test_warm_restart_zero_compiles_bit_identical(self, aot_dir):
        """The acceptance drill in miniature: solve cold (populating
        the disk cache), drop EVERY piece of in-memory compiled state a
        process restart would drop, preload, and re-solve — the warm
        solve must serve all executable lookups from disk, perform zero
        in-scope XLA compiles, and produce the identical RIB."""
        states, ps, me = _grid_states(4)
        oracle = SpfSolver(me).build_route_db(me, states, ps)

        # cold as a fresh process is: under xdist the worker brings the
        # executables of the files before this one, and one of this
        # shape class would be called, not compiled and written
        clear_all_jit_caches()
        cold = TpuSpfSolver(me)
        rib_cold = cold.build_route_db(me, states, ps)
        assert_rib_equal(oracle, rib_cold, "cold solve")
        assert aot_dir.summary()["writes"] >= 1

        # simulated process restart (bench.py boot A/B runs the same
        # sequence): the disk cache survives, nothing in memory does
        clear_all_jit_caches()
        jax.clear_caches()
        retrace.reset()
        aot_dir.reset_stats()
        pre = aot_dir.preload()
        assert pre["loaded"] >= 1

        scoped0 = _counter("xla_cache.scoped_compiles")
        warm = TpuSpfSolver(me)
        rib_warm = warm.build_route_db(me, states, ps)
        assert_rib_equal(oracle, rib_warm, "warm restart solve")

        s = aot_dir.summary()
        assert s["hits"] >= 1, s
        assert s["misses"] == 0, s  # every lookup served from disk
        assert s["hit_rate"] == 1.0
        # the sentinel proves it: installs, no in-scope compiles, no
        # retrace (or warm-violation) events
        assert _counter("xla_cache.scoped_compiles") == scoped0
        assert retrace.snapshot()["aot_installs"] >= 1
        assert retrace.drain_events() == []

    def test_speculative_next_class_bakes_on_dispatch(self, aot_dir):
        """ISSUE 20 tier-flip drill: a grid(4) (n_cap 16) solve with
        speculation on hands the baker the n_cap-32 class; a grid(5)
        fabric (25 nodes -> n_cap 32) then finds its full-solve
        executable already installed AND persisted."""
        states4, ps4, me4 = _grid_states(4)
        # fuse_n_cap=1 forces the unfused per-vantage dispatch — the
        # tier that speculates (fused batches never flip capacity)
        solver = TpuSpfSolver(me4, aot_speculate=True, fuse_n_cap=1)
        rib4 = solver.build_route_db(me4, states4, ps4)
        assert_rib_equal(
            SpfSolver(me4).build_route_db(me4, states4, ps4),
            rib4, "grid(4) with speculation",
        )
        assert baker.drain(300), "speculative bake did not finish"
        s = aot_dir.summary()
        assert s["speculative_bakes"] >= 1, s
        # the baked entry is the NEXT class up — the one grid(5) pads to
        kernels = {e["kernel"] for e in aot_dir.entries()}
        assert any("pipeline[n=32" in (k or "") for k in kernels), kernels

        # tier flip: the grown fabric's first solve converges and is
        # bit-identical — its executable was installed by the baker
        # (speculation off here: a background bake of the NEXT class
        # would race the miss-free assertion below)
        states5, ps5, me5 = _grid_states(5)
        solver5 = TpuSpfSolver(me5, fuse_n_cap=1)
        misses0 = aot_dir.summary()["misses"]
        rib5 = solver5.build_route_db(me5, states5, ps5)
        assert_rib_equal(
            SpfSolver(me5).build_route_db(me5, states5, ps5),
            rib5, "post-flip grid(5)",
        )
        # the flip's full-solve kernel never missed the cache: either
        # primed in-memory (zero lookups) or served from the baked file
        assert aot_dir.summary()["misses"] == misses0
