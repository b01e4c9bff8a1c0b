"""The solver's device programs compile for a TPU v5e that is described,
not attached (ISSUE 23 step 4).

No Pallas kernel exists here: the "kernels" are the jitted pipeline
factories of decision/tpu_solver.py and ops/ksp2.py. A CPU solve is run
with every factory wrapped by a recorder, which keeps each program's
jitted callable and the shapes of its first call; each recorded program
is then lowered with ShapeDtypeStructs placed on a described v5e:2x2
device and compiled by the TPU compiler installed here. What that
compiler refuses — a lowering it has no rule for, a donation it cannot
alias, a program that does not fit 16 GB — fails here, at no chip time.
The multichip variants are rebuilt by the one pipeline factory from their
variant records, on a 4-device Mesh of the described devices (the closure
binds the mesh), and their text must show the cross-device min.

Runs at the n_cap 4096 capacity class (2-3 s per program). The real
class, n_cap 131072, takes ~85 s per program: CHANGES.md PR 23 records
that run, made by hand with this module's `Capture` and scenarios.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU's library, and every xdist
worker imports every test file (on-chip-measurement guide §2).
"""

import dataclasses
import os
import re

import jax
import numpy as np
import pytest

from chip_smoke import set_metric
from openr_tpu.decision import tpu_solver as ts
from openr_tpu.models import topologies
from openr_tpu.ops import ksp2 as ksp2_ops
from openr_tpu.ops import xla_cache
from openr_tpu.types import PrefixForwardingAlgorithm, PrefixForwardingType

SIDE = 50  # 2500 nodes -> the n_cap 4096 class


# -- recording the programs a CPU solve builds -----------------------------


def _aval(x):
    return jax.ShapeDtypeStruct(
        np.shape(x), jax.dtypes.canonicalize_dtype(np.result_type(x))
    )


class Capture:
    """Wraps the solver's program factories; `programs` maps a label to
    (jitted callable, avals of its first call), `mesh_programs` a kind
    ("full", "incremental") to (variant record, avals)."""

    SINGLE_CHIP_FACTORIES = (
        (ts, "_scatter_jit"),
        (ksp2_ops, "_base_sssp_fn"),
        (ksp2_ops, "_masked_rows_fn"),
        (ksp2_ops, "_masked_rows_delta_fn"),
    )

    def __init__(self):
        self.programs: dict = {}
        self.mesh_programs: dict = {}
        self._undo: list = []
        self._real_instrument_jit = xla_cache.instrument_jit
        self._real_pipeline_for = ts.pipeline_for

    def __enter__(self):
        # the factories memoize their (already wrapped) results
        xla_cache.clear_all_jit_caches()
        self._patch(xla_cache, "instrument_jit", self._instrument_jit)
        self._patch(ts, "pipeline_for", self._pipeline_for)
        for mod, name in self.SINGLE_CHIP_FACTORIES:
            self._patch(mod, name, self._factory(name, getattr(mod, name)))
        return self

    def __exit__(self, *exc):
        for mod, name, old in reversed(self._undo):
            setattr(mod, name, old)
        xla_cache.clear_all_jit_caches()

    def _patch(self, mod, name, new) -> None:
        self._undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, new)

    def _instrument_jit(self, name, jitted, aot_key=None):
        run = self._real_instrument_jit(name, jitted, aot_key=aot_key)
        if name.startswith("pipeline_mc"):
            return run  # recorded by _pipeline_for, with its record

        def wrapper(*args, **kwargs):
            self.programs.setdefault(
                name, (jitted, jax.tree.map(_aval, args))
            )
            return run(*args, **kwargs)

        wrapper.__dict__.update(run.__dict__)
        return wrapper

    def _pipeline_for(self, variant):
        """A mesh-bound pipeline is recorded as its variant record, so
        the one factory can rebuild it on a mesh of described devices."""
        name, run = self._real_pipeline_for(variant)
        if variant.mesh is None:
            return name, run

        def call(*args):
            self.mesh_programs.setdefault(
                "incremental" if variant.incr else "full",
                (variant, jax.tree.map(_aval, args)),
            )
            return run(*args)

        return name, call

    def _factory(self, label, factory):
        def wrapped(*fargs):
            jitted = factory(*fargs)

            def call(*args):
                self.programs.setdefault(
                    f"{label}{fargs}", (jitted, jax.tree.map(_aval, args))
                )
                return jitted(*args)

            return call

        return wrapped


# -- scenarios: what a user's solves build, per variant --------------------


def _grid(side: int, ksp2_every: int = 0):
    adj_dbs, prefix_dbs = topologies.grid(side, node_labels=bool(ksp2_every))
    if ksp2_every:
        prefix_dbs = [
            dataclasses.replace(db, prefix_entries=tuple(
                dataclasses.replace(
                    e,
                    forwarding_type=PrefixForwardingType.SR_MPLS,
                    forwarding_algorithm=(
                        PrefixForwardingAlgorithm.KSP2_ED_ECMP
                    ),
                )
                for e in db.prefix_entries
            )) if i % ksp2_every == ksp2_every // 2 else db
            for i, db in enumerate(prefix_dbs)
        ]
    return adj_dbs, prefix_dbs, f"node-{side // 2}-{side // 2}"


def solve_then_churn(side: int, ksp2_every: int = 0, churn: int = 1, **kw):
    """A cold solve, then `churn` link-metric changes each followed by a
    warm solve — on one solver, through LinkState's own update path (the
    solver's delta sync reads its change journal)."""
    adj_dbs, prefix_dbs, me = _grid(side, ksp2_every)
    states, prefix_state = topologies.build_states(adj_dbs, prefix_dbs)
    solver = ts.TpuSpfSolver(me, **kw)
    assert solver.build_route_db(me, states, prefix_state) is not None
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    far, farther = (f"node-{side // 2}-{side - k}" for k in (2, 1))
    for step in range(churn):
        for db in set_metric(adj_dbs, index, far, farther, 3 + step):
            states[db.area].update_adjacency_database(db)
        assert solver.build_route_db(me, states, prefix_state) is not None
    return solver


def run_scenarios(side: int, ksp2_every: int, mc_threshold: int) -> Capture:
    with Capture() as cap:
        # Decision's default: full solve, then the incremental kernel
        solve_then_churn(side, incremental_spf=True)
        # the same with LFA: two more columns a row, in both bodies of
        # the narrow program's row stages
        solve_then_churn(side, incremental_spf=True, enable_lfa=True)
        # KSP2: base field, masked batch, then the delta batch
        solve_then_churn(side, ksp2_every, churn=2, incremental_spf=True)
        # the multichip tier, full and incremental
        solve_then_churn(
            side, incremental_spf=True,
            multichip_n_cap_threshold=mc_threshold,
        )
    return cap


# -- compiling them for the described chip ---------------------------------


def compile_single(one_chip, jitted, avals):
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        avals,
    )
    return jitted.lower(*args).compile()


def compile_mesh(mesh, variant, avals):
    # straight through the factory, past its caches: a mesh of described
    # devices must not stay behind as a live executable's key.
    # in_shardings are pinned by the factory: bare shapes suffice
    _name, run = ts._build_pipeline(*variant._replace(mesh=mesh))
    return run.jitted.lower(*avals).compile()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies as jax_topologies

    try:
        return jax_topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    # lint: allow(broad-except) any failure to describe means skip
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run would warn
    and compile again): keep the cache out of it. The program's own
    AOT store too: where an earlier test file of this process left it
    on, a second run in one checkout is answered from disk and lowers
    nothing for the capture to record."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    old_aot = xla_cache.get_aot().dir
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    xla_cache.configure_aot("off")
    yield
    xla_cache.configure_aot(old_aot or "off")
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def captured(topo, cache_off):
    return run_scenarios(SIDE, ksp2_every=300, mc_threshold=1024)


VARIANTS = {
    # variant -> (label prefix, with LFA?, must the text alias a
    # donated input?)
    "full": ("pipeline[n=4096,s=4,d=4,p=4096,a=2,bk1]", False, False),
    "incremental": ("pipeline_incr[n=4096,", False, False),
    "full_lfa": ("pipeline[n=4096,s=4,d=4,p=4096,a=2,lfa,bk1]", True, False),
    "incremental_lfa": ("pipeline_incr[n=4096,", True, False),
    "delta_scatter_donating": ("_scatter_jit", False, True),
    "ksp2_base": ("_base_sssp_fn", False, False),
    "ksp2_masked_batch": ("_masked_rows_fn", False, False),
    "ksp2_delta_batch": ("_masked_rows_delta_fn", False, False),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_program_compiles_for_a_described_v5e(captured, one_chip, variant):
    prefix, lfa, donating = VARIANTS[variant]
    labels = [
        k for k in captured.programs
        if k.startswith(prefix) and (",lfa" in k) == lfa
    ]
    assert labels, (variant, sorted(captured.programs))
    for label in labels:
        compiled = compile_single(one_chip, *captured.programs[label])
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 16 << 30, (label, mem)
        if donating:
            assert "input_output_alias" in compiled.as_text(), label


@pytest.mark.parametrize("variant", ["full", "incremental"])
def test_multichip_program_compiles_on_a_described_mesh(
    captured, topo, variant
):
    from jax.sharding import Mesh

    assert len(topo.devices) == 4
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "graph"))
    record, avals = captured.mesh_programs[variant]
    assert record.incr == (variant == "incremental")
    compiled = compile_mesh(mesh, record, avals)
    # the 'graph' axis shards the weight state: each relaxation round
    # ends in a cross-device min (the pmin of parallel/sharding.py)
    text = compiled.as_text()
    assert "all-reduce" in text and "minimum" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


@pytest.mark.parametrize("narrow", [False, True], ids=["all_rows", "narrow"])
def test_the_prefix_plane_class_compiles_with_one_conditional(
    one_chip, cache_off, narrow
):
    """fabric10k_pfx's incremental executable (524,288 rows over 16,384
    node columns, LFA, the 4,096-row budget) from its variant record
    alone: the cold pull's half of `compact` is one conditional of the
    entry computation, and neither compaction left a scan over every row
    behind (`jnp.nonzero`'s cumsum is a reduce-window of 524,288: 5 s of
    this compile each, 40 s inside a conditional; PERF.md section 6,
    PR 37). The `narrow` variant (ISSUE 44, what the dispatcher asks
    for) has that text whole as one side of a second conditional, the
    row stages over 4,096 candidate rows as the other, and the mask that
    finds them on the flat planes: no [524288, 2] array outside the
    all-rows side."""
    S = jax.ShapeDtypeStruct
    key = (16384, 1, 32768, 8, True, 8, 524288, 2)
    record = ts.PipelineVariant.checked(
        *key, ts._DELTA_BUDGET, True, True, True, emit_dist=True,
        dirty_cap=64, narrow=narrow,
    )
    avals = ts._pipeline_avals(key) + (
        S((8, 16384), np.int32),
        *(S((64,), np.int32) for _ in range(4)),
        S((), np.int32),
    )
    if narrow:
        avals += (S((ts._DELTA_BUDGET,), np.int32), S((), np.int32))
    _name, run = ts._build_pipeline(*record)
    compiled = compile_single(one_chip, run.jitted, avals)
    text = compiled.as_text()
    assert text.count(" conditional(") == 1 + narrow
    cold = "cond/branch_1_fun/compact/cond" if narrow else "compact/cond"
    assert f'op_name="jit(pipeline)/{cold}"' in text
    # the blocks' totals are scanned ([32, 128]); the rows are not
    assert re.search(r"= s32\[32,128\]\S* reduce-window\(", text)
    assert not re.search(r"= s32\[4096,128\]\S* reduce-window\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30
    if narrow:
        # the mask's operations work on vectors of all 1,048,576 cells
        # or shorter, never on a plane two cells wide
        mask = [
            line for line in text.splitlines()
            if 'op_name="jit(pipeline)/candidates/' in line
        ]
        assert mask and not any("[524288,2]" in line for line in mask)


def test_the_prefix_only_solve_compiles_with_no_loop(one_chip, cache_off):
    """fabric10k_pfx's prefix-only executable (`rows_only`: the row
    stages over 64 candidate rows of the 524,288 and the resident
    [8, 16384] plane) from its variant record alone: no relaxation and
    no cone, so no `while` at all; no cold pull, so no conditional; and
    no temporary the size of a [524288, 2] plane padded to lane tiles
    (the all-rows stages plan hundreds of MB of them). And the scatter
    that brings a changed row's cells into the 6,291,456 words of the
    announcer matrix, at the one bucket of 64 rows (768 cells) every
    prefix event of a window takes."""
    S = jax.ShapeDtypeStruct
    key = (16384, 1, 32768, 8, True, 8, 524288, 2)
    record = ts.PipelineVariant.checked(
        *key, ts._DELTA_BUDGET, True, True, True, rows_only=64,
    )
    assert record.namespace == "incr" and not record.emit_dist
    assert record.name.startswith("pipeline_rows[")
    avals = ts._pipeline_avals(key) + (
        S((8, 16384), np.int32), S((64,), np.int32),
    )
    _name, run = ts._build_pipeline(*record)
    compiled = compile_single(one_chip, run.jitted, avals)
    text = compiled.as_text()
    assert " while(" not in text
    assert " conditional(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    cells = 64 * 6 * 2
    scatter = compile_single(one_chip, ts._scatter_jit(), (
        S((6 * 524288 * 2,), np.int32), S((cells,), np.int32),
        S((cells,), np.int32),
    ))
    assert scatter.memory_analysis().temp_size_in_bytes < 1 << 30


def test_the_four_advertiser_class_compiles(one_chip, cache_off):
    """wan50k_region's incremental executable (ISSUE 47: 65,536 prefix
    rows of FOUR announcer slots over a 1,024-column node plane, LFA, the
    `narrow` variant the dispatcher asks for) from its variant record
    alone. Every cell before it ran `a_cap` 2 with one slot in use; here
    `select`, `nexthop` and `lfa` reduce and gather over four live cells a
    row, the flat candidate mask is [262144] long, and an uplink's step or
    a border router's drain takes the all-rows side and the cold pull in
    one epoch. The compiler plans ~0.4 GB of temporaries for it."""
    S = jax.ShapeDtypeStruct
    key = (1024, 4, 2048, 4, True, 4, 65536, 4)
    record = ts.PipelineVariant.checked(
        *key, ts._DELTA_BUDGET, True, True, True, emit_dist=True,
        dirty_cap=64, narrow=True,
    )
    assert "a=4" in record.name
    avals = ts._pipeline_avals(key) + (
        S((4, 1024), np.int32),
        *(S((64,), np.int32) for _ in range(4)),
        S((), np.int32),
        S((ts._DELTA_BUDGET,), np.int32), S((), np.int32),
    )
    _name, run = ts._build_pipeline(*record)
    compiled = compile_single(one_chip, run.jitted, avals)
    text = compiled.as_text()
    assert text.count(" conditional(") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    mask = [
        line for line in text.splitlines()
        if 'op_name="jit(pipeline)/candidates/' in line
    ]
    assert mask and not any("[65536,4]" in line for line in mask)
