"""The one seam that builds the solver's device programs (ISSUE 31):
`tpu_solver.PipelineVariant` says which executable, `pipeline_for`
fetches it from the one factory. What the rest of the system reads off
that seam is pinned here — display names letter for letter (the kernel
ledger, ctrl.tpu.kernels, the benchmark's warm-up check and the replay
log carry them), an identity no two variants share, the jit-cache
namespace and bucket an executable lands in, and the combinations that
do not exist."""

import itertools

import jax
import numpy as np
import pytest

from openr_tpu.decision import tpu_solver as ts
from openr_tpu.decision.tpu_solver import PipelineVariant, pipeline_for
from openr_tpu.ops.xla_cache import bounded_jit_cache
from openr_tpu.runtime.counters import counters

BUDGET = 4096
# what makes each kind, beside the shape class and the flags
KINDS = {
    "full": {},
    "incr": {"emit_dist": True, "dirty_cap": 64},
    "fused": {"fused": 3},
    "mc": {"mesh": True},
    "mc_incr": {"emit_dist": True, "dirty_cap": 64, "mesh": True},
    # the prefix-only solve: the row stages over a bucket of candidate
    # rows and the resident plane
    "rows": {"rows_only": 64},
    # the incremental solve the dispatcher asks for on one chip: its row
    # stages may run over candidate rows
    "narrow": {"emit_dist": True, "dirty_cap": 64, "narrow": True},
}
NAMESPACE = {
    "full": "", "fused": "", "incr": "incr",
    "mc": "multichip", "mc_incr": "multichip", "rows": "incr",
    "narrow": "incr",
}

# (kind, has_res, lfa, delta_exp) -> the name the parent's six
# _instrumented_* composed, written down before they were deleted
GOLDEN = {
    ("full", False, False, 0): "pipeline[n=256,s=4,d=4,p=256,a=2]",
    ("full", False, False, 3): "pipeline[n=256,s=4,d=4,p=256,a=2,bk3]",
    ("full", False, True, 0): "pipeline[n=256,s=4,d=4,p=256,a=2,lfa]",
    ("full", False, True, 3): "pipeline[n=256,s=4,d=4,p=256,a=2,lfa,bk3]",
    ("full", True, False, 0): "pipeline[n=256,s=4,d=4,p=256,a=2,res]",
    ("full", True, False, 3): "pipeline[n=256,s=4,d=4,p=256,a=2,res,bk3]",
    ("full", True, True, 0): "pipeline[n=256,s=4,d=4,p=256,a=2,res,lfa]",
    ("full", True, True, 3):
        "pipeline[n=256,s=4,d=4,p=256,a=2,res,lfa,bk3]",
    ("incr", False, False, 0):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64]",
    ("incr", False, False, 3):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,bk3]",
    ("incr", False, True, 0):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,lfa]",
    ("incr", False, True, 3):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,lfa,bk3]",
    ("incr", True, False, 0):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res]",
    ("incr", True, False, 3):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,bk3]",
    ("incr", True, True, 0):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,lfa]",
    ("incr", True, True, 3):
        "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,lfa,bk3]",
    ("fused", False, False, 0):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2]",
    ("fused", False, False, 3):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,bk3]",
    ("fused", False, True, 0):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,lfa]",
    ("fused", False, True, 3):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,lfa,bk3]",
    ("fused", True, False, 0):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res]",
    ("fused", True, False, 3):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res,bk3]",
    ("fused", True, True, 0):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res,lfa]",
    ("fused", True, True, 3):
        "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res,lfa,bk3]",
    ("mc", False, False, 0):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2]",
    ("mc", False, False, 3):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,bk3]",
    ("mc", False, True, 0):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,lfa]",
    ("mc", False, True, 3):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,lfa,bk3]",
    ("mc", True, False, 0):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res]",
    ("mc", True, False, 3):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res,bk3]",
    ("mc", True, True, 0):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res,lfa]",
    ("mc", True, True, 3):
        "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res,lfa,bk3]",
    ("mc_incr", False, False, 0):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2]",
    ("mc_incr", False, False, 3):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,bk3]",
    ("mc_incr", False, True, 0):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,lfa]",
    ("mc_incr", False, True, 3):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,lfa,bk3]",
    ("mc_incr", True, False, 0):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res]",
    ("mc_incr", True, False, 3):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res,bk3]",
    ("mc_incr", True, True, 0):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res,lfa]",
    ("mc_incr", True, True, 3):
        "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res,lfa,bk3]",
}
# the prefix-only solve has no parent: it is the full solve's shape
# class and flags under a kernel name of its own, with its row bucket
GOLDEN.update({
    ("rows", *key[1:]): name.replace("pipeline[", "pipeline_rows[").replace(
        "a=2", "a=2,rr=64"
    )
    for key, name in list(GOLDEN.items()) if key[0] == "full"
})
# the narrow incremental solve is the incremental one by name: the
# kernel ledger, the benchmark's warm-up check and the replay log read
# what they read before
GOLDEN.update({
    ("narrow", *key[1:]): name
    for key, name in list(GOLDEN.items()) if key[0] == "incr"
})
FLAGS = list(itertools.product((False, True), (False, True), (0, 3)))


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("batch", "graph"))


def variant(kind, mesh, has_res=True, lfa=False, delta_exp=0, n_cap=256,
            **over) -> PipelineVariant:
    what = dict(KINDS[kind])
    if what.get("mesh"):
        what["mesh"] = mesh
    what.update(over)
    what.setdefault("budget", BUDGET)
    return PipelineVariant.checked(
        n_cap, 4, 8, 4, has_res, 4, 256, 2, lfa=lfa,
        delta_exp=delta_exp, **what,
    )


def _count(key: str) -> float:
    return counters.get_counter(key) or 0


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_display_names_are_the_parents(kind, mesh):
    for has_res, lfa, delta_exp in FLAGS:
        v = variant(kind, mesh, has_res, lfa, delta_exp)
        assert v.name == GOLDEN[kind, has_res, lfa, delta_exp]
        assert v.kernel == ("bucketed" if delta_exp else "sync")
    # what the name leaves out is still part of the identity
    assert v._replace(block_v4=True).name == v.name
    assert v._replace(block_v4=True).aot_key != v.aot_key


def test_aot_keys_are_distinct_and_kinds_name_their_namespace(mesh):
    records = [
        variant(kind, mesh, *flags) for kind in KINDS for flags in FLAGS
    ]
    base = records[0]
    # every field that the display name omits, and the variant fields
    records += [
        base._replace(r_cap=16), base._replace(kr_cap=8),
        base._replace(budget=64), base._replace(block_v4=True),
        base._replace(sentinels=False), base._replace(emit_dist=True),
        variant("incr", mesh, dirty_cap=256),
        variant("fused", mesh, fused=2),
        variant("rows", mesh, rows_only=256),
        variant("narrow", mesh, dirty_cap=256),
    ]
    assert len(set(records)) == len(records)
    keys = {r.aot_key for r in records}
    assert len(keys) == len(records)
    # a key is text: the mesh rides as its tag, never as a device list
    assert all("mesh='2x2'" in r.aot_key for r in records if r.mesh)
    for kind in KINDS:
        v = variant(kind, mesh)
        assert v.namespace == NAMESPACE[kind], kind
        assert v.incr == ("dirty_cap" in KINDS[kind])
    assert set(ts._PIPELINE_CACHES) == set(NAMESPACE.values())
    # the row bucket is in the key of the executable that has one, and
    # the keys that were there before the field are what they were
    rows = variant("rows", mesh)
    assert rows.aot_key == variant("full", mesh).aot_key.replace(
        "mesh=None", "rows_only=64, mesh=None"
    )
    assert not any(
        "rows_only" in r.aot_key for r in records if not r.rows_only
    )
    assert variant("narrow", mesh).aot_key == variant(
        "incr", mesh
    ).aot_key.replace("mesh=None", "narrow=True, mesh=None")
    assert not any("narrow" in r.aot_key for r in records if not r.narrow)
    assert variant("full", mesh).aot_key == (
        "PipelineVariant(n_cap=256, s_cap=4, r_cap=8, kr_cap=4, "
        "has_res=True, d_cap=4, p_cap=256, a_cap=2, budget=4096, "
        "lfa=False, block_v4=False, sentinels=True, emit_dist=False, "
        "delta_exp=0, dirty_cap=0, fused=0, mesh=None)"
    )


def test_two_shape_classes_occupy_two_buckets(mesh):
    """bounded_jit_cache reads the capacity signature off the key's
    positional ints: the record goes in splatted, so a shape class is a
    bucket and a flag flip is a variant within it."""
    cache = bounded_jit_cache(max_buckets=2, namespace="variants_test")(
        ts._build_pipeline
    )
    evictions = "xla_cache.variants_test_executable_evictions"
    e0 = _count(evictions)
    for n_cap in (64, 128):
        for lfa in (False, True):
            cache(*variant("full", mesh, lfa=lfa, n_cap=n_cap))
    assert _count(evictions) == e0  # two buckets, two variants each
    cache(*variant("full", mesh, n_cap=512))
    # the third class drops the oldest bucket, with both its variants
    assert _count(evictions) == e0 + 2
    # dirty_cap, fused and budget are capacity ints as well
    h0 = _count("xla_cache.variants_test_factory_hits")
    for other in (
        variant("incr", mesh, n_cap=512),
        variant("fused", mesh, n_cap=512),
        variant("full", mesh, n_cap=512, budget=64),
    ):
        cache(*other)
    assert _count(evictions) == e0 + 2 + 2 + 1 + 1
    assert _count("xla_cache.variants_test_factory_hits") == h0


BAD = {
    "fused_incremental": dict(fused=2, dirty_cap=64, emit_dist=True),
    "fused_on_a_mesh": dict(fused=2, mesh=True),
    "incremental_without_the_plane": dict(dirty_cap=64),
    "rows_only_incremental": dict(
        rows_only=64, dirty_cap=64, emit_dist=True
    ),
    "rows_only_emitting_the_plane": dict(rows_only=64, emit_dist=True),
    "rows_only_fused": dict(rows_only=64, fused=2),
    "rows_only_on_a_mesh": dict(rows_only=64, mesh=True),
    "rows_only_past_a_delta_pull": dict(rows_only=2 * BUDGET),
    "narrow_full_solve": dict(narrow=True),
    "narrow_on_a_mesh": dict(
        narrow=True, dirty_cap=64, emit_dist=True, mesh=True
    ),
    "narrow_rows_only": dict(narrow=True, rows_only=64),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_combinations_that_do_not_exist_are_refused(case, mesh):
    what = dict(BAD[case])
    if what.get("mesh"):
        what["mesh"] = mesh
    with pytest.raises(ValueError):
        PipelineVariant.checked(256, 4, 8, 4, True, 4, 256, 2, BUDGET, **what)
    # the factory checks too: a raw record cannot smuggle one in
    raw = PipelineVariant(256, 4, 8, 4, True, 4, 256, 2, BUDGET, **what)
    with pytest.raises(ValueError):
        pipeline_for(raw)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_one_executable_per_record(kind, mesh):
    """Fetching a record twice is one miss and one hit in its kind's
    namespace, and the same callable (the parent cached the jitted
    program and its instrumented wrapper apiece: two misses)."""
    v = variant(kind, mesh, n_cap=1 << 20)  # a class no other test has
    ns = NAMESPACE[kind]
    prefix = f"xla_cache.{ns}_" if ns else "xla_cache."
    m0, h0 = _count(prefix + "factory_misses"), _count(prefix + "factory_hits")
    name, run = pipeline_for(v)
    assert name == v.name == run.kernel_name
    assert _count(prefix + "factory_misses") == m0 + 1
    assert _count(prefix + "factory_hits") == h0
    again = pipeline_for(PipelineVariant(*v))
    assert again[1] is run
    assert _count(prefix + "factory_misses") == m0 + 1
    assert _count(prefix + "factory_hits") == h0 + 1
    assert not run.is_installed()  # nothing compiled: the jit is lazy


def _avals(v: PipelineVariant) -> tuple:
    avals = ts._pipeline_avals(v.shape_key)
    if v.incr:
        S = jax.ShapeDtypeStruct
        dirty = S((v.dirty_cap,), np.int32)
        avals += (
            S((v.d_cap, v.n_cap), np.int32), dirty, dirty, dirty, dirty,
            S((), np.int32),
        )
    if v.rows_only:
        S = jax.ShapeDtypeStruct
        avals += (
            S((v.d_cap, v.n_cap), np.int32), S((v.rows_only,), np.int32),
        )
    if v.narrow:
        avals += (
            jax.ShapeDtypeStruct((v.budget,), np.int32),
            jax.ShapeDtypeStruct((), np.int32),
        )
    return avals


# sha256 (16 hex digits) of `jitted.lower(*avals).as_text()` of every
# kind that PR 42 found, at every combination of FLAGS, taken on
# the parent of PR 42 (560e0bc) with jax 0.9.0 — PR 31's check, kept: the
# row stages were lifted into one body (`tpu_solver._row_stages`) that
# the prefix-only program shares, and no other program may read another
# word for it. A PR that changes these programs on purpose writes the
# digests anew (`_lowered_digest` below prints what it finds); a change
# of jax may move them all at once.
LOWERED = {
    "pipeline[n=256,s=4,d=4,p=256,a=2,bk3]":
        "f40c5d4f1eccb28c",
    "pipeline[n=256,s=4,d=4,p=256,a=2,lfa,bk3]":
        "bd54ae60722d74f4",
    "pipeline[n=256,s=4,d=4,p=256,a=2,lfa]":
        "0eb84c4719d242b8",
    "pipeline[n=256,s=4,d=4,p=256,a=2,res,bk3]":
        "57af2efdec5e6c5c",
    "pipeline[n=256,s=4,d=4,p=256,a=2,res,lfa,bk3]":
        "4b0df23d3ebcb25c",
    "pipeline[n=256,s=4,d=4,p=256,a=2,res,lfa]":
        "35e08c33f52c7582",
    "pipeline[n=256,s=4,d=4,p=256,a=2,res]":
        "06c8f1c925ca3d02",
    "pipeline[n=256,s=4,d=4,p=256,a=2]":
        "fde8945907359b22",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,bk3]":
        "2a4aa037ad856b7a",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,lfa,bk3]":
        "72a55688745cec3a",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,lfa]":
        "b121d5ba07f0dc89",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res,bk3]":
        "d190e09a0004c607",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res,lfa,bk3]":
        "47f7c0489d28992c",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res,lfa]":
        "f5dc897044da6681",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2,res]":
        "8ea84e0336974fff",
    "pipeline_fused[g=3,n=256,s=4,d=4,p=256,a=2]":
        "c62f4da0819198c0",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,bk3]":
        "2ef5e651f7859760",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,lfa,bk3]":
        "6bc39b1a01a0853e",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,lfa]":
        "ce20afa0cbdb9e2e",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,bk3]":
        "fa9a57ccee98ad52",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,lfa,bk3]":
        "970f355be0b42567",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,lfa]":
        "5c876354db6eef67",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res]":
        "572aceaefaf34973",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64]":
        "ca9fa326087ca334",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,bk3]":
        "338a5a70112b1372",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,lfa,bk3]":
        "747df264345a898b",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,lfa]":
        "c94392866f1c2c90",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res,bk3]":
        "423c5adb640d21a3",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res,lfa,bk3]":
        "b85eae15daa6eb6b",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res,lfa]":
        "a4d7efac696f87af",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2,res]":
        "ce0ab1817fc4e953",
    "pipeline_mc[n=256,s=4,d=4,p=256,a=2,mesh=2x2]":
        "d4fae5dbf9c5abba",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,bk3]":
        "094a1cc3bc93dfb0",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,lfa,bk3]":
        "ba8bae09ae490d29",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,lfa]":
        "c119b93f51e905d7",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res,bk3]":
        "9da06852c1363670",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res,lfa,bk3]":
        "ca3c286c33ba53e0",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res,lfa]":
        "18fbd753f9da7c30",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2,res]":
        "3604bfec2bfe2d16",
    "pipeline_mc_incr[n=256,s=4,d=4,p=256,a=2,dd=64,mesh=2x2]":
        "061f12554fe6f56c",
}
# the two programs whose Python guards PR 46 removed with the streaming
# pipeline (the solver's knob before the prefix-only program, `not
# stream` before `narrow`), taken on its parent (ec0ec7a) before
# anything else was edited. The narrow incremental solve goes by the
# incremental one's names; the prefix-only program solves nothing, so
# neither the residual nor the kernel reaches its text.
LOWERED_NARROW = {
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,bk3]":
        "32068d8406a9b39e",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,lfa,bk3]":
        "50b9aa7d15d77127",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,lfa]":
        "9fe461aa04251553",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,bk3]":
        "3fed8406a78037fc",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,lfa,bk3]":
        "b6c2c580d2feb20c",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res,lfa]":
        "57d4def68539b78a",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64,res]":
        "2bdfabb4555c1b23",
    "pipeline_incr[n=256,s=4,d=4,p=256,a=2,dd=64]":
        "3a86b945483faa4f",
}
LOWERED_ROWS = {
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64,bk3]":
        "85b2b38e8b9b250a",
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64,lfa,bk3]":
        "1f7d19a8ef79a9fb",
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64,lfa]":
        "1f7d19a8ef79a9fb",
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64,res,bk3]":
        "85b2b38e8b9b250a",
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64,res,lfa,bk3]":
        "1f7d19a8ef79a9fb",
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64,res,lfa]":
        "1f7d19a8ef79a9fb",
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64,res]":
        "85b2b38e8b9b250a",
    "pipeline_rows[n=256,s=4,d=4,p=256,a=2,rr=64]":
        "85b2b38e8b9b250a",
}
PINNED = {"narrow": LOWERED_NARROW, "rows": LOWERED_ROWS}


def _lowered_digest(v: PipelineVariant) -> str:
    import hashlib

    avals = _avals(v)
    if v.fused:
        avals = tuple((a,) * v.fused for a in avals)
    text = pipeline_for(v)[1].jitted.lower(*avals).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_lowered_text_is_the_parents(kind, mesh):
    pinned = PINNED.get(kind, LOWERED)
    got = {}
    for flags in FLAGS:
        v = variant(kind, mesh, *flags)
        got[v.name] = _lowered_digest(v)
    assert got == {name: pinned[name] for name in got}


def test_the_prefix_only_program_is_another_at_every_bucket(mesh):
    """The one program that did change: it takes the plane and the
    candidate rows, and neither solves nor compacts a cold pull."""
    digests = set(LOWERED.values())
    for rows in ts._DIRTY_BUCKETS:
        v = variant("rows", mesh, lfa=True, rows_only=rows)
        text = pipeline_for(v)[1].jitted.lower(*_avals(v)).as_text()
        assert "stablehlo.while" not in text and "stablehlo.case" not in text
        assert f"tensor<{rows}xi32>" in text  # cand_rows
        digests.add(_lowered_digest(v))
    assert len(digests) == len(LOWERED) + len(ts._DIRTY_BUCKETS)


def test_the_narrow_incremental_program_is_another(mesh):
    """ISSUE 44's program: the incremental solve with a second body of
    the row stages, over a delta pull's worth of candidate rows, beside
    the all-rows text (whose every other program is the parent's, digest
    for digest, above) under one conditional on the device; two more
    arguments, the host's rows and its word; one more word in the tail
    of both pull buffers."""
    digests = set(LOWERED.values())
    for flags in FLAGS:
        v = variant("narrow", mesh, *flags)
        wide = variant("incr", mesh, *flags)
        assert v.name == wide.name and v.aot_key != wide.aot_key
        text = pipeline_for(v)[1].jitted.lower(*_avals(v)).as_text()
        base = pipeline_for(wide)[1].jitted.lower(*_avals(wide)).as_text()
        # the branch, and inside its all-rows side the cold pull's
        assert text.count("stablehlo.case") == 2
        assert base.count("stablehlo.case") == 1
        assert f"tensor<{BUDGET}xi32>" in text  # the host's rows
        outs = [
            jax.eval_shape(pipeline_for(r)[1].jitted, *_avals(r))
            for r in (v, wide)
        ]
        for buf in (0, 1):
            assert outs[0][buf].shape[0] == outs[1][buf].shape[0] + 1
        assert [o.shape for o in outs[0][2:]] == [
            o.shape for o in outs[1][2:]
        ]
        digests.add(_lowered_digest(v))
    assert len(digests) == len(LOWERED) + len(FLAGS)


def test_budget_alone_makes_another_executable(mesh):
    """tests/test_convergence_trace.py shrinks tpu_solver._DELTA_BUDGET
    mid-test to force a full pull: that works because the budget is part
    of an executable's identity, not read inside the traced program."""
    big = variant("full", mesh)
    small = big._replace(budget=64)
    assert big.name == small.name and big.aot_key != small.aot_key
    run_big, run_small = pipeline_for(big)[1], pipeline_for(small)[1]
    assert run_big is not run_small
    delta = [
        jax.eval_shape(r.jitted, *_avals(big))[0].shape[0]
        for r in (run_big, run_small)
    ]
    wa = wd = 1
    assert delta[0] - delta[1] == (BUDGET - 64) * (2 + wa + wd)


def test_jit_options_follow_from_the_record(mesh):
    def text(v):
        return pipeline_for(v)[1].jitted.lower(*_avals(v)).as_text()

    kept = text(variant("narrow", mesh))
    assert "jit_pipeline" in kept  # the HLO module's name
    # no pipeline donates: an abandoned prepare still finds `prev`
    assert "jax.buffer_donor" not in kept and "tf.aliasing_output" not in kept
    sharded = text(variant("mc_incr", mesh))
    assert "mhlo.sharding" in sharded or "sdy.sharding" in sharded
    fused = variant("fused", mesh)
    lowered = pipeline_for(fused)[1].jitted.lower(
        *((a,) * fused.fused for a in _avals(fused))
    )
    assert "jit_fused" in lowered.as_text()
    assert len(lowered.out_info) == fused.fused


def test_a_variant_moves_to_the_next_shape_class(mesh):
    v = variant("full", mesh, lfa=True, delta_exp=2, emit_dist=True)
    nxt = ts._next_shape_key(v.shape_key)
    up = v.at(nxt, mesh)
    assert up.shape_key == nxt and up.mesh is mesh
    assert up[8:-1] == v[8:-1]
    assert up.namespace == "multichip" and v.namespace == ""
    with pytest.raises(ValueError):
        variant("fused", mesh).at(nxt, mesh)
