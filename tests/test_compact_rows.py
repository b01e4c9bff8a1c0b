"""The `compact` scope's two contracts (ISSUE 37): what leaves the device
is bit for bit what left it before, and the cold pull's half runs only in
an epoch that reads it.

  rows     `ops/compact.rows_any` equals the reshaped `any`, and
           `ops/compact.first_true_rows` and `true_rows` equal
           `jnp.nonzero(mask, size=..., fill_value=...)[0]` for every
           mask: lengths from the smallest prefix plane a test builds
           (8 rows) to fabric10k_pfx's 524,288, every delta budget, masks
           empty, of one row, of a budget's worth less one, exactly, and
           plus one, full, and random from 1e-5 to 0.5;
  buffers  every dispatch of a solver under randomized churn — full,
           incremental — is replayed through the frozen
           pipeline of the parent commit (`jnp.nonzero` in both halves,
           the cold half unconditional): `delta_buf` equal in every
           epoch, `full_buf` equal whenever the host reads it, and zeros
           between its scalars whenever it does not; the five resident
           arrays equal in every epoch (a prefix-only solve writes only
           its candidate rows into them: tests/test_prefix_churn.py);
  predicate  `want_full | count > budget`: at budget + 1 changed rows
           exactly the cold half runs and at budget it does not, and a
           vantage with no table asks for the whole table though fewer
           rows than the budget differ from the zeroed planes;
  payload  what the incremental path downloads and what the host makes
           of it, with and without the two alternate columns: the
           device's changed set is the host column compare's under
           withdrawals, an idle epoch is one delta payload of zero rows
           and a busy one's bytes, and warm churn compiles nothing.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.decision import tpu_solver as ts
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.decision.column_delta import cols_changed_mask
from openr_tpu.ops import compact
from openr_tpu.runtime.counters import counters
from tests.test_incremental_spf import ME, _Churn, _cnt, _grid
from tests.test_tpu_solver import assert_rib_equal

# -- the helpers against jnp.nonzero ----------------------------------------

LENGTHS = [8, 64, 128, 4096, 131072, 524288]
SIZES = [64, 256, 1024, 4096]
MASKS = [
    "empty", "first", "last", "block-end", "block-start", "size-1", "size",
    "size+1", "all", "p1e-5", "p1e-3", "p0.03", "p0.5",
]


def _mask(kind: str, p: int, size: int) -> np.ndarray:
    rng = np.random.default_rng(zlib.crc32(f"{kind}/{p}/{size}".encode()))
    mask = np.zeros(p, bool)
    if kind == "first":
        mask[0] = True
    elif kind == "last":
        mask[-1] = True
    elif kind == "block-end":
        mask[min(127, p - 1)] = True
    elif kind == "block-start":
        mask[128 % p] = True
    elif kind.startswith("size"):
        n = min(size + int(kind[4:] or 0), p)
        mask[rng.choice(p, n, replace=False)] = True
    elif kind == "all":
        mask[:] = True
    elif kind.startswith("p"):
        mask = rng.random(p) < float(kind[1:])
    return mask


@functools.lru_cache(maxsize=None)
def _jitted(which: str, p: int, size: int):
    if which == "first":
        return jax.jit(lambda m: compact.first_true_rows(m, size, p))
    if which == "all":
        return jax.jit(lambda m: compact.true_rows(m, p))
    return jax.jit(lambda m: jnp.nonzero(m, size=size, fill_value=p)[0])


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("p", LENGTHS)
def test_first_true_rows_is_a_sized_nonzero(p, size, kind):
    mask = jnp.asarray(_mask(kind, p, size))
    want = np.asarray(_jitted("nonzero", p, size)(mask))
    got = np.asarray(_jitted("first", p, size)(mask))
    assert got.dtype == np.int32 and got.shape == (size,)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("p", LENGTHS)
def test_true_rows_is_a_full_size_nonzero(p, kind):
    mask = jnp.asarray(_mask(kind, p, 64))
    want = np.asarray(_jitted("nonzero", p, p)(mask))
    got = np.asarray(_jitted("all", p, p)(mask))
    assert got.dtype == np.int32 and got.shape == (p,)
    np.testing.assert_array_equal(got, want)


def test_first_true_rows_under_vmap():
    """A `fused` group's pipeline runs under vmap: one mask an area."""
    rng = np.random.default_rng(7)
    masks = jnp.asarray(rng.random((3, 512)) < 0.2)
    got = jax.vmap(lambda m: compact.first_true_rows(m, 64, 512))(masks)
    want = jnp.stack([
        jnp.nonzero(m, size=64, fill_value=512)[0] for m in masks
    ])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("p,a", [
    (8, 1), (8, 2), (64, 4), (256, 2), (16, 128), (8, 256), (24, 3),
    (524288, 2),
])
def test_rows_any_is_any_over_a_rows_cells(p, a):
    """`rows_any` (the prefix-only solve's sentinel pass over the flags
    plane) equals the reshaped `any` at every width: pooled a lane tile
    wide where a row's cells divide one, plain where they do not."""
    rng = np.random.default_rng(p * 131 + a)
    for density in (0.0, 0.02, 0.5, 1.0):
        cells = rng.random(p * a) < density
        got = np.asarray(jax.jit(
            lambda c: compact.rows_any(c, p, a)
        )(jnp.asarray(cells.astype(np.int32))))
        assert got.dtype == bool and got.shape == (p,)
        np.testing.assert_array_equal(got, cells.reshape(p, a).any(axis=1))


# -- the pipeline's buffers against the parent's ----------------------------


def _frozen_compact_changed_rows(changed, trips, metric, s3w, nhw,
                                 lfa_slot, lfa_metric, budget: int,
                                 p_cap: int, lfa: bool):
    """`compact_changed_rows` as PR 37's parent commit (8c7b8a0) has
    it, less the route-ok column that went with the streaming pipeline:
    the oracle of the delta half."""
    count = changed.sum().astype(jnp.int32)
    cidx = jnp.nonzero(changed, size=budget, fill_value=p_cap)[0]
    safe = jnp.clip(cidx, 0, p_cap - 1).astype(jnp.int32)
    parts = [
        count[None],
        trips[None].astype(jnp.int32),
        cidx.astype(jnp.int32),
        metric[safe],
        s3w[safe].ravel(),
        nhw[safe].ravel(),
    ]
    if lfa:
        parts += [lfa_slot[safe], lfa_metric[safe]]
    return count, parts


def _frozen_true_rows(mask, fill):
    return jnp.nonzero(mask, size=mask.shape[0], fill_value=fill)[0].astype(
        jnp.int32
    )


def parent_pipeline(monkeypatch, variant: ts.PipelineVariant):
    """The parent commit's pipeline for `variant`, as a jitted callable
    of today's arguments: both compactions by `jnp.nonzero`, and — called
    with want_full = 1, which `parent_buffers` does — the cold half in
    every epoch."""
    with monkeypatch.context() as m:
        m.setattr(compact, "compact_changed_rows",
                  _frozen_compact_changed_rows)
        m.setattr(compact, "true_rows", _frozen_true_rows)
        closure = ts._make_pipeline(
            *variant.shape_key, variant.budget, variant.lfa,
            variant.block_v4, variant.sentinels, variant.emit_dist,
            incr=variant.incr, mesh=None, kernel=variant.kernel,
            delta_exp=variant.delta_exp,
        )
    return jax.jit(closure)


def parent_buffers(oracle, args, variant=None) -> tuple:
    """(delta_buf, full_buf, the five resident arrays) of the parent's
    pipeline on a dispatch's arguments (want_full, argument 9, forced
    to 1; without the two last of a `narrow` variant's, the host's rows
    and its word: the parent looks at every row)."""
    args = list(args)
    if variant is not None and variant.narrow:
        args = args[:-2]
    args[9] = np.int32(1)
    delta_buf, full_buf, *resident = oracle(*args)
    return np.asarray(delta_buf), np.asarray(full_buf), [
        np.asarray(r) for r in resident[:5]
    ]


def record_variants(monkeypatch) -> dict:
    """id(executable) -> its variant record, for every `pipeline_for`
    from here on: `_run_exec` is handed the executable alone."""
    variants: dict = {}
    real_for = ts.pipeline_for

    def pipeline_for(variant):
        name, run = real_for(variant)
        variants[id(run)] = variant
        return name, run

    monkeypatch.setattr(ts, "pipeline_for", pipeline_for)
    return variants


def tail_len(variant: ts.PipelineVariant) -> int:
    """Scalars after the rows of either of the parent's pull buffers."""
    return 2 * variant.sentinels + 3 * variant.incr + 1


def split_looked(buf: np.ndarray, variant: ts.PipelineVariant) -> tuple:
    """(a pull buffer as the parent lays it out, the rows the row stages
    looked at): a `narrow` variant's tail carries that one word more, at
    [-5], before the cone's three and the rounds."""
    if not variant.narrow:
        return buf, None
    return np.delete(buf, len(buf) - 5), int(buf[-5])


class Recorder:
    """Wraps a solver's `_run_exec`: every dispatch's arguments go
    through the parent's pipeline, then to the executable under test
    (neither donates them), and the two pairs of
    buffers are compared by the contract. `epochs` keeps (variant,
    want_full, count, cold) of each dispatch, and `looked` the rows its
    row stages looked at (None but for a `narrow` variant: whichever
    branch it took, what it leaves is the parent's, which looks at
    every row)."""

    def __init__(self, monkeypatch, solver: TpuSpfSolver):
        self.oracles: dict = {}
        self.variants = record_variants(monkeypatch)
        self.epochs: list = []
        self.looked: list = []
        real_exec = solver._run_exec

        def run_exec(namespace, kernel_name, signature, run, args, area):
            variant = self.variants[id(run)]
            assert not variant.fused and variant.mesh is None
            oracle = self.oracles.get(variant)
            if oracle is None:
                oracle = self.oracles[variant] = parent_pipeline(
                    monkeypatch, variant
                )
            want_d, want_f, want_res = parent_buffers(oracle, args, variant)
            want_full = int(np.asarray(args[9]))
            outs = real_exec(namespace, kernel_name, signature, run, args,
                             area)
            ctx = f"{variant.name} want_full={want_full}"
            for k, want in enumerate(want_res):
                np.testing.assert_array_equal(
                    np.asarray(outs[2 + k]), want, err_msg=f"{ctx} [{k}]"
                )
            self.check(variant, want_full, outs, want_d, want_f)
            return outs

        monkeypatch.setattr(solver, "_run_exec", run_exec)

    def check(self, variant, want_full, outs, want_d, want_f):
        got_d, looked = split_looked(np.asarray(outs[0]), variant)
        got_f, looked_f = split_looked(np.asarray(outs[1]), variant)
        ctx = f"{variant.name} want_full={want_full}"
        assert looked == looked_f, ctx
        self.looked.append(looked)
        tail = tail_len(variant)
        if variant.rows_only:
            # a prefix-only solve (no weight changed: the resident plane
            # stands; only its candidate rows are looked at): its oracle
            # is the parent's FULL solve of the same arguments, every row
            # of it, which it must equal in everything but the work it
            # did not do: trips (word 1) and rounds (the last) read 0,
            # and of the cold pull, which such an epoch never reads, the
            # scalars alone are there
            want_d, want_f = want_d.copy(), want_f.copy()
            for buf in (want_d, want_f):
                buf[1] = buf[-1] = 0
            np.testing.assert_array_equal(got_d, want_d, err_msg=ctx)
            assert not want_full and int(got_d[0]) <= variant.rows_only, ctx
            np.testing.assert_array_equal(
                got_f, np.concatenate([[0, 0], want_f[-tail:]]), err_msg=ctx
            )
            self.epochs.append((variant, want_full, int(got_d[0]), False))
            return
        np.testing.assert_array_equal(got_d, want_d, err_msg=ctx)
        count = int(got_d[0])
        cold = bool(want_full) or count > variant.budget
        assert got_f.shape == want_f.shape, ctx
        # trips and the scalar tail read the same in every epoch
        assert got_f[1] == want_f[1], ctx
        np.testing.assert_array_equal(got_f[-tail:], want_f[-tail:], ctx)
        if cold:
            np.testing.assert_array_equal(got_f, want_f, err_msg=ctx)
        else:
            assert got_f[0] == 0 and not got_f[2:-tail].any(), ctx
        self.epochs.append((variant, want_full, count, cold))


MODES = {
    "full": {"incremental_spf": False},
    "incremental": {"incremental_spf": True},
}
LFA = pytest.mark.parametrize("lfa", [False, True], ids=["plain", "lfa"])


def _cold_count() -> int:
    return _cnt("decision.tpu.cold_compactions")


def _epoch_count() -> int:
    return _cnt("decision.tpu.epochs")


def drive_randomized_churn(monkeypatch, adj_dbs, states, ps, me: str,
                           mode: str, seed: int, steps: int = 8,
                           **solver_kw):
    """Randomized metric changes and link downs and ups on a solver in
    `mode`, every dispatch checked against the parent's buffers and every
    table against the oracle's; returns the Recorder."""
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(me, **{k: v for k, v in solver_kw.items()
                           if k == "enable_lfa"})
    tpu = TpuSpfSolver(me, **MODES[mode], **solver_kw)
    rec = Recorder(monkeypatch, tpu)
    cold0, epochs0 = _cold_count(), _epoch_count()

    def solve(ctx):
        want = cpu.build_route_db(me, states, ps)
        got = tpu.build_route_db(me, states, ps)
        assert_rib_equal(want, got, f"{ctx} ({mode})")

    solve("the first solve")
    rng = np.random.default_rng(seed)
    edges = [e for e in churn.edges() if me not in e]
    down = None
    for i in range(steps):
        pick = int(rng.integers(4))
        if down is not None and pick < 2:
            u, v, su, sv = down
            churn.link_up(u, v, su, sv)
            down = None
            ctx = f"step {i}: up {u} - {v}"
        elif down is None and pick == 0:
            u, v = edges[int(rng.integers(len(edges)))]
            down = (u, v, churn.dbs[u], churn.dbs[v])
            churn.link_down(u, v)
            ctx = f"step {i}: down {u} - {v}"
        else:
            u, v = edges[int(rng.integers(len(edges)))]
            metric = int((1, 3, 50, 100000)[int(rng.integers(4))])
            churn.set_metric(u, v, metric)
            ctx = f"step {i}: metric {u} - {v} = {metric}"
        solve(ctx)
    assert len(rec.epochs) == steps + 1
    # the host's rule and the device's predicate agree in every epoch
    assert _epoch_count() - epochs0 == steps + 1
    assert _cold_count() - cold0 == sum(cold for *_, cold in rec.epochs)
    return rec


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("mode", list(MODES))
def test_buffers_equal_the_parents_on_randomized_churn(
    monkeypatch, mode, seed
):
    adj_dbs, states, ps = _grid()
    rec = drive_randomized_churn(monkeypatch, adj_dbs, states, ps, ME,
                                 mode, seed)
    first, later = rec.epochs[0], rec.epochs[1:]
    # the first solve asks for the whole table; a warm epoch of a few
    # changed rows does not build it
    assert first[1] == 1 and first[3]
    assert not any(cold for *_, cold in later), rec.epochs
    assert any(count for _, _, count, _ in later), rec.epochs
    # the mode's own executable ran (an ineligible epoch of the
    # incremental mode falls back to the full solve)
    kinds = {v.incr for v, *_ in later}
    assert (mode == "incremental") in kinds, kinds
    assert mode != "full" or len(kinds) == 1, kinds
    # an incremental solve on one chip is the narrow one (ISSUE 44),
    # and on the grid some of its epochs look at fewer
    # rows than all: what they leave is the parent's all the same
    looked = [n for (v, *_), n in zip(rec.epochs, rec.looked) if v.narrow]
    assert bool(looked) == (mode == "incremental"), rec.looked
    assert all(n is None for (v, *_), n in zip(rec.epochs, rec.looked)
               if not v.narrow)
    if looked:
        p_cap = rec.epochs[0][0].p_cap
        assert any(n < p_cap for n in looked), looked


@pytest.mark.parametrize("mode", list(MODES))
def test_buffers_equal_the_parents_with_alternates(monkeypatch, mode):
    """LFA on: two more columns a row in both buffers."""
    adj_dbs, states, ps = _grid()
    rec = drive_randomized_churn(monkeypatch, adj_dbs, states, ps, ME,
                                 mode, 5, steps=5, enable_lfa=True)
    assert all(v.lfa for v, *_ in rec.epochs)


def _captured_dispatch(monkeypatch, budget: int, kind: str,
                       lfa: bool = False):
    """(variant at `budget`, arguments, outputs' planes) of a warm
    dispatch of `kind` on the grid: the stuff to call pipeline closures
    with by hand."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    tpu = TpuSpfSolver(ME, **MODES[kind], enable_lfa=lfa)
    seen = []
    variants = record_variants(monkeypatch)
    real_exec = tpu._run_exec

    def run_exec(namespace, kernel_name, signature, run, args, area):
        host = [np.asarray(a) for a in args]
        outs = real_exec(namespace, kernel_name, signature, run, args, area)
        seen.append((variants[id(run)], host,
                     [np.asarray(o) for o in outs[2:7]]))
        return outs

    monkeypatch.setattr(tpu, "_run_exec", run_exec)
    tpu.build_route_db(ME, states, ps)
    churn.set_metric("node-0-1", "node-1-1", 40)
    tpu.build_route_db(ME, states, ps)
    variant, args, planes = seen[-1]
    assert variant.lfa == lfa
    return variant._replace(budget=budget), args, planes


@LFA
@pytest.mark.parametrize("over", [-1, 0, 1])
@pytest.mark.parametrize("kind", list(MODES))
def test_cold_half_runs_from_budget_plus_one(monkeypatch, kind, over, lfa):
    """With a table (want_full 0) and exactly budget - 1, budget and
    budget + 1 rows changed: only the last builds `full_buf`, and it is
    the parent's; with want_full 1 all three do. With alternates the
    cold half gathers two more columns a row."""
    budget = 16
    variant, args, planes = _captured_dispatch(
        monkeypatch, budget, kind, lfa
    )
    assert variant.p_cap > budget + 1
    # previous planes = this epoch's outputs, but for `budget + over`
    # rows of the metric plane: exactly that many rows read as changed
    rows = np.random.default_rng(over + 5).choice(
        variant.p_cap, budget + over, replace=False
    )
    prev = [p.copy() for p in planes]
    prev[0][rows] += 1
    args = list(args)
    args[10:15] = prev
    if variant.narrow:
        # planes made up by hand are not the resident plane's: the host's
        # word for that is `wide` (and its rows at this budget: none)
        args[-2:] = [np.full(budget, variant.p_cap, np.int32), np.int32(1)]
    _name, run = ts._build_pipeline(*variant)
    oracle = parent_pipeline(monkeypatch, variant)
    want_d, want_f, _resident = parent_buffers(oracle, args, variant)
    tail = tail_len(variant)
    for want_full in (0, 1):
        args[9] = np.int32(want_full)
        got_d, got_f, *_ = (np.asarray(o) for o in run(*args))
        got_d, looked = split_looked(got_d, variant)
        got_f, _ = split_looked(got_f, variant)
        assert looked in (None, variant.p_cap)
        np.testing.assert_array_equal(got_d, want_d)
        assert got_d[0] == budget + over
        np.testing.assert_array_equal(got_f[-tail:], want_f[-tail:])
        if want_full or over > 0:
            np.testing.assert_array_equal(got_f, want_f)
            assert got_f[0] > budget + 1  # the whole table, not the delta
        else:
            assert got_f[0] == 0 and not got_f[2:-tail].any()
            assert got_f[1] == want_f[1]


@pytest.mark.parametrize("word", ["neither", "wide", "want_full"])
def test_the_narrow_variant_takes_the_host_s_word(monkeypatch, word):
    """A warm incremental dispatch's own arguments (resident outputs of
    the resident plane, the host's rows known) look at a few rows; the
    host's `wide` makes it every row, and so does `want_full`, which
    also builds the cold pull: all three leave what the parent's
    all-rows program leaves."""
    variant, args, _planes = _captured_dispatch(
        monkeypatch, ts._DELTA_BUDGET, "incremental"
    )
    assert variant.narrow
    args = list(args)
    assert int(args[-1]) == 0 and int(args[9]) == 0
    args[-1] = np.int32(word == "wide")
    args[9] = np.int32(word == "want_full")
    oracle = parent_pipeline(monkeypatch, variant)
    want_d, want_f, want_res = parent_buffers(oracle, args, variant)
    _name, run = ts._build_pipeline(*variant)
    got_d, got_f, *resident = (np.asarray(o) for o in run(*args))
    got_d, looked = split_looked(got_d, variant)
    got_f, _ = split_looked(got_f, variant)
    if word == "neither":
        assert 0 < looked < variant.p_cap
    else:
        assert looked == variant.p_cap
    np.testing.assert_array_equal(got_d, want_d)
    for got, want in zip(resident[:5], want_res):
        np.testing.assert_array_equal(got, want)
    tail = tail_len(variant)
    np.testing.assert_array_equal(got_f[-tail:], want_f[-tail:])
    if word == "want_full":
        np.testing.assert_array_equal(got_f, want_f)
    else:
        assert got_f[0] == 0 and not got_f[2:-tail].any()


@LFA
@pytest.mark.parametrize("mode", list(MODES))
def test_a_vantage_with_no_table_gets_the_whole_table(monkeypatch, mode,
                                                      lfa):
    """vs.valid false with fewer routes than the budget: against zeroed
    planes fewer rows than the budget read as changed, so a predicate
    inferred from the count alone would ship no table. First solve, then
    a reset."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(ME, enable_lfa=lfa)
    tpu = TpuSpfSolver(ME, **MODES[mode], enable_lfa=lfa)
    rec = Recorder(monkeypatch, tpu)

    def solve(ctx, cold: bool):
        cold0 = _cold_count()
        got = tpu.build_route_db(ME, states, ps)
        assert_rib_equal(cpu.build_route_db(ME, states, ps), got, ctx)
        variant, want_full, count, was_cold = rec.epochs[-1]
        assert (bool(want_full), was_cold) == (cold, cold), ctx
        assert _cold_count() - cold0 == int(cold), ctx
        stats = tpu.last_device_stats
        assert stats["full_pull"] == cold, ctx
        spans = {name: attrs for name, _, _, _, attrs
                 in tpu.last_timing["spans"]}
        assert spans["tpu.pull"]["cold_compact"] == cold, ctx
        return variant, count

    variant, count = solve("the first solve", True)
    assert 0 < count < variant.budget
    churn.set_metric("node-0-1", "node-1-1", 40)
    solve("a warm epoch", False)
    (vs,) = tpu._vstates.values()
    vs.valid = False
    churn.set_metric("node-0-1", "node-1-1", 7)
    variant, count = solve("after a reset", True)
    assert 0 < count < variant.budget
    churn.set_metric("node-0-1", "node-1-1", 9)
    solve("warm again", False)


# -- what the incremental path downloads, and what the host makes of it -----


def _retraces() -> float:
    return sum(counters.get_counters("xla_cache.retraces.").values())


def _warm_incremental(lfa: bool):
    """(solver, churn, states, ps) of an incremental solver on the grid
    after its cold full pull and one warm epoch: the incremental
    executable is built and the vantage has a table."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    tpu = TpuSpfSolver(ME, incremental_spf=True, enable_lfa=lfa)
    tpu.build_route_db(ME, states, ps)
    churn.set_metric("node-0-1", "node-1-1", 9)
    tpu.build_route_db(ME, states, ps)
    assert tpu.last_timing["incremental"]
    return tpu, churn, states, ps


@LFA
def test_device_changed_set_is_the_host_column_compares(lfa):
    """The rows an epoch's delta payload names are exactly the rows in
    which the host's compare of the old and new column bundles
    (`cols_changed_mask`, what `fast_unicast_column_diff` runs over the
    journal) finds a difference, and the delta that comes of them is the
    CPU oracle's — through a withdrawal (a corner cut off: its loopback
    goes by the ok -> False lane) and its return."""
    tpu, churn, states, ps = _warm_incremental(lfa)
    cpu = SpfSolver(ME, enable_lfa=lfa)
    (vs,) = tpu._vstates.values()
    db = tpu.build_route_db(ME, states, ps)
    want_db = dict(cpu.build_route_db(ME, states, ps).unicast_routes)

    def step(ctx):
        nonlocal db, want_db
        old = vs.crib.view()
        new_db = tpu.build_route_db(ME, states, ps)
        assert not tpu.last_device_stats["full_pull"], ctx
        new = vs.crib.view()
        host = np.flatnonzero(
            cols_changed_mask(old.cols, new.cols, slice(None))
        )
        device = np.sort(vs.crib.changed_rows_since(old.epoch))
        np.testing.assert_array_equal(device, host, err_msg=ctx)
        assert tpu.last_device_stats["changed_rows"] == len(host), ctx
        upd = db.calculate_update(new_db)
        assert upd.columns is not None, ctx
        want_new = dict(cpu.build_route_db(ME, states, ps).unicast_routes)
        assert dict(upd.unicast_routes_to_update) == {
            p: e for p, e in want_new.items() if want_db.get(p) != e
        }, ctx
        assert sorted(upd.unicast_routes_to_delete) == sorted(
            p for p in want_db if p not in want_new
        ), ctx
        db, want_db = new_db, want_new
        return upd

    churn.set_metric("node-0-1", "node-1-1", 1)
    assert step("a metric comes down").unicast_routes_to_update
    corner = "node-0-0"
    saved = [churn.dbs[n] for n in (corner, "node-0-1", "node-1-0")]
    churn.link_down(corner, "node-0-1")
    churn.link_down(corner, "node-1-0")
    assert step("a corner is cut off").unicast_routes_to_delete
    for adj_db in saved:
        churn._put(adj_db)
    assert step("and comes back").unicast_routes_to_update


@LFA
def test_an_idle_epoch_is_one_delta_payload_of_zero_rows(lfa):
    """An epoch in which nothing changed still downloads the delta
    payload and nothing else: zero rows, and a busy epoch's bytes less
    the four words a solve puts in the tail (the rows looked at and the
    cone's three: nothing is dirty, so the row stages alone run, over no
    row) — the payload has the budget's shape, two columns a row wider
    with alternates, not the changed rows'."""
    tpu, churn, states, ps = _warm_incremental(lfa)
    churn.set_metric("node-0-1", "node-1-1", 1)
    tpu.build_route_db(ME, states, ps)
    busy = tpu.last_device_stats
    assert busy["changed_rows"] > 0 and not busy["full_pull"]
    (vs,) = tpu._vstates.values()
    d_cap, a_cap = vs.shape_key[5], vs.shape_key[7]
    # count and trips, the rows, the sentinels and the rounds
    words = 2 + ts._DELTA_BUDGET * (
        2 + -(-a_cap // 16) + -(-d_cap // 16) + 2 * lfa
    ) + 2 + 1
    assert busy["bytes_downloaded"] == 4 * (words + 4)
    for i in range(2):
        tpu.build_route_db(ME, states, ps)
        idle = tpu.last_device_stats
        assert idle["prefix_only"] and not idle["full_pull"], i
        assert idle["changed_rows"] == 0, i
        assert idle["bytes_downloaded"] == 4 * words, i
        assert tpu.last_timing["bytes_downloaded"] == 4 * words, i


@LFA
def test_warm_churn_compiles_nothing(lfa):
    """Once the incremental executable is built, churn of the same dirty
    bucket builds and traces no other, in any namespace."""
    tpu, churn, states, ps = _warm_incremental(lfa)
    retraces = _retraces()
    built = {
        key: value for key, value in counters.get_counters(
            "xla_cache."
        ).items() if key.endswith("factory_misses")
    }
    for metric in (12, 19, 4, 88, 2):
        churn.set_metric("node-0-1", "node-1-1", metric)
        tpu.build_route_db(ME, states, ps)
        assert tpu.last_timing["incremental"], metric
    assert _retraces() == retraces
    assert {
        key: counters.get_counter(key) for key in built
    } == built
