"""tools.lint suite + runtime affinity sentinel tests.

Fixture-based coverage for the eight AST checkers (seeded violations
must be flagged, clean idioms must not), the pragma/allowlist
suppression machinery, a repo-runs-clean regression guard, and the
thread-ownership sentinel — including the chaos-lane drill that proves
a deliberate cross-thread `TpuSpfSolver` dispatch trips it.
"""

import json
import subprocess
import sys
import textwrap
import threading

import pytest

from openr_tpu.runtime import affinity
from openr_tpu.runtime.counters import counters
from tools.lint import affinity as affinity_check
from tools.lint import blocking as blocking_check
from tools.lint import donation as donation_check
from tools.lint import excepts as excepts_check
from tools.lint import metric_names as metric_check
from tools.lint import purity as purity_check
from tools.lint import recompile as recompile_check
from tools.lint import shardcheck as shard_check
from tools.lint.core import (
    REPO_ROOT,
    Allowlist,
    Project,
    apply_suppressions,
)


def make_project(tmp_path, files, packages=("pkg",)):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Project(tmp_path, list(packages))


def codes(findings):
    return {f.code for f in findings}


# -- exception hygiene -----------------------------------------------------

EXCEPTS_FIXTURE = """\
    from openr_tpu.runtime.counters import counters

    def swallows():
        try:
            work()
        except Exception:
            pass  # seeded violation

    def counted():
        try:
            work()
        except Exception:
            counters.increment("pkg.errors")

    def reraises():
        try:
            work()
        except Exception:
            raise

    def narrow():
        try:
            work()
        except ValueError:
            pass

    def annotated():
        try:
            work()
        # lint: allow(broad-except) fixture: intentionally swallowed
        except Exception:
            pass
"""


def test_excepts_flags_swallow_and_honors_compliance(tmp_path):
    project = make_project(tmp_path, {"pkg/mod.py": EXCEPTS_FIXTURE})
    findings = excepts_check.run(project)
    assert [f.scope for f in findings] == ["swallows", "annotated"]
    allow = Allowlist.load(tmp_path / "missing.json")
    remaining = apply_suppressions(findings, project, allow)
    assert [f.scope for f in remaining] == ["swallows"]
    assert remaining[0].code == "broad-except"


def test_bare_pragma_is_itself_a_finding(tmp_path):
    project = make_project(tmp_path, {
        "pkg/mod.py": """\
            def f():
                try:
                    work()
                # lint: allow(broad-except)
                except Exception:
                    pass
        """,
    })
    sf = project.files[0]
    assert [f.code for f in sf.pragma_errors] == ["bare-pragma"]
    # a reason-less pragma suppresses nothing
    assert codes(excepts_check.run(project)) == {"broad-except"}


# -- blocking-in-fiber -----------------------------------------------------

BLOCKING_FIXTURE = """\
    import time

    async def fiber(self, fut, sock):
        time.sleep(1)                       # seeded violation
        fut.result()                        # seeded violation
        sock.recv(65536)                    # seeded violation
        self.solver.collect_route_db(p)     # seeded violation
        await self.connect()                # awaited coroutine: fine
        fut.result(timeout=0)               # bounded wait: not flagged

    def host_side(fut):
        time.sleep(1)      # sync context: fine
        return fut.result()
"""


def test_blocking_flags_only_async_bodies(tmp_path):
    project = make_project(tmp_path, {"pkg/mod.py": BLOCKING_FIXTURE})
    findings = blocking_check.run(project)
    assert all(f.code == "blocking-call" for f in findings)
    assert {f.detail for f in findings} == {
        "time.sleep", "result()", "recv", "collect_route_db",
    }
    assert all(f.scope == "fiber" for f in findings)


# -- actor affinity (static) -----------------------------------------------

AFFINITY_FIXTURE = """\
    from openr_tpu.runtime import affinity

    class Actor:
        pass

    class Fib(Actor):
        pass

    def module_level(x):
        return x

    class Decision:
        def __init__(self, fib):
            self.fib = fib

        @affinity.executor_safe
        def collect(self):
            return self._pending

        async def run(self, loop, ex):
            await loop.run_in_executor(ex, self._prepare)   # escape
            await loop.run_in_executor(ex, lambda: self.x)  # escape
            await loop.run_in_executor(ex, self.collect)    # safe
            await loop.run_in_executor(ex, module_level)    # fine

        def submit_closure(self, ex):
            prep = self._dispatch_one()

            def local():
                return self.state

            ex.submit(prep)    # escape: self-derived closure
            ex.submit(local)   # escape: nested def captures locals

        def poke(self):
            self.fib.route_db = {}   # cross-actor write
"""


def test_affinity_static_checker(tmp_path):
    project = make_project(tmp_path, {"pkg/mod.py": AFFINITY_FIXTURE})
    assert project.actor_classes >= {"Actor", "Fib"}
    assert "collect" in project.executor_safe_names
    findings = affinity_check.run(project)
    escapes = [f for f in findings if f.code == "executor-escape"]
    xwrites = [f for f in findings if f.code == "cross-actor-write"]
    assert {f.detail for f in escapes} == {
        "self._prepare", "<lambda>", "prep", "local",
    }
    assert len(xwrites) == 1 and xwrites[0].scope == "Decision.poke"


# -- trace purity ----------------------------------------------------------

PURITY_FIXTURE = """\
    import numpy as np
    import jax
    import jax.numpy as jnp

    @jax.jit
    def traced(x):
        print(x)                      # seeded host-impurity
        while x.shape[0]:             # seeded traced-loop
            break
        return helper(x)

    def helper(x):
        return np.asarray(x)          # impure, reached from traced root

    def host_only(x):
        print(x)                      # untraced: fine
        return x.item()
"""


def test_purity_walks_call_graph_from_jit_roots(tmp_path):
    project = make_project(
        tmp_path,
        {"openr_tpu/ops/fixture_mod.py": PURITY_FIXTURE},
        packages=("openr_tpu",),
    )
    findings = purity_check.run(project)
    assert {(f.code, f.scope) for f in findings} == {
        ("host-impurity", "traced"),   # print
        ("traced-loop", "traced"),     # while
        ("host-impurity", "helper"),   # np.asarray via call graph
    }


def test_purity_clean_kernel_is_silent(tmp_path):
    project = make_project(
        tmp_path,
        {
            "openr_tpu/ops/clean_mod.py": """\
                import jax
                import jax.numpy as jnp
                import numpy as np

                @jax.jit
                def kernel(x):
                    return jnp.where(x > 0, x, np.int32(0))
            """,
        },
        packages=("openr_tpu",),
    )
    assert purity_check.run(project) == []


# -- metric names ----------------------------------------------------------

def test_metric_collision_detected(tmp_path):
    project = make_project(tmp_path, {
        "pkg/mod.py": """\
            def f(counters):
                counters.increment("decision.spf.runs")
                counters.increment("decision.spf_runs")
        """,
    })
    findings = metric_check.run(project)
    assert codes(findings) == {"metric-collision"}
    assert "normalize to" in findings[0].message


def test_metric_stat_families_expand(tmp_path):
    # a stat family claims its derived exposition names too
    project = make_project(tmp_path, {
        "pkg/mod.py": """\
            def f(counters):
                counters.add_stat_value("fib.program.ms", 1)
                counters.increment("fib.program.ms_max")
        """,
    })
    assert codes(metric_check.run(project)) == {"metric-collision"}


def test_metric_budget_components_expand(tmp_path):
    # ISSUE 17: the budget ledger emits `budget.<component>_ms` with a
    # runtime component name — the checker expands the placeholder over
    # the canonical taxonomy, so a concrete family colliding with one
    # of the expanded per-component names is caught
    project = make_project(tmp_path, {
        "pkg/mod.py": """\
            def f(counters, comp):
                counters.add_stat_value(f"budget.{comp}_ms", 1)
                counters.increment("budget.host_sync_ms_sum")
        """,
    })
    findings = metric_check.run(project)
    assert codes(findings) == {"metric-collision"}
    assert "budget.host_sync_ms" in findings[0].message


# -- allowlist round-trip --------------------------------------------------

def test_allowlist_round_trip_and_unused(tmp_path):
    project = make_project(tmp_path, {
        "pkg/mod.py": """\
            def swallows():
                try:
                    work()
                except Exception:
                    pass
        """,
    })
    (finding,) = excepts_check.run(project)
    al_path = tmp_path / "allowlist.json"
    al_path.write_text(json.dumps({"entries": [
        {"key": finding.key, "reason": "fixture: blessed"},
        {"key": "pkg/gone.py::f::broad-except::", "reason": "stale"},
    ]}))
    allow = Allowlist.load(al_path)
    assert not allow.errors
    assert apply_suppressions([finding], project, allow) == []
    # the matched key is consumed; the stale one surfaces as unused
    assert allow.unused() == ["pkg/gone.py::f::broad-except::"]


def test_allowlist_requires_reason(tmp_path):
    al_path = tmp_path / "allowlist.json"
    al_path.write_text(json.dumps({"entries": [{"key": "a::b::c::d"}]}))
    allow = Allowlist.load(al_path)
    assert allow.errors and "reason" in allow.errors[0]
    assert allow.entries == {}


def test_allowlist_keys_are_line_number_free(tmp_path):
    # inserting lines above the finding must not invalidate its key
    src = """\
        def swallows():
            try:
                work()
            except Exception:
                pass
    """
    p1 = make_project(tmp_path / "a", {"pkg/mod.py": src})
    p2 = make_project(tmp_path / "b", {"pkg/mod.py": "import os\n\n\n" + textwrap.dedent(src)})
    (f1,) = excepts_check.run(p1)
    (f2,) = excepts_check.run(p2)
    assert f1.line != f2.line
    assert f1.key == f2.key


def test_purity_traces_relax_kernel_roots():
    """The shared round-loop module (ops/relax.py) is device code:
    every loop body it hands to while_loop/fori_loop must be
    discovered as a traced root by the purity walker (regression
    guard: the ops/ module prefix covers the kernel extraction), and
    the shipped kernels must run clean."""
    project = Project(REPO_ROOT, ["openr_tpu"])
    sf = project.file("openr_tpu/ops/relax.py")
    assert sf is not None
    assert purity_check._is_traced_file(sf.rel)
    g = purity_check._ModuleGraph(sf)
    # make_relax's fori body, run_sync's trip loop, run_bucketed's
    # ladder pass + rung loop + epoch loop all ride lax control flow
    assert {
        "cls", "body", "cond", "one", "lbody", "lcond", "ebody", "econd",
    } <= g.traced
    assert not [
        f for f in purity_check.run(project)
        if f.path == "openr_tpu/ops/relax.py"
    ]


# -- recompile hygiene -----------------------------------------------------

RECOMPILE_FIXTURE = """\
    import functools

    import jax
    import jax.numpy as jnp

    _tuning = {"unroll": 4}       # mutable module global
    UNROLL = 4                    # ALL_CAPS constant: trace-safe

    def factory(n_cap, wide):
        scale = 2 if wide else 1

        def pipeline(x):
            k = _tuning["unroll"]         # seeded trace-capture
            return jnp.sum(x) * k * UNROLL * n_cap * scale

        return jax.jit(pipeline)

    @functools.lru_cache(maxsize=8)
    def cached_factory(n_cap):            # seeded unbounded-jit-cache
        def pipeline(x):
            return x * n_cap

        return jax.jit(pipeline)
"""


def test_recompile_flags_captures_and_unbounded_cache(tmp_path):
    project = make_project(
        tmp_path,
        {"openr_tpu/ops/fix_recompile.py": RECOMPILE_FIXTURE},
        packages=("openr_tpu",),
    )
    findings = recompile_check.run(project)
    assert {(f.code, f.detail) for f in findings} == {
        ("trace-capture", "_tuning"),
        ("unbounded-jit-cache", "cached_factory"),
    }
    # the capture finding names the mutable-global hazard, not a
    # generic unresolved symbol
    cap = next(f for f in findings if f.code == "trace-capture")
    assert "mutable module global" in cap.message


def test_recompile_clean_factory_is_silent(tmp_path):
    # everything the traced closure reads flows through the factory
    # parameters/locals, imports, or ALL_CAPS constants — the capacity
    # signature owns it all
    project = make_project(
        tmp_path,
        {
            "openr_tpu/ops/fix_recompile_ok.py": """\
                import jax
                import jax.numpy as jnp

                UNROLL = 4

                def factory(n_cap, wide):
                    scale = 2 if wide else 1

                    def pipeline(x):
                        return jnp.sum(x) * n_cap * scale * UNROLL

                    return jax.jit(pipeline)
            """,
        },
        packages=("openr_tpu",),
    )
    assert recompile_check.run(project) == []


# -- sharding contracts ----------------------------------------------------

# the PR 13 bug-shape, seeded: a mesh-aware jitted pull pipeline whose
# concatenated boundary buffer is never re-pinned, plus the
# traced-shift roll that GSPMD miscompiles to an unreduced partial-sum
SHARD_FIXTURE = """\
    import jax
    import jax.numpy as jnp

    def make_pull(mesh, rep):
        def pull(a, b, shift):
            delta_buf = jnp.concatenate([a, b])       # never constrained
            rolled = jnp.roll(delta_buf, shift, axis=1)
            return rolled
        return jax.jit(pull)

    def naked(x):
        def body(v):
            return jax.lax.pmin(v, "rows")
        return jax.jit(body)(x)
"""


def test_shardcheck_catches_pr13_regression_shape(tmp_path):
    project = make_project(
        tmp_path,
        {"openr_tpu/parallel/fix_shard.py": SHARD_FIXTURE},
        packages=("openr_tpu",),
    )
    findings = shard_check.run(project)
    got = {(f.code, f.detail) for f in findings}
    assert ("unconstrained-boundary", "delta_buf") in got
    assert ("sharded-axis-roll", "roll") in got
    assert ("naked-collective", "pmin") in got
    assert ("undeclared-axis", "pmin:rows") in got
    roll = next(f for f in findings if f.code == "sharded-axis-roll")
    assert "partial-sum" in roll.message


def test_shardcheck_clean_shard_map_module_is_silent(tmp_path):
    # the production shape: collectives under shard_map against a
    # declared axis; the boundary buffer re-pinned (on the mesh path
    # only — path-insensitive on purpose)
    project = make_project(
        tmp_path,
        {
            "openr_tpu/parallel/fix_shard_ok.py": """\
                import jax
                import jax.numpy as jnp
                from jax.sharding import Mesh, PartitionSpec as P

                def make_pull(mesh, rep):
                    def pull(a, b):
                        delta_buf = jnp.concatenate([a, b])
                        if mesh is not None:
                            delta_buf = jax.lax.with_sharding_constraint(
                                delta_buf, rep)
                        return delta_buf
                    return jax.jit(pull)

                def make_mc(mesh):
                    def local_fn(x):
                        i = jax.lax.axis_index("graph")
                        return jax.lax.pmin(x + i, "graph")
                    from jax.experimental.shard_map import shard_map
                    return shard_map(
                        local_fn, mesh=mesh,
                        in_specs=(P("graph"),), out_specs=P("graph"),
                    )
            """,
        },
        packages=("openr_tpu",),
    )
    assert shard_check.run(project) == []


def test_shardcheck_repo_declares_its_axes():
    # the production multichip module passes its own contract: both
    # mesh axes are declared, every collective sits under shard_map
    project = Project(REPO_ROOT, ["openr_tpu"])
    sf = project.file("openr_tpu/parallel/sharding.py")
    assert shard_check._declared_axes(sf) >= {"batch", "graph"}
    assert not [
        f for f in shard_check.run(project)
        if f.path == "openr_tpu/parallel/sharding.py"
    ]


# -- buffer donation -------------------------------------------------------

DONATION_FIXTURE = """\
    import jax

    def _scatter_jit(donate=False):
        def scatter(arr, idx, vals):
            return arr.at[idx].set(vals)
        if donate:
            return jax.jit(scatter, donate_argnums=(0,))
        return jax.jit(scatter)

    class Solver:
        def _scatter_counted(self, d_arr, idx, vals):
            return _scatter_jit(True)(d_arr, idx, vals)

        def bad(self, ad, idx, vals):
            stale = self._scatter_counted(ad.d_w, idx, vals)
            return stale, ad.d_w.shape       # seeded donated-read

        def good(self, ad, idx, vals):
            ad.d_w = self._scatter_counted(ad.d_w, idx, vals)
            return ad.d_w.shape              # rebind idiom: fine
"""


def test_donation_flags_read_after_donate_through_wrappers(tmp_path):
    project = make_project(
        tmp_path,
        {"openr_tpu/ops/fix_donation.py": DONATION_FIXTURE},
        packages=("openr_tpu",),
    )
    findings = donation_check.run(project)
    assert [(f.code, f.detail, f.scope) for f in findings] == [
        ("donated-read", "ad.d_w", "Solver.bad"),
    ]


def test_donation_kwargs_dict_form_indexes_as_donating(tmp_path):
    # _mc_scatter_jit's `{"donate_argnums": (0,)} if donate else {}`
    # shape must index the factory as donating
    project = make_project(
        tmp_path,
        {
            "openr_tpu/ops/fix_donation_kw.py": """\
                import jax

                def _mc_scatter_jit(sharding, donate=False):
                    def scatter(arr, idx, vals):
                        return arr.at[idx].set(vals)
                    kw = {"donate_argnums": (0,)} if donate else {}
                    return jax.jit(scatter, **kw)

                def syncs(buf, idx, vals, sh):
                    out = _mc_scatter_jit(sh, True)(buf, idx, vals)
                    return out + buf          # seeded donated-read
            """,
        },
        packages=("openr_tpu",),
    )
    findings = donation_check.run(project)
    assert [(f.code, f.detail) for f in findings] == [
        ("donated-read", "buf"),
    ]


# -- pragma placement on decorated defs ------------------------------------

def test_pragma_above_decorator_stack_covers_the_def(tmp_path):
    project = make_project(
        tmp_path,
        {
            "openr_tpu/ops/fix_decorated.py": """\
                import functools

                import jax

                # lint: allow(unbounded-jit-cache) fixture: blessed cache
                @functools.lru_cache(maxsize=2)
                @functools.wraps(print)
                def cached(n):
                    return jax.jit(lambda x: x * n)
            """,
        },
        packages=("openr_tpu",),
    )
    findings = recompile_check.run(project)
    assert codes(findings) == {"unbounded-jit-cache"}
    # the finding anchors at the `def` line, below the whole decorator
    # stack — the pragma above the first decorator must still cover it
    allow = Allowlist.load(tmp_path / "missing.json")
    assert apply_suppressions(findings, project, allow) == []


# -- CLI: stale allowlist fails, --files narrows the report ----------------

def test_unused_allowlist_entry_fails_full_run(tmp_path, capsys):
    from tools.lint.__main__ import main as lint_main

    al = tmp_path / "allowlist.json"
    al.write_text(json.dumps({"entries": [
        {"key": "openr_tpu/gone.py::f::broad-except::x",
         "reason": "stale fixture entry"},
    ]}))
    rc = lint_main(["--allowlist", str(al)])
    err = capsys.readouterr().err
    assert rc == 1
    assert "unused allowlist entry" in err
    assert "openr_tpu/gone.py::f::broad-except::x" in err


def test_files_lane_narrows_report_and_skips_staleness(tmp_path, capsys):
    # the diff-aware PR lane: a stale allowlist entry must NOT fail a
    # partial report (it can't prove staleness), and findings outside
    # the named files are filtered from the report
    from tools.lint.__main__ import main as lint_main

    al = tmp_path / "allowlist.json"
    al.write_text(json.dumps({"entries": [
        {"key": "openr_tpu/gone.py::f::broad-except::x",
         "reason": "stale fixture entry"},
    ]}))
    rc = lint_main([
        "--allowlist", str(al),
        "--files", "openr_tpu/ops/relax.py",
    ])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "unused allowlist entry" not in out.err


# -- the repo itself runs clean --------------------------------------------

def test_repo_lint_is_clean():
    """Regression guard: the shipped tree has zero unallowlisted
    findings (the CI gate this suite exists for)."""
    res = subprocess.run(
        [sys.executable, "-m", "tools.lint"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


# -- runtime sentinel ------------------------------------------------------

@pytest.fixture
def affinity_on():
    prev = affinity.enabled()
    affinity.set_enabled(True)
    yield
    affinity.set_enabled(prev)


class Box:
    pass


def _violations():
    return counters.get_counter("runtime.affinity.violations") or 0


def test_sentinel_disabled_is_inert():
    prev = affinity.enabled()
    affinity.set_enabled(False)
    try:
        obj = Box()
        affinity.bind_owner(obj, "box")
        assert "_affinity_ident" not in obj.__dict__
        done = []
        t = threading.Thread(
            target=lambda: done.append(affinity.assert_owner(obj))
        )
        t.start()
        t.join(timeout=10)
        assert done == [None]  # no binding, no raise, no counter
    finally:
        affinity.set_enabled(prev)


def test_sentinel_first_touch_binds_then_enforces(affinity_on):
    obj = Box()
    affinity.assert_owner(obj, "write")  # first touch claims ownership
    assert obj.__dict__["_affinity_ident"] == threading.get_ident()
    affinity.assert_owner(obj, "write")  # same thread: fine
    before = _violations()
    caught = []

    def rogue():
        try:
            affinity.assert_owner(obj, "rogue_write")
        except affinity.AffinityViolation as e:
            caught.append(e)

    t = threading.Thread(target=rogue, name="rogue")
    t.start()
    t.join(timeout=10)
    assert len(caught) == 1
    assert "rogue_write" in str(caught[0])
    assert "dispatch-collect" in str(caught[0])
    assert _violations() == before + 1


def test_sentinel_rebind_transfers_ownership(affinity_on):
    obj = Box()
    holder = []

    def bind_elsewhere():
        affinity.bind_owner(obj, "box")
        holder.append(obj.__dict__["_affinity_ident"])

    t = threading.Thread(target=bind_elsewhere)
    t.start()
    t.join(timeout=10)
    assert holder and holder[0] != threading.get_ident()
    # supervised-restart pattern: the new owner re-claims explicitly
    affinity.bind_owner(obj, "box")
    affinity.assert_owner(obj, "write")  # no raise


def test_actor_add_task_guarded(affinity_on):
    from tests.conftest import run_async
    from openr_tpu.runtime.actor import Actor

    @run_async
    async def drive():
        a = Actor("guinea")
        await a.start()  # binds the loop thread as owner
        caught = []

        async def noop():
            pass

        def rogue():
            coro = noop()
            try:
                a.add_task(coro, name="rogue")
            except affinity.AffinityViolation as e:
                caught.append(e)
                coro.close()

        t = threading.Thread(target=rogue, name="rogue")
        t.start()
        t.join(timeout=10)
        await a.stop()
        return caught

    caught = drive()
    assert len(caught) == 1
    assert "add_task" in str(caught[0])


# -- chaos drill: cross-thread solver dispatch -----------------------------

@pytest.mark.chaos
def test_chaos_sentinel_catches_cross_thread_solver_dispatch(affinity_on):
    """The drill the sentinel exists for: a deliberate cross-thread
    touch of `TpuSpfSolver` dispatch state (prev_dist seeding, vantage
    cache, drain journal) must fail loudly instead of corrupting
    routes. The owning thread solves once to bind; a rogue thread then
    re-dispatches and must be rejected."""
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from tests.test_spf_solver import prefix_db, square_states

    states = square_states()
    ps = PrefixState()
    ps.update_prefix_database(prefix_db("d", "fd00::d/128"))
    solver = TpuSpfSolver("a")
    db = solver.build_route_db("a", states, ps)  # binds this thread
    assert db is not None and "fd00::d/128" in db.unicast_routes

    before = _violations()
    outcome = []

    def rogue():
        try:
            outcome.append(("db", solver.build_route_db("a", states, ps)))
        except affinity.AffinityViolation as e:
            outcome.append(("violation", e))

    t = threading.Thread(target=rogue, name="rogue-solver")
    t.start()
    t.join(timeout=60)
    assert outcome and outcome[0][0] == "violation", (
        "cross-thread dispatch must trip the sentinel, got: "
        f"{outcome!r}"
    )
    assert "dispatch_route_db" in str(outcome[0][1])
    assert _violations() == before + 1

    # the owning thread is unaffected and keeps solving
    db2 = solver.build_route_db("a", states, ps)
    assert db2 is not None


def test_purity_and_donation_trace_the_pipeline_s_roots():
    """The pipeline is device code end to end. ops/compact.py rides the
    ops/ traced prefix (its column-diff + compaction stages are
    purity-analyzed), the solver module's `pipeline` jit root — which
    _build_pipeline jits — is discovered, and the stages' function-local
    imports resolve to the traced module, so a host impurity seeded in
    either stage would flow to the root's findings. The donation checker
    must index the delta scatter's donated resident array, and the
    shipped modules must run clean."""
    project = Project(REPO_ROOT, ["openr_tpu"])
    sf = project.file("openr_tpu/ops/compact.py")
    assert sf is not None
    assert purity_check._is_traced_file(sf.rel)
    solver = project.file("openr_tpu/decision/tpu_solver.py")
    g = purity_check._ModuleGraph(solver)
    assert "pipeline" in g.traced, g.traced
    assert g.imports.get("column_diff") == (
        "openr_tpu.ops.compact", "column_diff"
    )
    assert g.imports.get("compact_changed_rows") == (
        "openr_tpu.ops.compact", "compact_changed_rows"
    )
    # the delta scatter updates the resident array in place: the
    # read-after-donate rule must see its position
    donated = donation_check._factory_donations(g.defs["_scatter_jit"])
    assert donated == {0}, donated
    findings = [
        f
        for f in purity_check.run(project) + donation_check.run(project)
        if f.path in (
            "openr_tpu/ops/compact.py",
            "openr_tpu/decision/tpu_solver.py",
        )
    ]
    assert not findings, findings
