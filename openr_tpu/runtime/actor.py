"""Actor base — the module concurrency model.

Role of the reference's OpenrEventBase (openr/common/OpenrEventBase.h:30):
each module is an actor owning its state, running long-lived tasks
("fibers", ref addFiberTask h:48) that block on queue reads, plus timers.
Cross-actor communication is queues only; cross-actor reads go through
async request methods (role of folly::SemiFuture APIs).

We use one asyncio event loop for the whole process (the reference uses one
OS thread per module; asyncio gives the same single-writer-per-actor
guarantee with cheaper context switches). Each actor stamps a health
timestamp for the Watchdog (ref OpenrEventBase.h:76).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, Callable, Coroutine, Optional

from openr_tpu.messaging import QueueClosedError
from openr_tpu.runtime import affinity
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.tasks import record_crash, spawn_logged
from openr_tpu.runtime.throttle import ExponentialBackoff
from openr_tpu.runtime.tracing import tracer

log = logging.getLogger(__name__)

# Supervisor defaults (ref systemd Restart=on-failure + StartLimitBurst:
# the reference daemon leans on an external supervisor; in-process fibers
# get the same restart-with-backoff-then-escalate contract). Overridden
# per actor by Watchdog.watch_actor from watchdog_config.
SUPERVISOR_CRASH_BUDGET = 3
SUPERVISOR_BACKOFF_INITIAL_S = 0.05
SUPERVISOR_BACKOFF_MAX_S = 2.0
HEARTBEAT_S = 0.1
# a heartbeat this late asks the tracer what held the loop (the
# benchmark harness's own SETTLE_LATE_S: the lateness it will not settle
# over)
LOOP_LAG_HOLD_S = 0.020


class Timer:
    """Restartable one-shot timer (role of folly AsyncTimeout)."""

    def __init__(self, callback: Callable[[], Any], loop=None):
        self._callback = callback
        self._handle: Optional[asyncio.TimerHandle] = None
        self._loop = loop
        # owner registry (Actor._timers): fired one-shot timers remove
        # themselves so schedule()-per-event call sites don't grow the list
        # unboundedly over a long-running daemon
        self._registry: Optional[list] = None

    def schedule(self, delay_s: float) -> None:
        self.cancel()
        loop = self._loop or asyncio.get_running_loop()
        self._handle = loop.call_later(delay_s, self._fire)
        if self._registry is not None and self not in self._registry:
            self._registry.append(self)

    def _fire(self) -> None:
        self._handle = None
        if self._registry is not None and self in self._registry:
            self._registry.remove(self)
        res = self._callback()
        if asyncio.iscoroutine(res):
            spawn_logged(res, name=f"{type(self).__name__}.callback")

    def cancel(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
        if self._registry is not None and self in self._registry:
            self._registry.remove(self)

    @property
    def scheduled(self) -> bool:
        return self._handle is not None


class Actor:
    """Base for all modules (KvStore, Decision, Fib, ...)."""

    def __init__(self, name: str):
        self.name = name
        self._tasks: list[asyncio.Task] = []
        self._timers: list[Timer] = []
        self._stopped = asyncio.Event()
        self._running = False
        # Health timestamp for watchdog liveness (ref OpenrEventBase.h:76).
        self.last_alive_ts = time.monotonic()
        # Supervisor state: restarts are budgeted PER ACTOR (a flapping
        # fiber and a cascade across fibers both exhaust the same budget);
        # Watchdog.watch_actor overrides the knobs from config and wires
        # _escalate to its crash handler.
        self.crash_budget = SUPERVISOR_CRASH_BUDGET
        self.restart_backoff_initial_s = SUPERVISOR_BACKOFF_INITIAL_S
        self.restart_backoff_max_s = SUPERVISOR_BACKOFF_MAX_S
        self._escalate: Optional[Callable[[str], Any]] = None
        self._crash_count = 0

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        """Override run() for main logic; start() spawns it."""
        # the loop thread running start() owns this actor's state from
        # here on (role of the reference's per-module EventBase thread);
        # guarded operations assert against it when checks are enabled
        if affinity.enabled():
            affinity.bind_owner(self, self.name)
        self._running = True
        # every stack built of actors names the collector's pauses
        tracer.watch_gc()
        self.add_task(self._heartbeat_loop(), name=f"{self.name}.heartbeat")
        await self.on_start()

    async def on_start(self) -> None:  # override
        pass

    async def stop(self) -> None:
        self._running = False
        await self.on_stop()
        for t in self._timers:
            t.cancel()
        # snapshot: the prune-on-completion callback mutates _tasks while we
        # await, which would shift elements under a live iterator
        tasks = list(self._tasks)
        for task in tasks:
            task.cancel()
        for task in tasks:
            try:
                await task
            except (asyncio.CancelledError, QueueClosedError):
                pass
            # lint: allow(broad-except) teardown must drain every task
            except Exception:  # pragma: no cover
                log.exception("%s: task failed during stop", self.name)
        self._tasks.clear()
        self._stopped.set()

    async def on_stop(self) -> None:  # override
        pass

    # -- fibers / timers ---------------------------------------------------

    def add_task(
        self, coro: Coroutine[Any, Any, Any], name: str = ""
    ) -> asyncio.Task:
        """Role of OpenrEventBase::addFiberTask. QueueClosedError and
        cancellation terminate the task quietly (shutdown path)."""
        # spawning a fiber mutates _tasks and schedules onto the owning
        # loop — a cross-thread add_task would race both (use
        # call_soon_threadsafe from other threads)
        if affinity.enabled():
            affinity.assert_owner(self, "add_task")

        async def runner():
            try:
                await coro
            except (QueueClosedError, asyncio.CancelledError):
                pass
            except Exception as e:
                record_crash(name or f"{self.name}.task", e)
                log.exception("%s: task %s crashed", self.name, name)
                raise

        task = asyncio.get_running_loop().create_task(
            runner(), name=name or f"{self.name}.task"
        )
        self._tasks.append(task)
        # Prune on completion: short-lived tasks (per-publication floods,
        # client closes) must not accumulate for the actor's lifetime. Also
        # close the wrapped coroutine if the task was cancelled before its
        # first step (it would otherwise warn 'never awaited' at GC).
        def _done(t):
            if t in self._tasks:
                self._tasks.remove(t)
            # consume the exception (the runner already logged it) so GC
            # does not emit 'Task exception was never retrieved'
            if not t.cancelled():
                t.exception()
            try:
                coro.close()
            except RuntimeError:
                pass  # still running (normal completion path)

        task.add_done_callback(_done)
        return task

    def add_supervised_task(
        self,
        factory: Callable[[], Coroutine[Any, Any, Any]],
        name: str = "",
    ) -> asyncio.Task:
        """Supervised fiber (role of systemd Restart=on-failure for the
        reference daemon, scoped to one fiber): `factory` is a zero-arg
        callable returning a fresh coroutine — a crash restarts it with
        ExponentialBackoff after running the actor's recovery hook
        (on_fiber_restart), until the per-actor crash budget is exhausted
        and the failure escalates to the Watchdog crash handler."""
        return self.add_task(self._supervise(factory, name), name=name)

    async def _supervise(
        self, factory: Callable[[], Coroutine[Any, Any, Any]], name: str
    ) -> None:
        backoff: Optional[ExponentialBackoff] = None
        while True:
            try:
                await factory()
                return
            except (QueueClosedError, asyncio.CancelledError):
                raise  # shutdown paths are not crashes
            except Exception as e:
                record_crash(name or f"{self.name}.task", e)
                self._crash_count += 1
                if self._crash_count > self.crash_budget:
                    counters.increment("runtime.supervisor.escalations")
                    reason = (
                        f"{self.name}: fiber {name or '?'} exceeded crash "
                        f"budget ({self.crash_budget}): "
                        f"{type(e).__name__}: {e}"
                    )
                    log.critical(reason)
                    if self._escalate is not None:
                        self._escalate(reason)
                    raise
                # knobs are read lazily so Watchdog.watch_actor config
                # applied after start() still takes effect
                if backoff is None:
                    backoff = ExponentialBackoff(
                        self.restart_backoff_initial_s,
                        self.restart_backoff_max_s,
                    )
                backoff.report_error()
                delay = backoff.time_until_retry_s()
                counters.increment("runtime.supervisor.restarts")
                counters.increment(
                    f"runtime.supervisor.restarts.{self.name}"
                )
                log.warning(
                    "%s: supervisor restarting fiber %s in %.2fs "
                    "(crash %d/%d): %s",
                    self.name, name, delay, self._crash_count,
                    self.crash_budget, e,
                )
                self._emit_supervisor_restart(name, e)
                await asyncio.sleep(delay)
                try:
                    await self.on_fiber_restart(name)
                except Exception:
                    # the restart still proceeds — a broken recovery
                    # hook must not wedge the supervisor loop
                    counters.increment(
                        "runtime.supervisor.recovery_errors"
                    )
                    log.exception(
                        "%s: recovery hook failed for fiber %s",
                        self.name, name,
                    )

    async def on_fiber_restart(self, task_name: str) -> None:
        """Recovery hook run before a supervised fiber restarts (override:
        re-subscribe queues, force a full rebuild/resync, ...)."""

    def _emit_supervisor_restart(self, name: str, exc: Exception) -> None:
        """Surface the restart: SUPERVISOR_RESTART log sample (when the
        actor carries a log-sample queue) + a span event in the tracer's
        closed ring so drills can see restarts next to convergence."""
        q = getattr(self, "_log_samples", None) or getattr(
            self, "_log_sample_q", None
        )
        if q is not None:
            try:
                from openr_tpu.runtime.monitor import LogSample

                q.push(
                    LogSample(
                        event="SUPERVISOR_RESTART",
                        node_name=getattr(self, "node_name", self.name),
                        values={
                            "category": "supervisor",
                            "actor": self.name,
                            "task": name,
                            "restart": self._crash_count,
                            "error": f"{type(exc).__name__}: {exc}",
                        },
                    )
                )
            # lint: allow(broad-except) best-effort telemetry only
            except Exception:  # pragma: no cover - telemetry must not kill
                log.debug("%s: restart log sample failed", self.name)
        try:
            ctx = tracer.start_trace(
                "runtime.supervisor.restart",
                actor=self.name,
                task=name,
                restart=self._crash_count,
                error=type(exc).__name__,
            )
            if ctx is not None:
                tracer.end_trace(ctx, status="supervisor_restart")
        # lint: allow(broad-except) best-effort telemetry only
        except Exception:  # pragma: no cover
            log.debug("%s: restart span failed", self.name)

    def make_timer(self, callback: Callable[[], Any]) -> Timer:
        t = Timer(callback)
        # registered while scheduled only (self-removing on fire): _timers
        # stays bounded by the number of concurrently pending timers
        t._registry = self._timers
        return t

    def schedule(self, delay_s: float, callback: Callable[[], Any]) -> Timer:
        t = self.make_timer(callback)
        t.schedule(delay_s)
        return t

    # -- watchdog hook -----------------------------------------------------

    async def _heartbeat_loop(self) -> None:
        """Stamp liveness for the Watchdog, and read what the stamp's
        own lateness says: the beat knows when it was due, so a late one
        is a witness that the loop was held (tracer.note_loop_lag names
        what nothing else accounts for)."""
        due = time.monotonic()
        while self._running:
            now = self.last_alive_ts = time.monotonic()
            counters.add_stat_value("runtime.loop_lag_ms", (now - due) * 1e3)
            tracer.drain_gc()
            if now - due >= LOOP_LAG_HOLD_S:
                tracer.note_loop_lag(self.name, due, now)
            due = now + HEARTBEAT_S
            await asyncio.sleep(HEARTBEAT_S)

    def seconds_since_alive(self) -> float:
        return time.monotonic() - self.last_alive_ts


async def run_actors(*actors: Actor) -> None:
    """Start actors in order; awaitable handle for tests/main."""
    for a in actors:
        await a.start()


async def stop_actors(*actors: Actor) -> None:
    """Stop in reverse order (ref Main.cpp:592-599 teardown ordering)."""
    for a in reversed(actors):
        await a.stop()
