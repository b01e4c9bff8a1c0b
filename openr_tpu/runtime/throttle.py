"""Rate-limiting primitives for expensive callbacks.

Roles of the reference's openr/common/AsyncThrottle.h:31,
AsyncDebounce.h:25 and ExponentialBackoff.{h,cpp}. AsyncDebounce is what
batches SPF runs in Decision (debounce_min..max window doubling); the same
semantics here drive the TPU solver's batching window.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Callable, Optional

from openr_tpu.runtime.tasks import spawn_logged


class AsyncThrottle:
    """Invoke `callback` at most once per `interval_s`; calls made while
    armed coalesce into the single pending invocation
    (ref AsyncThrottle.h:31)."""

    def __init__(self, interval_s: float, callback: Callable[[], Any]):
        self.interval_s = interval_s
        self._callback = callback
        self._handle: Optional[asyncio.TimerHandle] = None

    def __call__(self) -> None:
        if self._handle is not None:
            return  # already armed; coalesce
        loop = asyncio.get_running_loop()
        self._handle = loop.call_later(self.interval_s, self._fire)

    def _fire(self) -> None:
        self._handle = None
        res = self._callback()
        if asyncio.iscoroutine(res):
            spawn_logged(res, name=f"{type(self).__name__}.callback")

    def cancel(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None

    @property
    def is_active(self) -> bool:
        return self._handle is not None


class AsyncDebounce:
    """Debounce with exponential backoff, matching the reference semantics
    exactly (ref AsyncDebounce.h:44-75): each call *reschedules* the pending
    fire with a doubled window (min_s, 2*min_s, ... max_s) — postponing it —
    until the window saturates at `max_s`, after which further calls leave
    the pending fire untouched (so a sustained storm still fires roughly
    every max_s, bounding staleness). Firing resets the window to zero.
    This is what batches SPF runs under link-flap churn without starving
    them (a no-postpone variant diverged from the reference and was
    replaced)."""

    def __init__(self, min_s: float, max_s: float, callback: Callable[[], Any]):
        assert 0 < min_s <= max_s, "debounce window must be positive"
        self.min_s = min_s
        self.max_s = max_s
        self._callback = callback
        self._handle: Optional[asyncio.TimerHandle] = None
        self._armed = False  # a fire is pending
        self._current = 0.0  # current backoff window (valid while armed)

    def __call__(self) -> None:
        if self._armed and self._current >= self.max_s:
            # At max backoff: do not postpone the already-scheduled fire.
            return
        self._current = (
            self.min_s if not self._armed else min(self._current * 2, self.max_s)
        )
        self._armed = True
        if self._handle is not None:
            self._handle.cancel()
        loop = asyncio.get_running_loop()
        self._handle = loop.call_later(self._current, self._fire)

    def _fire(self) -> None:
        self._handle = None
        self._armed = False  # reset backoff so the next call starts at min_s
        res = self._callback()
        if asyncio.iscoroutine(res):
            spawn_logged(res, name=f"{type(self).__name__}.callback")

    def cancel(self) -> None:
        """ref cancelScheduledTimeout: cancel pending fire + reset backoff."""
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
        self._armed = False

    @property
    def is_active(self) -> bool:
        return self._handle is not None


class ExponentialBackoff:
    """Error backoff with doubling retry window
    (ref openr/common/ExponentialBackoff.{h,cpp})."""

    def __init__(self, initial_s: float, max_s: float):
        self.initial_s = initial_s
        self.max_s = max_s
        self._current = 0.0
        self._last_error_ts = 0.0

    def report_success(self) -> None:
        self._current = 0.0

    def report_error(self) -> None:
        self._current = (
            self.initial_s if self._current == 0 else min(self._current * 2, self.max_s)
        )
        self._last_error_ts = time.monotonic()

    def can_try_now(self) -> bool:
        return self.time_until_retry_s() <= 0

    def time_until_retry_s(self) -> float:
        if self._current == 0:
            return 0.0
        return max(0.0, self._last_error_ts + self._current - time.monotonic())

    @property
    def has_error(self) -> bool:
        return self._current > 0
