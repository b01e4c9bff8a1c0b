"""Actor / throttle / debounce / backoff / persistent-store tests
(semantics of ref openr/common/tests, openr/config-store/tests)."""

import asyncio

from openr_tpu.kvstore.wrapper import wait_until
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.runtime import (
    Actor,
    AsyncDebounce,
    AsyncThrottle,
    ExponentialBackoff,
    PersistentStore,
)
from tests.conftest import run_async


@run_async
async def test_actor_task_consumes_queue_and_stops_cleanly():
    q = ReplicateQueue()
    got = []

    class Consumer(Actor):
        async def on_start(self):
            self.reader = q.get_reader()
            self.add_task(self._run(), name="consume")

        async def _run(self):
            while True:
                got.append(await self.reader.get())

    a = Consumer("consumer")
    await a.start()
    q.push(1)
    q.push(2)
    await asyncio.sleep(0.02)
    assert got == [1, 2]
    await a.stop()  # cancels the blocked fiber without error


@run_async
async def test_throttle_coalesces():
    fired = []
    th = AsyncThrottle(0.02, lambda: fired.append(1))
    for _ in range(10):
        th()
    assert th.is_active
    await asyncio.sleep(0.05)
    assert len(fired) == 1
    th()
    await asyncio.sleep(0.05)
    assert len(fired) == 2


@run_async
async def test_debounce_bounded_staleness_under_storm():
    fired = []
    db = AsyncDebounce(0.01, 0.04, lambda: fired.append(1))
    # 200ms storm, calls faster than min window: fires must keep happening
    # (bounded staleness), coalesced but never starved
    for _ in range(50):
        db()
        await asyncio.sleep(0.004)
    await asyncio.sleep(0.06)
    assert 3 <= len(fired) <= 12  # coalesced (not 50) but not starved (not 1)
    n = len(fired)
    await asyncio.sleep(0.05)  # quiet period resets window to min
    db()
    await asyncio.sleep(0.02)
    assert len(fired) == n + 1


@run_async
async def test_debounce_postpones_like_reference():
    # Reference contract (AsyncDebounce.h:44-52): every call below max
    # backoff RESCHEDULES the pending fire with a doubled window; calls at
    # max backoff leave it alone. Postponement is read off the timer
    # handles (the loop's own schedule), not off how long a sleep took:
    # a loaded host stretches sleeps, it does not move deadlines earlier.
    loop = asyncio.get_running_loop()
    fired = []
    db = AsyncDebounce(0.02, 0.08, lambda: fired.append(loop.time()))
    t0 = loop.time()
    db()  # scheduled +0.02
    first = db._handle
    assert t0 + 0.02 <= first.when() <= loop.time() + 0.02
    t1 = loop.time()
    db()  # rescheduled +0.04 from now — the original +0.02 must NOT fire
    assert first.cancelled()
    assert t1 + 0.04 <= db._handle.when() <= loop.time() + 0.04
    deadline = db._handle.when()
    await wait_until(lambda: fired)
    assert len(fired) == 1 and fired[0] >= deadline  # postponed, fired once
    # cancel resets backoff: next call starts again at min
    db()
    db.cancel()
    await asyncio.sleep(0.1)
    assert len(fired) == 1
    t2 = loop.time()
    db()
    assert t2 + 0.02 <= db._handle.when() <= loop.time() + 0.02
    await wait_until(lambda: len(fired) == 2)


def test_exponential_backoff():
    bo = ExponentialBackoff(0.1, 0.4)
    assert bo.can_try_now()
    bo.report_error()
    assert not bo.can_try_now()
    assert 0 < bo.time_until_retry_s() <= 0.1
    bo.report_error()
    assert bo.time_until_retry_s() <= 0.2
    bo.report_error()
    bo.report_error()
    assert bo.time_until_retry_s() <= 0.4  # capped
    bo.report_success()
    assert bo.can_try_now()


def test_persistent_store_roundtrip(tmp_path):
    path = str(tmp_path / "store.bin")
    ps = PersistentStore(path)
    ps.store("k1", b"v1")
    ps.store("k2", b"v2")
    ps.erase("k1")
    ps.close()
    ps2 = PersistentStore(path)
    assert ps2.load("k1") is None
    assert ps2.load("k2") == b"v2"
    assert ps2.keys() == ["k2"]
    ps2.close()


def test_persistent_store_compaction_and_truncated_tail(tmp_path):
    path = str(tmp_path / "store.bin")
    ps = PersistentStore(path)
    for i in range(600):  # force compaction (slack 256)
        ps.store("key", b"x" * i)
    ps.close()
    # simulate crash mid-write: append garbage partial record
    with open(path, "ab") as fh:
        fh.write(b"\x01\xff\xff")
    ps2 = PersistentStore(path)
    assert ps2.load("key") == b"x" * 599
    ps2.close()


def test_persistent_store_writes_after_crash_recovery_survive(tmp_path):
    # Regression for ADVICE r1 high: recovery must truncate the partial
    # tail record, else appends after recovery land beyond garbage bytes
    # and are lost on the next restart.
    path = str(tmp_path / "store.bin")
    ps = PersistentStore(path)
    ps.store("k1", b"v1")
    ps.close()
    with open(path, "ab") as fh:
        fh.write(b"\x01\x03\x00")  # partial header (crash mid-write)
    ps2 = PersistentStore(path)
    assert ps2.load("k1") == b"v1"
    ps2.store("k2", b"v2")  # written after recovery
    ps2.close()
    ps3 = PersistentStore(path)
    assert ps3.load("k1") == b"v1"
    assert ps3.load("k2") == b"v2"
    ps3.close()


def test_persistent_store_objects(tmp_path):
    from openr_tpu.types import PrefixEntry, PrefixType

    path = str(tmp_path / "store.bin")
    ps = PersistentStore(path)
    entry = PrefixEntry(prefix="10.0.0.0/24", type=PrefixType.CONFIG)
    ps.store_obj("pfx", entry)
    ps.close()
    ps2 = PersistentStore(path)
    assert ps2.load_obj("pfx", PrefixEntry) == entry
    ps2.close()
