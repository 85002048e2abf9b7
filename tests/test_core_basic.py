"""Core API tests: tasks, objects, actors, options.

Mirrors the reference's python/ray/tests/test_basic.py coverage tier.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions


def test_put_get(ray_start_shared):
    ref = ray_tpu.put(42)
    assert ray_tpu.get(ref, timeout=240) == 42
    ref2 = ray_tpu.put({"a": [1, 2, 3], "b": "x"})
    assert ray_tpu.get(ref2, timeout=240) == {"a": [1, 2, 3], "b": "x"}


def test_put_get_numpy_zero_copy(ray_start_shared):
    arr = np.arange(500_000, dtype=np.float64)
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref, timeout=10)
    np.testing.assert_array_equal(out, arr)


def test_simple_task(ray_start_shared):
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(1), timeout=60) == 2


def test_task_chaining(ray_start_shared):
    @ray_tpu.remote
    def f(x):
        return x * 2

    ref = f.remote(1)
    for _ in range(4):
        ref = f.remote(ref)
    assert ray_tpu.get(ref, timeout=60) == 32


def test_task_large_args_and_returns(ray_start_shared):
    @ray_tpu.remote
    def double(a):
        return a * 2

    arr = np.ones(300_000, dtype=np.float32)
    out = ray_tpu.get(double.remote(arr), timeout=60)
    assert out.shape == arr.shape
    assert out[0] == 2.0


def test_multiple_returns(ray_start_shared):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c], timeout=60) == [1, 2, 3]


def test_task_error_propagates(ray_start_shared):
    @ray_tpu.remote
    def boom():
        raise ValueError("boom!")

    with pytest.raises(exceptions.TaskError) as ei:
        ray_tpu.get(boom.remote(), timeout=60)
    assert "boom!" in str(ei.value)


def test_get_does_not_see_the_error_of_an_attempt_being_retried(
        ray_start_shared, tmp_path):
    """The retry outlasts the getter's 2 s wait step: the getter looks
    in the memory store again while it runs, and must not find the first
    attempt's error there."""
    marker = str(tmp_path / "first_attempt_ran")

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def flaky(path):
        import os
        if not os.path.exists(path):
            open(path, "w").close()
            raise ValueError("first attempt fails")
        time.sleep(2.5)
        return "ok"

    assert ray_tpu.get(flaky.remote(marker), timeout=60) == "ok"


def test_wait(ray_start_shared):
    @ray_tpu.remote
    def fast():
        return "fast"

    @ray_tpu.remote
    def slow():
        time.sleep(3)
        return "slow"

    f, s = fast.remote(), slow.remote()
    ready, pending = ray_tpu.wait([f, s], num_returns=1, timeout=30)
    assert ready and ray_tpu.get(ready[0], timeout=240) == "fast"
    assert pending == [s] or not pending


def test_actor_basics(ray_start_shared):
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def incr(self, k=1):
            self.n += k
            return self.n

        def get(self):
            return self.n

    c = Counter.remote(10)
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 11
    assert ray_tpu.get(c.incr.remote(5), timeout=30) == 16
    assert ray_tpu.get(c.get.remote(), timeout=30) == 16


def test_actor_error(ray_start_shared):
    @ray_tpu.remote
    class Bad:
        def fail(self):
            raise RuntimeError("actor oops")

    b = Bad.remote()
    with pytest.raises(exceptions.ActorError) as ei:
        ray_tpu.get(b.fail.remote(), timeout=60)
    assert "actor oops" in str(ei.value)


def test_named_actor(ray_start_shared):
    @ray_tpu.remote
    class Store:
        def __init__(self):
            self.v = None

        def set(self, v):
            self.v = v
            return True

        def get(self):
            return self.v

    s = Store.options(name="kvstore").remote()
    ray_tpu.get(s.set.remote("hello"), timeout=60)
    s2 = ray_tpu.get_actor("kvstore")
    assert ray_tpu.get(s2.get.remote(), timeout=30) == "hello"


def test_actor_kill(ray_start_shared):
    @ray_tpu.remote
    class Victim:
        def ping(self):
            return "pong"

    v = Victim.remote()
    assert ray_tpu.get(v.ping.remote(), timeout=60) == "pong"
    ray_tpu.kill(v)
    time.sleep(0.5)
    with pytest.raises((exceptions.ActorDiedError, exceptions.ActorError,
                        exceptions.ActorUnavailableError)):
        ray_tpu.get(v.ping.remote(), timeout=30)


def test_options_validation(ray_start_shared):
    with pytest.raises(ValueError):
        @ray_tpu.remote(num_cpus=-1)
        def f():
            pass

    with pytest.raises(ValueError):
        @ray_tpu.remote(bogus_option=1)
        def g():
            pass


def test_nested_tasks(ray_start_shared):
    @ray_tpu.remote
    def inner(x):
        return x + 1

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x), timeout=240) + 10

    assert ray_tpu.get(outer.remote(1), timeout=90) == 12


def test_cluster_resources(ray_start_shared):
    res = ray_tpu.cluster_resources()
    assert res.get("CPU", 0) >= 4


def test_actor_call_with_temporary_put_ref(ray_start_shared):
    """A put() ref passed as an actor-call arg with no other Python
    reference must stay pinned until the call completes — the un-pinned
    path freed the object mid-flight and wedged the actor forever
    (regression: Ape-X/IMPALA weight broadcasts)."""
    import numpy as np

    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.w = None

        def set_w(self, w):
            self.w = w
            return float(w.sum())

        def ping(self):
            return "ok"

    h = Holder.remote()
    big = np.ones((256, 256), np.float32)  # plasma-sized
    # temporary ref: dropped by the driver the moment .remote() returns
    h.set_w.remote(ray_tpu.put(big))
    # the queued ping only runs if set_w did not wedge the actor
    assert ray_tpu.get(h.ping.remote(), timeout=30) == "ok"
    assert ray_tpu.get(h.set_w.remote(ray_tpu.put(big * 2)),
                       timeout=30) == float(big.sum() * 2)


def test_gc_during_refcount_no_deadlock(ray_start_shared):
    """GC firing inside refcount critical sections must not deadlock:
    ObjectRef.__del__ only defers its decrement (regression for a
    GC-in-add_local self-deadlock caught in the full-suite run)."""
    import gc

    old = gc.get_threshold()
    gc.set_threshold(1, 1, 1)  # collect on almost every allocation
    try:
        for i in range(200):
            refs = [ray_tpu.put((i, j)) for j in range(5)]
            assert ray_tpu.get(refs, timeout=60) == [(i, j)
                                                    for j in range(5)]
            del refs
    finally:
        gc.set_threshold(*old)
    # deferred decrements actually APPLY: after draining, the dropped
    # put-ids are gone from the refcount table
    w = ray_tpu._worker_mod.global_worker()
    w.reference_counter.drain_deferred()
    assert not w.reference_counter._deferred
    import gc as _gc
    _gc.collect()
    w.reference_counter.drain_deferred()
    remaining = len(w.reference_counter.table)
    assert remaining < 50, f"refcount table leaked: {remaining} entries"


def test_pipelined_actor_calls_execute_in_order(ray_start_shared):
    """Per-caller actor ordering (reference: actor_scheduling_queue.cc):
    fire-and-forget calls must execute in submission order even though
    their async sends race — create-then-train style pipelining depends
    on it."""
    @ray_tpu.remote
    class Log:
        def __init__(self):
            self.seen = []

        def add(self, i):
            self.seen.append(i)
            return i

        def dump(self):
            return list(self.seen)

    for _ in range(5):  # the race was intermittent — several rounds
        log = Log.remote()
        for i in range(20):
            log.add.remote(i)  # no gets: sends race on the event loop
        assert ray_tpu.get(log.dump.remote(),
                           timeout=60) == list(range(20))
        ray_tpu.kill(log)
