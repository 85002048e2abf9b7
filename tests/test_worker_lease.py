"""Worker-lease fast lane (reference:
src/ray/core_worker/transport/normal_task_submitter.cc — the reference's
normal-task path is lease-based: the owner leases a worker from the
raylet and pushes tasks to it directly).

Here the lease lane sits beside the GCS-routed default: a no-dep
CPU-only task costs 2 messages total (owner->worker request, reply with
the result) instead of 6 across 3 processes.  These tests pin the
engagement, arbitration, and fallback semantics.
"""

import time

import pytest

import ray_tpu


@pytest.fixture
def one_cpu_cluster():
    ray_tpu.init(num_cpus=1, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _driver():
    from ray_tpu._private import worker as wmod
    return wmod._global_worker


def _lease_engaged(w) -> bool:
    """True when the ACTIVE lease lane holds a live lease: the native
    direct pool when RTPU_NATIVE_RPC is on and the pump loaded, the
    asyncio pool otherwise (both implement the same lease contract)."""
    dc = w._direct_client
    if dc is not None and dc.usable():
        return any(L.addr for pool in dc.pools.values() for L in pool)
    return any(L.addr for pool in w._worker_leases.values() for L in pool)


def test_lease_lane_engages_and_results_are_correct(one_cpu_cluster):
    @ray_tpu.remote
    def f(x):
        return x * 2

    assert ray_tpu.get(f.remote(1), timeout=240) == 2
    deadline = time.time() + 10
    w = _driver()
    while time.time() < deadline and not _lease_engaged(w):
        ray_tpu.get(f.remote(0), timeout=240)
    assert _lease_engaged(w), \
        "lease never engaged for a qualifying CPU task"
    # correctness through the leased path, including app errors
    assert ray_tpu.get([f.remote(i) for i in range(50)], timeout=240) == \
        [i * 2 for i in range(50)]

    @ray_tpu.remote
    def boom():
        raise ValueError("expected")

    with pytest.raises(Exception, match="expected"):
        ray_tpu.get(boom.remote(), timeout=240)
    # and still correct afterwards
    assert ray_tpu.get(f.remote(21), timeout=240) == 42


def test_lease_skips_custom_resource_tasks(one_cpu_cluster):
    """Custom resources imply node placement — they must ride the
    normal scheduler path (the round-5 regression: a nodeB-only task
    parked forever on a local lease acquisition)."""
    @ray_tpu.remote
    def f():
        return "ok"

    w = _driver()
    spec = {"resources": {"CPU": 1.0, "nodeB": 1.0}}
    assert not w._lease_qualifies(spec)
    assert w._lease_qualifies({"resources": {"CPU": 1.0}})
    assert not w._lease_qualifies({"resources": {"CPU": 1.0},
                                   "plasma_deps": ["ab"]})
    assert not w._lease_qualifies({"resources": {"TPU": 1.0}})


def test_idle_lease_releases_capacity(one_cpu_cluster):
    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get([f.remote() for _ in range(10)], timeout=240)
    w = _driver()
    deadline = time.time() + 15
    while time.time() < deadline and _lease_engaged(w):
        time.sleep(0.25)
    assert not _lease_engaged(w), "idle lease still pinning capacity"
    # capacity is back: a fresh non-leasable task can run
    @ray_tpu.remote(max_retries=0)
    def g():
        return 2

    assert ray_tpu.get(
        g.options(scheduling_strategy="SPREAD").remote(), timeout=30) == 2


def test_cancel_reaches_leased_tasks(one_cpu_cluster):
    """cancel() must work for tasks the raylet never saw (pushed
    directly to a leased worker, or still parked driver-side)."""
    @ray_tpu.remote
    def quick():
        return 1

    # the lease engages
    ray_tpu.get([quick.remote() for _ in range(5)], timeout=240)

    @ray_tpu.remote
    def slow():
        time.sleep(30)
        return "finished"

    ref = slow.remote()
    time.sleep(0.5)  # let it start (or park) through the lease lane
    ray_tpu.cancel(ref)
    with pytest.raises(Exception):  # TaskCancelledError (or worker kill)
        ray_tpu.get(ref, timeout=25)


def test_mixed_workload_not_starved_by_leases(one_cpu_cluster):
    """With every CPU leased, a non-qualifying task must still run —
    the raylet revokes a lease under contention."""
    @ray_tpu.remote
    def fast(x):
        return x

    # keep the lease lane hot
    ray_tpu.get([fast.remote(i) for i in range(20)], timeout=240)

    @ray_tpu.remote
    def other():
        return "ran"

    # SPREAD strategy disqualifies the task from leasing, so it needs
    # real (non-leased) capacity -> the raylet must revoke
    ref = other.options(scheduling_strategy="SPREAD").remote()
    assert ray_tpu.get(ref, timeout=60) == "ran"


def test_disconnect_with_multiple_leases_refunds_all():
    """Regression (round-5 ADVICE high-severity): an owner disconnecting
    while holding 2+ leases must refund EVERY lease — _on_disconnect
    used to iterate conn.meta['leases'] while _release_lease pruned it
    in place, skipping every other entry and leaking its capacity
    forever."""
    import ray_tpu
    from ray_tpu._private import protocol

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    try:
        w = _driver()
        raylet_tcp = next(n["raylet_address"] for n in ray_tpu.nodes()
                          if n["alive"])
        # a second "owner": raw connection that takes 2 leases and dies
        conn = w.io.run(protocol.connect(raylet_tcp))
        grants = []
        for _ in range(2):
            r = w.call_sync(conn, "lease_worker",
                            {"resources": {"CPU": 1.0}}, timeout=60)
            assert "lease_id" in r, r
            grants.append(r["lease_id"])
        info = w.call_sync(w.raylet, "get_info", {})
        assert info["available"].get("CPU", 0) == 0  # both CPUs leased
        w.io.run(conn.aclose())  # owner dies holding both leases
        deadline = time.time() + 15
        cpu_avail = -1.0
        while time.time() < deadline:
            info = w.call_sync(w.raylet, "get_info", {})
            cpu_avail = info["available"].get("CPU", 0)
            if cpu_avail == info["resources"].get("CPU"):
                break
            time.sleep(0.2)
        assert cpu_avail == info["resources"].get("CPU"), \
            f"leaked lease capacity: available CPU {cpu_avail} after " \
            f"owner disconnect (leases={grants})"
        # and the refunded capacity is actually usable
        @ray_tpu.remote(num_cpus=2)
        def big():
            return "ok"

        assert ray_tpu.get(big.remote(), timeout=60) == "ok"
    finally:
        ray_tpu.shutdown()
