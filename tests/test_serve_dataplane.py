"""Serve data-plane tests: load-aware routing (power-of-two-choices),
adaptive micro-batching, and replica backpressure (bounded ingress
queue → retriable shed → HTTP 503). Tier-1, CPU-only."""

import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.exceptions import (BatchSubmitTimeoutError,
                                      ReplicaOverloadedError)


# --------------------------------------------------- replica backpressure

class _SlowCallable:
    def __init__(self, delay):
        self.delay = delay

    def __call__(self, x):
        time.sleep(self.delay)
        return x


def _make_replica(cls, mcq, max_queued, *init_args):
    import cloudpickle

    from ray_tpu.serve._private.replica import ReplicaActor
    return ReplicaActor("TestDep", cloudpickle.dumps(cls), init_args, {},
                        max_concurrent_queries=mcq,
                        max_queued_requests=max_queued)


def test_replica_sheds_past_bounded_queue():
    # 1 execution slot + 1 waiting-room slot: of 6 concurrent requests,
    # exactly 2 are admitted and 4 shed with a retriable error
    r = _make_replica(_SlowCallable, 1, 1, 0.3)
    results, errors = [], []
    barrier = threading.Barrier(6)

    def call(i):
        barrier.wait()
        try:
            results.append(r.handle_request("__call__", (i,), {}))
        except ReplicaOverloadedError as e:
            errors.append(e)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 2
    assert len(errors) == 4
    assert "retriable" in str(errors[0])
    m = r.get_metrics()
    assert m["total_shed"] == 4
    assert m["queue_len"] == 0  # fully drained
    assert m["ewma_service_time_s"] > 0


def test_replica_load_telemetry():
    r = _make_replica(_SlowCallable, 4, 4, 0.01)
    for i in range(3):
        r.handle_request("__call__", (i,), {})
    load = r.get_load()
    assert load["queue_len"] == 0
    assert load["ewma_s"] >= 0.01
    assert abs(load["ts"] - time.time()) < 5.0
    assert load["shed"] == 0


# ------------------------------------------------------- replica selection

class _FakeReplica:
    def __init__(self, id_hex):
        self._id_hex = id_hex


def _replica_set(policy, mcq=100, n=2):
    from ray_tpu.serve._private.router import ReplicaSet
    rs = ReplicaSet("dep", max_concurrent_queries=mcq)
    reps = [_FakeReplica(chr(ord("a") + i) * 8) for i in range(n)]
    rs.update_replicas(reps, routing_policy=policy)
    return rs, reps


def test_p2c_prefers_reported_less_loaded():
    rs, (a, b) = _replica_set("p2c")
    now = time.time()
    rs.record_report(a._id_hex, queue_len=50, ewma_s=0.1, ts=now)
    rs.record_report(b._id_hex, queue_len=0, ewma_s=0.1, ts=now)
    picks = {a._id_hex: 0, b._id_hex: 0}
    for _ in range(40):
        r = rs.assign(timeout=1.0)
        picks[r._id_hex] += 1
        rs.release(r)
    # with 2 replicas both are always sampled; the lower queue wins
    assert picks[b._id_hex] == 40


def test_stale_report_falls_back_to_local_counts():
    rs, (a, b) = _replica_set("p2c")
    # a's report is ancient and must be ignored, despite the huge queue
    rs.record_report(a._id_hex, queue_len=1000, ewma_s=0.1,
                     ts=time.time() - 3600)
    with rs._cv:  # 5 of our own requests outstanding on b
        rs._in_flight[b._id_hex] = 5
    for _ in range(10):
        r = rs.assign(timeout=1.0)
        assert r._id_hex == a._id_hex
        rs.release(r)


def test_round_robin_policy_alternates():
    rs, (a, b) = _replica_set("round_robin")
    order = []
    for _ in range(4):
        r = rs.assign(timeout=1.0)
        order.append(r._id_hex)
        rs.release(r)
    assert order == [a._id_hex, b._id_hex, a._id_hex, b._id_hex]


def test_assign_timeout_message_reflects_racing_update():
    # regression: update_replicas racing the wait loop must not leave a
    # stale replica count in the TimeoutError message
    rs, (a, b) = _replica_set("round_robin", mcq=1)
    rs.assign(timeout=1.0)  # saturate a
    rs.assign(timeout=1.0)  # saturate b

    def shrink():
        time.sleep(0.3)
        rs.update_replicas([a])  # b disappears mid-wait

    t = threading.Thread(target=shrink)
    t.start()
    with pytest.raises(TimeoutError) as ei:
        rs.assign(timeout=0.9)
    t.join()
    msg = str(ei.value)
    assert "(1 replicas" in msg
    assert "2 replicas" not in msg


class _FakeRemoteMethod:
    def remote(self, *a, **k):
        raise TimeoutError("controller busy")


class _FakeController:
    def __init__(self):
        self.get_route_table = _FakeRemoteMethod()
        self.listen_for_change = _FakeRemoteMethod()


def test_router_seed_failure_is_logged_not_swallowed(caplog):
    from ray_tpu.serve._private.router import Router
    with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.router"):
        router = Router(_FakeController())
        router.stop()
    assert any("seed" in rec.getMessage()
               for rec in caplog.records), caplog.records


# ------------------------------------------------------------- batching

def test_singleton_pad_flush_shape():
    sizes = []

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.01,
                 pad_to_bucket=True, min_pad_bucket=4)
    def handler(items):
        sizes.append(len(items))
        return items

    # a singleton flush must also pad — an unpadded stray shape would
    # mean a fresh JAX compile mid-traffic
    assert handler(7) == 7
    assert sizes == [4]


def test_batch_fn_error_unblocks_all_waiters():
    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
    def handler(items):
        raise ValueError("boom")

    errs = []

    def call(i):
        try:
            handler(i)
        except ValueError as e:
            errs.append(str(e))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == ["boom"] * 3


def test_late_enqueue_rearms_flusher():
    release = threading.Event()
    sizes = []

    @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
    def handler(items):
        sizes.append(len(items))
        release.wait(5.0)
        return items

    results = []

    def call(i):
        results.append(handler(i))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.3)  # first flush is now blocked inside the batch fn
    release.set()
    for t in threads:
        t.join(timeout=10.0)
    assert sorted(results) == [0, 1, 2]
    assert sum(sizes) == 3
    assert max(sizes) <= 2  # cap respected across re-armed flushes


def test_submit_timeout_surfaces_clear_error():
    @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01,
                 submit_timeout_s=0.2)
    def handler(items):
        time.sleep(2.0)  # wedged batch fn
        return items

    t0 = time.monotonic()
    with pytest.raises(BatchSubmitTimeoutError) as ei:
        handler(1)
    assert time.monotonic() - t0 < 1.5  # did not wait out the batch fn
    assert "submit_timeout_s" in str(ei.value)


def test_adaptive_batching_flushes_idle_queue_immediately():
    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.3,
                 adaptive=True)
    def fast(items):
        return items

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.3,
                 adaptive=False)
    def fixed(items):
        return items

    t0 = time.perf_counter()
    assert fast(1) == 1
    adaptive_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    assert fixed(1) == 1
    fixed_dt = time.perf_counter() - t0
    assert adaptive_dt < 0.15, adaptive_dt  # no idle wait window
    assert fixed_dt >= 0.25, fixed_dt  # fixed mode pays the full window


def test_prewarm_compiles_every_bucket():
    sizes = []

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.01,
                 pad_to_bucket=True)
    def handler(items):
        sizes.append(len(items))
        return items

    handler.prewarm(0)
    assert sizes == [1, 2, 4, 8]


def test_method_prewarm_uses_instance():
    class Scorer:
        def __init__(self, scale):
            self.scale = scale

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.01,
                     pad_to_bucket=True)
        def score(self, items):
            return [i * self.scale for i in items]

    s = Scorer(10)
    s.score.prewarm(s, 1)  # must not raise; compiles buckets 1,2,4
    assert s.score(2) == 20


# ------------------------------------------------------- cluster tests

@pytest.fixture(scope="module")
def serve_cluster():
    # env must be set BEFORE init so worker processes (proxy, replicas)
    # inherit it
    os.environ["RTPU_SERVE_PROXY_ASSIGN_TIMEOUT_S"] = "0.4"
    ctx = ray_tpu.init(num_cpus=8, ignore_reinit_error=True,
                       object_store_memory=128 * 1024 * 1024)
    yield ctx
    serve.shutdown()
    ray_tpu.shutdown()
    os.environ.pop("RTPU_SERVE_PROXY_ASSIGN_TIMEOUT_S", None)


def test_saturated_deployment_sheds_503(serve_cluster):
    @serve.deployment(num_replicas=1, max_concurrent_queries=1,
                      max_queued_requests=0)
    class OneSlot:
        def __call__(self, payload=None):
            time.sleep(1.2)
            return {"ok": True}

    serve.run(OneSlot.bind(), name="shed", route_prefix="/oneslot",
              http_port=8124)
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)
    outcomes = []
    lock = threading.Lock()

    def get():
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/oneslot", timeout=30)
            body = json.loads(resp.read())
            with lock:
                outcomes.append((resp.status, body))
        except urllib.error.HTTPError as e:
            body = json.loads(e.read())
            with lock:
                outcomes.append((e.code, body))

    threads = [threading.Thread(target=get) for _ in range(3)]
    for t in threads:
        t.start()
        time.sleep(0.05)
    for t in threads:
        t.join(timeout=120)
    codes = [c for c, _ in outcomes]
    assert 200 in codes, outcomes  # the admitted request completed
    shed = [(c, b) for c, b in outcomes if c == 503]
    assert shed, outcomes  # saturation shed instead of queueing
    assert all(b.get("retryable") for _, b in shed), outcomes


def test_replica_shed_is_retriable_actor_error(serve_cluster):
    from ray_tpu.actor import get_actor_by_id
    from ray_tpu.serve.controller import CONTROLLER_NAME
    from ray_tpu.serve._private.router import is_overload_error

    @serve.deployment(num_replicas=1, max_concurrent_queries=1,
                      max_queued_requests=0, name="ShedDirect")
    class OneSlot2:
        def __call__(self, x):
            time.sleep(0.8)
            return x

    serve.run(OneSlot2.bind(), name="shed2", http_port=None)
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    _, table = ray_tpu.get(controller.get_route_table.remote(), timeout=240)
    replica = get_actor_by_id(table["ShedDirect"]["replicas"][0])
    # bypass the router's own in-flight cap: hit the replica directly,
    # like a second router that hasn't seen this load yet would
    refs = [replica.handle_request.remote("__call__", (i,), {})
            for i in range(4)]
    results, errors = [], []
    for ref in refs:
        try:
            results.append(ray_tpu.get(ref, timeout=30.0))
        except Exception as e:  # noqa: BLE001 — asserting on type below
            errors.append(e)
    assert results, "the admitted request must complete"
    assert errors, "overflow must be shed"
    assert all(is_overload_error(e) for e in errors), errors


def test_router_receives_load_reports_via_long_poll(serve_cluster):
    from ray_tpu.serve import handle as handle_mod

    @serve.deployment(num_replicas=2, name="LoadRep")
    class Echo2:
        def __call__(self, x):
            return x

    h = serve.run(Echo2.bind(), name="loadrep", http_port=None)
    assert ray_tpu.get(h.remote(7), timeout=30.0) == 7
    router = handle_mod._router
    assert router is not None
    deadline = time.time() + 15.0
    reports = {}
    while time.time() < deadline:
        rs = router._sets.get("LoadRep")
        if rs is not None:
            with rs._cv:
                reports = dict(rs._reports)
            if reports:
                break
        time.sleep(0.2)
    assert reports, "controller never published replica_load"
    sample = next(iter(reports.values()))
    assert "queue_len" in sample and "ts" in sample
