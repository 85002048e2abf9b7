"""Serve layer tests (reference strategy: serve/tests/* against a local
cluster — controller reconcile, handles, HTTP, batching, autoscaling)."""

import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def serve_cluster():
    ctx = ray_tpu.init(num_cpus=8, ignore_reinit_error=True,
                       object_store_memory=128 * 1024 * 1024)
    yield ctx
    serve.shutdown()
    ray_tpu.shutdown()


def test_deploy_and_handle(serve_cluster):
    @serve.deployment(num_replicas=2)
    class Adder:
        def __init__(self, offset):
            self.offset = offset

        def __call__(self, x):
            return x + self.offset

        def mult(self, x):
            return x * self.offset

    h = serve.run(Adder.bind(10), http_port=None)
    assert ray_tpu.get(h.remote(5), timeout=240) == 15
    # method routing
    assert ray_tpu.get(h.mult.remote(5), timeout=240) == 50
    st = serve.status()
    assert st["Adder"]["status"] == "HEALTHY"
    assert st["Adder"]["live_replicas"] == 2


def test_function_deployment_and_composition(serve_cluster):
    @serve.deployment
    class Preprocessor:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Ingress:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            y = ray_tpu.get(self.pre.remote(x), timeout=240)
            return y + 1

    h = serve.run(Ingress.bind(Preprocessor.bind()), http_port=None)
    assert ray_tpu.get(h.remote(10), timeout=240) == 21


def test_rolling_update_reconfigure(serve_cluster):
    @serve.deployment(num_replicas=1, user_config={"factor": 2})
    class Scaler:
        def __init__(self):
            self.factor = 1

        def reconfigure(self, cfg):
            self.factor = cfg["factor"]

        def __call__(self, x):
            return x * self.factor

    h = serve.run(Scaler.bind(), http_port=None)
    assert ray_tpu.get(h.remote(10), timeout=240) == 20
    # redeploy with new user_config → new version → rolling replace
    h = serve.run(Scaler.options(user_config={"factor": 5}).bind(),
                  http_port=None)
    deadline = time.time() + 30
    while time.time() < deadline:
        if ray_tpu.get(h.remote(10), timeout=240) == 50:
            break
        time.sleep(0.2)
    assert ray_tpu.get(h.remote(10), timeout=240) == 50


def test_http_proxy(serve_cluster):
    @serve.deployment
    class Echo:
        def __call__(self, payload=None):
            return {"echo": payload}

    serve.run(Echo.bind(), route_prefix="/echo", http_port=8123)
    # the proxy may have bound a fallback port; ask the proxy actor
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)
    body = json.dumps({"msg": "hi"}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/echo", data=body,
        headers={"Content-Type": "application/json"})
    resp = json.loads(urllib.request.urlopen(req, timeout=30).read())
    assert resp == {"echo": {"msg": "hi"}}
    # GET with query params
    resp2 = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/echo?a=1", timeout=30).read())
    assert resp2 == {"echo": {"a": "1"}}
    # 404 for unknown route when no "/" route exists... "/echo" matches
    # everything under /echo only; /nope should 404.
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=30)
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_batching_pads_to_bucket():
    calls = []

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05,
                 pad_to_bucket=True)
    def handler(items):
        calls.append(len(items))
        return [i * 2 for i in items]

    import threading
    results = {}

    def call(i):
        results[i] = handler(i)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == {0: 0, 1: 2, 2: 4}
    # 3 concurrent requests → padded to bucket of 4 (or served in
    # smaller flushes, each a power of two)
    assert all(c in (1, 2, 4, 8) for c in calls)


def test_batching_caps_at_max_batch_size():
    sizes = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
    def handler(items):
        sizes.append(len(items))
        return list(items)

    import threading
    threads = [threading.Thread(target=handler, args=(i,))
               for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert max(sizes) <= 4
    assert sum(sizes) == 12


def test_batching_per_instance_isolation():
    class Scorer:
        def __init__(self, scale):
            self.scale = scale

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.02)
        def score(self, items):
            return [i * self.scale for i in items]

    a, b = Scorer(10), Scorer(100)
    import threading
    results = {}

    def call(obj, key, x):
        results[key] = obj.score(x)

    ts = [threading.Thread(target=call, args=(a, "a1", 1)),
          threading.Thread(target=call, args=(a, "a2", 2)),
          threading.Thread(target=call, args=(b, "b1", 1)),
          threading.Thread(target=call, args=(b, "b2", 2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # items from instance b must never be scored with instance a's scale
    assert results == {"a1": 10, "a2": 20, "b1": 100, "b2": 200}


def test_autoscaling_policy_decisions():
    from ray_tpu.serve._private.autoscaling import (AutoscalingConfig,
                                                    AutoscalingPolicy)
    p = AutoscalingPolicy(AutoscalingConfig(
        min_replicas=1, max_replicas=4,
        target_num_ongoing_requests_per_replica=2,
        upscale_delay_s=0.0, downscale_delay_s=0.0))
    # 8 ongoing / target 2 → 4 replicas
    assert p.get_decision(1, 8.0, now=100.0) == 4
    # idle → scale back to min
    assert p.get_decision(4, 0.0, now=200.0) == 1
    # at target → hold
    assert p.get_decision(2, 4.0, now=300.0) == 2


def test_autoscaling_hysteresis():
    from ray_tpu.serve._private.autoscaling import (AutoscalingConfig,
                                                    AutoscalingPolicy)
    p = AutoscalingPolicy(AutoscalingConfig(
        min_replicas=1, max_replicas=4,
        target_num_ongoing_requests_per_replica=1,
        upscale_delay_s=5.0, downscale_delay_s=5.0))
    # spike shorter than upscale_delay → no change
    assert p.get_decision(1, 4.0, now=0.0) == 1
    assert p.get_decision(1, 4.0, now=2.0) == 1
    assert p.get_decision(1, 4.0, now=6.0) == 4


def test_function_deployment_and_delete(serve_cluster):
    @serve.deployment
    def stateless(x):
        return x + 100

    h = serve.run(stateless.options(name="ToDelete").bind(),
                  http_port=None)
    assert ray_tpu.get(h.remote(1), timeout=240) == 101
    serve.delete("ToDelete")
    deadline = time.time() + 15
    while time.time() < deadline and "ToDelete" in serve.status():
        time.sleep(0.2)
    assert "ToDelete" not in serve.status()
