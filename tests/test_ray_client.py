"""ray:// client connectivity — a driver OUTSIDE the cluster host process.

Reference analogue: python/ray/tests/test_client.py. The server runs in a
subprocess holding a real cluster; this test process connects over TCP
with ray_tpu.init("ray://...") and uses the public API end to end.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

SERVER_SCRIPT = """
import os, sys, time
os.environ.setdefault("RTPU_PRESTART_WORKERS", "0")
os.environ["JAX_PLATFORMS"] = "cpu"
import ray_tpu
from ray_tpu.util.client.server import ClientServer
ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
srv = ClientServer(port=0, host="127.0.0.1")
print(f"PORT={srv.port}", flush=True)
# serve until the parent kills us
while True:
    time.sleep(1)
"""


@pytest.fixture(scope="module")
def client_server(stop_driver):
    env = dict(os.environ)
    env.pop("RTPU_ADDRESS", None)
    proc = subprocess.Popen(
        [sys.executable, "-u", "-c", SERVER_SCRIPT],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    port = None
    deadline = time.time() + 120
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("PORT="):
            port = int(line.strip().split("=", 1)[1])
            break
    if port is None:
        proc.kill()
        pytest.fail("client server did not start")
    yield port
    stop_driver(proc)


@pytest.fixture()
def ray_client(client_server):
    import ray_tpu
    ray_tpu.init(address=f"ray://127.0.0.1:{client_server}")
    yield
    ray_tpu.shutdown()


def test_client_put_get_roundtrip(ray_client):
    import ray_tpu
    from ray_tpu.util.client import ClientObjectRef
    arr = np.arange(1000, dtype=np.float32)
    ref = ray_tpu.put(arr)
    assert isinstance(ref, ClientObjectRef)
    out = ray_tpu.get(ref, timeout=240)
    np.testing.assert_array_equal(out, arr)


def test_client_remote_task(ray_client):
    import ray_tpu

    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(2, 40), timeout=240) == 42
    # ref args resolve server-side to the real objects
    ref = ray_tpu.put(10)
    assert ray_tpu.get(add.remote(ref, 5), timeout=240) == 15
    # options + multiple returns
    @ray_tpu.remote
    def pair(x):
        return x, x + 1

    r1, r2 = ray_tpu.get(pair.options(num_returns=2).remote(7), timeout=240)
    assert (r1, r2) == (7, 8)


def test_client_wait(ray_client):
    import ray_tpu

    @ray_tpu.remote
    def slow(t):
        import time as _t
        _t.sleep(t)
        return t

    fast = slow.remote(0.01)
    slow_ref = slow.remote(5.0)
    ready, not_ready = ray_tpu.wait([fast, slow_ref], num_returns=1,
                                    timeout=10.0)
    assert ready == [fast] and not_ready == [slow_ref]


def test_client_actor_lifecycle(ray_client):
    import ray_tpu
    from ray_tpu.util.client import ClientActorHandle

    @ray_tpu.remote
    class Counter:
        def __init__(self, start):
            self.x = start

        def incr(self, n=1):
            self.x += n
            return self.x

    c = Counter.remote(100)
    assert isinstance(c, ClientActorHandle)
    assert ray_tpu.get(c.incr.remote(), timeout=240) == 101
    assert ray_tpu.get(c.incr.remote(9), timeout=240) == 110
    # actor handles pass through task args (rehydrated server-side)
    @ray_tpu.remote
    def poke(counter):
        return ray_tpu.get(counter.incr.remote(5), timeout=240)

    assert ray_tpu.get(poke.remote(c), timeout=240) == 115
    ray_tpu.kill(c)


def test_client_named_actor(ray_client):
    import ray_tpu

    @ray_tpu.remote
    class KV:
        def __init__(self):
            self.d = {}

        def set(self, k, v):
            self.d[k] = v
            return True

        def get(self, k):
            return self.d.get(k)

    KV.options(name="kv_client_test").remote()
    h = ray_tpu.get_actor("kv_client_test")
    assert ray_tpu.get(h.set.remote("a", 1), timeout=240)
    assert ray_tpu.get(h.get.remote("a"), timeout=240) == 1


def test_client_cluster_info_and_errors(ray_client):
    import ray_tpu
    res = ray_tpu.cluster_resources()
    assert res.get("CPU", 0) >= 4
    assert ray_tpu.is_initialized()

    @ray_tpu.remote
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(Exception, match="kaboom"):
        ray_tpu.get(boom.remote(), timeout=240)
