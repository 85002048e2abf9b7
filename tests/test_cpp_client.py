"""C++ client API: compile with g++ and drive a live cluster end to end.

Reference analogue: cpp/src/ray/test/cluster/cluster_mode_test.cc — a
non-Python driver performing put/get, named cross-language invocation,
error propagation, KV, and cluster info over the wire protocol.
"""

import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SERVER_SCRIPT = """
import os, time
os.environ.setdefault("RTPU_PRESTART_WORKERS", "0")
os.environ["JAX_PLATFORMS"] = "cpu"
import ray_tpu
from ray_tpu.util.client.server import ClientServer
from ray_tpu.util import cross_language

ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
cross_language.register_function("math.add", lambda a, b: a + b)
cross_language.register_function("str.concat", lambda a, b: a + b)

def boom():
    raise ValueError("kaboom")

cross_language.register_function("math.boom", boom)
srv = ClientServer(port=0, host="127.0.0.1")
print(f"PORT={srv.port}", flush=True)
while True:
    time.sleep(1)
"""


@pytest.fixture(scope="module")
def cpp_binary(tmp_path_factory):
    gxx = shutil.which("g++")
    if gxx is None:
        pytest.skip("g++ not available")
    out = tmp_path_factory.mktemp("cpp") / "smoke"
    src = os.path.join(REPO, "src", "cpp_client", "smoke_main.cc")
    subprocess.run(
        [gxx, "-std=c++17", "-O2", "-Wall", "-Werror", "-o", str(out),
         src, "-I", os.path.join(REPO, "src", "cpp_client")],
        check=True)
    return str(out)


@pytest.fixture(scope="module")
def server_port(stop_driver):
    env = dict(os.environ)
    env.pop("RTPU_ADDRESS", None)
    proc = subprocess.Popen(
        [sys.executable, "-u", "-c", SERVER_SCRIPT],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=REPO)
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


def test_cpp_client_end_to_end(cpp_binary, server_port):
    r = subprocess.run([cpp_binary, str(server_port)],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout={r.stdout!r} stderr={r.stderr!r}"
    assert "CPP_CLIENT_OK" in r.stdout
