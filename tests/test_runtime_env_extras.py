"""Conda/container runtime envs + FastAPI-style Serve ingress.

Reference analogues: _private/runtime_env/conda.py (content-addressed
conda envs, gated on the binary), runtime_env/container.py (podman-
wrapped workers), serve/api.py @serve.ingress(app). The conda and
container runtimes aren't installed in this image, so the tests drive
the gates with fake binaries — exactly how the GCE provider tests
inject a fake transport.
"""

import json
import os
import stat
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import runtime_env as renv


# ------------------------------------------------------------------ conda

def test_conda_gated_when_missing(tmp_path, monkeypatch):
    monkeypatch.delenv("CONDA_EXE", raising=False)
    monkeypatch.setenv("PATH", str(tmp_path))  # no conda anywhere
    with pytest.raises(RuntimeError, match="conda install"):
        renv._ensure_conda_env("myenv", str(tmp_path))


def test_conda_named_and_dict_envs(tmp_path, monkeypatch):
    """A fake conda binary proves both resolution paths: named envs
    resolve under `conda info --base`, dict specs materialize a
    content-addressed env exactly once."""
    base = tmp_path / "conda_base"
    envdir = base / "envs" / "myenv" / "bin"
    envdir.mkdir(parents=True)
    (envdir / "python").write_text("")
    fake = tmp_path / "conda"
    fake.write_text(f"""#!/bin/sh
case "$1" in
  info) echo {base} ;;
  env)  # conda env create -p <dir> -f <yml> --yes
        mkdir -p "$4/bin" && : > "$4/bin/python" ;;
esac
""")
    fake.chmod(fake.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("CONDA_EXE", str(fake))

    py = renv._ensure_conda_env("myenv", str(tmp_path / "cache"))
    assert py == str(envdir / "python")
    with pytest.raises(RuntimeError, match="not found"):
        renv._ensure_conda_env("missing-env", str(tmp_path / "cache"))

    spec = {"dependencies": ["pip", {"pip": ["six"]}]}
    py2 = renv._ensure_conda_env(spec, str(tmp_path / "cache"))
    assert os.path.exists(py2)
    # second call hits the .ready marker (no re-create): drop the fake
    # binary's exec bit to prove conda isn't invoked again
    assert renv._ensure_conda_env(spec, str(tmp_path / "cache")) == py2


def test_conda_env_dir_passthrough(tmp_path, monkeypatch):
    monkeypatch.setenv("CONDA_EXE", "/bin/sh")  # exists; unused
    d = tmp_path / "someenv"
    (d / "bin").mkdir(parents=True)
    assert renv._ensure_conda_env(str(d), str(tmp_path)) == \
        str(d / "bin" / "python")


# -------------------------------------------------------------- container

def test_container_command_shape(tmp_path, monkeypatch):
    monkeypatch.setenv("RTPU_CONTAINER_RUNTIME", "/usr/bin/fakectr")
    cmd = renv.container_command(
        {"image": "img:1", "run_options": ["--gpus=none"]},
        "/sess", "/cache", env_keys=["RTPU_NODE_ID"])
    assert cmd[0] == "/usr/bin/fakectr"
    assert cmd[-1] == "img:1"
    assert "-v" in cmd and "/sess:/sess" in cmd
    assert cmd[cmd.index("-e") + 1] == "RTPU_NODE_ID"
    assert "--gpus=none" in cmd
    with pytest.raises(RuntimeError, match="image"):
        renv.container_command({}, "/s", "/c")
    monkeypatch.delenv("RTPU_CONTAINER_RUNTIME")
    monkeypatch.setenv("PATH", str(tmp_path))
    with pytest.raises(RuntimeError, match="podman or docker"):
        renv.container_command({"image": "x"}, "/s", "/c")


def test_container_worker_end_to_end(tmp_path):
    """A fake container runtime (drops the wrapper args, execs the
    worker command) proves the raylet's containerized spawn path: the
    task really runs behind the runtime prefix."""
    fake = tmp_path / "fakectr"
    fake.write_text("""#!/bin/sh
while [ "$1" != "TESTIMG" ]; do shift; done
shift
export RTPU_RAN_IN_CONTAINER=1
exec "$@"
""")
    fake.chmod(fake.stat().st_mode | stat.S_IEXEC)
    os.environ["RTPU_CONTAINER_RUNTIME"] = str(fake)
    try:
        ray_tpu.init(num_cpus=2, ignore_reinit_error=True,
                     object_store_memory=64 * 1024 * 1024)

        @ray_tpu.remote(runtime_env={"container": {"image": "TESTIMG"}})
        def probe():
            return os.environ.get("RTPU_RAN_IN_CONTAINER")

        assert ray_tpu.get(probe.remote(), timeout=60) == "1"
    finally:
        os.environ.pop("RTPU_CONTAINER_RUNTIME", None)
        ray_tpu.shutdown()


def test_conda_worker_end_to_end(tmp_path):
    """A fake conda that materializes envs whose bin/python symlinks the
    real interpreter proves the full spawn path: env creation happens
    ONCE (cache), the worker launches through the env's python, and
    same-env tasks reuse the pooled worker."""
    import sys

    calls = tmp_path / "create_calls"
    fake = tmp_path / "conda"
    # the fake env's bin/python is an exec WRAPPER around the real
    # interpreter (a symlink would lose the venv's pyvenv.cfg context)
    # that stamps the env dir into the worker's environment
    fake.write_text(f"""#!/bin/sh
case "$1" in
  info) echo {tmp_path}/conda_base ;;
  env)  echo created >> {calls}
        mkdir -p "$4/bin"
        printf '#!/bin/sh\\nexport RTPU_FAKE_CONDA_ENV="%s"\\nexec {sys.executable} "$@"\\n' "$4" > "$4/bin/python"
        chmod +x "$4/bin/python" ;;
esac
""")
    fake.chmod(fake.stat().st_mode | stat.S_IEXEC)
    os.environ["CONDA_EXE"] = str(fake)
    try:
        ray_tpu.init(num_cpus=2, ignore_reinit_error=True,
                     object_store_memory=64 * 1024 * 1024)
        # unique spec per run: the conda cache is content-addressed and
        # host-wide, so a fixed spec would reuse an env materialized by
        # a PREVIOUS test run's fake
        import uuid
        spec = {"dependencies": [f"python=3  # {uuid.uuid4().hex}"]}

        @ray_tpu.remote(runtime_env={"conda": spec})
        def probe():
            return os.environ.get("RTPU_FAKE_CONDA_ENV"), os.getpid()

        env1, pid1 = ray_tpu.get(probe.remote(), timeout=120)
        assert env1 and "/conda/" in env1  # launched through the env
        # same env -> same materialized env dir and NO second env
        # create (the content-addressed cache; the pid may differ —
        # the pool can hold several same-env workers)
        env2, pid2 = ray_tpu.get(probe.remote(), timeout=120)
        assert env2 == env1
        assert calls.read_text().count("created") == 1
    finally:
        os.environ.pop("CONDA_EXE", None)
        ray_tpu.shutdown()


# ---------------------------------------------------------------- ingress

def test_api_router_dispatch_unit():
    app = serve.APIRouter()

    class Svc:
        scale = 10

        @app.get("/items/{item_id}")
        def get_item(self, item_id: int):
            return {"id": item_id, "scaled": item_id * self.scale}

        @app.post("/items")
        def create(self, body):
            return {"created": body}

    from ray_tpu.serve.ingress import _dispatch
    svc = Svc()
    out = _dispatch(svc, app.routes, "/items/7", "GET", None)
    assert out == {"id": 7, "scaled": 70}
    out = _dispatch(svc, app.routes, "/items", "POST", [1, 2])
    assert out == {"created": [1, 2]}
    with pytest.raises(LookupError, match="405"):
        _dispatch(svc, app.routes, "/items/7", "DELETE", None)
    with pytest.raises(LookupError, match="404"):
        _dispatch(svc, app.routes, "/nope", "GET", None)


def test_serve_ingress_http_end_to_end():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 object_store_memory=64 * 1024 * 1024)
    try:
        app = serve.APIRouter()

        @serve.deployment
        @serve.ingress(app)
        class Calc:
            def __init__(self):
                self.base = 100

            @app.get("/add/{x}")
            def add(self, x: int):
                return {"sum": self.base + x}

            @app.post("/mul")
            def mul(self, factor):
                return {"product": self.base * factor}

        serve.run(Calc.bind(), route_prefix="/calc", http_port=8155)
        proxy = ray_tpu.get_actor("SERVE_PROXY")
        port = ray_tpu.get(proxy.get_port.remote(), timeout=240)

        got = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/calc/add/23", timeout=30).read())
        assert got == {"sum": 123}

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/calc/mul", data=b"7",
            headers={"Content-Type": "application/json"})
        got = json.loads(urllib.request.urlopen(req, timeout=30).read())
        assert got == {"product": 700}

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/calc/nope", timeout=30)
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/calc/add/1", data=b"{}",
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert ei.value.code == 405
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
