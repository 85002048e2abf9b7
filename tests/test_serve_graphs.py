"""Serve deployment graphs, DAGDriver, multi-app, config schema.

Reference analogues: serve/tests/test_deployment_graph*.py,
test_multi_application.py, test_schema.py, test_cli.py (scaled down).
"""

import json
import urllib.error
import urllib.request

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


def _get(port, path, payload=None):
    url = f"http://127.0.0.1:{port}{path}"
    if payload is not None:
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
    else:
        req = url
    return json.loads(urllib.request.urlopen(req, timeout=30).read())


def test_dag_driver_multiplexes_routes(serve_cluster):
    from ray_tpu.serve.drivers import DAGDriver

    @serve.deployment
    class Doubler:
        def __call__(self, x=0):
            return {"doubled": 2 * x}

    @serve.deployment
    class Negator:
        def __call__(self, x=0):
            return {"negated": -x}

    app = DAGDriver.bind({"/double": Doubler.bind(),
                          "/negate": Negator.bind()})
    serve.run(app, http_port=8124)
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)
    assert _get(port, "/double", 21) == {"doubled": 42}
    assert _get(port, "/negate", 5) == {"negated": -5}
    # unknown sub-route → error surfaced (500 from the driver's KeyError)
    with pytest.raises(urllib.error.HTTPError):
        _get(port, "/nothing", 1)


def test_dag_driver_under_non_root_prefix(serve_cluster):
    from ray_tpu.serve.drivers import DAGDriver

    @serve.deployment
    class Upper:
        def __call__(self, x=""):
            return {"up": str(x).upper()}

    app = DAGDriver.options(name="ApiDriver").bind({"/up": Upper.bind()})
    serve.run(app, name="api_app", route_prefix="/api", http_port=8124)
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)
    # the driver sees the path BELOW its route prefix
    assert _get(port, "/api/up", "hi") == {"up": "HI"}
    serve.delete_application("api_app")


def test_duplicate_deployment_name_across_apps_rejected(serve_cluster):
    @serve.deployment(name="SharedName")
    class One:
        def __call__(self, x=None):
            return 1

    @serve.deployment(name="SharedName")
    class Two:
        def __call__(self, x=None):
            return 2

    serve.run(One.bind(), name="first_app", route_prefix="/one",
              http_port=None)
    with pytest.raises(RuntimeError, match="unique across apps"):
        serve.run(Two.bind(), name="second_app", route_prefix="/two",
                  http_port=None)
    serve.delete_application("first_app")


def test_multi_app_coexistence(serve_cluster):
    @serve.deployment(name="AppA")
    class A:
        def __call__(self, x=None):
            return {"app": "a"}

    @serve.deployment(name="AppB")
    class B:
        def __call__(self, x=None):
            return {"app": "b"}

    serve.run(A.bind(), name="app_a", route_prefix="/a", http_port=8124)
    serve.run(B.bind(), name="app_b", route_prefix="/b", http_port=8124)
    apps = serve.list_applications()
    assert "app_a" in apps and "app_b" in apps
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)
    # deploying app_b must NOT have torn down app_a
    assert _get(port, "/a") == {"app": "a"}
    assert _get(port, "/b") == {"app": "b"}
    # app-scoped deletion
    serve.delete_application("app_a")
    assert "app_a" not in serve.list_applications()
    assert _get(port, "/b") == {"app": "b"}


def test_schema_build_and_overrides():
    from ray_tpu.serve.schema import (ServeApplicationSchema, build_app)
    schema = ServeApplicationSchema(
        name="cfg_app",
        import_path="tests.serve_test_app:app",
        deployments=[{"name": "ConfigEcho", "num_replicas": 2,
                      "max_concurrent_queries": 7}])
    app = build_app(schema)
    nodes = app._collect()
    (node,) = [n for n in nodes if n.deployment.name == "ConfigEcho"]
    assert node.deployment.config["num_replicas"] == 2
    assert node.deployment.config["max_concurrent_queries"] == 7


def test_deploy_config_end_to_end(serve_cluster):
    from ray_tpu.serve.schema import deploy_config
    names = deploy_config({
        "http_options": {"port": 8124},
        "applications": [{
            "name": "cfg_app",
            "import_path": "tests.serve_test_app:app",
            "route_prefix": "/cfg",
        }],
    })
    assert names == ["cfg_app"]
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)
    assert _get(port, "/cfg", {"k": 1}) == {"cfg_echo": {"k": 1}}
    st = serve.status()
    assert st["ConfigEcho"]["app"] == "cfg_app"


def test_builder_function_import_path():
    from ray_tpu.serve.schema import ServeApplicationSchema, build_app
    schema = ServeApplicationSchema(
        name="built", import_path="tests.serve_test_app:build_echo",
        args={"prefix": "yo"})
    app = build_app(schema)
    assert app.root.deployment.name == "ConfigEcho"


def test_http_adapters_unit():
    import numpy as np
    from ray_tpu.serve import http_adapters as ha
    a = ha.json_to_ndarray({"array": [[1, 2], [3, 4]]})
    assert a.shape == (2, 2) and a.dtype == np.float32
    assert ha.json_to_ndarray([1.0, 2.0]).tolist() == [1.0, 2.0]
    with pytest.raises(ValueError):
        ha.json_to_ndarray({"wrong": 1})
    multi = ha.json_to_multi_ndarray({"x": [1], "y": [2, 3]})
    assert set(multi) == {"x", "y"} and multi["y"].shape == (2,)
    assert ha.starlette_request({"a": 1}) == {"a": 1}
    df = ha.pandas_read_json([{"a": 1, "b": 2}, {"a": 3, "b": 4}])
    assert list(df.columns) == ["a", "b"] and len(df) == 2


def test_dag_driver_with_http_adapter(serve_cluster):
    from ray_tpu.serve.drivers import DAGDriver
    from ray_tpu.serve.http_adapters import json_to_ndarray

    @serve.deployment
    class SumModel:
        def __call__(self, arr):
            return {"sum": float(arr.sum())}

    app = DAGDriver.options(name="AdapterDriver").bind(
        {"/sum": SumModel.bind()}, http_adapter=json_to_ndarray)
    serve.run(app, http_port=8127)
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)
    assert _get(port, "/sum", {"array": [1, 2, 3.5]}) == {"sum": 6.5}
