"""Unit tests over the interface-mock layer — no clusters, no sockets.

Reference analogue: the C++ unit suites under ``src/ray/*/test`` built
on ``src/mock/ray/**`` gmock doubles (SURVEY §4: components test in
isolation against mock interfaces). These cover logic that the
integration suite can only reach statistically: actor-call ordering,
pull admission, wire-schema validation, version negotiation.
"""

import asyncio

import pytest

from ray_tpu._private import schema
from ray_tpu._private.testing import MockConnection, MockStore, make_bare
from ray_tpu.common.ids import ObjectID


# ------------------------------------------------------------- wire schema

def test_schema_validate_good_and_bad():
    assert schema.validate("resource_report", {
        "node_id": "n1", "available": {"CPU": 1.0}}) == []
    errs = schema.validate("resource_report", {"available": "nope"})
    assert any("node_id" in e and "missing" in e for e in errs)
    assert any("available" in e and "expected" in e for e in errs)
    # unknown fields pass (proto3 forward-compat rule)
    assert schema.validate("kv_get", {"key": "k", "future_field": 1}) == []
    # unknown methods pass through
    assert schema.validate("not_a_method", {"x": 1}) == []


def test_schema_hello_negotiation():
    assert schema.check_hello(schema.hello_payload()) is None
    bad = {"protocol_version": [schema.PROTOCOL_VERSION[0] + 1, 0]}
    assert "incompatible" in schema.check_hello(bad)
    # minor skew is compatible
    minor = {"protocol_version": [schema.PROTOCOL_VERSION[0], 99]}
    assert schema.check_hello(minor) is None
    assert len(schema.schema_hash()) == 16


def test_server_rejects_invalid_payload_when_enabled(monkeypatch):
    from ray_tpu._private import protocol
    monkeypatch.setenv("RTPU_VALIDATE_WIRE", "1")
    async def kv_get(payload, conn):
        return {"value": None}

    server = protocol.Server({"kv_get": kv_get})

    async def drive():
        with pytest.raises(protocol.RpcError, match="wire schema"):
            await server._handle("kv_get", {"wrong": 1}, None)
        # __hello__ negotiates without a registered handler
        reply = await server._handle(
            "__hello__", schema.hello_payload(), None)
        assert reply["schema_hash"] == schema.schema_hash()

    asyncio.run(drive())


# ----------------------------------------------------- actor-call ordering

def _bare_receiver():
    from ray_tpu._private.worker import Worker
    return make_bare(Worker, _actor_seq={}, _actor_waiting={})


def test_ordering_parks_until_predecessor():
    w = _bare_receiver()
    order = []

    async def handler(seq, upto=0):
        await w._order_actor_call("c", seq, upto)
        order.append(seq)
        w._release_actor_call("c", seq)

    async def drive():
        # seq 3 and 2 arrive before 1: both park; 1 unlocks the chain
        t3 = asyncio.create_task(handler(3))
        t2 = asyncio.create_task(handler(2))
        await asyncio.sleep(0.05)
        assert order == []
        await handler(1)
        await asyncio.gather(t2, t3)

    asyncio.run(drive())
    assert order == [1, 2, 3]


def test_ordering_fast_forwards_on_processed_up_to():
    w = _bare_receiver()
    done = []

    async def drive():
        # fresh receiver (actor restart): first arrival has seq 42 but
        # advertises 41 already processed — dispatch immediately
        await asyncio.wait_for(
            w._order_actor_call("c", 42, processed_up_to=41), timeout=1)
        done.append(42)
        assert w._actor_seq["c"] == 42

    asyncio.run(drive())
    assert done == [42]


def test_ordering_duplicate_dispatches_immediately():
    w = _bare_receiver()

    async def drive():
        await w._order_actor_call("c", 1, 0)
        w._release_actor_call("c", 1)
        # a retry of seq 1 must not park behind itself
        await asyncio.wait_for(w._order_actor_call("c", 1, 0), timeout=1)

    asyncio.run(drive())


# --------------------------------------------------------- pull admission

def test_pull_admission_caps_inflight_bytes():
    from ray_tpu._private.raylet import Raylet
    from ray_tpu.common.config import SystemConfig

    MB = 1024 * 1024
    store = MockStore(capacity=100 * MB)
    r = make_bare(Raylet, store=store, _pull_inflight_bytes=0,
                  _pull_waiters=None,
                  config=SystemConfig(pull_admission_fraction=0.5))
    acquired = []

    async def drive():
        a = await r._admit_pull(30 * MB)   # budget = 50 MB
        acquired.append(a)
        b_task = asyncio.create_task(r._admit_pull(30 * MB))  # exceeds
        await asyncio.sleep(0.05)
        assert not b_task.done()      # blocked on the budget
        await r._release_pull(a)
        acquired.append(await asyncio.wait_for(b_task, timeout=1))
        await r._release_pull(acquired[-1])
        # one object larger than the whole budget still admits (clamped)
        c = await asyncio.wait_for(r._admit_pull(10_000 * MB), timeout=1)
        assert c <= 50 * MB
        await r._release_pull(c)

    asyncio.run(drive())
    assert acquired == [30 * MB, 30 * MB]
    assert r._pull_inflight_bytes == 0


# ------------------------------------------------------------------ mocks

def test_mock_connection_records_and_scripts():
    conn = MockConnection({"ping": "pong",
                           "echo": lambda p: {"got": p}})

    async def drive():
        assert await conn.call("ping") == "pong"
        assert await conn.call("echo", {"x": 1}) == {"got": {"x": 1}}
        await conn.notify("fire", {"y": 2})

    asyncio.run(drive())
    assert conn.calls_to("ping") == [None]
    assert conn.notifications == [("fire", {"y": 2})]


def test_mock_store_plasma_surface():
    from ray_tpu.exceptions import ObjectStoreFullError
    store = MockStore(capacity=10)
    oid = ObjectID.from_random()
    store.put_bytes(oid, b"12345")
    assert store.contains(oid)
    buf = store.get_buffer(oid)
    assert bytes(buf) == b"12345"
    store.release(oid)
    with pytest.raises(ObjectStoreFullError):
        store.create(ObjectID.from_random(), 6)
    assert store.delete(oid)
    assert not store.contains(oid)


# ------------------------------------------------------------ usage stats

def test_usage_stats_opt_in(tmp_path, monkeypatch):
    from ray_tpu._private import usage
    monkeypatch.delenv("RTPU_USAGE_STATS_ENABLED", raising=False)
    assert usage.write_report(str(tmp_path)) is None  # opt-in: off

    monkeypatch.setenv("RTPU_USAGE_STATS_ENABLED", "1")
    usage.record_library_usage("tune")
    path = usage.write_report(str(tmp_path), {"node_id": "n1"})
    import json
    doc = json.load(open(path))
    assert doc["schema_version"] == 1
    assert "tune" in doc["libraries_used"]
    assert doc["node_id"] == "n1"
    assert doc["python_version"]


# --------------------------------------------------- the per-test limit

def test_a_test_that_waits_for_ever_fails_alone(tmp_path):
    """tests/conftest.py's limit, patched to 1 s in a pytest of its own:
    a body that waits on an event nobody sets fails inside 5 s, the
    failure names the phase and carries every thread's stack, a fixture
    that waits in its teardown fails that teardown, and the tests after
    them in the same file still run."""
    import os
    import re
    import subprocess
    import sys
    conftest = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "conftest.py")
    (tmp_path / "conftest.py").write_text(f"""
import importlib.util
spec = importlib.util.spec_from_file_location("suite_conftest", {conftest!r})
suite = importlib.util.module_from_spec(spec)
spec.loader.exec_module(suite)
suite.TEST_LIMIT_S = 1.0


def pytest_configure(config):
    config.pluginmanager.register(suite, "suite_conftest")
""")
    (tmp_path / "test_waits.py").write_text("""
import threading
import pytest


def test_waits():
    threading.Event().wait()


@pytest.fixture
def never_torn_down():
    yield
    threading.Event().wait()


def test_teardown_waits(never_torn_down):
    pass


def test_next():
    pass
""")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "-v", "--durations=0",
         "test_waits.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "test_waits.py::test_waits FAILED" in out, out
    assert "test_waits.py::test_teardown_waits ERROR" in out, out
    assert "test_waits.py::test_next PASSED" in out, out
    assert "1 failed, 2 passed, 1 error" in out, out
    assert "test_waits: call still running after 1 s" in out, out
    assert "test_teardown_waits: teardown still running after 1 s" in out
    # every thread's stack, the waiting frame of the main thread in it
    assert "Current thread" in out and "in test_waits" in out, out
    # (a phase each: under load the other phases of the same test pass
    # the threshold of --durations too)
    took = {(m[1], m[2]): float(m[0]) for m in re.findall(
        r"([0-9.]+)s (call|teardown) +test_waits.py::(\w+)", out)}
    assert 1.0 <= took["call", "test_waits"] < 5.0, took
    assert 1.0 <= took["teardown", "test_teardown_waits"] < 5.0, took
