"""Shutdown hygiene of the RPC plane (reference: the reference's
core_worker/raylet destructors join their io_service threads —
src/ray/common/asio/ — so no pending handler outlives its loop).

These are the regression tests for the round-4 verdict item "every
long-lived process sprays 'Task was destroyed but it is pending!' on
shutdown": Connection.close() must cancel its read loop, aclose() must
wait for the unwind, EventLoopThread.stop() must drain every pending
task before closing the loop, and single-flight dialing must never
leak a raced Connection.
"""

import asyncio
import gc

import pytest

from ray_tpu._private import protocol


async def _echo_handler(method, payload, conn):
    return payload


@pytest.fixture
def io():
    t = protocol.EventLoopThread(name="test-io")
    yield t
    t.stop()


def test_connection_close_cancels_read_loop(io):
    async def scenario():
        server = protocol.Server({"echo": lambda p, c: _echo_handler(
            "echo", p, c)})
        port = await server.start_tcp("127.0.0.1", 0)
        conn = await protocol.connect(f"127.0.0.1:{port}")
        assert await conn.call("echo", {"x": 1}) == {"x": 1}
        task = conn._task
        assert not task.done()
        await conn.aclose()
        assert task.done()
        server.close()
        return True

    assert io.run(scenario())


def test_event_loop_thread_stop_drains_pending_tasks():
    t = protocol.EventLoopThread(name="drain-io")

    async def hang_forever():
        await asyncio.Event().wait()

    futs = [t.run_async(hang_forever()) for _ in range(5)]
    t.stop()
    assert t.loop.is_closed()
    for f in futs:
        assert f.done()  # cancelled by the drain, not abandoned
    # a second stop is a no-op, not a drain scheduled onto a dead loop
    t.stop()
    gc.collect()  # would emit "Task was destroyed" if the drain missed any


def test_single_flight_connect_dedups_racing_dials(io):
    async def scenario():
        server = protocol.Server({"echo": lambda p, c: _echo_handler(
            "echo", p, c)})
        port = await server.start_tcp("127.0.0.1", 0)
        cache, pending, dials = {}, {}, []

        async def dial(addr):
            dials.append(addr)
            await asyncio.sleep(0.01)  # hold the dial open so callers pile up
            return await protocol.connect(addr)

        conns = await asyncio.gather(*[
            protocol.single_flight_connect(
                cache, pending, f"127.0.0.1:{port}", dial)
            for _ in range(20)])
        assert len(dials) == 1  # one leader, 19 waiters
        assert all(c is conns[0] for c in conns)
        assert not pending
        await conns[0].aclose()
        server.close()
        return True

    assert io.run(scenario())


def test_single_flight_failed_leader_lets_waiter_retry(io):
    async def scenario():
        cache, pending = {}, {}
        attempts = []

        async def dial(addr):
            attempts.append(addr)
            if len(attempts) == 1:
                await asyncio.sleep(0.01)
                raise ConnectionError("first dial refused")
            server = protocol.Server({})
            port = await server.start_tcp("127.0.0.1", 0)
            return await protocol.connect(f"127.0.0.1:{port}")

        results = await asyncio.gather(*[
            protocol.single_flight_connect(cache, pending, "fake:1", dial)
            for _ in range(4)], return_exceptions=True)
        # the leader saw its own ConnectionError; a waiter retried as
        # leader and the rest shared its successful dial
        errs = [r for r in results if isinstance(r, Exception)]
        conns = [r for r in results if isinstance(r, protocol.Connection)]
        assert len(errs) == 1 and isinstance(errs[0], ConnectionError)
        assert len(conns) == 3 and all(c is conns[0] for c in conns)
        assert len(attempts) == 2
        await conns[0].aclose()
        return True

    assert io.run(scenario())


def test_single_flight_waiter_cancellation_propagates(io):
    async def scenario():
        cache, pending = {}, {}
        started = asyncio.Event()

        async def dial(addr):
            started.set()
            await asyncio.sleep(5)
            raise AssertionError("dial should have been abandoned")

        leader = asyncio.ensure_future(
            protocol.single_flight_connect(cache, pending, "fake:2", dial))
        await started.wait()
        waiter = asyncio.ensure_future(
            protocol.single_flight_connect(cache, pending, "fake:2", dial))
        await asyncio.sleep(0.01)
        waiter.cancel()
        with pytest.raises(asyncio.CancelledError):
            await waiter
        leader.cancel()
        with pytest.raises(asyncio.CancelledError):
            await leader
        assert not pending  # leader unwound its single-flight slot
        return True

    assert io.run(scenario())


def test_stop_after_loop_thread_exit_is_clean():
    """stop() on an EventLoopThread whose loop thread already exited
    must not schedule the drain onto the dead loop — the coroutine
    would never be awaited (flagged at GC) and the loop never closed."""
    import warnings

    t = protocol.EventLoopThread(name="dead-io")
    # simulate a crashed/early-exited loop thread
    t.loop.call_soon_threadsafe(t.loop.stop)
    t._thread.join(timeout=5)
    assert not t._thread.is_alive()
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # "never awaited"
        t.stop()
        gc.collect()
    assert t.loop.is_closed()
    t.stop()  # second call stays a no-op


def test_hello_records_peer_version(io):
    """__hello__ stores what the peer negotiated in conn.meta so
    handlers can gate minor-version features on it."""
    from ray_tpu._private import schema

    async def scenario():
        server = protocol.Server({})
        port = await server.start_tcp("127.0.0.1", 0)
        conn = await protocol.connect(f"127.0.0.1:{port}")
        reply = await conn.call("__hello__", schema.hello_payload())
        assert reply["protocol_version"] == list(schema.PROTOCOL_VERSION)
        sconn = next(iter(server.connections))
        assert sconn.meta["peer_protocol_version"] == \
            schema.PROTOCOL_VERSION
        await conn.aclose()
        server.close()
        return True

    assert io.run(scenario())


def test_dispatch_status_batch_gated_on_peer_minor(io):
    """A peer that never negotiated >=1.1 gets per-task
    task_dispatch_status notifies; a 1.1+ peer gets the coalesced
    batch."""
    import types

    from ray_tpu._private.raylet import Raylet

    async def scenario():
        sent = []

        class FakeConn:
            def __init__(self, meta):
                self.meta = meta

            async def notify(self, method, payload):
                sent.append((self.meta.get("tag"), method, payload))

        legacy = FakeConn({"tag": "legacy"})  # no hello ever
        old = FakeConn({"tag": "old",
                        "peer_protocol_version": (1, 0)})
        modern = FakeConn({"tag": "modern",
                           "peer_protocol_version": (1, 1)})
        fake = types.SimpleNamespace(
            _dispatch_status_flush_scheduled=True,
            _dispatch_status_buf={
                1: (legacy, [{"task_id": "a"}, {"task_id": "b"}]),
                2: (old, [{"task_id": "c"}]),
                3: (modern, [{"task_id": "d"}, {"task_id": "e"}]),
            })
        Raylet._flush_dispatch_statuses(fake)
        await asyncio.sleep(0.05)
        by_tag = {}
        for tag, method, payload in sent:
            by_tag.setdefault(tag, []).append((method, payload))
        assert by_tag["legacy"] == [
            ("task_dispatch_status", {"task_id": "a"}),
            ("task_dispatch_status", {"task_id": "b"})]
        assert by_tag["old"] == [
            ("task_dispatch_status", {"task_id": "c"})]
        assert by_tag["modern"] == [
            ("task_dispatch_status_batch",
             {"statuses": [{"task_id": "d"}, {"task_id": "e"}]})]
        return True

    assert io.run(scenario())


def test_calls_queued_on_a_dead_peer_share_one_reconnect_deadline(io):
    """Five calls to a peer that is gone all come back with the error
    inside one `reconnect_timeout_s` (plus a dial each), not one after
    the other with a timeout of its own each: a driver whose GCS had
    died sat 300 s in `serve.shutdown()` behind its own background
    calls."""
    import socket
    import time
    with socket.socket() as s:      # a port nobody listens on
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    rc = protocol.ReconnectingConnection(f"127.0.0.1:{port}",
                                         reconnect_timeout_s=1.0)

    async def scenario():
        return await asyncio.gather(
            *[rc.call("kv_get", {"key": "k"}) for _ in range(5)],
            return_exceptions=True)

    t0 = time.monotonic()
    results = io.run(scenario(), timeout=30)
    took = time.monotonic() - t0
    assert all(isinstance(r, ConnectionError) for r in results), results
    assert took < 3.0, took         # five timeouts in a row would be 5 s


def test_disconnect_closes_its_server_on_the_io_loop():
    """asyncio's Server belongs to its loop: closed from the caller's
    thread it raced the loop's own detach of a closing connection
    (TypeError in Server._wakeup in one whole run of seven), and the
    disconnect then never stopped its io loop."""
    import threading

    import ray_tpu
    from ray_tpu._private import worker as worker_mod
    ray_tpu.init(num_cpus=1, object_store_memory=64 * 1024 * 1024)
    closed_on = []
    try:
        w = worker_mod.global_worker()
        server, io_thread = w._server, w.io._thread
        real_close = server.close

        def close():
            closed_on.append(threading.current_thread())
            real_close()
        server.close = close
    finally:
        ray_tpu.shutdown()
    assert closed_on == [io_thread]
