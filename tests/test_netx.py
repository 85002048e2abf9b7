"""netx: the cross-node transport plane (tier-1).

Covers the 1.8 acceptance surface (docs/WIRE_PROTOCOL.md §1.8):

* endpoint registry unit behaviour — ``node_ip``/``host_of``/``pick``
  dial-side choice, the ``RTPU_NET_FORCE_TCP`` harness override;
* the ``px_*`` pull protocol against a miniature scripted pump server —
  full-object streaming, crc rejection, and stall-resume from the
  contiguous high-water mark (a dropped chunk is never papered over);
* a simulated two-"host" cluster (distinct ``RTPU_NODE_IP`` per raylet
  + ``RTPU_NET_FORCE_TCP``) where object pulls, direct-lane actor
  calls and compiled-DAG hops all cross the raylet boundary over TCP
  only;
* the ``net.partition`` chaos site — an asymmetric severance drops
  frames BEFORE the wire, so retries fall back and heal with no lost
  or duplicated invocation, and a ``px_chunk`` frame drop at the TCP
  boundary resumes instead of sealing a hole into plasma.
"""

import json
import os
import threading
import time
import zlib

import msgpack
import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import chaos, netx, protocol, rpccore
from ray_tpu._private.cluster_utils import Cluster
from ray_tpu._private.netx import endpoints
from ray_tpu.dag import InputNode


@pytest.fixture(autouse=True)
def _netx_hygiene():
    """Chaos config and cached node identity must not leak between
    tests (both ride env vars that every process spawn inherits)."""
    yield
    os.environ.pop("RTPU_CHAOS", None)
    os.environ.pop("RTPU_CHAOS_LOG", None)
    chaos.clear()
    netx.reset_client_for_tests()
    endpoints._reset_for_tests()


def _require_native():
    if rpccore._lib() is None:
        pytest.skip("native rpc library unavailable on this host")


# ------------------------------------------------------- endpoint registry


def test_host_of_and_endpoint_pick(monkeypatch):
    monkeypatch.delenv("RTPU_NET_FORCE_TCP", raising=False)
    monkeypatch.setenv("RTPU_NODE_IP", "10.0.0.7")
    endpoints._reset_for_tests()
    assert endpoints.node_ip() == "10.0.0.7"
    assert endpoints.host_of("/tmp/w.sock") == ""
    assert endpoints.host_of("unix:/tmp/w.sock") == ""
    assert endpoints.host_of("10.0.0.8:7001") == "10.0.0.8"
    assert endpoints.host_of("tcp:10.0.0.8:7001") == "10.0.0.8"
    # on-box peer (loopback or our own advertised IP): unix wins
    assert endpoints.pick("/tmp/w.sock", "127.0.0.1:7001") == "/tmp/w.sock"
    assert endpoints.pick("/tmp/w.sock", "10.0.0.7:7001") == "/tmp/w.sock"
    # off-box peer: the TCP endpoint
    assert endpoints.pick("/tmp/w.sock", "10.0.0.8:7001") == "10.0.0.8:7001"
    assert endpoints.pick("", "10.0.0.8:7001") == "10.0.0.8:7001"
    # degraded advertisements
    assert endpoints.pick("/tmp/w.sock", "") == "/tmp/w.sock"
    assert endpoints.pick(None, None) == ""
    # harness override: every peer is off-box, the TCP lane is exercised
    monkeypatch.setenv("RTPU_NET_FORCE_TCP", "1")
    assert endpoints.pick("/tmp/w.sock", "127.0.0.1:7001") == \
        "127.0.0.1:7001"


def test_node_ip_is_cached_per_process(monkeypatch):
    monkeypatch.setenv("RTPU_NODE_IP", "10.1.1.1")
    endpoints._reset_for_tests()
    assert endpoints.node_ip() == "10.1.1.1"
    # identity is read ONCE, like the rest of the node's identity
    monkeypatch.setenv("RTPU_NODE_IP", "10.2.2.2")
    assert endpoints.node_ip() == "10.1.1.1"
    endpoints._reset_for_tests()
    assert endpoints.node_ip() == "10.2.2.2"


def test_partition_spec_is_directional_and_heals():
    """The sustained-partition spec shape: fires on EVERY matching hit
    (at=1, every=1, max_fires=0) for one direction of one host pair,
    then ``until_s`` heals it."""
    e = chaos.ChaosEngine(seed=0, schedule=[
        {"site": "net.partition", "op": "partition", "at": 1, "every": 1,
         "max_fires": 0, "method": "a>b", "until_s": 0.3}])
    assert all(e.hit("net.partition", "a>b") for _ in range(5))
    assert e.hit("net.partition", "b>a") is None  # reverse stays up
    assert e.hit("net.partition", "a>c") is None  # other peers stay up
    time.sleep(0.35)
    assert e.hit("net.partition", "a>b") is None  # healed


def test_partitioned_gate(monkeypatch):
    monkeypatch.setenv("RTPU_NODE_IP", "127.0.0.1")
    endpoints._reset_for_tests()
    assert not endpoints.partitioned("127.0.0.2")  # no engine: no faults
    chaos.configure(seed=0, schedule=[
        {"site": "net.partition", "op": "partition", "at": 1, "every": 1,
         "max_fires": 0, "method": "127.0.0.1>127.0.0.2", "until_s": 30.0}])
    assert endpoints.partitioned("127.0.0.2")
    assert not endpoints.partitioned("127.0.0.3")
    assert not endpoints.partitioned("")  # unix peers have no host
    chaos.clear()
    assert not endpoints.partitioned("127.0.0.2")


# ----------------------------------------------------- px_* pull protocol


_CHUNK = 64 * 1024


class _MiniPxServer:
    """A raylet-shaped ``px_*`` peer on a native pump — small enough to
    script transfer faults the real server never emits (mid-stream
    silence, corrupted crc)."""

    def __init__(self, data, chunk=_CHUNK, serve_limits=None,
                 corrupt_crc_at=None):
        self.data = data
        self.chunk = chunk
        self.serve_limits = list(serve_limits or [])  # per-pull chunk cap
        self.corrupt_crc_at = corrupt_crc_at  # (pull_index, chunk_index)
        self.pulls = []
        self.pump = rpccore.Pump()
        port = self.pump.listen_tcp("127.0.0.1", 0)
        self.address = f"127.0.0.1:{port}"
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="mini-px")
        self.thread.start()

    def close(self):
        self.pump.shutdown()
        self.thread.join(timeout=5)
        self.pump.destroy()

    def _reply(self, cid, seq, method, payload):
        self.pump.send(cid, msgpack.packb(
            [protocol.REPLY, seq, method, payload], use_bin_type=True))

    def _run(self):
        while True:
            try:
                evs = self.pump.next_batch(100)
            except Exception:
                return
            if evs is None:
                return
            for cid, kind, body in evs:
                if kind != rpccore.KIND_FRAME:
                    continue
                mtype, seq, method, payload = msgpack.unpackb(
                    body, raw=False)
                if mtype != protocol.REQUEST:
                    continue  # px_ack/ping notifies: the script ignores
                if method == "ping":
                    self._reply(cid, seq, "ping", {})
                elif method == "px_get":
                    self._reply(cid, seq, "px_get",
                                {"found": True, "busy": False,
                                 "total_size": len(self.data)})
                elif method == "px_pull":
                    self._serve_pull(cid, seq, payload)

    def _serve_pull(self, cid, seq, payload):
        idx = len(self.pulls)
        self.pulls.append(dict(payload))
        self._reply(cid, seq, "px_pull",
                    {"found": True, "total_size": len(self.data)})
        off = int(payload["offset"])
        limit = (self.serve_limits[idx]
                 if idx < len(self.serve_limits) else None)
        sent = 0
        while off < len(self.data):
            if limit is not None and sent >= limit:
                return  # the link "goes quiet" mid-stream
            d = self.data[off:off + self.chunk]
            crc = zlib.crc32(d) & 0xFFFFFFFF
            if self.corrupt_crc_at == (idx, sent):
                crc ^= 1
            self.pump.send(cid, msgpack.packb(
                [protocol.NOTIFY, None, "px_chunk",
                 {"stream": payload["stream"], "offset": off, "data": d,
                  "crc": crc, "total_size": len(self.data),
                  "last": off + len(d) >= len(self.data)}],
                use_bin_type=True))
            off += len(d)
            sent += 1


def _pattern_bytes(n):
    return bytes(bytearray((i * 7 + 3) % 256 for i in range(n)))


def test_px_pull_streams_full_object():
    _require_native()
    data = _pattern_bytes(6 * _CHUNK + 13)
    srv = _MiniPxServer(data)
    client = netx.NetxClient()
    try:
        hdr = client.get_header(srv.address, "ab" * 8)
        assert hdr["found"] and hdr["total_size"] == len(data)
        buf = bytearray(len(data))
        n = client.pull_into(srv.address, "ab" * 8, buf, len(data))
        assert n == len(data) and bytes(buf) == data
        assert client.stats["chunks_in"] == 7
        assert client.stats["bytes_in"] == len(data)
        assert len(srv.pulls) == 1 and srv.pulls[0]["offset"] == 0
    finally:
        client.close()
        srv.close()


def test_px_pull_stall_resumes_from_high_water_mark():
    _require_native()
    data = _pattern_bytes(5 * _CHUNK)
    srv = _MiniPxServer(data, serve_limits=[2])  # pull 1 dies at 2 chunks
    client = netx.NetxClient()
    try:
        buf = bytearray(len(data))
        n = client.pull_into(srv.address, "cd" * 8, buf, len(data),
                             stall_timeout=0.6)
        assert n == len(data) and bytes(buf) == data
        # resume re-requested from the high-water mark, never byte 0
        assert [p["offset"] for p in srv.pulls] == [0, 2 * _CHUNK]
    finally:
        client.close()
        srv.close()


def test_px_pull_crc_mismatch_is_a_data_error():
    """crc failures are replica failures, not transport flaps: they
    raise immediately instead of burning resume attempts."""
    _require_native()
    data = _pattern_bytes(4 * _CHUNK)
    srv = _MiniPxServer(data, corrupt_crc_at=(0, 1))
    client = netx.NetxClient()
    try:
        buf = bytearray(len(data))
        with pytest.raises(IOError, match="crc"):
            client.pull_into(srv.address, "ef" * 8, buf, len(data),
                             stall_timeout=0.6)
        assert len(srv.pulls) == 1  # no retry against known-bad data
    finally:
        client.close()
        srv.close()


# ------------------------------------------- simulated two-"host" cluster


@ray_tpu.remote
class _AddK:
    def __init__(self, k):
        self.k = k

    def add(self, x):
        return x + self.k


def _two_host_cluster(monkeypatch):
    """Two raylets on one machine that can only reach each other over
    TCP: each advertises a distinct loopback alias as its node IP and
    ``RTPU_NET_FORCE_TCP`` makes every dial treat the peer as off-box."""
    monkeypatch.setenv("RTPU_NODE_IP", "127.0.0.1")
    monkeypatch.setenv("RTPU_NET_FORCE_TCP", "1")
    endpoints._reset_for_tests()
    netx.reset_client_for_tests()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2,
                                      "resources": {"hosta": 4}})
    cluster.add_node(num_cpus=2, resources={"hostb": 4},
                     env_overrides={"RTPU_NODE_IP": "127.0.0.2",
                                    "RTPU_NET_FORCE_TCP": "1"})
    cluster.connect()
    cluster.wait_for_nodes()
    return cluster


def _pull_six_megabytes_made_on_host_b():
    @ray_tpu.remote(resources={"hostb": 1})
    def make(n):
        return (np.arange(n) % 251).astype(np.uint8)

    n = 6_000_000
    arr = ray_tpu.get(make.remote(n), timeout=120)
    assert arr.shape == (n,)
    assert int(arr[0]) == 0 and int(arr[1_000_000]) == \
        1_000_000 % 251 and int(arr[-1]) == (n - 1) % 251


def test_two_host_cluster_runs_all_lanes_over_tcp(monkeypatch):
    """Object pulls, direct-lane actor calls and compiled-DAG hops all
    cross the raylet boundary with TCP as the only shared transport."""
    _require_native()
    cluster = _two_host_cluster(monkeypatch)
    try:
        hosts = {netx.host_of(n.get("netx_address") or "")
                 for n in ray_tpu.nodes() if n["alive"]}
        assert {"127.0.0.1", "127.0.0.2"} <= hosts

        # bulk object created on "host" B, pulled across the TCP plane
        _pull_six_megabytes_made_on_host_b()

        # direct-lane actor calls ride the netx TCP fast path
        @ray_tpu.remote(resources={"hostb": 1})
        class Counter:
            def __init__(self):
                self.v = 0

            def add(self, k):
                self.v += k
                return self.v

            def where(self):
                import os as _os
                return _os.environ.get("RTPU_NODE_IP", "")

        c = Counter.remote()
        assert ray_tpu.get(c.where.remote(), timeout=60) == "127.0.0.2"
        vals = ray_tpu.get([c.add.remote(1) for _ in range(25)],
                           timeout=90)
        assert vals == list(range(1, 26))
        nx = netx.get_client()
        assert nx is not None and nx.stats["requests"] >= 25

        # compiled-DAG hop: host A stage feeds host B stage over the
        # TCP channel listener
        with InputNode() as inp:
            a = _AddK.options(resources={"hosta": 1}).bind(1)
            b = _AddK.options(resources={"hostb": 1}).bind(10)
            dag = b.add.bind(a.add.bind(inp))
        cdag = dag.compile()
        try:
            assert cdag._compiled and not cdag._fallback_only
            assert [cdag.execute(i) for i in range(5)] == \
                [11 + i for i in range(5)]
        finally:
            cdag.teardown()
    finally:
        cluster.shutdown()


def test_net_partition_heals_with_no_lost_or_duplicated_calls(monkeypatch):
    """Sever the driver→hostB request direction mid-stream of actor
    calls. The partition drops frames BEFORE the wire, so fallback
    retries re-send an invocation that never arrived — each call
    executes exactly once, in order, and the lane heals at
    ``until_s``."""
    _require_native()
    cluster = _two_host_cluster(monkeypatch)
    try:
        @ray_tpu.remote(resources={"hostb": 1}, max_task_retries=-1)
        class Counter:
            def __init__(self):
                self.v = 0

            def add(self, k):
                self.v += k
                return self.v

        c = Counter.remote()
        assert ray_tpu.get(c.add.remote(1), timeout=60) == 1  # lane warm
        chaos.configure(seed=1, schedule=[
            {"site": "net.partition", "op": "partition", "at": 1,
             "every": 1, "max_fires": 0,
             "method": "127.0.0.1>127.0.0.2", "until_s": 1.0}])
        refs = [c.add.remote(1) for _ in range(10)]
        vals = ray_tpu.get(refs, timeout=90)
        assert vals == list(range(2, 12))
        time.sleep(1.1)  # past until_s: the direction is restored
        assert ray_tpu.get(c.add.remote(1), timeout=60) == 12
    finally:
        cluster.shutdown()


def test_px_chunk_drop_at_tcp_boundary_resumes(monkeypatch, tmp_path):
    """A px_chunk frame lost at the TCP boundary (chaos drop in the
    pulling raylet) leaves a gap the later chunks must not paper over:
    the stream stalls at the contiguous high-water mark and the pull
    resumes — the sealed object is bit-exact."""
    _require_native()
    log = tmp_path / "chaos.jsonl"
    monkeypatch.setenv("RTPU_NET_STALL_S", "1.5")
    os.environ["RTPU_CHAOS"] = json.dumps({
        "seed": 3,
        "schedule": [{"site": "protocol.recv", "op": "drop",
                      "method": "px_chunk", "at": 1,
                      "proc": "raylet", "head": True}]})
    os.environ["RTPU_CHAOS_LOG"] = str(log)
    cluster = _two_host_cluster(monkeypatch)
    try:
        @ray_tpu.remote(resources={"hostb": 1})
        def make(n):
            return np.full(n, 7, dtype=np.uint8)

        n = 10 * 1024 * 1024
        arr = ray_tpu.get(make.remote(n), timeout=120)
        assert arr.shape == (n,)
        assert int(arr.min()) == 7 and int(arr.max()) == 7
        # the fault actually fired where intended (the head raylet's
        # netx receive path)
        fired = [e for e in chaos.read_log(str(log))
                 if e.get("method") == "px_chunk"]
        assert fired, "chaos drop on px_chunk never fired"
    finally:
        cluster.shutdown()


# ------------------------------------------------------------ bench smoke


def test_a_call_longer_than_the_idle_limit_leaves_its_connection_usable(
        monkeypatch):
    """An actor call that outlasts ``RTPU_NET_IDLE_S`` and the call made
    right after its answer: the connection carried an answer a moment
    ago and is not idle. (Where the reaper's look fell on the answer's
    arrival the connection was closed as idle, its address went into
    redial backoff, and the next call failed ``ActorUnavailableError ...
    in reconnect backoff``: two Kimi-Linear benchmark runs of PR 36
    ended so after a 60-78 s reference check.)"""
    _require_native()
    monkeypatch.setenv("RTPU_NET_IDLE_S", "1")
    ray_tpu.init(num_cpus=2)
    stop = threading.Event()
    try:
        @ray_tpu.remote
        class Slow:
            def wait(self, seconds):
                time.sleep(seconds)
                return seconds

            def ping(self):
                return "pong"

        a = Slow.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
        client = netx.get_client()
        assert client is not None and client.stats["requests"] >= 1

        def hurry():    # the reaper looks at every wake, not once a second
            while not stop.wait(0.0005):
                client._last_tend = 0.0
        threading.Thread(target=hurry, daemon=True).start()
        for _ in range(3):
            assert ray_tpu.get(a.wait.remote(1.5), timeout=60) == 1.5
            assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
        assert client.stats["reaped"] == 0
    finally:
        stop.set()
        ray_tpu.shutdown()


def test_with_the_plane_off_a_pull_between_hosts_takes_the_chunk_rpcs(
        monkeypatch):
    """``RTPU_NETX=0`` (and a peer the plane cannot dial) leaves an
    object pull on the raylets' asyncio chunk RPCs: the bytes made on
    "host" B arrive whole on "host" A, and no netx client exists."""
    _require_native()
    monkeypatch.setenv("RTPU_NETX", "0")
    cluster = _two_host_cluster(monkeypatch)
    try:
        _pull_six_megabytes_made_on_host_b()
        assert netx.get_client() is None
    finally:
        cluster.shutdown()
