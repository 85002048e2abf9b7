"""Compiled actor DAGs (tier-1): build/compile/execute round-trips,
result equivalence vs dynamic ``.execute()``, plasmax ring-buffer reuse,
version-gated negotiation, and chaos-seeded stage-kill fallback
(docs/COMPILED_DAGS.md; reference strategy: the reference's
python/ray/dag compiled-graph tests)."""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu._private import chaos
from ray_tpu._private import worker as wmod
from ray_tpu.dag import InputNode, MultiOutputNode
from ray_tpu.dag.compiled_dag import CompileError, CompiledDAG


@pytest.fixture(scope="module")
def dag_cluster():
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                       object_store_memory=256 * 1024 * 1024)
    yield ctx
    ray_tpu.shutdown()


@ray_tpu.remote
class AddK:
    def __init__(self, k):
        self.k = k

    def add(self, x):
        return x + self.k

    def boom(self, x):
        raise ValueError(f"boom on {x}")


def _pipeline():
    with InputNode() as inp:
        a, b, c = AddK.bind(1), AddK.bind(10), AddK.bind(100)
        dag = c.add.bind(b.add.bind(a.add.bind(inp)))
    return dag, (a, b, c)


def test_compile_execute_roundtrip_equivalence(dag_cluster):
    dag, _ = _pipeline()
    dynamic = [ray_tpu.get(dag.execute(i), timeout=240) for i in range(3)]
    cdag = dag.compile()
    try:
        assert cdag._compiled and not cdag._fallback_only
        compiled = [cdag.execute(i) for i in range(3)]
        # equivalence: the compiled graph computes exactly what the
        # dynamic path computes on the same graph
        assert compiled == dynamic == [111 + i for i in range(3)]
        # repeated invocations keep working (pre-wired channels reused)
        assert [cdag.execute(i) for i in range(20)] == \
            [111 + i for i in range(20)]
    finally:
        cdag.teardown()


def test_compiled_pipelined_async(dag_cluster):
    dag, _ = _pipeline()
    cdag = dag.compile()
    try:
        assert cdag._compiled
        futs = [cdag.execute_async(i) for i in range(50)]
        assert [f.result(30) for f in futs] == \
            [111 + i for i in range(50)]
    finally:
        cdag.teardown()


def test_app_error_propagates_without_teardown(dag_cluster):
    with InputNode() as inp:
        a, b = AddK.bind(1), AddK.bind(10)
        dag = b.add.bind(a.boom.bind(inp))
    cdag = dag.compile()
    try:
        assert cdag._compiled
        with pytest.raises(ray_tpu.exceptions.RayTpuError,
                           match="boom on 7"):
            cdag.execute(7)
        # an APPLICATION error is a result, not a channel failure: the
        # graph stays compiled and keeps serving
        assert cdag._compiled
        with pytest.raises(ray_tpu.exceptions.RayTpuError):
            cdag.execute(8)
    finally:
        cdag.teardown()


def test_multi_output_node_dynamic_and_compiled(dag_cluster):
    with InputNode() as inp:
        src = AddK.bind(1)
        mid = src.add.bind(inp)
        dag = MultiOutputNode(
            [AddK.bind(10).add.bind(mid), AddK.bind(100).add.bind(mid)])
    refs = dag.execute(5)
    assert isinstance(refs, list) and len(refs) == 2
    assert ray_tpu.get(refs, timeout=240) == [16, 106]
    cdag = dag.compile()
    try:
        assert cdag._compiled
        assert cdag.execute(5) == [16, 106]
        assert cdag.execute(0) == [11, 101]
    finally:
        cdag.teardown()


def test_class_node_caches_actor_across_executions(dag_cluster):
    """Regression (dag/dag_node.py ClassNode): the actor is created ONCE
    per DAG instance — a 3-execute run must not leak 3 actors."""
    from ray_tpu.experimental.state import api as state_api

    @ray_tpu.remote
    class ChurnProbe:
        def ping(self, x):
            return x

    def alive_probes():
        return [a for a in state_api.list_actors()
                if a.get("class_name") == "ChurnProbe"
                and a.get("state") not in ("DEAD",)]

    before = len(alive_probes())
    with InputNode() as inp:
        dag = ChurnProbe.bind().ping.bind(inp)
    for i in range(3):
        assert ray_tpu.get(dag.execute(i), timeout=240) == i
    assert len(alive_probes()) == before + 1


def test_uncompilable_graph_degrades_to_dynamic(dag_cluster):
    @ray_tpu.remote
    def double(x):
        return x * 2

    with InputNode() as inp:
        dag = double.bind(inp)  # function stage: no process to pre-wire
    cdag = dag.compile()
    assert cdag._fallback_only and not cdag._compiled
    assert cdag.execute(21) == 42  # transparently dynamic


def test_ring_buffer_reuse_stays_flat(dag_cluster):
    """Acceptance gate: plasmax segment usage flat across 100 compiled
    triggers carrying >inline payloads (seal/unseal ring cycling — no
    create-per-object)."""
    np = pytest.importorskip("numpy")
    with InputNode() as inp:
        a, b, c = AddK.bind(1.0), AddK.bind(1.0), AddK.bind(1.0)
        dag = c.add.bind(b.add.bind(a.add.bind(inp)))
    cdag = dag.compile()
    try:
        assert cdag._compiled
        arr = np.zeros(32 * 1024, dtype=np.float64)  # 256 KB > inline
        for _ in range(4):  # >= ring depth: lazy slots exist before t0
            cdag.execute(arr)
        w = wmod._global_worker
        s0 = w.plasma.stats()
        for _ in range(100):
            out = cdag.execute(arr)
        s1 = w.plasma.stats()
        assert float(out[0]) == 3.0
        assert s1["used_bytes"] == s0["used_bytes"]
        assert s1["num_created"] == s0["num_created"]
    finally:
        cdag.teardown()


def test_version_gate_refuses_legacy_peer(dag_cluster):
    """1.5 negotiation (the PR-4 pattern): a stage worker that declared
    wire schema 1.4 cannot host compiled channels — _negotiate raises
    and the graph degrades to dynamic instead of failing mid-graph."""
    import asyncio

    from ray_tpu._private import protocol

    class Legacy14Server(protocol.Server):
        async def _handle(self, method, payload, conn):
            if method == "__hello__":
                return {"protocol_version": [1, 4],
                        "schema_hash": "0" * 16}
            raise protocol.RpcError(f"no such method: {method}")

    w = wmod._global_worker
    server = Legacy14Server({})
    path = os.path.join(w.session_dir, "legacy14.sock")
    w.io.run(server.start_unix(path))
    try:
        conn = w.io.run(w._peer(f"unix:{path}"))
        with pytest.raises(CompileError, match="1.4 < 1.5"):
            CompiledDAG._negotiate(w, conn, f"unix:{path}")
        # the negotiated version is remembered on the connection
        assert conn.meta["peer_protocol_version"] == (1, 4)
    finally:
        server.close()

    # same-version peers pass: compiling against the live cluster works
    dag, _ = _pipeline()
    cdag = dag.compile()
    try:
        assert cdag._compiled
    finally:
        cdag.teardown()


def test_compile_failure_degrades_then_recompiles(dag_cluster,
                                                  monkeypatch):
    """A transient compile failure (e.g. channel refused) runs dynamic
    and re-compiles after the backoff — transparently."""
    dag, _ = _pipeline()
    monkeypatch.setattr(
        CompiledDAG, "_open_channels_broken", True, raising=False)
    real = CompiledDAG._compile

    def flaky(self):
        if getattr(CompiledDAG, "_open_channels_broken", False):
            raise CompileError("injected: channel refused")
        return real(self)

    monkeypatch.setattr(CompiledDAG, "_compile", flaky)
    cdag = dag.compile()
    try:
        assert not cdag._compiled and not cdag._fallback_only
        assert cdag.execute(1) == 112  # dynamic fallback
        monkeypatch.setattr(
            CompiledDAG, "_open_channels_broken", False, raising=False)
        time.sleep(CompiledDAG._COMPILE_RETRY_S + 0.1)
        assert cdag.execute(2) == 113
        assert cdag._compiled  # re-compiled past the backoff
    finally:
        cdag.teardown()


def test_dag_bench_smoke(dag_cluster):
    """Fifty round trips through the compiled three-stage pipeline give
    the right answers and average under 50 ms each."""
    dag, _ = _pipeline()
    cdag = dag.compile()
    try:
        assert cdag._compiled
        t0 = time.perf_counter()
        n = 50
        for i in range(n):
            assert cdag.execute(i) == 111 + i
        compiled_s = (time.perf_counter() - t0) / n
        # sanity bound, not the perf gate: compiled round trips must be
        # far under the ~2 ms dynamic hop cost even on a loaded CI box
        assert compiled_s < 0.05
    finally:
        cdag.teardown()


# --------------------------------------------------------- chaos coverage
#
# These manage their OWN cluster (PR-4 machinery: RTPU_CHAOS reaches
# workers via the spawn environment, and the shared cluster's idle
# workers — spawned chaos-free — would be reused for the stage actors).
# They run after every dag_cluster test in this module.


def _chaos_env(cfg, log_path):
    ray_tpu.shutdown()  # the module-shared cluster predates the env
    os.environ["RTPU_CHAOS"] = json.dumps(cfg)
    os.environ["RTPU_CHAOS_LOG"] = str(log_path)
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 object_store_memory=256 * 1024 * 1024)


def _clear_chaos_env():
    ray_tpu.shutdown()
    os.environ.pop("RTPU_CHAOS", None)
    os.environ.pop("RTPU_CHAOS_LOG", None)
    chaos.clear()


def test_chaos_stage_kill_falls_back_exactly_once(tmp_path):
    """Acceptance gate: SIGKILL the middle stage's worker mid-graph (the
    seeded ``dag.stage`` op). The compiled graph degrades to dynamic
    dispatch with no lost or duplicated invocation — the surviving sink
    observes every input exactly once — and the chaos log records the
    replayable fault."""
    log = tmp_path / "dag_chaos.jsonl"
    # stage ids are topo order: 0=a (entry), 1=b (middle), 2=c (sink);
    # kill the worker hosting stage 1 at its 3rd compiled execution
    _chaos_env({"seed": 7, "schedule": [
        {"site": "dag.stage", "op": "kill", "at": 3, "method": "1",
         "proc": "worker"}]}, log)
    try:
        @ray_tpu.remote
        class CountingStage:
            def __init__(self, k):
                self.k = k
                self.seen = {}

            def f(self, x):
                self.seen[x] = self.seen.get(x, 0) + 1
                return x + self.k

            def seen_counts(self):
                return dict(self.seen)

        with InputNode() as inp:
            a, b, c = (CountingStage.bind(1), CountingStage.bind(10),
                       CountingStage.bind(100))
            dag = c.f.bind(b.f.bind(a.f.bind(inp)))
        cdag = dag.compile(execute_timeout_s=15.0)
        try:
            assert cdag._compiled
            out = [cdag.execute(i) for i in range(6)]
            # no lost and no duplicated invocation: every input yields
            # exactly one correct result...
            assert out == [111 + i for i in range(6)]
            # ...and the SINK (downstream of the kill) executed each
            # invocation exactly once — the in-flight one arrived via
            # the dynamic fallback, not twice. (The sink sees each
            # input shifted by the two upstream stages: i + 11.)
            counts = ray_tpu.get(
                c._cached_actor.seen_counts.remote(), timeout=240)
            assert sorted(counts) == [11 + i for i in range(6)]
            assert all(n == 1 for n in counts.values()), counts
        finally:
            cdag.teardown()
        fired = [(r["site"], r["op"], r["n"])
                 for r in chaos.read_log(str(log))]
        assert ("dag.stage", "kill", 3) in fired, fired
    finally:
        _clear_chaos_env()


def test_chaos_channel_reset_recovers(tmp_path):
    """Seeded ``dag.channel`` reset severs a peer channel mid-stream;
    the affected invocation re-runs dynamically and later calls
    re-compile — no lost results."""
    log = tmp_path / "dag_reset.jsonl"
    _chaos_env({"seed": 8, "schedule": [
        {"site": "dag.channel", "op": "reset", "at": 4,
         "method": "dag_exec", "proc": "worker"}]}, log)
    try:
        @ray_tpu.remote
        class Plus:
            def __init__(self, k):
                self.k = k

            def f(self, x):
                return x + self.k

        with InputNode() as inp:
            a, b = Plus.bind(1), Plus.bind(10)
            dag = b.f.bind(a.f.bind(inp))
        cdag = dag.compile(execute_timeout_s=15.0)
        try:
            assert cdag._compiled
            out = [cdag.execute(i) for i in range(8)]
            assert out == [11 + i for i in range(8)]
        finally:
            cdag.teardown()
        assert any(r["op"] == "reset"
                   for r in chaos.read_log(str(log)))
    finally:
        _clear_chaos_env()
