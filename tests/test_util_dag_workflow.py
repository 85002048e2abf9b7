"""util / dag / workflow tests (reference strategy: ray/tests/test_actor_pool,
test_queue, dag tests, workflow/tests)."""

import os
import tempfile
import time

import pytest

import ray_tpu


def test_actor_pool_map(ray_start_shared):
    @ray_tpu.remote
    class Worker:
        def double(self, x):
            return x * 2

    from ray_tpu.util import ActorPool
    pool = ActorPool([Worker.remote() for _ in range(2)])
    out = list(pool.map(lambda a, v: a.double.remote(v), range(6)))
    assert out == [0, 2, 4, 6, 8, 10]
    out2 = sorted(pool.map_unordered(
        lambda a, v: a.double.remote(v), range(4)))
    assert out2 == [0, 2, 4, 6]


def test_queue_basic(ray_start_shared):
    from ray_tpu.util import Empty, Queue
    q = Queue(maxsize=4)
    q.put(1)
    q.put("two")
    assert q.qsize() == 2
    assert q.get() == 1
    assert q.get() == "two"
    with pytest.raises(Empty):
        q.get_nowait()


def test_queue_across_tasks(ray_start_shared):
    from ray_tpu.util import Queue
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return "done"

    ray_tpu.get(producer.remote(q, 3), timeout=240)
    assert [q.get(timeout=10) for _ in range(3)] == [0, 1, 2]


def test_multiprocessing_pool(ray_start_shared):
    from ray_tpu.util.multiprocessing import Pool
    with Pool(processes=2) as p:
        assert p.map(_sq, range(8)) == [x * x for x in range(8)]
        r = p.apply_async(_sq, (9,))
        assert r.get(timeout=30) == 81
        assert sorted(p.imap_unordered(_sq, [1, 2, 3])) == [1, 4, 9]


def _sq(x):
    return x * x


def test_metrics_roundtrip(ray_start_shared):
    from ray_tpu.util import metrics
    c = metrics.Counter("test_requests", description="reqs",
                        tag_keys=("route",))
    c.inc(1.0, tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    g = metrics.Gauge("test_temp")
    g.set(42.0)
    h = metrics.Histogram("test_lat", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    deadline = time.time() + 15
    while time.time() < deadline:
        dump = {(m["name"], tuple(sorted(m["tags"].items()))): m
                for m in metrics.dump_metrics()}
        if (dump.get(("test_requests", (("route", "/a"),)), {})
                .get("value") == 3.0
                and ("test_lat", ()) in dump
                and dump[("test_lat", ())]["count"] == 3):
            break
        time.sleep(0.1)
    assert dump[("test_requests", (("route", "/a"),))]["value"] == 3.0
    assert dump[("test_temp", ())]["value"] == 42.0
    assert dump[("test_lat", ())]["count"] == 3
    text = metrics.prometheus_text()
    assert "test_requests" in text and "test_lat_bucket" in text


def test_dag_function_nodes(ray_start_shared):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    @ray_tpu.remote
    def mul(a, b):
        return a * b

    from ray_tpu.dag import InputNode
    with InputNode() as inp:
        dag = mul.bind(add.bind(inp, 10), 2)
    assert ray_tpu.get(dag.execute(5), timeout=240) == 30
    assert ray_tpu.get(dag.execute(0), timeout=240) == 20


def test_dag_shared_subgraph_runs_once(ray_start_shared):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    @ray_tpu.remote
    def pair(a, b):
        return (a, b)

    c = Counter.remote()

    @ray_tpu.remote
    def bump_via(c):
        return ray_tpu.get(c.bump.remote(), timeout=240)

    shared = bump_via.bind(c)
    dag = pair.bind(shared, shared)
    a, b = ray_tpu.get(dag.execute(), timeout=240)
    # the shared node must execute once, both consumers see one value
    assert a == b == 1


def test_dag_actor_nodes(ray_start_shared):
    @ray_tpu.remote
    class Acc:
        def __init__(self, start):
            self.v = start

        def add(self, x):
            self.v += x
            return self.v

    from ray_tpu.dag import InputNode
    with InputNode() as inp:
        node = Acc.bind(100)
        dag = node.add.bind(inp)
    assert ray_tpu.get(dag.execute(5), timeout=240) == 105


def test_workflow_run_and_resume(ray_start_shared, tmp_path):
    from ray_tpu import workflow
    workflow.set_storage(str(tmp_path))
    calls_file = tmp_path / "calls.txt"

    @ray_tpu.remote
    def record(x):
        with open(calls_file, "a") as f:
            f.write(f"{x}\n")
        return x * 2

    @ray_tpu.remote
    def combine(a, b):
        return a + b

    dag = combine.bind(record.bind(1), record.bind(2))
    out = workflow.run(dag, workflow_id="wf1")
    assert out == 6
    assert workflow.get_status("wf1") == "SUCCESSFUL"
    assert workflow.get_output("wf1") == 6
    n_calls = len(calls_file.read_text().splitlines())
    assert n_calls == 2
    # resume: all steps checkpointed → no re-execution
    assert workflow.resume("wf1") == 6
    assert len(calls_file.read_text().splitlines()) == n_calls


def test_workflow_failure_then_resume(ray_start_shared, tmp_path):
    from ray_tpu import workflow
    workflow.set_storage(str(tmp_path))
    flag = tmp_path / "fail.flag"
    flag.write_text("1")
    side = tmp_path / "side.txt"

    @ray_tpu.remote
    def step_a():
        with open(side, "a") as f:
            f.write("a\n")
        return 10

    @ray_tpu.remote
    def step_b(a, flag_path):
        if os.path.exists(flag_path):
            raise RuntimeError("injected failure")
        return a + 1

    dag = step_b.bind(step_a.bind(), str(flag))
    with pytest.raises(Exception):
        workflow.run(dag, workflow_id="wf2")
    assert workflow.get_status("wf2") == "FAILED"
    # step_a's checkpoint must survive the failure
    flag.unlink()
    out = workflow.resume("wf2")
    assert out == 11
    # step_a ran exactly once across both attempts
    assert side.read_text().splitlines() == ["a"]


def test_workflow_kwarg_steps_get_distinct_ids(ray_start_shared,
                                               tmp_path):
    from ray_tpu import workflow
    workflow.set_storage(str(tmp_path))

    @ray_tpu.remote
    def tag(x, mode="a"):
        return f"{x}-{mode}"

    @ray_tpu.remote
    def join(a, b):
        return (a, b)

    dag = join.bind(tag.bind(1, mode="a"), tag.bind(1, mode="b"))
    out = workflow.run(dag, workflow_id="wf-kw")
    # steps differing only in kwargs must NOT share a checkpoint
    assert out == ("1-a", "1-b")


def test_queue_no_thread_starvation(ray_start_shared):
    """Many blocked getters must not deadlock the queue actor
    (blocking is client-side polling, server calls are short)."""
    import threading
    from ray_tpu.util import Queue
    q = Queue()
    results = []

    def consumer():
        # generous timeout: 10 pollers share one client connection, and
        # under full-suite load a poll round-trip can take seconds
        results.append(q.get(timeout=120))

    threads = [threading.Thread(target=consumer) for _ in range(10)]
    for t in threads:
        t.start()
    time.sleep(0.5)
    for i in range(10):
        q.put(i)
    for t in threads:
        t.join(timeout=150)
    assert not any(t.is_alive() for t in threads), "getters starved"
    assert sorted(results) == list(range(10))


def test_workflow_run_async(ray_start_shared, tmp_path):
    from ray_tpu import workflow
    workflow.set_storage(str(tmp_path))

    @ray_tpu.remote
    def fast(x):
        return x + 1

    ref = workflow.run_async(fast.bind(1), workflow_id="wf3")
    assert ray_tpu.get(ref, timeout=60) == 2
