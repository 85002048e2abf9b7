"""Structured events, log browsing, dashboard endpoints, cluster gauges,
and the state engine: task/object listing with cursor pagination +
server-side filters, bounded task-table memory, the task-event pipeline
(PENDING_SCHEDULING → ... → FINISHED/FAILED), timeline flush cursor.

Reference analogues: event framework tests, dashboard modules tests
(`ray list tasks/objects`, `ray list cluster-events`, `ray logs`).
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.experimental.state import api as state


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                       object_store_memory=128 * 1024 * 1024)
    yield ctx
    ray_tpu.shutdown()


def _list_tasks_until(predicate, timeout=20, **kw):
    """Poll list_tasks until ``predicate(result)`` (the pipeline is
    asynchronous: events batch-flush every ~0.5 s)."""
    deadline = time.time() + timeout
    while True:
        tasks = state.list_tasks(**kw)
        if predicate(tasks) or time.time() > deadline:
            return tasks
        time.sleep(0.3)


def test_node_added_event(cluster):
    events = state.list_cluster_events()
    labels = [e.get("label") for e in events]
    assert "NODE_ADDED" in labels
    ev = next(e for e in events if e.get("label") == "NODE_ADDED")
    assert ev["severity"] == "INFO"
    assert ev["source"] == "gcs"
    assert ev["fields"]["resources"].get("CPU") == 4


def test_worker_death_event(cluster):
    import os
    import signal

    @ray_tpu.remote
    def suicide():
        os.kill(os.getpid(), signal.SIGKILL)

    with pytest.raises(Exception):
        ray_tpu.get(suicide.options(max_retries=0).remote(), timeout=60)
    deadline = time.time() + 30
    while time.time() < deadline:
        labels = [e.get("label") for e in state.list_cluster_events()]
        if "WORKER_DIED" in labels:
            break
        time.sleep(0.5)
    assert "WORKER_DIED" in labels
    # severity filter works
    errors = state.list_cluster_events(severity="ERROR")
    assert all(e["severity"] == "ERROR" for e in errors)
    assert any(e["label"] == "WORKER_DIED" for e in errors)


def test_actor_dead_event(cluster):
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    ray_tpu.get(a.ping.remote(), timeout=30)
    ray_tpu.kill(a)
    deadline = time.time() + 30
    found = False
    while time.time() < deadline and not found:
        found = any(e.get("label") == "ACTOR_DEAD"
                    for e in state.list_cluster_events())
        time.sleep(0.5)
    assert found


def test_list_and_get_logs(cluster):
    logs = state.list_logs()
    assert any(name.startswith("gcs") for name in logs)
    assert any("events" in name for name in logs)
    gcs_log = next(n for n in logs if n.startswith("gcs"))
    content = state.get_log(gcs_log)
    assert "GCS listening" in content
    with pytest.raises(ValueError, match="escapes"):
        state.get_log("../../etc/passwd")


def test_dashboard_events_logs_metrics(cluster):
    from ray_tpu.dashboard.dashboard import start_dashboard
    port = start_dashboard(port=18265)

    def get(path):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30).read()

    events = json.loads(get("/api/events"))["events"]
    assert any(e["label"] == "NODE_ADDED" for e in events)
    logs = json.loads(get("/api/logs"))["logs"]
    assert logs
    text = get(f"/api/logs/{logs[0]}").decode()
    assert isinstance(text, str)
    pgs = json.loads(get("/api/placement_groups"))
    assert "placement_groups" in pgs
    metrics = get("/metrics").decode()
    assert "ray_tpu_cluster_nodes_alive 1.0" in metrics
    assert 'ray_tpu_cluster_resource_total{resource="CPU"} 4.0' in metrics


def test_grafana_dashboards_generated(tmp_path):
    """Generated boards are valid Grafana JSON wired to the exported
    metric names (reference: grafana_dashboard_factory.py)."""
    import json
    import re

    from ray_tpu.dashboard.grafana import (generate_dashboards,
                                           write_dashboards)
    boards = generate_dashboards()
    assert {"ray_tpu_core", "ray_tpu_scheduler", "ray_tpu_object_store",
            "ray_tpu_nodes"} <= set(boards)
    metric_re = re.compile(r"ray_tpu_[a-z_]+")
    for doc in boards.values():
        assert doc["panels"], doc["title"]
        for p in doc["panels"]:
            assert p["targets"], p["title"]
            for t in p["targets"]:
                assert metric_re.search(t["expr"]), t["expr"]
        json.dumps(doc)  # serializable
    # every expr references a gauge family the /metrics endpoint exports
    exported_prefixes = (
        "ray_tpu_cluster_", "ray_tpu_node_")
    for doc in boards.values():
        for p in doc["panels"]:
            for t in p["targets"]:
                assert any(pref in t["expr"]
                           for pref in exported_prefixes), t["expr"]
    paths = write_dashboards(str(tmp_path))
    assert len(paths) == 4 and all(
        json.load(open(p)) for p in paths)


def test_dashboard_frontend_page(cluster):
    from ray_tpu.dashboard.dashboard import start_dashboard
    port = start_dashboard(port=18265)  # reuses the module's instance
    html = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/", timeout=30).read().decode()
    assert "<!doctype html>" in html
    assert "/api/cluster_status" in html
    assert "ray_tpu" in html


def test_node_stats_agent(cluster):
    """Per-node agent snapshot (reference: dashboard/agent.py reporter
    + metric_defs.cc native gauges) carries physical + scheduler +
    object-store readings that move under load."""
    @ray_tpu.remote
    def burn(x):
        return bytes(2 * 1024 * 1024)  # forces plasma traffic

    refs = [burn.remote(i) for i in range(20)]
    ray_tpu.get(refs, timeout=240)
    state.node_stats()  # prime the cpu_percent delta sample
    time.sleep(0.5)     # the delta needs ticks between the two reads
    stats = state.node_stats()
    assert len(stats) == 1
    s = stats[0]
    assert s["physical"]["mem_total_bytes"] > 0
    assert s["physical"]["mem_available_bytes"] > 0
    assert "cpu_percent" in s["physical"]
    assert s["physical"]["disk_free_bytes"] > 0
    sched = s["scheduler"]
    assert sched["tasks_dispatched_total"] >= 20
    assert sched["workers_alive"] >= 1
    assert sched["resources_total"]["CPU"] == 4.0
    store = s["object_store"]
    assert store["capacity"] > 0
    assert store["num_created"] >= 20
    for key in ("used_bytes", "spilled_objects", "spill_count_total",
                "restored_bytes_total", "pull_inflight_bytes",
                "pushes_inflight", "pinned_objects"):
        assert key in store, key
    del refs


def test_list_tasks_lifecycle(cluster):
    """Tasks flow through the event pipeline into the GCS table with
    lifecycle state, node/pid attribution, duration, trace ids, and
    error detail for failures."""
    @ray_tpu.remote
    def obs_ok(i):
        return i + 1

    @ray_tpu.remote(max_retries=0)
    def obs_fail():
        raise RuntimeError("observed-boom")

    assert ray_tpu.get([obs_ok.remote(i) for i in range(6)],
                       timeout=60) == list(range(1, 7))
    with pytest.raises(Exception):
        ray_tpu.get(obs_fail.remote(), timeout=60)

    tasks = _list_tasks_until(
        lambda ts: sum(1 for t in ts if t.get("name") == "obs_ok"
                       and t["state"] == "FINISHED") >= 6
        and any(t.get("name") == "obs_fail" and t["state"] == "FAILED"
                for t in ts))
    done = [t for t in tasks if t.get("name") == "obs_ok"
            and t["state"] == "FINISHED"]
    assert len(done) >= 6
    rec = done[0]
    assert rec["node_id"] and rec["worker_pid"] > 0
    assert rec.get("duration_s") is not None
    assert rec.get("trace_ctx", {}).get("trace_id")
    failed = next(t for t in tasks if t.get("name") == "obs_fail")
    assert failed["state"] == "FAILED"
    assert "observed-boom" in (failed.get("error") or "")


def test_list_tasks_retry_attempt_visible(cluster):
    """A retried task's record carries the attempt number and ends
    FINISHED (the retry restarted the lifecycle)."""
    import tempfile
    marker = tempfile.mktemp(prefix="rtpu_obs_retry_")

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def flaky(path):
        import os as _os
        if not _os.path.exists(path):
            open(path, "w").close()
            raise ValueError("first attempt fails")
        return "ok"

    assert ray_tpu.get(flaky.remote(marker), timeout=60) == "ok"
    tasks = _list_tasks_until(
        lambda ts: any(t.get("name") == "flaky"
                       and t["state"] == "FINISHED"
                       and t.get("attempt", 0) >= 1 for t in ts),
        filters={"name": "flaky"})
    rec = next(t for t in tasks if t["state"] == "FINISHED")
    assert rec["attempt"] >= 1


def test_list_tasks_pagination_roundtrip(cluster):
    """Walk >=3 cursor pages; the union equals the full set with no
    duplicates (stable id-sorted cursor)."""
    @ray_tpu.remote
    def page_task(i):
        return i

    ray_tpu.get([page_task.remote(i) for i in range(9)], timeout=60)
    full = _list_tasks_until(
        lambda ts: sum(1 for t in ts
                       if t.get("name") == "page_task") >= 9)
    page_size = max(1, len(full) // 3)
    pages, token = [], None
    while True:
        page = state.list_tasks(page_size=page_size,
                                continuation_token=token)
        assert len(page) <= page_size
        pages.append(page)
        token = page.next_token
        if token is None:
            break
    assert len(pages) >= 3
    ids = [t["task_id"] for p in pages for t in p]
    assert len(ids) == len(set(ids)), "duplicate rows across pages"
    assert set(ids) == {t["task_id"] for t in full}


def test_list_tasks_filter_pushdown(cluster):
    """Filters evaluate server-side: a reply's total is the count of
    the rows that match (its own rows: a second listing may already
    hold a task whose events were flushed in between), and every row
    matches."""
    tasks = state.list_tasks(filters={"state": "FINISHED"})
    assert tasks and all(t["state"] == "FINISHED" for t in tasks)
    assert tasks.total == len(tasks)
    by_name = _list_tasks_until(lambda ts: len(ts) >= 9,
                                filters={"name": "page_task"})
    assert len(by_name) >= 9 and by_name.total == len(by_name)
    assert all(t["name"] == "page_task" for t in by_name)
    none = state.list_tasks(filters={"name": "no-such-task"})
    assert list(none) == [] and none.total == 0


def test_task_table_bounded_memory_unit():
    """The GCS table never exceeds its cap: overflow evicts oldest
    TERMINAL records first and counts every eviction."""
    from ray_tpu._private.gcs import TaskEventTable
    t = TaskEventTable(cap=100)
    for i in range(250):
        t.apply({"task_id": f"t{i:04d}", "state": "PENDING_SCHEDULING",
                 "ts": float(i)})
        t.apply({"task_id": f"t{i:04d}", "state": "FINISHED",
                 "ts": float(i) + 0.5})
    assert len(t.records) == 100
    assert t.dropped == 150
    # the survivors are the NEWEST records (oldest-terminal evicted)
    assert "t0249" in t.records and "t0000" not in t.records
    s = t.summary()
    assert s["dropped"] == 150 and s["cap"] == 100
    assert s["by_state"]["FINISHED"] == 100
    # live (non-terminal) records out-survive older terminal ones
    t2 = TaskEventTable(cap=10)
    t2.apply({"task_id": "live", "state": "RUNNING", "ts": 0.0})
    for i in range(30):
        t2.apply({"task_id": f"d{i:03d}", "state": "FAILED",
                  "ts": float(i)})
    assert "live" in t2.records and len(t2.records) == 10


def test_task_table_cap_exceeded_drop_counter_exposed(cluster):
    """Shrinking the live table cap evicts immediately and the drop
    counter is visible through the listing API and the summary."""
    from ray_tpu._private import worker as wmod
    w = wmod._global_worker
    try:
        r = w.call_sync(w.gcs, "configure_state", {"task_table_max": 5})
        assert r["task_table_max"] == 5
        tasks = state.list_tasks()
        assert len(tasks) <= 5
        assert tasks.dropped > 0
        assert state.summarize_tasks()["dropped"] >= tasks.dropped
        assert state.summarize_cluster()["tasks"]["dropped"] >= \
            tasks.dropped
    finally:
        w.call_sync(w.gcs, "configure_state", {"task_table_max": 32768})


def test_list_objects_plasma_index(cluster):
    """Object listing aggregates per-raylet plasma indexes: a pinned
    primary shows up with its node, owner, and size."""
    import numpy as np
    blob = ray_tpu.put(np.zeros(1024 * 1024, dtype=np.uint8))
    deadline = time.time() + 15
    row = None
    while time.time() < deadline and row is None:
        for o in state.list_objects():
            if o["object_id"] == blob.hex():
                row = o
                break
        time.sleep(0.2)
    assert row is not None, "pinned primary never listed"
    assert row["pinned"] and row["size_bytes"] >= 1024 * 1024
    assert row["locations"] and row.get("owner")
    # filter pushdown on objects too
    mine = state.list_objects(filters={"object_id": blob.hex()})
    assert len(mine) == 1
    del blob


def test_paginated_actor_and_node_listing(cluster):
    """The pagination retrofit covers the pre-existing tables."""
    @ray_tpu.remote
    class PagedActor:
        def ping(self):
            return 1

    actors = [PagedActor.remote() for _ in range(4)]
    ray_tpu.get([a.ping.remote() for a in actors], timeout=60)
    page = state.list_actors(page_size=2)
    assert len(page) == 2 and page.next_token
    rest = state.list_actors(page_size=100,
                             continuation_token=page.next_token)
    ids = [a["actor_id"] for a in page + rest]
    assert len(ids) == len(set(ids)) == len(state.list_actors())
    alive = state.list_actors(filters={"state": "ALIVE"})
    assert all(a["state"] == "ALIVE" for a in alive)
    nodes = state.list_nodes(filters={"alive": True})
    assert len(nodes) == 1
    for a in actors:
        ray_tpu.kill(a)


def test_summarize_cluster_single_rpc(cluster):
    """summarize_cluster is one GCS rpc now: counts + resource totals
    + the task-table summary, no full-table fetches client-side."""
    s = state.summarize_cluster()
    assert s["nodes_alive"] >= 1 and s["nodes_total"] >= 1
    assert "actors_by_state" in s and "jobs_total" in s
    assert s["cluster_resources"].get("CPU") == 4
    t = s["tasks"]
    assert t["total"] >= 1 and "by_state" in t and "dropped" in t


def test_timeline_flush_only_advances_cursor_on_success(cluster):
    """Satellite regression: a failed kv_put must NOT advance
    _last_pushed_total — the events retry on the next flush instead of
    silently vanishing."""
    from ray_tpu._private import worker as wmod
    from ray_tpu.util import timeline
    w = wmod._global_worker
    orig = w.call_sync
    fails = {"n": 0}

    def failing(conn, method, payload, timeout=None):
        if method == "kv_put" and \
                str(payload.get("key", "")).startswith("@timeline/"):
            fails["n"] += 1
            raise RuntimeError("injected kv_put failure")
        return orig(conn, method, payload, timeout=timeout)

    w.call_sync = failing
    try:
        timeline.record("flush-probe", "X", ts=time.time() * 1e6,
                        dur=5.0, pid=os.getpid())
        with timeline._lock:
            cursor_before = timeline._last_pushed_total
            assert timeline._total_recorded > cursor_before
        timeline.flush()
        assert fails["n"] >= 1
        with timeline._lock:
            assert timeline._last_pushed_total == cursor_before, \
                "cursor advanced past a FAILED push"
    finally:
        w.call_sync = orig
    timeline.flush()  # now succeeds and advances
    with timeline._lock:
        assert timeline._last_pushed_total == timeline._total_recorded
    assert any(e.get("name") == "flush-probe"
               for e in timeline.timeline_dump())


def test_metrics_preaggregated_flush(cluster):
    """Satellite: a hot loop recording a Counter folds into the local
    buffer (one batch per flush tick), not one actor call per point —
    and the totals still converge exactly."""
    from ray_tpu.util import metrics
    assert os.environ.get("RTPU_METRICS_SYNC") != "1"
    c = metrics.Counter("preagg_total", tag_keys=("k",))
    for _ in range(5000):
        c.inc(1.0, tags={"k": "hot"})
    with metrics._pending_lock:
        buffered = sum(e["value"] for e in metrics._pending.values()
                       if e["name"] == "preagg_total")
    assert buffered > 0, "hot-loop points must buffer locally"
    h = metrics.Histogram("preagg_lat", boundaries=[0.1, 1.0])
    for v in (0.05, 0.5, 5.0, 0.6):
        h.observe(v)
    deadline = time.time() + 15
    while time.time() < deadline:
        dump = {m["name"]: m for m in metrics.dump_metrics()}
        if dump.get("preagg_total", {}).get("value") == 5000.0 and \
                dump.get("preagg_lat", {}).get("count") == 4:
            break
        time.sleep(0.2)
    assert dump["preagg_total"]["value"] == 5000.0
    assert dump["preagg_lat"]["count"] == 4
    assert dump["preagg_lat"]["buckets"] == [1, 2, 1]


def test_dashboard_state_routes(cluster):
    """/api/tasks (paged + filtered), /api/objects, /api/summary/tasks,
    /api/timeline, /api/serve/metrics, and the task/serve gauges on
    /metrics."""
    from ray_tpu.dashboard.dashboard import start_dashboard
    port = start_dashboard(port=18265)

    # self-sufficient workload (earlier tests shrink/restore the table)
    @ray_tpu.remote
    def dash_task(i):
        return i

    ray_tpu.get([dash_task.remote(i) for i in range(8)], timeout=60)
    _list_tasks_until(
        lambda ts: sum(1 for t in ts if t.get("name") == "dash_task"
                       and t["state"] == "FINISHED") >= 8)

    def get(path):
        return json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30).read())

    doc = get("/api/tasks?limit=3")
    assert len(doc["tasks"]) == 3 and doc["next_token"]
    assert doc["total"] >= 3 and "dropped" in doc
    fin = get("/api/tasks?state=FINISHED&limit=5")
    assert fin["tasks"] and all(t["state"] == "FINISHED"
                                for t in fin["tasks"])
    page2 = get(f"/api/tasks?limit=3&token={doc['next_token']}")
    ids1 = {t["task_id"] for t in doc["tasks"]}
    ids2 = {t["task_id"] for t in page2["tasks"]}
    assert not ids1 & ids2
    assert "objects" in get("/api/objects")
    summ = get("/api/summary/tasks")
    assert summ["summary"] and "by_state" in summ
    tl = get("/api/timeline")["events"]
    assert any(e.get("ph") == "X" for e in tl)
    assert get("/api/serve/metrics") == {"deployments": {}}
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
    assert 'ray_tpu_cluster_tasks{state="FINISHED"}' in text
    assert "ray_tpu_cluster_task_table_dropped" in text
    html = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/", timeout=30).read().decode()
    for marker in ("/api/tasks", "/api/serve/metrics", "Task timeline",
                   "loadTimeline"):
        assert marker in html


def test_dashboard_profile_flamegraph_endpoint(cluster):
    """The timed-sampling flamegraph endpoint (VERDICT: shipped
    untested): folded-stack output in the collapsed format
    flamegraph.pl / speedscope import — 'frame;frame;frame count'."""
    from ray_tpu.dashboard.dashboard import start_dashboard
    port = start_dashboard(port=18265)

    # keep a worker busy so the sampler has a stack to fold
    @ray_tpu.remote
    def spin(sec):
        t0 = time.time()
        n = 0
        while time.time() - t0 < sec:
            n += 1
        return n

    ref = spin.remote(3.0)
    doc = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/api/profile/flamegraph?duration_s=1.0",
        timeout=60).read())
    ray_tpu.get(ref, timeout=60)
    workers = [w for n in doc.get("nodes", [])
               for w in n.get("workers", []) if not w.get("error")]
    assert workers, doc
    profiled = [w for w in workers if w.get("folded")]
    assert profiled, workers
    for w in profiled:
        assert w.get("samples", 0) >= 1
        line = w["folded"].strip().splitlines()[0]
        stack, _, count = line.rpartition(" ")
        assert stack and count.isdigit(), line
    # the spinning worker shows a multi-frame folded stack
    assert any(";" in w["folded"] for w in profiled), profiled

    stacks = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/api/profile/stacks",
        timeout=60).read())
    assert stacks.get("nodes"), stacks


def test_dashboard_gameday_panel_and_slo_gauges(cluster):
    """The game-day surface: /api/gameday serves the last published
    report, /metrics exports the ray_tpu_slo_* gauges from it, and the
    frontend carries the panel."""
    from ray_tpu.dashboard.dashboard import start_dashboard
    from ray_tpu.gameday import store
    port = start_dashboard(port=18265)

    report = {
        "scenario": "unit", "seed": 7, "duration_s": 1.0,
        "phases": {"peak": {"total": 100, "admitted": 99, "shed": 1,
                            "failed": 0, "p50_ms": 4.0, "p99_ms": 20.0,
                            "p999_ms": 35.0, "max_ms": 40.0,
                            "mean_ms": 5.0}},
        "overall": {"total": 100, "admitted": 99, "shed": 1,
                    "failed": 0, "p50_ms": 4.0, "p99_ms": 20.0,
                    "p999_ms": 35.0, "max_ms": 40.0, "mean_ms": 5.0},
        "slo": {"availability_target": 0.999, "availability_burn": 0.0,
                "latency_target_ms": 250.0, "latency_burn": 0.2},
        "reconciliation": {"ok": True, "checks": []},
        "passed": True,
    }
    assert store.publish_report(report)

    doc = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/api/gameday", timeout=30).read())
    assert doc["report"]["scenario"] == "unit"
    assert doc["report"]["overall"]["admitted"] == 99

    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
    assert ('ray_tpu_slo_requests{scenario="unit",phase="peak",'
            'outcome="admitted"} 99.0') in text
    assert ('ray_tpu_slo_latency_p99_seconds{scenario="unit",'
            'phase="peak"} 0.02') in text
    assert ('ray_tpu_slo_error_budget_burn{scenario="unit",'
            'slo="latency"} 0.2') in text
    assert 'ray_tpu_slo_reconcile_ok{scenario="unit"} 1.0' in text
    assert 'ray_tpu_slo_passed{scenario="unit"} 1.0' in text

    html = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/", timeout=30).read().decode()
    for marker in ("Game day", "/api/gameday", "gd-tiles"):
        assert marker in html


_CHAOS_LISTING_SCRIPT = r"""
import json, time
import ray_tpu
from ray_tpu.experimental.state import api as state

ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024 * 1024)

@ray_tpu.remote(max_retries=0)
def victim(i):
    return i

errors = 0
for i in range(4):
    try:
        # SPREAD routes through the raylet dispatch path (the lease
        # fast lane transparently resubmits on worker death, which
        # would mask the failure this test asserts on)
        ray_tpu.get(victim.options(
            scheduling_strategy="SPREAD").remote(i), timeout=120)
    except Exception:
        errors += 1
assert errors >= 1, "chaos kill never surfaced"
deadline = time.time() + 30
failed = []
while time.time() < deadline:
    failed = list(state.list_tasks(filters={"state": "FAILED",
                                            "name": "victim"}))
    # the owner's FAILED event (no node) can be flushed a beat before
    # the raylet's, which names the node: wait for both
    if failed and failed[0].get("node_id"):
        break
    time.sleep(0.5)
assert failed, "FAILED task never listed"
rec = failed[0]
assert "WORKER_DIED" in (rec.get("error") or ""), rec
assert rec.get("node_id"), rec
print("CHAOS_LISTING_OK", json.dumps(rec.get("error")))
ray_tpu.shutdown()
"""


def test_chaos_killed_task_listed_failed_with_error(tmp_path):
    """Chaos-seeded run (worker SIGKILL at its 2nd execution, no
    retries): the killed task appears in list_tasks as FAILED with the
    WORKER_DIED error detail — reported by the raylet, since the dead
    worker can't report itself. Runs in a subprocess so the chaos env
    doesn't leak into the shared cluster."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               RTPU_CHAOS=json.dumps({"seed": 5, "schedule": [
                   {"site": "worker.execute", "op": "kill", "at": 2,
                    "proc": "worker"}]}))
    env.pop("RTPU_ADDRESS", None)
    r = subprocess.run([sys.executable, "-c", _CHAOS_LISTING_SCRIPT],
                       env=env, capture_output=True, text=True,
                       timeout=240)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "CHAOS_LISTING_OK" in r.stdout


def test_node_stats_in_prometheus_and_api(cluster):
    from ray_tpu.dashboard.dashboard import start_dashboard
    port = start_dashboard(port=18265)

    def get(path):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30).read()

    doc = json.loads(get("/api/nodes/stats"))
    assert doc["nodes"] and "scheduler" in doc["nodes"][0]
    metrics = get("/metrics").decode()
    for gauge in (
            "ray_tpu_node_mem_total_bytes",
            "ray_tpu_node_mem_available_bytes",
            "ray_tpu_node_disk_free_bytes",
            "ray_tpu_node_scheduler_tasks_pending",
            "ray_tpu_node_scheduler_tasks_running",
            "ray_tpu_node_scheduler_tasks_dispatched_total",
            "ray_tpu_node_scheduler_tasks_spilled_back_total",
            "ray_tpu_node_scheduler_workers_alive",
            "ray_tpu_node_scheduler_workers_idle",
            "ray_tpu_node_scheduler_actors_alive",
            "ray_tpu_node_resource_available",
            "ray_tpu_node_object_store_used_bytes",
            "ray_tpu_node_object_store_capacity",
            "ray_tpu_node_object_store_num_objects",
            "ray_tpu_node_object_store_num_created",
            "ray_tpu_node_object_store_num_evicted",
            "ray_tpu_node_object_store_spilled_objects",
            "ray_tpu_node_object_store_spill_count_total",
            "ray_tpu_node_object_store_pull_inflight_bytes",
            "ray_tpu_node_tpu_num_chips",
    ):
        assert gauge in metrics, gauge
    assert 'node="' in metrics
