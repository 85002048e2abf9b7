"""L5 ops-layer tests: state API, jobs, dashboard HTTP, autoscaler, CLI.
(reference strategy: dashboard/modules/job tests, autoscaler fake-node
tests — SURVEY.md §4 'fake node provider for autoscaler logic')."""

import json
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu


def test_state_api(ray_start_shared):
    from ray_tpu.experimental.state import (list_actors, list_nodes,
                                            summarize_cluster)

    @ray_tpu.remote
    class Marker:
        def ping(self):
            return "ok"

    m = Marker.options(name="state-marker").remote()
    ray_tpu.get(m.ping.remote(), timeout=240)
    nodes = list_nodes()
    assert len(nodes) >= 1 and nodes[0]["alive"]
    actors = list_actors()
    assert any(a.get("name") == "state-marker" for a in actors)
    s = summarize_cluster()
    assert s["nodes_alive"] >= 1
    assert s["cluster_resources"].get("CPU", 0) > 0


def test_job_submission_in_cluster(ray_start_shared):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint="echo hello-from-job && echo err-line >&2")
    status = client.wait_until_finish(job_id, timeout=60)
    assert status == JobStatus.SUCCEEDED
    logs = client.get_job_logs(job_id)
    assert "hello-from-job" in logs
    assert "err-line" in logs
    jobs = client.list_jobs()
    assert any(j["job_id"] == job_id for j in jobs)


def test_job_failure_status(ray_start_shared):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient
    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint="exit 3")
    assert client.wait_until_finish(job_id, timeout=60) == \
        JobStatus.FAILED
    assert client.get_job_info(job_id)["return_code"] == 3


def test_job_stop(ray_start_shared):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient
    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint="sleep 120")
    time.sleep(0.5)
    client.stop_job(job_id)
    deadline = time.time() + 30
    while time.time() < deadline:
        if client.get_job_status(job_id) == JobStatus.STOPPED:
            break
        time.sleep(0.2)
    assert client.get_job_status(job_id) == JobStatus.STOPPED


def test_dashboard_http(ray_start_shared):
    from ray_tpu.dashboard import start_dashboard
    port = start_dashboard(port=8270)

    def get(path):
        return json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30).read())

    assert urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz",
        timeout=30).read() == b"ok"
    status = get("/api/cluster_status")
    assert status["nodes_alive"] >= 1
    nodes = get("/api/nodes")["nodes"]
    assert len(nodes) >= 1
    # job submit through REST
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/jobs/",
        data=json.dumps({"entrypoint": "echo via-rest"}).encode(),
        headers={"Content-Type": "application/json"})
    job_id = json.loads(
        urllib.request.urlopen(r, timeout=60).read())["job_id"]
    deadline = time.time() + 60
    while time.time() < deadline:
        info = get(f"/api/jobs/{job_id}")
        if info["status"] in ("SUCCEEDED", "FAILED"):
            break
        time.sleep(0.3)
    assert info["status"] == "SUCCEEDED"
    assert "via-rest" in get(f"/api/jobs/{job_id}/logs")["logs"]
    # prometheus endpoint responds
    urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                           timeout=30).read()


def test_timeline_records_tasks(ray_start_shared):
    from ray_tpu.util.timeline import timeline_dump

    @ray_tpu.remote
    def traced():
        return 1

    ray_tpu.get([traced.remote() for _ in range(3)], timeout=240)
    time.sleep(3.0)  # wait for the workers' background flushers
    events = timeline_dump()
    task_events = [e for e in events
                   if e.get("cat") == "task" and "traced" in
                   str(e.get("name"))]
    assert len(task_events) >= 1


def test_cli_help_and_status():
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "--help"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0
    assert "job" in out.stdout and "start" in out.stdout
