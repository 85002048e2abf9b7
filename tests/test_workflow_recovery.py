"""Workflow crash recovery, management actor, events, cancel.

Reference strategy: workflow/tests/test_recovery.py (kill the driver
mid-step, resume, assert exactly-once step effects) +
test_events.py + workflow_access tests.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu


@pytest.fixture
def wf_storage(tmp_path, monkeypatch):
    root = str(tmp_path / "wf")
    monkeypatch.setenv("RTPU_WORKFLOW_STORAGE", root)
    from ray_tpu import workflow
    workflow.set_storage(root)
    yield root


def test_kill9_mid_step_resume_exactly_once(wf_storage, tmp_path):
    """Kill -9 the driver while step2 executes; resume in a NEW process
    context and prove step1 did NOT re-run (exactly-once per committed
    step) while the workflow still completes correctly."""
    effects = str(tmp_path / "effects")
    os.makedirs(effects)
    script = f"""
import os, sys, time
sys.path.insert(0, {os.getcwd()!r})
import ray_tpu
from ray_tpu import workflow
workflow.set_storage({wf_storage!r})
ctx = ray_tpu.init(num_cpus=2, object_store_memory=128*1024*1024)
print("SESSION_DIR=" + ctx["session_dir"], flush=True)

@ray_tpu.remote
def step1(x):
    with open(os.path.join({effects!r}, "step1"), "a") as f:
        f.write("ran\\n")
    return x + 1

@ray_tpu.remote
def step2(x):
    eff = os.path.join({effects!r}, "step2")
    first = not os.path.exists(eff)
    with open(eff, "a") as f:
        f.write("started\\n")
    print("STEP2_STARTED", flush=True)
    if first:
        time.sleep(60)  # killed here; the resumed attempt skips the nap
    return x * 10

@ray_tpu.remote
def step3(x):
    return x + 5

dag = step3.bind(step2.bind(step1.bind(1)))
workflow.run(dag, workflow_id="chaos")
"""
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    deadline = time.time() + 120
    started = False
    session_dir = None
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("SESSION_DIR="):
            session_dir = line.strip().split("=", 1)[1]
        if "STEP2_STARTED" in line:
            started = True
            break
        if proc.poll() is not None:
            break
    assert started, "driver never reached step2"
    time.sleep(0.5)  # let step1's checkpoint land
    os.killpg(proc.pid, signal.SIGKILL)
    proc.wait(timeout=10)
    # reap the dead driver's cluster, and no other: its GCS, raylet and
    # workers are the processes that carry its session directory. Under
    # xdist the clusters of the other test workers run beside it, and a
    # kill by command line (`ray_tpu._private`) takes those down too:
    # their tests then fail with "raylet connection lost" or "cannot
    # reach <gcs>", in whichever files happen to run at that moment.
    assert session_dir, "driver never printed its session directory"
    mark = f"RTPU_SESSION_DIR={session_dir}".encode()
    for p in os.listdir("/proc"):
        if not p.isdigit():
            continue
        try:
            with open(f"/proc/{p}/environ", "rb") as f:
                env = f.read().split(b"\0")
        except OSError:
            continue
        if mark in env:
            os.kill(int(p), signal.SIGKILL)
    time.sleep(1)

    with open(os.path.join(effects, "step1")) as f:
        assert f.read() == "ran\n"  # committed exactly once pre-crash

    from ray_tpu import workflow
    assert workflow.get_status("chaos") == "RUNNING"  # crashed mid-run

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    try:
        # make the resumed step2 fast: monkey-see, the DAG was persisted
        # with the sleeping body — instead resume must SKIP step1 (its
        # checkpoint exists) and re-run step2/step3. Patch time.sleep in
        # the resumed workers via the persisted body's 60s? No: resume
        # re-executes step2's real body; cap the wait by asserting the
        # step1 effect count instead of waiting for completion is not
        # enough — so run resume in a thread with a generous timeout.
        import threading
        result = {}

        def _resume():
            result["value"] = workflow.resume("chaos")

        t = threading.Thread(target=_resume, daemon=True)
        t.start()
        t.join(timeout=120)
        assert "value" in result, "resume did not complete"
        assert result["value"] == (1 + 1) * 10 + 5
        # step1 never re-ran (exactly-once); step2 ran at-least-once
        with open(os.path.join(effects, "step1")) as f:
            assert f.read() == "ran\n"
        with open(os.path.join(effects, "step2")) as f:
            starts = f.read().count("started")
        assert starts >= 2  # pre-crash attempt + resumed attempt
        assert workflow.get_status("chaos") == "SUCCESSFUL"
    finally:
        ray_tpu.shutdown()


@pytest.fixture
def wf_cluster(wf_storage):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_management_actor_submit_status_list(wf_cluster):
    from ray_tpu import workflow
    from ray_tpu.workflow.workflow_access import get_management_actor
    import cloudpickle

    @ray_tpu.remote
    def add(a, b):
        return a + b

    actor = get_management_actor()
    assert ray_tpu.get(actor.ping.remote(), timeout=240) == "ok"
    blob = cloudpickle.dumps((add.bind(2, 3), None))
    wid = ray_tpu.get(actor.submit.remote(blob, "mgmt-wf"), timeout=240)
    assert wid == "mgmt-wf"
    deadline = time.time() + 60

    def status():
        return ray_tpu.get(actor.get_status.remote("mgmt-wf"), timeout=240)
    while time.time() < deadline and status() != "SUCCESSFUL":
        time.sleep(0.2)
    assert status() == "SUCCESSFUL"
    assert workflow.get_output("mgmt-wf") == 5
    rows = ray_tpu.get(actor.list_all.remote("SUCCESSFUL"), timeout=240)
    assert any(r["workflow_id"] == "mgmt-wf" for r in rows)


def test_resume_all_skips_live_and_revives_crashed(wf_cluster):
    from ray_tpu import workflow
    from ray_tpu.workflow.storage import WorkflowStorage
    import cloudpickle

    @ray_tpu.remote
    def add_one(x):
        return x + 1

    blob = cloudpickle.dumps((add_one.bind(41), None))
    # "crashed": RUNNING status, stale (absent) claim
    crashed = WorkflowStorage("crashed-wf")
    crashed.save_status("RUNNING")
    crashed.save_dag(blob)
    # "live": RUNNING status with a fresh claim
    live = WorkflowStorage("live-wf")
    live.save_status("RUNNING")
    live.save_dag(blob)
    live.touch_claim()

    resumed = workflow.resume_all()
    assert "crashed-wf" in resumed
    assert "live-wf" not in resumed
    deadline = time.time() + 60
    while time.time() < deadline and \
            workflow.get_status("crashed-wf") != "SUCCESSFUL":
        time.sleep(0.2)
    assert workflow.get_output("crashed-wf") == 42


def test_resume_refuses_cancelled_workflow(wf_cluster):
    from ray_tpu import workflow

    @ray_tpu.remote
    def f(x):
        return x

    workflow.run(f.bind(0), workflow_id="torefuse")
    # force a cancelled, incomplete workflow state
    from ray_tpu.workflow.storage import WorkflowStorage
    st = WorkflowStorage("canc-wf")
    st.save_status("CANCELED")
    st.save_dag(b"irrelevant")
    with pytest.raises(workflow.WorkflowCancelledError):
        workflow.resume("canc-wf")


def test_cancel_stops_between_steps(wf_cluster, tmp_path):
    from ray_tpu import workflow
    marker = str(tmp_path / "s2ran")

    @ray_tpu.remote
    def slow_step(x):
        time.sleep(3)
        return x

    @ray_tpu.remote
    def never_step(x, m):
        open(m, "w").write("ran")
        return x

    dag = never_step.bind(slow_step.bind(1), marker)
    ref = workflow.run_async(dag, workflow_id="cancel-wf")
    # (the workflow's task has to reach a worker and say RUNNING before
    # there is anything to cancel: beside five other pytest workers that
    # can take longer than a fixed nap)
    deadline = time.time() + 60
    while workflow.get_status("cancel-wf") != "RUNNING" \
            and time.time() < deadline:
        time.sleep(0.05)
    assert workflow.cancel("cancel-wf")
    with pytest.raises(Exception):
        ray_tpu.get(ref, timeout=60)
    assert workflow.get_status("cancel-wf") == "CANCELED"
    assert not os.path.exists(marker)  # the next step never launched
    assert "cancel-wf" in [r["workflow_id"]
                           for r in workflow.list_all("CANCELED")]


def test_event_listener_checkpointed(wf_cluster, tmp_path):
    from ray_tpu import workflow

    @ray_tpu.remote
    def after_event(ts):
        return ("fired", ts)

    fire_at = time.time() + 1.0
    dag = after_event.bind(
        workflow.wait_for_event(workflow.TimerListener, fire_at))
    out = workflow.run(dag, workflow_id="event-wf")
    assert out[0] == "fired" and abs(out[1] - fire_at) < 1e-6
    # resume does not wait again: the event payload was checkpointed
    t0 = time.time()
    assert workflow.resume("event-wf") == out
    assert time.time() - t0 < 1.0
