"""Serve control-plane HA tests (reference strategy:
serve/tests/test_controller_recovery.py + test_deploy_* rollout suites).

The scenarios mirror docs/SERVE_HA.md's failure matrix: a controller
killed mid-load (journal recovery + replica re-adoption, traffic from
cached route tables), health-gated start-before-stop rolling updates
with zero failed requests, graceful drain on downscale/delete, and the
chaos-seeded kills (`serve.controller.tick` / `serve.replica.request`).
"""

import json
import os
import pickle
import signal
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import chaos


@pytest.fixture(autouse=True)
def _chaos_env_hygiene():
    yield
    os.environ.pop("RTPU_CHAOS", None)
    os.environ.pop("RTPU_CHAOS_LOG", None)
    chaos.clear()


@pytest.fixture(scope="module")
def ha_cluster():
    ctx = ray_tpu.init(num_cpus=8, ignore_reinit_error=True,
                       object_store_memory=128 * 1024 * 1024)
    yield ctx
    serve.shutdown()
    ray_tpu.shutdown()


def _controller_info(timeout=60.0):
    """get_controller_info from whatever controller incarnation is
    live, retrying across a restart window."""
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            return ctrl, ray_tpu.get(
                ctrl.get_controller_info.remote(), timeout=5.0)
        except Exception as e:
            last = e
            time.sleep(0.5)
    raise AssertionError(f"controller unreachable: {last}")


def _wait_status(name, pred, timeout=45.0):
    deadline = time.time() + timeout
    st = {}
    while time.time() < deadline:
        st = serve.status()
        if pred(st.get(name, {})):
            return st[name]
        time.sleep(0.3)
    raise AssertionError(f"status never converged for {name}: {st}")


# --------------------------------------------- controller restart + HA


def test_controller_restart_recovers_and_readopts(ha_cluster):
    """SIGKILL the controller mid-load: traffic keeps flowing from the
    cached route table, the restarted controller rebuilds state from
    the GCS journal and re-adopts the SAME replica actors (no replica
    restarts), statuses converge HEALTHY, and a handle pickled before
    the crash still routes after it."""

    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, x):
            return x + 1

    h = serve.run(Echo.bind(), http_port=None)
    assert ray_tpu.get(h.remote(1), timeout=30.0) == 2
    pickled_handle = pickle.dumps(h)

    ctrl, info = _controller_info()
    _, table0 = ray_tpu.get(ctrl.get_route_table.remote(), timeout=10.0)
    ids_before = sorted(table0["Echo"]["replicas"])
    assert len(ids_before) == 2

    os.kill(info["pid"], signal.SIGKILL)

    # data plane keeps serving through the outage (handle path never
    # touches the controller once the route table is cached)
    for i in range(15):
        assert ray_tpu.get(h.remote(i), timeout=15.0) == i + 1
        time.sleep(0.05)

    ctrl, info2 = _controller_info()
    assert info2["pid"] != info["pid"]
    assert info2["recovered"], info2
    assert info2["adopted_replicas"] >= 2, info2

    _wait_status("Echo", lambda s: s.get("status") == "HEALTHY")
    _, table1 = ray_tpu.get(ctrl.get_route_table.remote(), timeout=10.0)
    assert sorted(table1["Echo"]["replicas"]) == ids_before, \
        "replicas were restarted instead of re-adopted"

    # a handle deserialized across the restart still routes
    h2 = pickle.loads(pickled_handle)
    assert ray_tpu.get(h2.remote(41), timeout=30.0) == 42

    # the reconnected long-poll applies post-restart updates (version
    # counters regressed to the new incarnation's)
    @serve.deployment(num_replicas=2, user_config={"off": 10})
    class Echo:  # noqa: F811
        def __init__(self):
            self.off = 1

        def reconfigure(self, cfg):
            self.off = cfg["off"]

        def __call__(self, x):
            return x + self.off

    h3 = serve.run(Echo.bind(), http_port=None, _blocking_timeout=90.0)
    deadline = time.time() + 30
    while time.time() < deadline:
        if ray_tpu.get(h3.remote(1), timeout=15.0) == 11:
            break
        time.sleep(0.2)
    assert ray_tpu.get(h3.remote(1), timeout=15.0) == 11
    serve.delete("Echo")


# ------------------------------------------------- rolling update + drain


def _versioned(name, v, extra=None):
    cfg = dict(num_replicas=2, user_config={"v": v}, name=name,
               graceful_shutdown_timeout_s=8.0)
    cfg.update(extra or {})

    @serve.deployment(**cfg)
    class Versioned:
        def __init__(self):
            self.v = None

        def reconfigure(self, c):
            self.v = c["v"]

        def __call__(self, x):
            time.sleep(0.03)
            return self.v

    return Versioned


def test_rolling_update_zero_failed_requests(ha_cluster):
    """Health-gated start-before-stop: a redeploy under sustained load
    completes with ZERO failed requests (the old stop-then-start order
    dropped every request routed to a replica killed before its
    replacement existed)."""
    h = serve.run(_versioned("Roll", 1).bind(), http_port=None)
    assert ray_tpu.get(h.remote(0), timeout=30.0) == 1

    errors, results = [], []
    stop = threading.Event()

    def load():
        while not stop.is_set():
            try:
                results.append(ray_tpu.get(h.remote(0), timeout=20.0))
            except Exception as e:  # noqa: BLE001 — every failure counts
                errors.append(repr(e))

    threads = [threading.Thread(target=load) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.8)
    serve.run(_versioned("Roll", 2).bind(), http_port=None,
              _blocking_timeout=90.0)
    time.sleep(0.8)
    stop.set()
    for t in threads:
        t.join(timeout=120)

    assert not errors, f"{len(errors)} dropped during rollout: {errors[:5]}"
    assert results, "load loop never completed a request"
    assert ray_tpu.get(h.remote(0), timeout=15.0) == 2
    st = _wait_status("Roll", lambda s: s.get("status") == "HEALTHY")
    assert st["live_replicas"] == 2
    # old AND new versions served during the window — the update really
    # overlapped instead of stopping the world
    assert 1 in results and 2 in results
    serve.delete("Roll")


def test_health_gate_keeps_old_version_serving(ha_cluster):
    """A new version whose replicas never pass health checks must NOT
    take down the old version: the gate drains old replicas only
    one-for-one against READY new ones."""
    h = serve.run(_versioned("Gate", 1).bind(), http_port=None)
    assert ray_tpu.get(h.remote(0), timeout=30.0) == 1

    @serve.deployment(num_replicas=2, name="Gate")
    class Broken:
        def __init__(self):
            raise RuntimeError("bad build: constructor always fails")

        def __call__(self, x):
            return -1

    # deploy_application returns immediately; reconciliation tries (and
    # fails) to bring the broken version up in surge waves
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    import cloudpickle
    cfg = dict(Broken.config)
    cfg["name"] = "Gate"
    cfg["app_name"] = "default"
    cfg["serialized_callable"] = cloudpickle.dumps(Broken.func_or_class)
    cfg["init_args"] = ()
    cfg["init_kwargs"] = {}
    assert ray_tpu.get(ctrl.deploy_application.remote([cfg]),
                       timeout=30.0) == "ok"

    # the old version keeps serving the whole time
    deadline = time.time() + 6.0
    while time.time() < deadline:
        assert ray_tpu.get(h.remote(0), timeout=20.0) == 1
        time.sleep(0.25)
    st = serve.status()["Gate"]
    assert st["status"] == "UPDATING", st
    assert st["stale_replicas"] >= 2, st  # old replicas still in the table

    # a fixed build rolls out normally
    serve.run(_versioned("Gate", 3).bind(), http_port=None,
              _blocking_timeout=120.0)
    deadline = time.time() + 30
    while time.time() < deadline:
        if ray_tpu.get(h.remote(0), timeout=20.0) == 3:
            break
        time.sleep(0.2)
    assert ray_tpu.get(h.remote(0), timeout=20.0) == 3
    serve.delete("Gate")


def test_flaky_health_probe_does_not_kill_replica(ha_cluster, tmp_path):
    """Fewer than RTPU_SERVE_HEALTH_FAILURES consecutive probe failures
    must NOT remove a replica — one flaky probe used to kill a healthy
    replica on the spot."""
    marker = str(tmp_path / "flaky_fails")

    @serve.deployment(num_replicas=1, name="Flaky")
    class Flaky:
        def __init__(self, path):
            self.path = path

        def check_health(self):
            # fail exactly two probes (threshold is 3), then recover
            n = 0
            if os.path.exists(self.path):
                n = int(open(self.path).read() or 0)
            if n < 2:
                with open(self.path, "w") as f:
                    f.write(str(n + 1))
                raise RuntimeError(f"flaky probe {n + 1}")

        def __call__(self, x):
            return x * 3

    h = serve.run(Flaky.bind(marker), http_port=None,
                  _blocking_timeout=90.0)
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    _, table0 = ray_tpu.get(ctrl.get_route_table.remote(), timeout=10.0)
    rid = table0["Flaky"]["replicas"]
    # ride through > threshold health-check rounds
    deadline = time.time() + 6.0
    while time.time() < deadline:
        assert ray_tpu.get(h.remote(2), timeout=20.0) == 6
        time.sleep(0.4)
    _, table1 = ray_tpu.get(ctrl.get_route_table.remote(), timeout=10.0)
    assert table1["Flaky"]["replicas"] == rid, \
        "flaky (sub-threshold) probes killed a healthy replica"
    assert int(open(marker).read()) == 2  # the probes really did fail
    serve.delete("Flaky")


def test_graceful_drain_completes_inflight_on_downscale(ha_cluster):
    """Downscale routes through the drain path: the victim leaves the
    route table first, finishes its in-flight requests, and only then
    dies — no dropped work."""

    @serve.deployment(num_replicas=2, name="Slow",
                      graceful_shutdown_timeout_s=10.0)
    def slow(x):
        time.sleep(1.2)
        return x * 2

    hs = serve.run(slow.options(name="Slow").bind(), name="slowapp",
                   http_port=None)
    refs = [hs.remote(i) for i in range(4)]
    time.sleep(0.1)
    # same version hash (num_replicas is excluded) → pure downscale
    serve.run(slow.options(name="Slow", num_replicas=1).bind(),
              name="slowapp", http_port=None, _blocking_timeout=60.0)
    assert sorted(ray_tpu.get(refs, timeout=60.0)) == [0, 2, 4, 6]
    st = _wait_status("Slow", lambda s: s.get("live_replicas") == 1
                      and s.get("draining_replicas", 1) == 0)
    assert st["status"] == "HEALTHY"
    serve.delete_application("slowapp")


# ------------------------------------------------------------- unit-level


def _bare_controller():
    """A ServeController shell for pure-logic tests: no cluster, no
    control loop, no journal."""
    from ray_tpu.serve.controller import ServeController
    import threading as _t
    c = ServeController.__new__(ServeController)
    c._lock = _t.RLock()
    c._deployments = {}
    c._last_errors = {}
    c._last_error = None
    c._last_load_table = {}
    c._replica_nodes = {}
    c._draining_nodes = {}
    return c


class _FakeHandle:
    def __init__(self, hex_id):
        self._id_hex = hex_id


def test_downscale_victim_is_least_loaded():
    """The autoscaler/downscale eviction order is ascending reported
    queue depth — not dict-iteration order (which routinely picked the
    busiest replica)."""
    c = _bare_controller()
    a, b, d = _FakeHandle("aa"), _FakeHandle("bb"), _FakeHandle("dd")
    c._last_load_table = {"Dep": {
        "aa": {"queue_len": 7.0}, "bb": {"queue_len": 0.0},
        "dd": {"queue_len": 3.0}}}
    order = c._least_loaded("Dep", [a, b, d])
    assert [h._id_hex for h in order] == ["bb", "dd", "aa"]
    # replicas without a report sort as idle (safe victims)
    e = _FakeHandle("ee")
    order = c._least_loaded("Dep", [a, e])
    assert [h._id_hex for h in order] == ["ee", "aa"]


def test_controller_error_scoped_to_failing_deployment():
    """last_controller_error lands ONLY on the deployment whose
    reconcile/health pass failed, not on every deployment."""
    from ray_tpu.serve.controller import _DeploymentInfo
    c = _bare_controller()
    for name in ("Good", "Bad"):
        info = _DeploymentInfo({"name": name, "num_replicas": 0})
        c._deployments[name] = info
    c._last_errors["Bad"] = "Traceback: boom"
    statuses = c.get_deployment_statuses()
    assert "last_controller_error" not in statuses["Good"]
    assert statuses["Bad"]["last_controller_error"] == "Traceback: boom"


def test_longpoll_host_handles_version_regression():
    """A client cursor AHEAD of the host (previous controller
    incarnation) returns immediately instead of parking for the full
    listen timeout."""
    from ray_tpu.serve._private.long_poll import LongPollHost
    host = LongPollHost()
    host.notify_changed("route_table", {"a": 1})
    t0 = time.monotonic()
    version, snap = host.listen("route_table", last_version=99,
                                timeout=5.0)
    assert time.monotonic() - t0 < 1.0
    assert version == 1 and snap == {"a": 1}


def test_wait_healthy_reports_controller_death(ha_cluster):
    """api._wait_healthy raises a clear controller-death error, not a
    bare deployment timeout, when the controller actor is gone for
    good (killed with no_restart)."""
    from ray_tpu.serve import api as serve_api

    @serve.deployment(name="Doomed")
    def doomed(x):
        return x

    serve.run(doomed.options(name="Doomed").bind(), http_port=None)
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    ray_tpu.kill(ctrl)  # no_restart=True: max_restarts is zeroed
    time.sleep(0.5)
    with pytest.raises(RuntimeError, match="controller has died"):
        serve_api._wait_healthy(ctrl, ["Doomed"], timeout=15.0)
    # leave the module cluster usable: a fresh start() builds a new
    # controller (the old name is freed by DEAD state)
    serve.shutdown()


# ------------------------------------------------------ chaos-seeded e2e


def test_chaos_replica_kill_traffic_survives(tmp_path):
    """RTPU_CHAOS kills a replica at its 5th accepted request; the
    proxy retries onto surviving replicas and the controller replaces
    the dead one — HTTP GETs keep succeeding end to end."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()  # chaos must ride a FRESH cluster's env
    log = str(tmp_path / "chaos.jsonl")
    os.environ["RTPU_CHAOS"] = json.dumps({
        "seed": 21,
        "schedule": [{"site": "serve.replica.request", "op": "kill",
                      "at": 5, "method": "ChaosEcho", "proc": "worker"}]})
    os.environ["RTPU_CHAOS_LOG"] = log
    ray_tpu.init(num_cpus=8, object_store_memory=128 * 1024 * 1024)
    try:
        @serve.deployment(num_replicas=2, name="ChaosEcho")
        def echo(x=None):
            return {"ok": True}

        serve.run(echo.options(name="ChaosEcho").bind(),
                  route_prefix="/chaos", http_port=8321)
        proxy = ray_tpu.get_actor("SERVE_PROXY")
        port = ray_tpu.get(proxy.get_port.remote(), timeout=10.0)

        import urllib.error
        import urllib.request

        def get():
            # Every replica process carries the schedule, so both die at
            # their own 5th request, a request apart, and so do their
            # replacements. The proxy retries a GET for ~3 s; whether a
            # replacement is up by then is how fast this box starts a
            # process (beside five other test workers it was not, once in
            # seven whole runs). What the proxy then answers is a 503
            # marked retryable, and a client that honours it, within a
            # deadline, is what "traffic survives" means here; any other
            # status fails the test at once.
            deadline = time.time() + 60
            while True:
                try:
                    return json.loads(urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/chaos",
                        timeout=30).read())
                except urllib.error.HTTPError as e:
                    body = json.loads(e.read() or b"{}")
                    if e.code != 503 or not body.get("retryable") \
                            or time.time() > deadline:
                        raise
                    time.sleep(0.2)

        ok = 0
        for _ in range(25):
            resp = get()
            assert resp == {"ok": True}
            ok += 1
            time.sleep(0.05)
        assert ok == 25

        fired = chaos.read_log(log)
        assert any(r["site"] == "serve.replica.request" for r in fired), \
            "chaos never fired — the site is not wired"
        _wait_status("ChaosEcho",
                     lambda s: s.get("status") == "HEALTHY"
                     and s.get("live_replicas") == 2)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_chaos_controller_kill_zero_dropped_requests(tmp_path):
    """The acceptance scenario: RTPU_CHAOS SIGKILLs the controller at a
    fixed control-loop tick while handle traffic runs. Zero requests
    fail (the data plane routes from cached tables), and the restarted
    controller re-adopts the live replicas."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()  # chaos must ride a FRESH cluster's env
    os.environ["RTPU_CHAOS"] = json.dumps({
        "seed": 23,
        "schedule": [{"site": "serve.controller.tick", "op": "kill",
                      "at": 4, "proc": "worker"}]})
    ray_tpu.init(num_cpus=8, object_store_memory=128 * 1024 * 1024)
    try:
        @serve.deployment(num_replicas=2, name="Steady")
        def steady(x=None):
            return x + 1 if isinstance(x, int) else {"ok": True}

        h = serve.run(steady.options(name="Steady").bind(),
                      route_prefix="/steady", http_port=8331)
        proxy = ray_tpu.get_actor("SERVE_PROXY")
        port = ray_tpu.get(proxy.get_port.remote(), timeout=10.0)
        pid0 = _controller_info()[1]["pid"]

        errors, results = [], []
        stop = threading.Event()

        def load():
            i = 0
            while not stop.is_set():
                try:
                    assert ray_tpu.get(h.remote(i), timeout=20.0) == i + 1
                    results.append(i)
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                i += 1

        def http_load():
            import urllib.request
            while not stop.is_set():
                try:
                    resp = json.loads(urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/steady",
                        timeout=30).read())
                    assert resp == {"ok": True}
                    results.append(-1)
                except Exception as e:  # noqa: BLE001
                    errors.append("http: " + repr(e))
                time.sleep(0.05)

        threads = [threading.Thread(target=load) for _ in range(2)] + \
            [threading.Thread(target=http_load)]
        for t in threads:
            t.start()
        # the chaos kill lands ~4 ticks (~4s) in; ride through it
        deadline = time.time() + 45
        info = None
        while time.time() < deadline:
            try:
                info = _controller_info(timeout=5.0)[1]
                if info["pid"] != pid0 and info["recovered"]:
                    break
            except AssertionError:
                pass
            time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=120)
        assert info and info["pid"] != pid0, "chaos kill never fired"
        assert info["recovered"] and info["adopted_replicas"] >= 2, info
        assert not errors, \
            f"{len(errors)} requests dropped during controller outage: " \
            f"{errors[:5]}"
        assert len(results) > 20
        _wait_status("Steady", lambda s: s.get("status") == "HEALTHY")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -------------------------------------------------- node preemption drain


def test_node_preemption_replaces_replicas_before_drain(tmp_path):
    """A draining node's replicas get start-before-stop replacements on
    surviving nodes inside the grace window (the PR-4 preemption drain
    feeding the serve control plane)."""
    from ray_tpu._private.cluster_utils import Cluster
    from ray_tpu._private import worker as wmod
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    try:
        cluster.add_node(num_cpus=4)
        cluster.connect()
        cluster.wait_for_nodes()
        w = wmod._global_worker

        @serve.deployment(num_replicas=2, name="Spread",
                          graceful_shutdown_timeout_s=10.0,
                          ray_actor_options={"scheduling_strategy":
                                             "SPREAD"})
        def spread(x):
            return x + 1

        h = serve.run(spread.options(name="Spread").bind(),
                      http_port=None)
        assert ray_tpu.get(h.remote(1), timeout=30.0) == 2

        # find a node hosting a replica but NOT the controller
        actors = w.call_sync(w.gcs, "list_actors", {})
        ctrl_node = next(a["node_id"] for a in actors
                         if a.get("class_name") == "ServeController"
                         and a.get("state") == "ALIVE")
        replica_nodes = {a["node_id"] for a in actors
                         if a.get("class_name") == "ReplicaActor"
                         and a.get("state") == "ALIVE"}
        victims = replica_nodes - {ctrl_node}
        if not victims:
            pytest.skip("replicas co-located with the controller; "
                        "SPREAD did not separate them on this box")
        victim = next(iter(victims))

        w.call_sync(w.gcs, "preempt_node", {
            "node_id": victim, "grace_s": 10.0,
            "reason": "test spot notice"})

        # replacements start elsewhere; statuses converge with BOTH
        # replicas off the victim
        def replicas_ok(_st):
            acts = w.call_sync(w.gcs, "list_actors", {})
            live = [a for a in acts
                    if a.get("class_name") == "ReplicaActor"
                    and a.get("state") == "ALIVE"]
            return (_st.get("status") == "HEALTHY"
                    and _st.get("live_replicas") == 2
                    and all(a["node_id"] != victim for a in live))

        _wait_status("Spread", replicas_ok, timeout=60.0)
        # traffic still flows after failover
        assert ray_tpu.get(h.remote(2), timeout=30.0) == 3
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()
