"""Game-day SLO harness: deterministic open-loop load generation,
client-side SLO accounting, replayable composed scenarios, request-id
propagation (proxy→router→replica + ledger echo), and the flagship
tier-1 gate — rolling update + chaos-seeded controller kill under peak
open-loop load with ZERO client-observed failed requests and an exact
client/server reconciliation (docs/GAMEDAY.md; ROADMAP item 8).
"""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.gameday import loadgen, scenario, slo
from ray_tpu.gameday.reconcile import reconcile as run_reconcile


# ------------------------------------------------------------ pure units


def test_arrival_schedule_deterministic_and_seed_sensitive():
    """Same (spec, seed) -> byte-identical arrivals, ids included;
    a different seed is a different game day."""
    sc = scenario.load_scenario("flagship")
    a = [x.to_dict() for x in sc.arrival_schedule().arrivals]
    b = [x.to_dict() for x in
         scenario.load_scenario("flagship").arrival_schedule().arrivals]
    assert a == b and len(a) > 100
    c = [x.to_dict() for x in
         scenario.load_scenario("flagship",
                                seed=999).arrival_schedule().arrivals]
    assert c != a
    # ids embed the seed so two seeds can never alias in a ledger
    assert a[0]["rid"].startswith("flagship-411-")
    assert c[0]["rid"].startswith("flagship-999-")


def test_arrival_shapes():
    """The generator actually produces the advertised shapes: flash
    crowd bursts, diurnal crest, heavy-tail sizes, tenant skew."""
    sched = loadgen.build_schedule(
        [{"name": "fc", "duration_s": 8.0, "shape": "flash_crowd",
          "base_rps": 30, "burst_rps": 120, "burst_start_frac": 0.25,
          "burst_frac": 0.5}], seed=5)
    base = sched.rate_in(0.0, 2.0)
    burst = sched.rate_in(2.0, 6.0)
    assert burst > 2.5 * base, (base, burst)

    sched = loadgen.build_schedule(
        [{"name": "d", "duration_s": 10.0, "shape": "diurnal",
          "min_rps": 10, "peak_rps": 100}], seed=6)
    trough = (sched.rate_in(0.0, 1.0) + sched.rate_in(9.0, 10.0)) / 2
    crest = sched.rate_in(4.0, 6.0)
    assert crest > 2.0 * trough, (trough, crest)

    sched = loadgen.build_schedule(
        [{"name": "s", "duration_s": 20.0, "shape": "steady",
          "rps": 100}], seed=7, tenants=4, tenant_skew=1.2)
    sizes = sorted(a.size for a in sched.arrivals)
    median = sizes[len(sizes) // 2]
    assert sizes[-1] > 5 * median, "sizes are not heavy-tailed"
    by_tenant = {}
    for a in sched.arrivals:
        by_tenant[a.tenant] = by_tenant.get(a.tenant, 0) + 1
    shares = sorted(by_tenant.values(), reverse=True)
    assert shares[0] > 1.8 * shares[-1], f"no tenant skew: {by_tenant}"


def test_histogram_quantiles_close_to_exact():
    import random
    np = pytest.importorskip("numpy")
    rng = random.Random(3)
    vals = [rng.lognormvariate(-4, 1.0) for _ in range(5000)]
    h = slo.LatencyHistogram()
    for v in vals:
        h.record(v)
    for q in (0.5, 0.99, 0.999):
        got = h.quantile(q)
        want = float(np.percentile(vals, q * 100))
        # log buckets grow 2.5%/step; the conservative upper edge may
        # sit one bucket above the exact sample
        assert want <= got <= want * 1.06, (q, got, want)
    assert h.quantile(0.999) <= h.max_s


def test_error_budget_burn_math():
    # 99.9% over 1000 requests: the budget is exactly one failure
    assert slo.error_budget_burn(1000, 0, 0.999) == 0.0
    assert slo.error_budget_burn(1000, 1, 0.999) == pytest.approx(1.0)
    assert slo.error_budget_burn(1000, 3, 0.999) == pytest.approx(3.0)
    # a zero-failure SLO has no budget: any failure burns infinitely
    assert slo.error_budget_burn(10, 1, 1.0) == float("inf")


def test_scenario_replayable_and_json_roundtrip(tmp_path):
    """Same seed -> same chaos schedule AND same arrivals, including
    through a JSON spec file round-trip: the replay property the
    flagship acceptance criterion leans on."""
    sc = scenario.load_scenario("flagship")
    cc1 = scenario.chaos_config(sc)
    cc2 = scenario.chaos_config(scenario.load_scenario("flagship"))
    assert cc1 == cc2
    assert cc1["schedule"], "flagship must schedule a controller kill"

    path = tmp_path / "spec.json"
    path.write_text(json.dumps(sc.to_dict()))
    sc2 = scenario.load_scenario(str(path))
    assert scenario.chaos_config(sc2) == cc1
    assert [a.to_dict() for a in sc2.arrival_schedule().arrivals] == \
        [a.to_dict() for a in sc.arrival_schedule().arrivals]
    # scale stretches phase durations and stays deterministic
    half = sc.arrival_schedule(0.5)
    assert half.duration_s == pytest.approx(
        sc.arrival_schedule(1.0).duration_s / 2)
    assert [a.to_dict() for a in half.arrivals] == \
        [a.to_dict() for a in sc.arrival_schedule(0.5).arrivals]
    assert len(half.arrivals) > 50


def test_open_loop_charges_stall_to_scheduled_arrivals():
    """The anti-coordinated-omission property: with one worker wedged
    behind a slow request, arrivals scheduled during the stall report
    the queueing delay a real user would have seen — not the healthy
    service time of whenever they finally got sent."""
    arrivals = [loadgen.Arrival(i * 0.02, f"r{i}", "p", "t", 1.0)
                for i in range(5)]
    sched = loadgen.ArrivalSchedule(
        arrivals, [{"name": "p", "duration_s": 0.1}], seed=0)

    def send(_a):
        time.sleep(0.15)

    lg = loadgen.OpenLoopRunner(sched, send, max_workers=1)
    records = sorted(lg.run(), key=lambda r: r.rid)
    assert all(r.outcome == "ok" for r in records)
    # worker serializes 5 x 150 ms; the last arrival (scheduled t=80ms)
    # completes ~t=750ms => open-loop latency ~670ms >> its 150 ms
    # service time
    assert records[-1].latency_s > 0.4, records[-1].latency_s
    assert records[-1].service_s < 0.3
    # the first request saw no queue: latency ~ service time
    assert records[0].latency_s < 0.3


def test_reconcile_detects_each_mismatch_class():
    sc = scenario.load_scenario("flagship")
    client = {"ok": ["a", "b"], "shed": ["c"], "failed": []}
    view = {
        "replica_ledgers": [
            {"deployment": "GameDay", "replica": "R1", "live": True,
             "records": [["a", "ok", 0.01], ["c", "shed", 0.0]]},
            {"deployment": "GameDay", "replica": "R2", "live": False,
             "records": [["b", "ok", 0.02]]}],
        "replica_metrics": {"R1": {"total_requests": 1,
                                   "total_shed": 1}},
        "serve_metrics": {"GameDay": {"requests_total": 1,
                                      "shed_total": 1}},
        "task_delta": {"finished": 2, "failed": 1, "dropped": 0,
                       "events_dropped": 0},
        "prometheus": {"serve": {"GameDay": {"requests_total": 1,
                                             "shed_total": 1}}},
        "chaos_fired": [{"site": "serve.controller.tick", "op": "kill",
                         "n": 6}],
        "chaos_expected": scenario.chaos_config(sc),
    }
    assert run_reconcile(sc, client, view)["ok"]

    def run(mutate):
        import copy
        v = copy.deepcopy(view)
        c = {k: list(vs) for k, vs in client.items()}
        mutate(c, v)
        return {chk["name"]: chk["ok"] for chk in
                run_reconcile(sc, c, v)["checks"]}

    # a client success the server never completed
    checks = run(lambda c, v: c["ok"].append("ghost"))
    assert not checks["completed-join"]
    # a server completion the client saw fail (unexplained outcome)
    checks = run(lambda c, v: (c["ok"].remove("b"),
                               c["failed"].append("b")))
    assert not checks["admitted-equals-completed"]
    # a shed the server never listed
    checks = run(lambda c, v: c["shed"].append("ghost-shed"))
    assert not checks["shed-listed"]
    # replica counters drifting from the replica's own ledger
    checks = run(lambda c, v: v["replica_metrics"]["R1"].update(
        total_requests=99))
    assert not checks["replica-totals"]
    # controller aggregation disagreeing with replica counters
    checks = run(lambda c, v: v["serve_metrics"]["GameDay"].update(
        requests_total=99))
    assert not checks["serve-metrics-agree"]
    # the state engine counting a different story
    checks = run(lambda c, v: v["task_delta"].update(finished=99))
    assert not checks["state-engine-tasks"]
    # Prometheus exporting something else
    checks = run(lambda c, v: v["prometheus"]["serve"]["GameDay"].update(
        requests_total=99))
    assert not checks["prometheus-serve-gauges"]
    # a fault that fired off-schedule
    checks = run(lambda c, v: v["chaos_fired"].append(
        {"site": "serve.replica.request", "op": "kill", "n": 3}))
    assert not checks["chaos-schedule-replay"]
    # a lossy task table downgrades to skip, not to a false failure
    checks = run(lambda c, v: v["task_delta"].update(finished=99,
                                                     dropped=5))
    assert checks["state-engine-tasks"]


# -------------------------------------------- request-id plumbing (e2e)


@pytest.fixture(scope="module")
def rid_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield
    # the flagship test may already have torn this cluster down (it
    # must own a fresh one for the chaos env) — teardown is best-effort
    try:
        from ray_tpu import serve
        serve.shutdown()
    except Exception:
        pass
    try:
        ray_tpu.shutdown()
    except Exception:
        pass


def _request_logs():
    """All live replica request ledgers, via the route table."""
    from ray_tpu.actor import get_actor_by_id
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    _, table = ray_tpu.get(ctrl.get_route_table.remote(), timeout=10.0)
    logs = []
    for info in table.values():
        for hex_id in info["replicas"]:
            h = get_actor_by_id(hex_id)
            logs.append(ray_tpu.get(h.get_request_log.remote(),
                                    timeout=10.0))
    return logs


def test_request_id_handle_path_lands_in_ledger(rid_cluster):
    """A handle caller tags a request with __rtpu_request_id__: user
    code must never see the kwarg, and the replica ledger must record
    (id, ok, latency)."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=1, name="Rid")
    def echo(payload=None, **kwargs):
        # the reserved kwarg must have been stripped
        assert "__rtpu_request_id__" not in kwargs, kwargs
        return {"got": payload}

    h = serve.run(echo.options(name="Rid").bind(), http_port=None)
    out = ray_tpu.get(h.remote({"x": 1}, __rtpu_request_id__="req-abc"),
                      timeout=30.0)
    assert out == {"got": {"x": 1}}

    logs = _request_logs()
    assert logs and logs[0]["deployment"] == "Rid"
    assert logs[0]["replica"].startswith("SERVE_REPLICA::Rid#")
    entries = {rid: (outcome, lat)
               for rid, outcome, lat in logs[0]["records"]}
    assert "req-abc" in entries, entries
    outcome, lat = entries["req-abc"]
    assert outcome == "ok" and lat >= 0.0
    assert not logs[0]["truncated"]


def test_request_id_http_header_roundtrip(rid_cluster):
    """X-Request-Id propagates proxy -> router -> replica (ledger
    entry) and is echoed on the response."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=1, name="RidHttp")
    def echo(payload=None):
        return {"ok": True}

    serve.run(echo.options(name="RidHttp").bind(),
              route_prefix="/rid", http_port=8341)
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=10.0)

    req = urllib.request.Request(f"http://127.0.0.1:{port}/rid",
                                 headers={"X-Request-Id": "http-42"})
    resp = urllib.request.urlopen(req, timeout=30)
    assert json.loads(resp.read()) == {"ok": True}
    assert resp.headers.get("X-Request-Id") == "http-42"

    rids = [rid for log in _request_logs()
            for rid, _o, _l in log["records"]]
    assert "http-42" in rids, rids


# ---------------------------------------------------- flagship (tier-1)


def _run_flagship(scale):
    from ray_tpu.gameday import load_scenario, run_scenario
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    sc = load_scenario("flagship")
    return sc, run_scenario(sc, scale=scale, dashboard_port=18472)


def test_flagship_gameday_zero_failed_and_exact_reconcile():
    """THE acceptance gate (ISSUE 11): a rolling update AND a
    chaos-seeded controller SIGKILL land during peak open-loop load;
    the game day passes only if no client-observed request failed, the
    client ledger reconciles exactly with the state engine / replica
    ledgers / Prometheus, and the fired faults match the seeded
    schedule."""
    sc, result = _run_flagship(scale=0.5)
    rep = result.report

    # zero client-observed failures through the whole composed scenario
    assert rep["overall"]["failed"] == 0, \
        [r.error for r in result.records if r.outcome == "failed"][:5]
    assert rep["overall"]["admitted"] > 100
    assert not rep["action_errors"], rep["action_errors"]

    # the faults really fired, per the seeded schedule
    fired = rep["chaos_fired"]
    assert any(f["site"] == "serve.controller.tick" for f in fired), \
        "controller kill never fired"

    # outside-in: every reconciliation check green
    recon = rep["reconciliation"]
    assert recon["ok"], [c for c in recon["checks"] if not c["ok"]]
    assert recon["counts"]["client_ok"] == rep["overall"]["admitted"]

    # the SLO verdict and its export round-trip
    assert rep["passed"], rep["slo"]
    assert rep.get("slo_gauges_published"), \
        "ray_tpu_slo_* gauges missing from /metrics after publish"

    # replay property: rebuilding the scenario reproduces the exact
    # fault schedule and arrival ids the run used
    from ray_tpu.gameday import load_scenario
    again = load_scenario("flagship")
    assert scenario.chaos_config(again) == \
        result.server_view["chaos_expected"]
    assert [a.rid for a in again.arrival_schedule(0.5).arrivals] == \
        [r.rid for r in sorted(result.records, key=lambda r: r.sched_t)]


# ------------------------------------------------------------- slow soak


@pytest.mark.slow
def test_diurnal_soak_gameday():
    """Three diurnal cycles with two rolling updates and a controller
    kill — the long-haul version of the flagship gate."""
    from ray_tpu.gameday import load_scenario, run_scenario
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    sc = load_scenario("diurnal-soak")
    result = run_scenario(sc, scale=1.0, dashboard_port=18473)
    rep = result.report
    assert rep["overall"]["failed"] == 0
    assert rep["reconciliation"]["ok"], \
        [c for c in rep["reconciliation"]["checks"] if not c["ok"]]
    assert rep["passed"], rep["slo"]


@pytest.mark.slow
def test_replica_storm_gameday_bounded_blast_radius():
    """A replica SIGKILL mid-load: failures stay inside the scenario's
    budget and reconciliation (with lost-ledger tolerance) holds."""
    from ray_tpu.gameday import load_scenario, run_scenario
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    sc = load_scenario("replica-storm")
    result = run_scenario(sc, scale=1.0, dashboard_port=18474)
    rep = result.report
    fired = rep["chaos_fired"]
    assert any(f["site"] == "serve.replica.request" for f in fired)
    burn = rep["slo"]["availability_burn"]
    assert 0.0 <= burn <= 1.0, rep["overall"]
    assert rep["reconciliation"]["ok"], \
        [c for c in rep["reconciliation"]["checks"] if not c["ok"]]
