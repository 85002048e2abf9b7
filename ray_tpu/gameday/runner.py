"""Game-day execution: deploy, load, fault, collect, reconcile.

One ``run_scenario`` call is a complete game day:

1. Export the scenario's seeded chaos schedule (``RTPU_CHAOS``) and
   start a fresh cluster — the env rides process spawn, so the same
   schedule reaches the controller/replica workers deterministically.
2. Deploy the synthetic ``GameDay`` workload (configurable service
   time; request "work" multiplies it, so the heavy-tail sizes the
   load generator draws become heavy-tail service demand).
3. Fire the precomputed open-loop schedule through a deployment
   handle (request ids ride the ``__rtpu_request_id__`` kwarg into
   replica ledgers) while a background thread executes the timed
   actions (rolling updates, scale changes) and the chaos engine
   executes the seeded kills.
4. Quiesce, then collect every server-side view: live replica ledgers
   + counters, ledgers flushed by replicas retired mid-run, the
   controller's serve metrics, the state engine's task-table delta,
   a Prometheus ``/metrics`` scrape, and the chaos log.
5. Reconcile client vs server (``reconcile.py``), build the SLO
   report, publish it to the GCS KV (dashboard panel + ``ray_tpu_slo_*``
   gauges), and verify the published gauges actually appear.

A note on controller kills: the chaos engine is per-process, so a
``controller_kill`` schedule fires once per controller *incarnation* —
a long collection window may see the restarted controller die again at
the same tick count. That is by design (every incarnation replays the
same schedule); recovery is sub-second, every collection step retries
through restart windows, and reconciliation compares the deduplicated
(site, op, hit) set against the schedule.
"""

from __future__ import annotations

import json
import logging
import os
import re
import tempfile
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional

from ray_tpu.gameday import store
from ray_tpu.gameday.loadgen import Arrival, OpenLoopRunner
from ray_tpu.gameday.reconcile import reconcile
from ray_tpu.gameday.scenario import (DEPLOYMENT_NAME, Scenario,
                                      chaos_config)
from ray_tpu.gameday.slo import build_report, ledger

logger = logging.getLogger("ray_tpu.gameday")

_REQUEST_TASK_NAME = "ReplicaActor.handle_request"


class GameDayApp:
    """The workload under test: a configurable-latency echo whose
    version is visible in responses (so a rolling update's overlap is
    observable) and whose per-request cost scales with the arrival's
    heavy-tail ``work`` factor."""

    def __init__(self, service_time_ms: float = 3.0):
        self._service_s = max(0.0, float(service_time_ms)) / 1e3
        self.version = 0

    def reconfigure(self, cfg):
        self.version = int(cfg.get("v", 0))
        if "service_time_ms" in cfg:
            self._service_s = max(0.0,
                                  float(cfg["service_time_ms"])) / 1e3

    def __call__(self, payload=None):
        work = 1.0
        if isinstance(payload, dict):
            try:
                work = float(payload.get("work", 1.0))
            except (TypeError, ValueError):
                work = 1.0
        time.sleep(self._service_s * min(max(work, 0.0), 50.0))
        return {"v": self.version}


class GameDayResult:
    def __init__(self, scenario: Scenario, records: List[Any],
                 report: Dict[str, Any], server_view: Dict[str, Any]):
        self.scenario = scenario
        self.records = records
        self.report = report
        self.server_view = server_view

    @property
    def reconciliation(self) -> Dict[str, Any]:
        return self.report.get("reconciliation") or {}

    @property
    def passed(self) -> bool:
        return bool(self.report.get("passed"))


def _bind_app(sc: Scenario, version: int):
    from ray_tpu import serve
    cfg = sc.deployment
    if cfg.get("workload") == "llm":
        # the stateful LLM workload (serve/llm): continuous batching +
        # paged KV + streaming; version rides user_config so a rolling
        # update replaces replicas exactly like the echo app's
        from ray_tpu.serve.llm import LLMServer
        llm = cfg.get("llm") or {}
        dep = serve.deployment(
            name=DEPLOYMENT_NAME,
            num_replicas=int(cfg.get("num_replicas", 2)),
            max_concurrent_queries=int(
                cfg.get("max_concurrent_queries", 32)),
            max_queued_requests=cfg.get("max_queued_requests"),
            user_config={"v": version},
            llm_roles=cfg.get("llm_roles"),
            graceful_shutdown_timeout_s=cfg.get(
                "graceful_shutdown_timeout_s", 20.0))(LLMServer)
        return dep.bind(llm.get("model", "toy"),
                        llm.get("model_config"),
                        llm.get("engine_config"))
    dep = serve.deployment(
        name=DEPLOYMENT_NAME,
        num_replicas=int(cfg.get("num_replicas", 3)),
        max_concurrent_queries=int(cfg.get("max_concurrent_queries", 16)),
        max_queued_requests=cfg.get("max_queued_requests"),
        user_config={"v": version,
                     "service_time_ms": cfg.get("service_time_ms", 3.0)},
        graceful_shutdown_timeout_s=cfg.get("graceful_shutdown_timeout_s",
                                            10.0))(GameDayApp)
    return dep.bind(cfg.get("service_time_ms", 3.0))


def _retry(fn, timeout: float = 30.0, default=None):
    """Run ``fn`` until it returns non-None, riding through controller
    restart windows (a killed controller answers again in <1 s)."""
    deadline = time.time() + timeout
    while True:
        try:
            out = fn()
            if out is not None:
                return out
        # errors are expected while the controller restarts; the
        # deadline below is the real failure signal
        except Exception:  # rtpulint: ignore[RTPU007]
            pass
        if time.time() >= deadline:
            return default
        time.sleep(0.4)


def _live_replica_handles() -> Dict[str, Any]:
    """Route-table replica ids -> actor handles (post-quiesce: the
    ready set IS the live set the controller aggregates metrics
    over)."""
    import ray_tpu
    from ray_tpu.actor import get_actor_by_id

    def table():
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        _, t = ray_tpu.get(ctrl.get_route_table.remote(), timeout=5.0)
        return t

    t = _retry(table, timeout=30.0, default={}) or {}
    handles = {}
    for _dep, info in t.items():
        for hex_id in info.get("replicas") or []:
            try:
                handles[hex_id] = get_actor_by_id(hex_id)
            except Exception:
                logger.debug("gameday: replica %s in route table but "
                             "unresolvable (torn down mid-sweep?)",
                             hex_id, exc_info=True)
    return handles


def _all_alive_replica_handles() -> Dict[str, Any]:
    """EVERY alive ``SERVE_REPLICA::*`` actor — including replicas a
    rolling update is still draining (out of the route table but
    holding ledger records the reconciliation join needs; a kill-
    cycling controller can stretch a drain past collection time)."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.actor import ActorHandle
    from ray_tpu.common.ids import ActorID
    handles = {}
    try:
        w = global_worker()
        for a in w.call_sync(w.gcs, "list_actors", {}, timeout=10):
            if a.get("class_name") != "ReplicaActor" or \
                    a.get("state") != "ALIVE":
                continue
            try:
                h = ActorHandle(ActorID.from_hex(a["actor_id"]),
                                "ReplicaActor")
                if a.get("worker_address"):
                    h._worker_address = a["worker_address"]
                handles[a["actor_id"]] = h
            except Exception:
                logger.debug("gameday: could not build handle for "
                             "replica %s", a.get("actor_id"),
                             exc_info=True)
    except Exception:
        logger.warning("gameday: alive-replica sweep failed",
                       exc_info=True)
    return handles


def _task_counts() -> Dict[str, int]:
    """FINISHED/FAILED counts for the replica request method from one
    ``summarize_tasks`` RPC, plus the table's loss counters."""
    from ray_tpu.experimental.state import api as state
    s = state.summarize_tasks()
    fin = fail = 0
    for row in s.get("summary") or []:
        if row.get("name") == _REQUEST_TASK_NAME:
            by = row.get("by_state") or {}
            fin = int(by.get("FINISHED", 0))
            fail = int(by.get("FAILED", 0))
    return {"finished": fin, "failed": fail,
            "dropped": int(s.get("dropped", 0)),
            "events_dropped": int(s.get("events_dropped", 0))}


def _parse_serve_gauges(text: str) -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for m in re.finditer(
            r'ray_tpu_serve_(\w+)\{deployment="([^"]+)"\}\s+([0-9.eE+-]+)',
            text):
        out.setdefault(m.group(2), {})[m.group(1)] = float(m.group(3))
    return out


def _scrape_metrics(port: Optional[int]) -> Optional[str]:
    if port is None:
        return None
    try:
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=15).read().decode()
    except Exception:
        return None


def _quiesce(handles: Dict[str, Any], timeout: float = 25.0
             ) -> Dict[str, Dict[str, Any]]:
    """Wait until the controller's aggregated serve metrics agree with
    the replicas' own counters (totals stop moving once load stops and
    a metrics tick lands), then return the per-replica counters. On
    timeout returns the last direct read — reconciliation will surface
    the disagreement as a failed check rather than hiding it."""
    import ray_tpu
    from ray_tpu import serve
    deadline = time.time() + timeout
    reps: Dict[str, Dict[str, Any]] = {}
    while time.time() < deadline:
        reps = {}
        try:
            for hex_id, h in handles.items():
                reps[hex_id] = ray_tpu.get(h.get_metrics.remote(),
                                           timeout=5.0)
        except Exception:
            time.sleep(0.5)
            continue
        sm = serve.metrics()
        if sm:
            sum_req = sum(m.get("total_requests", 0)
                          for m in reps.values())
            sum_shed = sum(m.get("total_shed", 0) for m in reps.values())
            agg_req = sum(d.get("requests_total", 0) for d in sm.values())
            agg_shed = sum(d.get("shed_total", 0) for d in sm.values())
            if sum_req == agg_req and sum_shed == agg_shed:
                return reps
        time.sleep(0.5)
    return reps


def run_scenario(scenario: Scenario, *, scale: float = 1.0,
                 num_cpus: int = 8, publish: bool = True,
                 dashboard_port: Optional[int] = 18470,
                 request_timeout_s: float = 30.0) -> GameDayResult:
    """Run one game day end to end on a fresh local cluster it owns
    (the chaos schedule must ride the env into every spawned process,
    so the cluster cannot pre-exist the scenario)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import chaos
    from ray_tpu.serve._private.router import is_overload_error

    if ray_tpu.is_initialized():
        raise RuntimeError(
            "gameday.run_scenario needs to own the cluster (the seeded "
            "chaos schedule rides process-spawn env); call it before "
            "ray_tpu.init, or after ray_tpu.shutdown()")

    schedule = scenario.arrival_schedule(scale)
    actions = scenario.timed_actions(scale)
    chaos_cfg = chaos_config(scenario)

    prev_env = {k: os.environ.get(k)
                for k in ("RTPU_CHAOS", "RTPU_CHAOS_LOG",
                          "RTPU_ACTOR_TASK_EVENTS",
                          "RTPU_TRACE_SAMPLE")}
    chaos_log = os.path.join(tempfile.mkdtemp(prefix="rtpu-gameday-"),
                             "chaos.jsonl")
    if chaos_cfg is not None:
        os.environ["RTPU_CHAOS"] = json.dumps(chaos_cfg)
        os.environ["RTPU_CHAOS_LOG"] = chaos_log
    else:
        os.environ.pop("RTPU_CHAOS", None)
    # the state-engine cross-check (reconcile C6) needs the task table
    # to see replica request tasks — actor-call events are opt-in
    os.environ["RTPU_ACTOR_TASK_EVENTS"] = "1"
    # a game day traces EVERY request (default head sampling is 10%):
    # the trace-completeness check (C9) and the p99 critical-path
    # aggregation need full span trees, and the run is bounded anyway
    os.environ.setdefault("RTPU_TRACE_SAMPLE", "1.0")
    from ray_tpu._private import tracing as _tracing
    _tracing.refresh()

    server_view: Dict[str, Any] = {"chaos_expected": chaos_cfg}
    t_setup = time.time()
    try:
        ray_tpu.init(num_cpus=num_cpus,
                     object_store_memory=256 * 1024 * 1024,
                     _system_config={"prestart_workers": False})
        # a previous cluster in this process may have left the global
        # serve router pinned to its (now dead) controller — drop it so
        # handles resolve against THIS cluster
        from ray_tpu.serve.handle import _reset_router
        _reset_router()
        store.clear_ledgers()
        dash_port = None
        if dashboard_port is not None:
            try:
                from ray_tpu.dashboard.dashboard import start_dashboard
                dash_port = start_dashboard(port=dashboard_port)
            except Exception:
                logger.warning("gameday: dashboard unavailable; "
                               "skipping the Prometheus cross-check")

        h = serve.run(_bind_app(scenario, 1), http_port=None,
                      _blocking_timeout=120.0)

        # warmup: touch every replica a few times so compile/startup
        # cost never lands inside a measured phase; warmup ids are
        # visible in replica ledgers (harmless to every join)
        is_llm = scenario.deployment.get("workload") == "llm"
        warm_payload = ({"tokens": [1, 2, 3], "max_new_tokens": 2}
                        if is_llm else {"work": 1.0})
        warm = 4 * int(scenario.deployment.get("num_replicas", 3))
        for i in range(warm):
            ray_tpu.get(h.remote(
                warm_payload,
                __rtpu_request_id__=f"warmup-{scenario.seed}-{i}"),
                timeout=60.0)
        time.sleep(1.5)  # task-event flush (0.5 s batches) settles
        task_base = _retry(_task_counts, timeout=15.0,
                           default={"finished": 0, "failed": 0,
                                    "dropped": 0, "events_dropped": 0})

        # ---- timed actions on their own clock ----
        action_errors: List[str] = []
        load_t0 = time.time() + 0.25  # shared epoch for load + actions

        def run_actions():
            ver = 1
            for a in actions:
                delay = load_t0 + a["t_s"] - time.time()
                if delay > 0:
                    time.sleep(delay)
                try:
                    if a["kind"] == "rolling_update":
                        ver += 1
                        serve.run(_bind_app(scenario, ver),
                                  http_port=None,
                                  _blocking_timeout=120.0)
                    elif a["kind"] == "scale":
                        sc2 = Scenario.from_dict(scenario.to_dict())
                        sc2.deployment["num_replicas"] = int(
                            a["num_replicas"])
                        serve.run(_bind_app(sc2, ver), http_port=None,
                                  _blocking_timeout=120.0)
                except Exception as e:  # noqa: BLE001 — surfaced below
                    action_errors.append(
                        f"{a['kind']}@{a['t_s']}s: "
                        f"{type(e).__name__}: {e}")

        action_thread = threading.Thread(target=run_actions, daemon=True)
        action_thread.start()

        # ---- open-loop load ----
        # requests go through the shared Router directly so admission
        # control is visible: a replica that sheds raises
        # ReplicaOverloadedError (server-side shed, ledgered), and an
        # assign that can't place the request within assign_timeout_s
        # means every replica is saturated — the same condition the
        # HTTP proxy maps to a retriable 503 (client-side shed, never
        # reached a replica)
        from ray_tpu import exceptions as rexc
        from ray_tpu.serve._private.replica import REQUEST_ID_KWARG
        from ray_tpu.serve.handle import _get_router
        router = _get_router(ray_tpu.get_actor("SERVE_CONTROLLER"))
        assign_timeout = float(scenario.deployment.get(
            "assign_timeout_s", 30.0))

        def send(arrival: Arrival):
            # retry a request that landed on a dead replica on another
            # one, same id — the HTTP proxy's idempotent-GET behavior
            # (backoff + membership refresh); a retried request is ONE
            # logical request in every ledger. Once every known replica
            # is excluded, assign blocks until the controller publishes
            # replacements — a full-fleet wipeout is ridden out, not
            # failed, as long as recovery lands inside assign_timeout.
            exclude = None
            last: Optional[BaseException] = None
            for attempt in range(5):
                ref, release, replica = router.assign_request(
                    DEPLOYMENT_NAME, "__call__",
                    ({"work": arrival.size},),
                    {REQUEST_ID_KWARG: arrival.rid},
                    timeout=assign_timeout, exclude=exclude)
                try:
                    ray_tpu.get(ref, timeout=request_timeout_s)
                    return
                except (rexc.ActorDiedError,
                        rexc.ActorUnavailableError) as e:
                    last = e
                    exclude = (exclude or set()) | {replica._id_hex}
                    time.sleep(0.3 * (attempt + 1))
                    router.force_refresh()
                finally:
                    release()
            raise last

        def classify(err: BaseException) -> str:
            if is_overload_error(err):
                return "shed"
            # GetTimeoutError (accepted but slow) subclasses
            # TimeoutError — it is a failure, not a shed; only the
            # router's assign timeout (bare TimeoutError) is admission
            # shedding
            if isinstance(err, TimeoutError) and \
                    not isinstance(err, rexc.GetTimeoutError):
                return "shed"
            return "failed"

        # ---- LLM workload: streaming sends, counted per token ----
        # every request opens a stream and consumes it chunk by chunk;
        # the SLO ledger gets (rid -> tokens received, first-token
        # time), and reconciliation joins those counts against the
        # engines' token ledgers. A broken stream is retried WHOLE
        # (fresh generation, same rid — one logical request) or fails
        # cleanly; a partially-read stream never counts as ok.
        import random as _random

        from ray_tpu.serve.exceptions import StreamBrokenError
        token_counts: Dict[str, int] = {}
        first_token_at: Dict[str, float] = {}
        prompt_lens: Dict[str, int] = {}
        tc_lock = threading.Lock()

        _SYS_PROMPT_TOKENS = 32  # 2 full pages at the engine's bs=16

        def _tenant_prefix(tenant: str) -> List[int]:
            # every tenant's requests share a deterministic "system
            # prompt": with Zipf-skewed tenancy the hot tenants' traffic
            # is exactly the shared-prefix shape the radix prefix cache
            # exists for (32 tokens = 2 full pages at block_size 16)
            rng = _random.Random(f"sys:{tenant}")
            return [rng.randrange(256)
                    for _ in range(_SYS_PROMPT_TOKENS)]

        def _llm_payload(arrival: Arrival) -> Dict[str, Any]:
            # heavy-tail prompt AND output lengths from the arrival's
            # bounded-Pareto size — deterministic per request id
            rng = _random.Random(f"llm:{arrival.rid}")
            plen = max(2, min(48, int(2 + arrival.size * 3)))
            ntok = max(1, min(40, int(1 + arrival.size * 2)))
            tokens = _tenant_prefix(arrival.tenant) + \
                [rng.randrange(256) for _ in range(plen)]
            with tc_lock:
                prompt_lens[arrival.rid] = len(tokens)
            return {"tokens": tokens, "max_new_tokens": ntok}

        def send_llm(arrival: Arrival):
            payload = _llm_payload(arrival)
            last: Optional[BaseException] = None
            for attempt in range(3):
                stream = router.open_stream(
                    DEPLOYMENT_NAME, payload, request_id=arrival.rid,
                    assign_timeout=assign_timeout)
                n, t_first = 0, None
                try:
                    for ch in stream:
                        if t_first is None and ch.get("tokens"):
                            t_first = time.time()
                        n += len(ch.get("tokens") or ())
                    with tc_lock:
                        token_counts[arrival.rid] = n
                        if t_first is not None:
                            first_token_at[arrival.rid] = t_first
                    return
                except StreamBrokenError as e:
                    last = e
                    time.sleep(0.3 * (attempt + 1))
                    router.force_refresh()
            raise last

        if is_llm:
            send = send_llm

        lg = OpenLoopRunner(schedule, send, classify,
                            max_workers=scenario.max_workers)
        delay = load_t0 - time.time()
        if delay > 0:
            time.sleep(delay)
        records = lg.run()
        action_thread.join(timeout=180.0)

        # ---- collect the server's story ----
        time.sleep(1.5)  # final task-event batch flushes
        routed = _live_replica_handles()
        replica_metrics_raw = _quiesce(routed)
        # ledgers come from EVERY alive replica (a draining old-version
        # replica is out of the route table but still holds its half of
        # the join), merged with the ledgers retired replicas flushed
        # to the KV; per replica, the larger snapshot wins (the ledger
        # only grows, and double-counting one replica would read as
        # duplicate completions)
        by_name: Dict[str, Dict[str, Any]] = {}
        replica_metrics: Dict[str, Dict[str, Any]] = {}
        for hex_id, handle in _all_alive_replica_handles().items():
            try:
                led = ray_tpu.get(handle.get_request_log.remote(),
                                  timeout=10.0)
                led["live"] = hex_id in routed
                by_name[led["replica"]] = led
                m = replica_metrics_raw.get(hex_id)
                if m is not None:
                    replica_metrics[led["replica"]] = m
            except Exception:
                logger.warning("gameday: replica %s ledger read failed",
                               hex_id[:8], exc_info=True)
        for led in store.load_flushed_ledgers():
            have = by_name.get(led.get("replica"))
            if have is None:
                led["live"] = False
                by_name[led["replica"]] = led
            elif len(led.get("records") or ()) > \
                    len(have.get("records") or ()):
                led["live"] = have["live"]
                by_name[led["replica"]] = led
        replica_ledgers = list(by_name.values())

        # LLM workload: collect every alive engine's metrics + token
        # ledger (counter-free RPC), merged with the ledgers retired
        # replicas flushed on drain — the server half of the per-token
        # join
        llm_ledgers: List[Dict[str, Any]] = []
        llm_metrics: Dict[str, Any] = {}
        if is_llm:
            for hex_id, handle in _all_alive_replica_handles().items():
                try:
                    st = ray_tpu.get(handle.get_llm_state.remote(),
                                     timeout=10.0)
                except Exception:
                    logger.debug("gameday: get_llm_state from replica "
                                 "%s failed (drained mid-grade?)",
                                 hex_id, exc_info=True)
                    continue
                if st:
                    llm_metrics[hex_id] = {
                        k: v for k, v in st.items()
                        if k not in ("token_ledger", "step_log",
                                     "request_log")}
                    llm_ledgers.append(
                        {"replica": hex_id,
                         "records": st.get("token_ledger") or []})
            llm_ledgers.extend(store.load_flushed_llm_ledgers())

        serve_metrics = _retry(lambda: serve.metrics() or None,
                               timeout=20.0, default={})
        task_now = _retry(_task_counts, timeout=15.0, default=None)
        task_delta = None
        if task_now is not None and task_base is not None:
            task_delta = {
                "finished": task_now["finished"] - task_base["finished"],
                "failed": task_now["failed"] - task_base["failed"],
                "dropped": task_now["dropped"],
                "events_dropped": task_now["events_dropped"],
            }
        prom_text = _scrape_metrics(dash_port)
        fired = chaos.read_log(chaos_log) if chaos_cfg else []
        # dedup: every controller incarnation replays the same
        # schedule, so repeated (site, op, n) entries are one fault
        seen, fired_unique = set(), []
        for r in fired:
            key = (r.get("site"), r.get("op"), r.get("n"))
            if key not in seen:
                seen.add(key)
                fired_unique.append({"site": r.get("site"),
                                     "op": r.get("op"),
                                     "n": r.get("n")})

        # distributed traces of the sampled admitted cohort: the
        # tracing plane must hold a complete span tree for every
        # request it claims to sample (reconcile C9) — and the tail's
        # traces feed the critical-path aggregation below
        from ray_tpu._private import tracing
        from ray_tpu.experimental.state import api as state_api
        ok_records = [r for r in records if r.outcome == "ok"]
        sampled = [r.rid for r in ok_records if tracing.sampled(r.rid)]
        trace_cap = int(os.environ.get("RTPU_GAMEDAY_TRACE_MAX", 500))
        if len(sampled) > trace_cap:
            logger.info("gameday: checking %d of %d sampled traces "
                        "(RTPU_GAMEDAY_TRACE_MAX)", trace_cap,
                        len(sampled))
            sampled = sampled[:trace_cap]
        traces: Dict[str, Any] = {}
        traces_lossy = False

        def fetch_traces(rids):
            nonlocal traces_lossy
            for rid in rids:
                try:
                    doc = state_api.get_trace(rid)
                except Exception:
                    logger.debug("gameday: trace fetch for %s failed",
                                 rid, exc_info=True)
                    traces_lossy = True
                    continue
                if doc.get("dropped_spans"):
                    traces_lossy = True
                if doc.get("spans"):
                    traces[rid] = doc["spans"]

        fetch_traces(sampled)
        # one settle pass: the last requests' spans may still be inside
        # a 0.5 s flush tick (or a draining replica's shutdown flush)
        from ray_tpu._private.tracing import tree_complete
        laggards = [rid for rid in sampled
                    if rid not in traces
                    or not tree_complete(traces[rid])[0]]
        if laggards:
            time.sleep(1.2)
            fetch_traces(laggards)

        server_view.update({
            "replica_ledgers": replica_ledgers,
            "replica_metrics": replica_metrics,
            "serve_metrics": serve_metrics,
            "task_delta": task_delta,
            "prometheus": ({"serve": _parse_serve_gauges(prom_text)}
                           if prom_text is not None else {}),
            "chaos_fired": fired_unique,
            "traces": traces,
            "traces_sampled": sampled,
            "traces_lossy": traces_lossy,
        })
        if is_llm:
            with tc_lock:
                server_view["llm_client_tokens"] = dict(token_counts)
                server_view["llm_client_prompts"] = dict(prompt_lens)
            server_view["llm_ledgers"] = llm_ledgers
            server_view["llm_metrics"] = llm_metrics

        # ---- grade + publish ----
        # split client sheds: a replica-shed has a server ledger record
        # to join against; an admission-shed (router assign timeout —
        # every replica saturated) never reached a replica, so the
        # reconciler checks its ABSENCE from server records instead
        client_ledger = ledger(records)
        unplaced = {r.rid for r in records
                    if r.outcome == "shed" and r.error
                    and r.error.startswith("TimeoutError")}
        client_ledger["unplaced"] = sorted(unplaced)
        client_ledger["shed"] = [rid for rid in client_ledger["shed"]
                                 if rid not in unplaced]
        recon = reconcile(scenario, client_ledger, server_view)
        report = build_report(
            records, scenario=scenario.name, seed=scenario.seed,
            availability_target=scenario.slo["availability_target"],
            latency_target_ms=scenario.slo.get("latency_target_ms"),
            count_shed_as_bad=scenario.slo.get("count_shed_as_bad",
                                               False),
            duration_s=schedule.duration_s)
        report["scale"] = scale
        report["setup_s"] = round(load_t0 - t_setup, 2)
        if is_llm:
            # per-token SLO accounting: throughput + open-loop TTFT
            # (first token time measured against the SCHEDULED arrival
            # — a stalled engine charges every token it delayed)
            with tc_lock:
                tok_total = sum(token_counts.values())
                ttfts = sorted(
                    max(0.0, t1 - (load_t0 + a.t))
                    for a in schedule.arrivals
                    for t1 in (first_token_at.get(a.rid),)
                    if t1 is not None)

            def _q(vals, frac):
                return (round(vals[min(len(vals) - 1,
                                       int(frac * len(vals)))] * 1e3, 3)
                        if vals else 0.0)

            report["llm"] = {
                "tokens_total": tok_total,
                "tokens_per_s": round(
                    tok_total / max(schedule.duration_s, 1e-9), 3),
                "requests_with_tokens": len(token_counts),
                "ttft_p50_ms": _q(ttfts, 0.50),
                "ttft_p99_ms": _q(ttfts, 0.99),
            }
        report["actions"] = actions
        report["action_errors"] = action_errors
        report["chaos_fired"] = fired_unique
        report["reconciliation"] = recon
        # where does the tail spend its time? aggregate critical path
        # over the p99 cohort's traces (ISSUE 13: latency attribution
        # before optimization)
        p99_ms = report.get("overall", {}).get("p99_ms") or 0.0
        cohort = [traces[r.rid] for r in ok_records
                  if r.rid in traces and r.latency_s * 1e3 >= p99_ms]
        report["critical_path_p99"] = \
            tracing.aggregate_critical_path(cohort[:50])
        burn = report["slo"]["availability_burn"]
        report["passed"] = (recon["ok"] and not action_errors
                            and 0.0 <= burn <= 1.0)
        report["ts"] = time.time()
        if publish:
            publish_ok = store.publish_report(report)
            if publish_ok and dash_port is not None:
                # the publish itself is under test: the SLO gauges must
                # round-trip through the KV into /metrics
                text = _scrape_metrics(dash_port)
                publish_ok = bool(text) and "ray_tpu_slo_" in text
            report["slo_gauges_published"] = bool(publish_ok)
        return GameDayResult(scenario, records, report, server_view)
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        from ray_tpu._private import tracing as _tracing
        _tracing.refresh()
        chaos.clear()
