"""LLM serving on a local cluster (docs/LLM_SERVING.md; ROADMAP item
1): token streaming end to end (handle iterator + HTTP SSE, first token
BEFORE generation completes), KV-aware graceful drain through a rolling
update, LLM gauges, trace phase spans; and in subprocesses a mid-stream
replica kill (clean failure or retry, never silent truncation) and the
llm-chat game day with per-token reconciliation. Tier-1, CPU-only."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm import LLMServer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- cluster tests


@pytest.fixture(scope="module")
def llm_cluster():
    ctx = ray_tpu.init(num_cpus=8, ignore_reinit_error=True,
                       object_store_memory=128 * 1024 * 1024)
    deps = []

    def deploy(name, http_port=None, route=None, **kw):
        llm_kw = {"model": kw.pop("model", "toy"),
                  "model_config": kw.pop("model_config", {}),
                  "engine_config": kw.pop("engine_config",
                                          {"num_blocks": 128,
                                           "block_size": 8,
                                           "max_seq_len": 256})}
        dep = serve.deployment(name=name, **kw)(LLMServer)
        h = serve.run(dep.bind(llm_kw["model"],
                               llm_kw["model_config"],
                               llm_kw["engine_config"]),
                      name=name, route_prefix=route or f"/{name}",
                      http_port=http_port)
        deps.append(name)
        return h

    yield deploy
    try:
        serve.shutdown()
    finally:
        # also when the cluster is gone and serve.shutdown() raises: a
        # driver left connected is what the next file's init() would get
        ray_tpu.shutdown()


def test_streaming_handle_end_to_end(llm_cluster):
    """Handle streaming delivers tokens incrementally: the first chunk
    reaches the client before the last token was made (it is not
    ``done``, holds fewer than all ten, and lands well before the
    stream is done), and the final token list equals the unary result.
    How many chunks the ten come in is the client's poller's doing."""
    h = llm_cluster("llmh", num_replicas=1, max_concurrent_queries=16,
                    model_config={"per_seq_delay_s": 0.02})
    payload = {"prompt": "the quick brown fox", "max_new_tokens": 10}
    unary = ray_tpu.get(h.remote(payload), timeout=60.0)
    assert unary["n_tokens"] == 10

    chunks, stamps = [], []
    for ch in h.stream(payload):
        chunks.append(ch)
        stamps.append(time.time())
    toks = [t for c in chunks for t in c["tokens"]]
    assert toks == unary["tokens"]
    assert chunks[-1]["done"] and chunks[-1]["finish_reason"] == "length"
    assert not chunks[0]["done"] and len(chunks[0]["tokens"]) < 10, \
        "tokens must stream, not arrive in bulk"
    # first chunk lands well before the stream completes
    assert stamps[0] < stamps[-1] - 0.05


def test_streaming_http_sse_first_token_early(llm_cluster):
    """SSE through the proxy: events arrive incrementally on the
    socket (first data event before [DONE] by a real margin),
    X-Request-Id echoes, token payloads match the unary path."""
    import http.client
    llm_cluster("llmsse", http_port=8917, num_replicas=1,
                max_concurrent_queries=16,
                model_config={"per_seq_delay_s": 0.02})
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote(), timeout=240)

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    body = json.dumps({"prompt": "stream me", "max_new_tokens": 10,
                       "stream": True})
    conn.request("POST", "/llmsse", body,
                 {"Content-Type": "application/json",
                  "X-Request-Id": "sse-e2e-1"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    assert resp.getheader("X-Request-Id") == "sse-e2e-1"
    events, stamps = [], []
    while True:
        line = resp.fp.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        if line[6:] == b"[DONE]":
            stamps.append(("done", time.time()))
            break
        events.append(json.loads(line[6:]))
        stamps.append(("data", time.time()))
    conn.close()
    toks = [t for e in events for t in e.get("tokens", [])]
    assert len(toks) == 10
    assert events[-1].get("done") and not events[-1].get("error")
    data_times = [t for kind, t in stamps if kind == "data"]
    done_time = dict(stamps[-1:])  # ("done", t)
    assert len(events) >= 3, "SSE must deliver multiple events"
    # the FIRST token event beat the end of generation by a margin
    assert data_times[0] < done_time["done"] - 0.05

    # unary through the same route still works (no stream flag)
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llmsse",
        json.dumps({"prompt": "stream me",
                    "max_new_tokens": 10}).encode(),
        {"Content-Type": "application/json"})
    u = json.loads(urllib.request.urlopen(req, timeout=60).read())
    assert u["tokens"] == toks


def test_rolling_update_drains_kv_zero_dropped_streams(llm_cluster):
    """KV-aware graceful drain (satellite): streams in flight when a
    rolling update lands must finish on the draining replicas — full
    token counts, zero broken streams — while the new version takes
    over fresh traffic."""
    name = "llmroll"
    h = llm_cluster(name, num_replicas=2, max_concurrent_queries=32,
                    model_config={"per_seq_delay_s": 0.03},
                    user_config={"v": 1},
                    graceful_shutdown_timeout_s=60.0)
    n_tok = 60   # ~2s+ of decoding: the update lands mid-stream
    streams = [h.stream({"tokens": [i + 1, i + 2, i + 3],
                         "max_new_tokens": n_tok},
                        request_id=f"roll-{i}") for i in range(4)]
    results: dict = {}
    errors: list = []

    def consume(i, st):
        toks = []
        try:
            for ch in st:
                toks += ch["tokens"]
            results[i] = (toks, st.finish_reason)
        except Exception as e:  # noqa: BLE001 — the assertion target
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=consume, args=(i, st))
               for i, st in enumerate(streams)]
    for t in threads:
        t.start()
    time.sleep(0.3)  # streams decoding; now redeploy a new version
    dep = serve.deployment(name=name, num_replicas=2,
                           max_concurrent_queries=32,
                           user_config={"v": 2},
                           graceful_shutdown_timeout_s=60.0)(LLMServer)
    serve.run(dep.bind("toy", {"per_seq_delay_s": 0.03},
                       {"num_blocks": 128, "block_size": 8,
                        "max_seq_len": 256}),
              name=name, route_prefix=f"/{name}", http_port=None,
              _blocking_timeout=120.0)
    for t in threads:
        t.join(timeout=120.0)
    assert not errors, errors
    assert len(results) == 4
    for i, (toks, reason) in results.items():
        assert len(toks) == n_tok, \
            f"stream {i} truncated: {len(toks)}/{n_tok}"
        assert reason == "length"
    # and the new version serves fresh requests
    out = ray_tpu.get(h.remote({"tokens": [9, 9], "max_new_tokens": 2}),
                      timeout=60.0)
    assert out["n_tokens"] == 2


def test_serve_metrics_and_prometheus_llm_gauges(llm_cluster):
    """Autoscaler-signal satellite: the controller aggregates engine
    telemetry per deployment and /metrics exports the
    ``ray_tpu_serve_llm_*`` gauges."""
    import urllib.request

    from ray_tpu.dashboard.dashboard import start_dashboard
    h = llm_cluster("llmmet", num_replicas=1, max_concurrent_queries=8)
    for i in range(3):
        ray_tpu.get(h.remote({"tokens": [1, 2, 3, 4],
                              "max_new_tokens": 6}), timeout=60.0)

    def llm_agg():
        m = serve.metrics().get("llmmet") or {}
        return m.get("llm")

    deadline = time.time() + 15.0
    agg = None
    while time.time() < deadline:
        agg = llm_agg()
        if agg and agg.get("generated_tokens_total", 0) >= 18:
            break
        time.sleep(0.5)
    assert agg, "controller never aggregated llm telemetry"
    assert agg["generated_tokens_total"] >= 18
    assert agg["kv_blocks_total"] > 0
    assert "tokens_per_s" in agg and "kv_occupancy" in agg

    port = start_dashboard(port=18475)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=15).read().decode()
    for gauge in ("ray_tpu_serve_llm_tokens_per_s",
                  "ray_tpu_serve_llm_kv_occupancy",
                  "ray_tpu_serve_llm_running_sequences",
                  "ray_tpu_serve_llm_waiting_sequences",
                  "ray_tpu_serve_llm_generated_tokens_total"):
        assert f'{gauge}{{deployment="llmmet"}}' in text, gauge


def test_trace_spans_cover_prefill_decode_kv(llm_cluster):
    """Tracing satellite: a sampled request's trace decomposes into
    the engine's phase spans (prefill + decode at minimum; kv_alloc
    and queue appear when they take measurable time), all parented
    into the request's span tree."""
    from ray_tpu._private import tracing
    from ray_tpu.experimental.state import api as state_api
    h = llm_cluster("llmtr", num_replicas=1, max_concurrent_queries=8,
                    model_config={"per_seq_delay_s": 0.005})
    rid = "trace-llm-1"
    st = h.stream({"tokens": [3, 1, 4, 1, 5], "max_new_tokens": 8},
                  request_id=rid)
    toks = [t for ch in st for t in ch["tokens"]]
    assert len(toks) == 8

    spans = None
    deadline = time.time() + 10.0
    while time.time() < deadline:
        doc = state_api.get_trace(rid)
        spans = doc.get("spans") or []
        names = {s["name"].split(":")[0] for s in spans}
        if {"llm.prefill", "llm.decode"} <= names:
            break
        time.sleep(0.5)
    names = {s["name"].split(":")[0] for s in spans}
    assert {"llm.prefill", "llm.decode"} <= names, sorted(names)
    ok, detail = tracing.tree_complete(spans)
    assert ok, detail
    decode = next(s for s in spans
                  if s["name"].startswith("llm.decode"))
    assert decode["attrs"]["tokens"] == 8
    assert decode["phase"] == "execute"


# -------------------------------------------- subprocess isolation tests


def _run_script(script, extra_env=None, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RTPU_PRESTART_WORKERS="0")
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True,
                          timeout=timeout, cwd=REPO_ROOT)


def test_mid_stream_replica_kill_is_clean_never_truncated():
    """Chaos satellite: a replica SIGKILLed mid-stream (seeded chaos,
    serve.replica.request op=kill) must surface as StreamBrokenError
    (or a retried-whole, full-length stream) — never a silently short
    token list presented as success."""
    script = r"""
import json, sys, time
import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.exceptions import StreamBrokenError
from ray_tpu.serve.llm import LLMServer

ray_tpu.init(num_cpus=4, object_store_memory=128*1024*1024,
             _system_config={"prestart_workers": False})
dep = serve.deployment(name="llmkill", num_replicas=1,
                       max_concurrent_queries=16)(LLMServer)
h = serve.run(dep.bind("toy", {"per_seq_delay_s": 0.03},
                       {"num_blocks": 128, "block_size": 8,
                        "max_seq_len": 256}),
              http_port=None, _blocking_timeout=120.0)
n_tok = 50
verdict = None
try:
    st = h.stream({"tokens": [1, 2, 3], "max_new_tokens": n_tok},
                  request_id="kill-1")
    toks = []
    for ch in st:   # the poll that trips the chaos counter kills the
        toks += ch["tokens"]  # replica under us
    # stream completed: only acceptable at FULL length
    verdict = {"outcome": "complete", "n": len(toks), "want": n_tok}
except StreamBrokenError as e:
    verdict = {"outcome": "broken", "tokens_so_far": e.tokens_so_far}
except Exception as e:
    verdict = {"outcome": "other", "error": repr(e)}
print("VERDICT=" + json.dumps(verdict))
serve.shutdown(); ray_tpu.shutdown()
"""
    # the replica dies at its 8th accepted request: the open + a few
    # polls land first, then a poll hits the counter mid-generation
    chaos = {"seed": 11, "schedule": [
        {"site": "serve.replica.request", "op": "kill", "at": 8,
         "method": "llmkill", "proc": "worker"}]}
    r = _run_script(script, {"RTPU_CHAOS": json.dumps(chaos)})
    assert r.returncode == 0, r.stdout + r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("VERDICT=")]
    assert line, r.stdout + r.stderr
    v = json.loads(line[0][len("VERDICT="):])
    if v["outcome"] == "complete":
        assert v["n"] == v["want"], f"silent truncation: {v}"
    else:
        assert v["outcome"] == "broken", v


def test_llm_chat_gameday_reconciles_per_token():
    """The llm-chat game day (satellite): heavy-tail streaming load +
    a rolling update, graded outside-in — zero failed requests and an
    exact per-token client/engine reconciliation."""
    script = r"""
import json
from ray_tpu.gameday.runner import run_scenario
from ray_tpu.gameday.scenario import load_scenario
res = run_scenario(load_scenario("llm-chat"), scale=0.4,
                   dashboard_port=18476)
out = {
    "passed": res.passed,
    "failed": res.report["overall"]["failed"],
    "admitted": res.report["overall"]["admitted"],
    "llm": res.report.get("llm"),
    "checks": {c["name"]: c["ok"]
               for c in res.reconciliation.get("checks", [])},
    "details": [c for c in res.reconciliation.get("checks", [])
                if not c["ok"]],
}
print("GAMEDAY=" + json.dumps(out))
"""
    r = _run_script(script, timeout=300)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("GAMEDAY=")]
    assert line, r.stdout + r.stderr
    out = json.loads(line[0][len("GAMEDAY="):])
    assert out["failed"] == 0, out
    assert out["admitted"] > 30, out
    assert out["checks"].get("llm-tokens") is True, out["details"]
    assert out["passed"], out["details"]
    assert out["llm"]["tokens_total"] > 100, out["llm"]
