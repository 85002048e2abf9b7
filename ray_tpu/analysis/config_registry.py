"""The authoritative ``RTPU_*`` configuration registry.

Every environment variable the runtime reads is declared here —
rtpulint RTPU005 fails on any ``RTPU_*`` read missing from this table
(with near-miss typo detection), and the round-trip test fails on any
entry the tree no longer reads, so the registry can't drift in either
direction. ``python -m ray_tpu.analysis --gen-docs`` renders it into
docs/CONFIGURATION.md.

Two sources compose :data:`CONFIG_VARS`:

* :data:`STATIC_VARS` — variables read directly by name somewhere in
  ``ray_tpu/`` (or by the test harness, subsystem ``testing``).
* the ``SystemConfig`` dataclass (``ray_tpu/common/config.py``), whose
  every field is overridable as ``RTPU_<FIELD_UPPER>`` via
  ``apply_env_overrides()`` — those names are derived programmatically
  so a new config field is registered the moment it's declared.

Entry shape: ``{"subsystem": str, "default": str, "description": str}``.
"""

from __future__ import annotations

from dataclasses import fields as _dc_fields
from typing import Dict

__all__ = ["CONFIG_VARS", "STATIC_VARS", "system_config_vars"]


def _e(subsystem: str, default: str, description: str) -> Dict[str, str]:
    return {"subsystem": subsystem, "default": default,
            "description": description}


STATIC_VARS: Dict[str, Dict[str, str]] = {
    # ---------------------------------------------------- bootstrap/core
    "RTPU_ADDRESS": _e(
        "core", "(unset)",
        "GCS address to connect to (`ray_tpu.init()` default, the CLI, "
        "job submission and the autoscaler all honor it)."),
    "RTPU_SESSION_DIR": _e(
        "core", "(per-session tmpdir)",
        "Per-session scratch directory: sockets, logs, object-store "
        "files, profiles."),
    "RTPU_GCS_ADDRESS": _e(
        "core", "(set by node launcher)",
        "GCS endpoint handed to spawned raylets/workers."),
    "RTPU_GCS_PORT": _e(
        "core", "0 (auto)",
        "Listen port for a standalone GCS process (`gcs_main`)."),
    "RTPU_GCS_STORE_DIR": _e(
        "core", "(unset = in-memory)",
        "Directory for the GCS persistent store (journal survives a "
        "GCS restart)."),
    "RTPU_RAYLET_ADDRESS": _e(
        "core", "(set by raylet)",
        "Raylet RPC address injected into spawned workers."),
    "RTPU_STORE_PATH": _e(
        "core", "(set by raylet)",
        "Plasma object-store socket path injected into workers."),
    "RTPU_NODE_ID": _e(
        "core", "(generated)",
        "Node id of the hosting raylet (workers, tracing and the TPU "
        "profiler tag records with it)."),
    "RTPU_WORKER_ID": _e(
        "core", "(generated)",
        "Worker id assigned by the spawning raylet."),
    "RTPU_IS_HEAD": _e(
        "core", "(set by launcher)",
        "Marks the raylet as the head node (hosts GCS-adjacent "
        "services; chaos `head` filters key off it)."),
    "RTPU_LABELS": _e(
        "core", "{}",
        "JSON dict of node labels for label-aware scheduling."),
    "RTPU_RESOURCES": _e(
        "core", "{}",
        "JSON dict of custom resources the raylet registers."),
    "RTPU_NUM_TPUS": _e(
        "core", "(autodetect)",
        "Overrides the TPU chip count the raylet advertises."),
    "RTPU_OBJECT_STORE_BYTES": _e(
        "core", "(SystemConfig default)",
        "Object-store capacity for a launched raylet."),
    "RTPU_SYSTEM_CONFIG": _e(
        "core", "(unset)",
        "JSON `SystemConfig` overrides distributed cluster-wide from "
        "the head (see the SystemConfig table below for per-field "
        "`RTPU_*` overrides)."),
    "RTPU_LOG_LEVEL": _e(
        "core", "INFO (WARNING in workers)",
        "Python logging level for GCS/raylet/worker processes."),
    "RTPU_JOB_ID": _e(
        "core", "(generated)",
        "Job id tag propagated to tasks submitted through the job "
        "API."),
    # --------------------------------------------------------- wire/rpc
    "RTPU_NATIVE_RPC": _e(
        "rpc", "1",
        "Use the native epoll frame pump + worker direct-execution "
        "lane (schema 1.7); 0 falls back to the asyncio wire."),
    "RTPU_NATIVE_SCHED": _e(
        "rpc", "1",
        "Use the native scheduling core (schedcore); 0 = pure-Python "
        "scheduler."),
    "RTPU_LOOP_STALL_S": _e(
        "rpc", "0 (off)",
        "Event-loop stall detector threshold in seconds: a loop "
        "blocked longer logs a stall with the offending stack."),
    "RTPU_VALIDATE_WIRE": _e(
        "rpc", "0",
        "Validate every inbound RPC payload against the schema table "
        "(tests enable it so schema drift fails immediately)."),
    # ------------------------------------------------------------- netx
    "RTPU_NETX": _e(
        "netx", "1",
        "Enable the cross-node transport plane (schema 1.8): TCP "
        "endpoint advertisement, direct-lane actor/task calls off-box, "
        "and chunk-pipelined object pulls; 0 keeps everything on the "
        "unix/asyncio paths."),
    "RTPU_NODE_IP": _e(
        "netx", "(resolved hostname)",
        "IP this node advertises for its TCP endpoints (raylet, direct "
        "lane, dag channels). Falls back to the resolved non-loopback "
        "hostname, then 127.0.0.1."),
    "RTPU_NET_FORCE_TCP": _e(
        "netx", "0",
        "Prefer host:port endpoints even for same-host peers (the "
        "multi-\"host\" test harness uses it to exercise the TCP lanes "
        "on one machine)."),
    "RTPU_NET_KEEPALIVE_S": _e(
        "netx", "10",
        "Quiet-connection ping interval for the netx pool; a peer "
        "missing ~3 windows is declared dead and redialed with "
        "backoff."),
    "RTPU_NET_IDLE_S": _e(
        "netx", "60",
        "Idle cross-node connections are reaped from the netx pool "
        "after this many seconds."),
    "RTPU_NET_RECONNECT_S": _e(
        "netx", "0.2",
        "Initial redial backoff after a netx connection failure; "
        "doubles per failure up to a 5 s cap."),
    "RTPU_NET_POOL_MAX": _e(
        "netx", "16",
        "Soft cap on pooled netx connections per process; LRU-idle "
        "peers are evicted beyond it."),
    "RTPU_NET_STALL_S": _e(
        "netx", "10",
        "A chunked object pull making no progress for this long is "
        "cancelled and resumed from its contiguous high-water mark "
        "(fresh connection, remaining attempts)."),
    # ------------------------------------------------------- observability
    "RTPU_CPROFILE_DIR": _e(
        "observability", "(unset = off)",
        "Write per-process cProfile dumps here on exit."),
    "RTPU_CPROFILE_PROCS": _e(
        "observability", "worker/raylet",
        "Comma list of roles to profile when RTPU_CPROFILE_DIR is "
        "set."),
    "RTPU_TASK_EVENTS_BUFFER": _e(
        "observability", "4096",
        "Per-worker task-event ring capacity before drops (drop "
        "counters ride the flush)."),
    "RTPU_TASK_EVENTS_FLUSH_S": _e(
        "observability", "1.0",
        "Task-event batch flush interval to the GCS state engine."),
    "RTPU_TASK_TABLE_MAX": _e(
        "observability", "10000",
        "Bounded GCS task-table size (oldest finished evicted "
        "first)."),
    "RTPU_ACTOR_TASK_EVENTS": _e(
        "observability", "0",
        "Extend the task-event pipeline to actor method calls so "
        "serve request flow reconciles per request (game days enable "
        "it)."),
    "RTPU_TRACING": _e(
        "observability", "1",
        "Master switch for distributed tracing spans."),
    "RTPU_TRACE_SAMPLE": _e(
        "observability", "0.1",
        "Head-sampling probability for traces (slow/failed requests "
        "are always kept)."),
    "RTPU_TRACE_SLOW_S": _e(
        "observability", "1.0",
        "Latency threshold above which a trace is always kept "
        "regardless of sampling."),
    "RTPU_TRACE_BUFFER": _e(
        "observability", "2048",
        "Per-process span ring capacity before drops."),
    "RTPU_TRACE_FLUSH_S": _e(
        "observability", "1.0",
        "Span batch flush interval to the GCS trace table."),
    "RTPU_TRACE_TABLE_MAX": _e(
        "observability", "50000",
        "Bounded GCS trace-table span capacity."),
    "RTPU_TRACE_MAX_SPANS": _e(
        "observability", "512",
        "Per-trace span cap in the GCS trace table."),
    "RTPU_METRICS_SYNC": _e(
        "observability", "0",
        "1 = ship every metric point as its own actor call instead of "
        "the batched background flusher (tests that assert "
        "immediately)."),
    "RTPU_METRICS_FLUSH_S": _e(
        "observability", "1.0",
        "Metrics local-table flush interval (one record_batch call "
        "per interval)."),
    "RTPU_USAGE_STATS_ENABLED": _e(
        "observability", "0",
        "Opt-in anonymous usage stats."),
    # ------------------------------------------------------------- chaos
    "RTPU_CHAOS": _e(
        "chaos", "(unset = off)",
        "Chaos engine config: a bare integer seed or a JSON object "
        "with `seed`/`schedule`/`p`/`delay_s` (docs/"
        "FAULT_TOLERANCE.md); inherited by every spawned process."),
    "RTPU_CHAOS_LOG": _e(
        "chaos", "(unset)",
        "JSONL path where every fired fault is appended (replay "
        "comparisons project the `ts` field away)."),
    # ------------------------------------------------------------- serve
    "RTPU_SERVE_ROUTING": _e(
        "serve", "p2c",
        "Router policy: `p2c` load-aware power-of-two-choices or "
        "`local` in-flight only."),
    "RTPU_SERVE_LOAD_STALENESS_S": _e(
        "serve", "5.0",
        "Max age of replica load reports before the router falls back "
        "to local in-flight counts."),
    "RTPU_SERVE_OVERLOAD_RETRIES": _e(
        "serve", "2",
        "How many other replicas the proxy tries after a shed "
        "(ReplicaOverloadedError) before returning 503."),
    "RTPU_SERVE_MAX_QUEUED": _e(
        "serve", "(per-deployment)",
        "Default bounded ingress queue per replica on top of "
        "max_concurrent_queries; overflow sheds retriably."),
    "RTPU_SERVE_REQUEST_LOG_MAX": _e(
        "serve", "10000",
        "Per-replica request-ledger capacity (game-day reconcile reads "
        "it)."),
    "RTPU_SERVE_ADAPTIVE_BATCH": _e(
        "serve", "1",
        "AIMD adaptive micro-batch wait window (0 = fixed "
        "batch_wait_timeout_s)."),
    "RTPU_SERVE_BATCH_SUBMIT_TIMEOUT_S": _e(
        "serve", "30.0",
        "Watchdog for a wedged batch function: pending items error "
        "instead of waiting forever."),
    "RTPU_SERVE_GRACEFUL_SHUTDOWN_S": _e(
        "serve", "10.0",
        "Drain window for replicas on shutdown/rolling update before "
        "force-kill."),
    "RTPU_SERVE_HEALTH_FAILURES": _e(
        "serve", "3",
        "Consecutive health-check failures before the controller "
        "replaces a replica."),
    "RTPU_SERVE_HEALTH_TIMEOUT_S": _e(
        "serve", "5.0",
        "Per-probe health-check timeout."),
    "RTPU_SERVE_MAX_SURGE": _e(
        "serve", "1",
        "Extra replicas a rolling update may run beyond target while "
        "a wave's new replicas come up (k8s maxSurge analogue)."),
    "RTPU_SERVE_PROXY_ASSIGN_TIMEOUT_S": _e(
        "serve", "15.0",
        "Proxy-side cap on waiting for a replica assignment before "
        "504."),
    # -------------------------------------------------------------- data
    "RTPU_DATA_STREAMING": _e(
        "data", "1",
        "Streaming data-plane executor (0 = bulk materialization "
        "fallback)."),
    "RTPU_DATA_MAX_INFLIGHT_TASKS": _e(
        "data", "(cores-derived)",
        "Streaming executor cap on concurrently in-flight block "
        "tasks."),
    "RTPU_DATA_MAX_BUFFERED_BYTES": _e(
        "data", "(store-derived)",
        "Streaming executor backpressure threshold on buffered block "
        "bytes."),
    "RTPU_DATA_STORE_HIGH_WATERMARK": _e(
        "data", "0.8",
        "Plasma occupancy fraction above which the streaming executor "
        "pauses admission."),
    "RTPU_PUSH_BASED_SHUFFLE": _e(
        "data", "0",
        "Push-based distributed shuffle for AllToAll stages."),
    # ----------------------------------------------------- train/tune/ckpt
    "RTPU_RESULTS_DIR": _e(
        "train", "~/ray_tpu_results",
        "Root directory for trainer/tuner run results and "
        "checkpoints."),
    "RTPU_TUNE_DISK_CKPT": _e(
        "tune", "1",
        "Persist trial checkpoints to disk (0 = in-memory only)."),
    "RTPU_TUNE_SNAPSHOT_PERIOD": _e(
        "tune", "10",
        "Experiment-state snapshot period in seconds."),
    "RTPU_CKPT_ASYNC": _e(
        "checkpoint", "1",
        "Async checkpointer: commit in the background, overlapping "
        "with the next step (0 = synchronous)."),
    "RTPU_CKPT_FSYNC": _e(
        "checkpoint", "1",
        "fsync checkpoint files + dirs before commit (0 trades "
        "durability for speed in tests)."),
    "RTPU_CKPT_VERIFY": _e(
        "checkpoint", "0",
        "Re-read and verify every checkpoint after commit."),
    # ------------------------------------------------------------ gameday
    "RTPU_GAMEDAY_TRACE_MAX": _e(
        "gameday", "(scenario default)",
        "Trace-table capacity override a game-day run configures on "
        "the state engine."),
    # ---------------------------------------------------------------- ops
    "RTPU_ATTN_EXACT": _e(
        "ops", "0",
        "Force the streaming flash-attention kernels (exact "
        "running-max softmax) where logits may exceed the whole-kv "
        "path's static cap; read at trace time."),
    "RTPU_ATTN_DEBUG": _e(
        "ops", "0",
        "Interpreter-mode Pallas attention kernels for debugging."),
    # ------------------------------------------------------------ storage
    "RTPU_STORAGE": _e(
        "storage", "(unset)",
        "Default cluster storage URI (`ray_tpu.init(storage=...)` "
        "fallback; raylets mount it for spill)."),
    "RTPU_WORKFLOW_STORAGE": _e(
        "storage", "(RTPU_STORAGE-derived)",
        "Workflow-engine storage URI override."),
    # ----------------------------------------------------------- runtime_env
    "RTPU_CONTAINER_RUNTIME": _e(
        "runtime_env", "(autodetect)",
        "Container runtime binary for containerized runtime_envs "
        "(podman/docker)."),
    # ------------------------------------------------------------- testing
    "RTPU_SCALE_FULL": _e(
        "testing", "0",
        "Run the scale suite at its full envelope instead of the "
        "CI-sized one."),
    "RTPU_TEST_FLAG": _e(
        "testing", "(unset)",
        "Scratch variable runtime_env tests round-trip through "
        "workers."),
    "RTPU_RAN_IN_CONTAINER": _e(
        "testing", "(unset)",
        "Sentinel the container-runtime_env test's fake runtime "
        "exports."),
    "RTPU_FAKE_CONDA_ENV": _e(
        "testing", "(unset)",
        "Sentinel the conda-runtime_env test's fake activate script "
        "exports."),
    "RTPU_ALLOW_MISSING_DEPS": _e(
        "testing", "0",
        "Let the test session run with optional deps missing instead "
        "of failing collection."),
}


def system_config_vars() -> Dict[str, Dict[str, str]]:
    """``RTPU_<FIELD>`` overrides derived from the SystemConfig
    dataclass — every field is env-overridable via
    ``apply_env_overrides()``."""
    from ray_tpu.common.config import SystemConfig
    out: Dict[str, Dict[str, str]] = {}
    for f in _dc_fields(SystemConfig):
        name = f"RTPU_{f.name.upper()}"
        out[name] = _e("system-config", repr(f.default),
                       f"Overrides `SystemConfig.{f.name}` "
                       f"(ray_tpu/common/config.py) cluster-wide.")
    return out


def _build() -> Dict[str, Dict[str, str]]:
    out = system_config_vars()
    out.update(STATIC_VARS)  # hand-written entries win on collision
    return out


CONFIG_VARS: Dict[str, Dict[str, str]] = _build()
