"""System config registry, env-var overridable.

Equivalent in role to the reference's RAY_CONFIG system
(reference: src/ray/common/ray_config_def.h — 184 entries, each overridable by
``RAY_<name>`` env var or ``ray.init(_system_config=...)``). Here every entry is
declared once with a type and default, overridable by ``RTPU_<NAME>`` env vars
or ``ray_tpu.init(_system_config={...})``; the head process snapshots the
resolved config and distributes it to every worker via the control-plane
handshake so all processes agree.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any, Dict


def _env(name: str, typ, default):
    raw = os.environ.get(f"RTPU_{name.upper()}")
    if raw is None:
        return default
    if typ is bool:
        return raw.lower() in ("1", "true", "yes")
    return typ(raw)


@dataclass
class SystemConfig:
    # ---- object store ----
    object_store_memory_bytes: int = 2 * 1024**3
    # objects smaller than this are inlined in the in-process memory store and
    # carried through the control plane rather than the shm store (analogue of
    # the reference's max_direct_call_object_size, ray_config_def.h)
    max_inline_object_size: int = 100 * 1024
    object_spilling_threshold: float = 0.8
    object_store_fallback_dir: str = ""
    # JSON spec for the spill backend (reference: object_spilling_config
    # in ray_config_def.h + _private/external_storage.py): e.g.
    # {"type": "smart_open", "params": {"uri_prefix": "s3://bkt/spill"}}
    object_spilling_config: str = ""
    # cap on in-flight inbound pull bytes as a fraction of store
    # capacity (reference: pull_manager.cc admission under pressure)
    pull_admission_fraction: float = 0.5
    # ---- scheduler ----
    scheduler_spread_threshold: float = 0.5
    worker_lease_timeout_s: float = 30.0
    max_pending_lease_requests_per_key: int = 10
    # ---- workers ----
    num_workers_soft_limit: int = -1  # -1: num_cpus
    idle_worker_kill_s: float = 300.0
    worker_start_timeout_s: float = 60.0
    # how long an executing task waits for an ObjectRef argument before
    # erroring (a freed/lost arg must not wedge the executor forever)
    arg_fetch_timeout_s: float = 300.0
    # max concurrent outbound object-pull streams a node serves for
    # LARGE objects; the surplus gets "busy" and retries against the
    # growing source set (tree broadcast — see raylet.handle_pull_object)
    object_serve_concurrency: int = 3
    object_serve_tree_min_bytes: int = 256 * 1024 * 1024
    prestart_workers: bool = True
    # ---- memory monitor / OOM protection (reference:
    # src/ray/common/memory_monitor.h + raylet/worker_killing_policy.h) ----
    memory_monitor_enabled: bool = True
    memory_usage_threshold: float = 0.95
    memory_monitor_refresh_ms: int = 500
    # ---- fault tolerance ----
    task_max_retries_default: int = 3
    actor_max_restarts_default: int = 0
    lineage_max_bytes: int = 1024**3
    health_check_period_s: float = 1.0
    # Death window. The reference's GCS declares death only after a
    # FAILURE STREAK of active probes (health_check_period 3s x
    # failure_threshold 5 on top of a 10s probe timeout — i.e. tens of
    # seconds), precisely so load spikes don't read as deaths. 10s here
    # killed 50 healthy-but-starved raylets during the 1 GiB broadcast
    # on the single-core CI box.
    health_check_timeout_s: float = 30.0
    # a raylet whose liveness thread beats but whose event loop reports
    # lag beyond this is treated as dead (wedged loop = dead node; busy
    # loop = alive). See raylet._start_liveness_thread.
    loop_stall_death_s: float = 60.0
    # default preemption grace window (TPU spot semantics: notice →
    # drain → host reclaim); a notice may carry its own grace_s
    preemption_grace_s: float = 10.0
    # how long a revoked lease waits for the owner's drain ack
    # (release_lease with inflight=0) before being force-reclaimed
    lease_revoke_ack_timeout_s: float = 5.0
    # ---- control plane ----
    gcs_port: int = 0  # 0 = auto
    rpc_connect_timeout_s: float = 10.0
    pubsub_poll_timeout_s: float = 30.0
    # ---- TPU ----
    tpu_chips_per_host: int = -1  # -1: autodetect
    tpu_visible_chips_env: str = "TPU_VISIBLE_CHIPS"
    # ---- metrics/events ----
    metrics_report_period_s: float = 5.0
    event_log_enabled: bool = True

    def apply_env_overrides(self):
        for f in fields(self):
            cur = getattr(self, f.name)
            setattr(self, f.name, _env(f.name, type(cur), cur))
        return self

    def update(self, overrides: Dict[str, Any]):
        for k, v in (overrides or {}).items():
            if not hasattr(self, k):
                raise ValueError(f"Unknown system config key: {k}")
            setattr(self, k, v)
        return self

    def to_json(self) -> str:
        return json.dumps({f.name: getattr(self, f.name) for f in fields(self)})

    @classmethod
    def from_json(cls, s: str) -> "SystemConfig":
        cfg = cls()
        cfg.update(json.loads(s))
        return cfg


_global_config: SystemConfig | None = None


def global_config() -> SystemConfig:
    global _global_config
    if _global_config is None:
        _global_config = SystemConfig().apply_env_overrides()
    return _global_config


def set_global_config(cfg: SystemConfig):
    global _global_config
    _global_config = cfg


def compile_cache_env(env: Dict[str, str]) -> None:
    """Point the environment of a process that will jit on a chip at the
    one XLA compile cache. jax reads both variables itself; code sets no
    cache directory anywhere else.

    Where the environment names no ``JAX_COMPILATION_CACHE_DIR``, the
    cache is one fixed path inside the checkout (never the temp dir, a
    pid or a time: the path is part of the cache key, so a directory that
    moves never hits).

    A Pallas kernel's serialized body carries the MLIR locations of its
    ops, and by default those hold the Python call stack of whoever
    traced it: the same step compiled from two call sites then has two
    keys and the cache never hits (measured on the v5e: 40 s again in a
    second process). Locations keep the innermost frame only: by the
    limit on their depth, not by
    ``JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS=false``, which also cuts
    every operation's ``op_name`` down to its primitive and so takes the
    ``jax.named_scope`` paths out of the compiled program and of every
    device trace (docs/TRACING.md, "Names on the device trace")."""
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".cache", "jax"))
    env.setdefault("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "1")
