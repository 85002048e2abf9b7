"""AsyncCheckpointer: snapshot device shards to host, write in background.

The train step blocks only for the host snapshot (device→host memcpy of
the shards this process *owns*); serialization, checksumming, fsync and
commit happen on a single background writer thread. One save may be in
flight at a time — a second ``save()`` blocks until the first lands
(backpressure, counted in the save's ``blocked_ms``) so checkpoints can
never consume unbounded host memory or reorder on disk.

Dedup of replicated state (orbax-style): a leaf's addressable shards are
written only where ``replica_id == 0``, and host-resident (unsharded)
leaves are written only by process 0 — instead of every host writing full
copies of the entire replicated tree.

Env knobs:
  RTPU_CKPT_ASYNC=0   write inline on the calling thread (the sync
                      baseline tests/test_checkpoint_engine.py holds an
                      asynchronous save's blocked time against)
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu.checkpoint.manager import CheckpointManager, PendingCheckpoint

logger = logging.getLogger(__name__)


def _async_enabled() -> bool:
    return os.environ.get("RTPU_CKPT_ASYNC", "1") != "0"


def sanitize_key(key: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", key) or "leaf"


@dataclass
class SaveStats:
    """Per-save accounting. ``blocked_ms`` is the time the *training*
    thread spent inside save() — backpressure wait + host snapshot;
    write/commit happen off-thread (or inline in sync mode, where they
    count toward blocked_ms too)."""

    step: int
    snapshot_ms: float = 0.0
    backpressure_ms: float = 0.0
    blocked_ms: float = 0.0
    write_ms: float = 0.0
    commit_ms: float = 0.0
    bytes: int = 0
    files: int = 0
    committed: bool = False
    error: Optional[str] = None

    def as_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in (
            "step", "snapshot_ms", "backpressure_ms", "blocked_ms",
            "write_ms", "commit_ms", "bytes", "files", "committed",
            "error")}


def snapshot_to_host(state, process_index: int = 0) -> List[Dict[str, Any]]:
    """Flatten a pytree into host-memory shard entries, deduplicating
    replicas. Copies (never aliases) device buffers so donated/reused
    buffers can't corrupt an in-flight save. Returns entries shaped like
    the on-disk per-process manifest: {key, data, index, shape, dtype}."""
    import numpy as np
    from jax.tree_util import tree_flatten_with_path

    from ray_tpu.air.checkpoint import _index_to_json

    leaves, _ = tree_flatten_with_path(state)
    entries: List[Dict[str, Any]] = []
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        if hasattr(leaf, "addressable_shards"):
            for shard in leaf.addressable_shards:
                if getattr(shard, "replica_id", 0) != 0:
                    continue  # replica owned by another shard/process
                entries.append({
                    "key": key,
                    "data": np.array(shard.data, copy=True),
                    "index": _index_to_json(shard.index),
                    "shape": list(leaf.shape),
                    "dtype": str(leaf.dtype)})
        else:
            if process_index != 0:
                continue  # host-replicated leaf: only process 0 writes
            arr = np.array(leaf, copy=True)
            entries.append({"key": key, "data": arr, "index": None,
                            "shape": list(arr.shape),
                            "dtype": str(arr.dtype)})
    return entries


def write_host_snapshot(pdir: str, entries: List[Dict[str, Any]]) -> int:
    """Write snapshot entries into one process dir with deterministic
    ``key__shard<i>.npy`` names + a per-process manifest.json (the schema
    ShardedCheckpoint.restore reassembles from). Returns bytes written."""
    import json
    import shutil

    import numpy as np

    # this process owns pdir exclusively: clear debris a previous attempt
    # at the same step may have left (restart after a mid-save death)
    if os.path.isdir(pdir):
        shutil.rmtree(pdir)
    os.makedirs(pdir, exist_ok=True)
    manifest = []
    shard_counts: Dict[str, int] = {}
    nbytes = 0
    for e in entries:
        san = sanitize_key(e["key"])
        i = shard_counts.get(san, 0)
        shard_counts[san] = i + 1
        fname = f"{san}__shard{i}.npy" if e["index"] is not None \
            else f"{san}__full.npy"
        if e["index"] is None and i:
            fname = f"{san}__full{i}.npy"  # sanitization collision
        np.save(os.path.join(pdir, fname), e["data"])
        nbytes += e["data"].nbytes
        manifest.append({"key": e["key"], "file": fname,
                         "index": e["index"], "shape": e["shape"],
                         "dtype": e["dtype"]})
    part = os.path.join(pdir, ".manifest.json.part")
    with open(part, "w") as f:
        json.dump(manifest, f)
    os.replace(part, os.path.join(pdir, "manifest.json"))
    return nbytes


class AsyncCheckpointer:
    """Background sharded saver bound to one CheckpointManager.

    commit semantics:
      - ``commit="auto"`` (default): the writer thread commits iff this is
        a single-process save (process_count == 1). Gangs leave commit to
        the driver, which owns the all-ranks round barrier.
      - ``commit=True`` / ``commit=False`` force it.
    """

    def __init__(self, manager: CheckpointManager, *,
                 process_index: int = 0, process_count: int = 1,
                 commit: Any = "auto"):
        self.manager = manager
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        if commit == "auto":
            commit = process_count == 1
        self._commit = bool(commit)
        self._stats: List[SaveStats] = []
        self._cond = threading.Condition()
        self._inflight: Optional[tuple] = None  # (step, entries, stats)
        self._stop = False
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    # ---------------------------------------------------------------- save

    def save(self, step: int, state,
             metadata: Optional[Dict[str, Any]] = None) -> PendingCheckpoint:
        """Snapshot ``state`` to host and hand off to the writer. Blocks
        only for (a) a previous save still in flight and (b) the host
        snapshot itself. Raises if the previous save failed."""
        t0 = time.perf_counter()
        stats = SaveStats(step=step)
        with self._cond:
            while self._inflight is not None and self._error is None:
                self._cond.wait(timeout=0.5)
            self._raise_on_error()
        stats.backpressure_ms = (time.perf_counter() - t0) * 1e3
        t1 = time.perf_counter()
        entries = snapshot_to_host(state, self.process_index)
        stats.snapshot_ms = (time.perf_counter() - t1) * 1e3
        if _async_enabled():
            with self._cond:
                self._ensure_thread()
                self._inflight = (step, entries, metadata, stats)
                self._cond.notify_all()
            stats.blocked_ms = (time.perf_counter() - t0) * 1e3
        else:
            self._write_one(step, entries, metadata, stats)
            stats.blocked_ms = (time.perf_counter() - t0) * 1e3
            self._raise_on_error()
        self._stats.append(stats)
        return PendingCheckpoint(step)

    def wait(self):
        """Barrier: block until the in-flight save (if any) fully landed;
        re-raise a writer failure."""
        with self._cond:
            while self._inflight is not None and self._error is None:
                self._cond.wait(timeout=0.5)
            self._raise_on_error()

    def finalize(self):
        """wait() + stop the writer thread. The checkpointer is reusable
        afterwards (a new save restarts the thread)."""
        self.wait()
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        self._stop = False

    # --------------------------------------------------------------- stats

    @property
    def stats(self) -> List[SaveStats]:
        return list(self._stats)

    def stats_summary(self) -> Dict[str, Any]:
        done = [s for s in self._stats if s.error is None]
        if not done:
            return {"saves": 0}
        return {
            "saves": len(done),
            "blocked_ms_mean": sum(s.blocked_ms for s in done) / len(done),
            "snapshot_ms_mean": sum(s.snapshot_ms for s in done) / len(done),
            "write_ms_mean": sum(s.write_ms for s in done) / len(done),
            "bytes_total": sum(s.bytes for s in done),
        }

    # -------------------------------------------------------------- writer

    def _raise_on_error(self):
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                f"async checkpoint save failed: {err!r}") from err

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._writer_loop, daemon=True,
                name=f"rtpu-ckpt-writer-p{self.process_index}")
            self._thread.start()

    def _writer_loop(self):
        while True:
            with self._cond:
                while self._inflight is None and not self._stop:
                    self._cond.wait(timeout=0.5)
                if self._stop:
                    return
                step, entries, metadata, stats = self._inflight
            try:
                self._write_one(step, entries, metadata, stats)
            finally:
                with self._cond:
                    self._inflight = None
                    self._cond.notify_all()

    def _write_one(self, step, entries, metadata, stats: SaveStats):
        try:
            t0 = time.perf_counter()
            tmp = self.manager.begin_step(step)
            pdir = os.path.join(tmp, f"process_{self.process_index}")
            stats.bytes = write_host_snapshot(pdir, entries)
            stats.files = len(entries)
            stats.write_ms = (time.perf_counter() - t0) * 1e3
            if self._commit:
                t1 = time.perf_counter()
                self.manager.commit_step(step, metadata=metadata)
                stats.commit_ms = (time.perf_counter() - t1) * 1e3
                stats.committed = True
        except BaseException as e:  # surfaced on the next save()/wait()
            stats.error = repr(e)
            with self._cond:
                self._error = e
            logger.warning("checkpoint step %d write failed: %r", step, e)
