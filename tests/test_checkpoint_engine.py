"""Checkpoint engine (ray_tpu.checkpoint): atomic commit, retention,
async sharded saves, failure paths, and gang-restart integration.

The failure-path coverage mirrors the preemptible-pod story: a save
killed mid-write must never become restorable, and the gang must restart
from the newest *intact* step."""

import json
import os
import time

import numpy as np
import pytest

from ray_tpu.air.checkpoint import Checkpoint, ShardedCheckpoint
from ray_tpu.checkpoint import (AsyncCheckpointer, CheckpointManager,
                                PendingCheckpoint)
from ray_tpu.checkpoint import async_checkpointer as ac_mod
from ray_tpu.checkpoint.manager import COMMIT_MARKER, MANIFEST_NAME


def _state(seed=0, n=256):
    rng = np.random.default_rng(seed)
    return {"params": {"w": rng.standard_normal(n).astype(np.float32),
                       "b": rng.standard_normal(4).astype(np.float32)},
            "step": np.asarray(seed, np.int32)}


# ------------------------------------------------------------- manager core


def test_atomic_commit_layout_and_load(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "root"))
    for step in range(3):
        mgr.stage(step, Checkpoint.from_dict({"step": step}))
        mgr.commit_step(step)
    assert mgr.committed_steps() == [0, 1, 2]
    assert mgr.latest_committed() == 2
    sdir = mgr.step_dir(2)
    assert os.path.exists(os.path.join(sdir, COMMIT_MARKER))
    with open(os.path.join(sdir, MANIFEST_NAME)) as f:
        manifest = json.load(f)
    assert "checkpoint.pkl" in manifest["files"]
    assert manifest["files"]["checkpoint.pkl"]["bytes"] > 0
    assert mgr.load().to_dict() == {"step": 2}
    assert mgr.load(1).to_dict() == {"step": 1}


def test_latest_committed_skips_partial_and_uncommitted(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "root"))
    mgr.stage(1, Checkpoint.from_dict({"step": 1}))
    mgr.commit_step(1)
    # a save that died mid-write: staged files, never committed
    tmp2 = mgr.begin_step(2)
    (tmp_path / "root").joinpath(os.path.basename(tmp2))  # exists
    with open(os.path.join(tmp2, "half_written.npy"), "wb") as f:
        f.write(b"\x00" * 128)
    # a save that died between rename and COMMIT: step dir, no marker
    os.makedirs(mgr.step_dir(3))
    with open(os.path.join(mgr.step_dir(3), "checkpoint.pkl"), "wb") as f:
        f.write(b"torn")
    assert mgr.latest_committed() == 1
    assert mgr.load().to_dict() == {"step": 1}
    with pytest.raises(FileNotFoundError):
        mgr.load(3)


def test_checksum_mismatch_detection(tmp_path, monkeypatch):
    mgr = CheckpointManager(str(tmp_path / "root"))
    for step in (1, 2):
        mgr.stage(step, Checkpoint.from_dict({"step": step}))
        mgr.commit_step(step)
    # flip bytes in step 2's payload without changing its size
    victim = os.path.join(mgr.step_dir(2), "checkpoint.pkl")
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.seek(size // 2)
        f.write(b"\xff\xff\xff")
    assert mgr.verify_step(1)
    assert not mgr.verify_step(2)
    # without verification the corrupt step still resolves…
    assert mgr.latest_committed() == 2
    # …with RTPU_CKPT_VERIFY=1 it is skipped and refuses to load
    monkeypatch.setenv("RTPU_CKPT_VERIFY", "1")
    assert mgr.latest_committed() == 1
    with pytest.raises(FileNotFoundError):
        mgr.load(2)


def test_retention_num_to_keep_and_keep_every_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "root"), num_to_keep=2,
                            keep_every_k=3)
    for step in range(7):
        mgr.stage(step, Checkpoint.from_dict({"step": step}))
        mgr.commit_step(step)
    # newest 2 = {5, 6}; every-3rd milestones = {0, 3, 6}
    assert mgr.committed_steps() == [0, 3, 5, 6]


def test_retention_from_checkpoint_config(tmp_path):
    from ray_tpu.air.config import CheckpointConfig
    cfg = CheckpointConfig(num_to_keep=1, keep_every_k=0)
    mgr = CheckpointManager(str(tmp_path / "root"), checkpoint_config=cfg)
    for step in range(3):
        mgr.stage(step, Checkpoint.from_dict({"step": step}))
        mgr.commit_step(step)
    assert mgr.committed_steps() == [2]


# ------------------------------------------------------- async checkpointer


def test_async_save_commit_restore_and_stats(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "root"), num_to_keep=2)
    ck = AsyncCheckpointer(mgr)  # single process: self-committing
    for step in range(3):
        pending = ck.save(step, _state(step))
        assert isinstance(pending, PendingCheckpoint)
        assert pending.step == step
    ck.finalize()
    assert mgr.latest_committed() == 2
    assert mgr.committed_steps() == [1, 2]  # retention applied
    restored = mgr.restore_state(_state(99))
    np.testing.assert_array_equal(restored["params"]["w"],
                                  _state(2)["params"]["w"])
    assert int(restored["step"]) == 2
    stats = ck.stats
    assert len(stats) == 3
    for st in stats:
        assert st.error is None and st.committed
        assert st.bytes > 0 and st.files > 0
        assert st.snapshot_ms >= 0 and st.write_ms > 0
        # async: the train thread never pays for write/commit
        assert st.blocked_ms <= st.snapshot_ms + st.backpressure_ms + 50


def test_kill_mid_write_previous_step_survives(tmp_path, monkeypatch):
    """A save that dies mid-write leaves latest_committed() on the
    previous intact step, and the engine recovers on the next save."""
    mgr = CheckpointManager(str(tmp_path / "root"))
    ck = AsyncCheckpointer(mgr)
    ck.save(0, _state(0))
    ck.wait()
    assert mgr.latest_committed() == 0

    real_write = ac_mod.write_host_snapshot

    def dying_write(pdir, entries):
        # emulate SIGKILL mid-file: half the entries land, then death
        real_write(pdir, entries[: len(entries) // 2])
        raise OSError("killed mid-write")

    monkeypatch.setattr(ac_mod, "write_host_snapshot", dying_write)
    ck.save(1, _state(1))
    with pytest.raises(RuntimeError, match="killed mid-write"):
        ck.wait()
    assert mgr.latest_committed() == 0  # torn step never visible
    assert os.path.isdir(mgr.tmp_dir(1))  # debris, not a checkpoint
    np.testing.assert_array_equal(
        mgr.restore_state(_state(9))["params"]["w"],
        _state(0)["params"]["w"])

    monkeypatch.setattr(ac_mod, "write_host_snapshot", real_write)
    ck.save(2, _state(2))
    ck.finalize()
    assert mgr.latest_committed() == 2
    assert not os.path.isdir(mgr.tmp_dir(1))  # debris reaped by retention


def test_backpressure_single_inflight(tmp_path, monkeypatch):
    """A second save blocks until the first write lands (bounded host
    memory), and the wait is accounted as backpressure."""
    import threading
    import time as _time

    mgr = CheckpointManager(str(tmp_path / "root"))
    ck = AsyncCheckpointer(mgr)
    real_write = ac_mod.write_host_snapshot
    gate = threading.Event()

    def slow_write(pdir, entries):
        gate.wait(5.0)
        return real_write(pdir, entries)

    monkeypatch.setattr(ac_mod, "write_host_snapshot", slow_write)
    ck.save(0, _state(0))
    t0 = _time.perf_counter()
    releaser = threading.Timer(0.3, gate.set)
    releaser.start()
    ck.save(1, _state(1))  # must wait for save 0 to clear
    waited = _time.perf_counter() - t0
    ck.finalize()
    releaser.cancel()
    assert waited >= 0.25
    assert ck.stats[1].backpressure_ms >= 200
    assert mgr.latest_committed() == 1


def test_restore_onto_different_process_count(tmp_path):
    """State written by a 2-process gang (each process owning half the
    rows) restores in a single process: shards are keyed by global index
    slices, not ranks."""
    full = np.arange(32, dtype=np.float32).reshape(8, 4)
    mgr = CheckpointManager(str(tmp_path / "root"))
    tmp = mgr.begin_step(0)
    for pidx, sl in ((0, slice(0, 4)), (1, slice(4, 8))):
        entries = [{"key": "params/w",
                    "data": full[sl],
                    "index": [[sl.start, sl.stop, None],
                              [None, None, None]],
                    "shape": list(full.shape), "dtype": "float32"}]
        if pidx == 0:  # host-replicated leaf: owner writes once
            entries.append({"key": "step", "data": np.asarray(7, np.int32),
                            "index": None, "shape": [],
                            "dtype": "int32"})
        ac_mod.write_host_snapshot(
            os.path.join(tmp, f"process_{pidx}"), entries)
    mgr.commit_step(0)
    target = {"params": {"w": np.zeros_like(full)},
              "step": np.asarray(0, np.int32)}
    restored = mgr.restore_state(target)
    np.testing.assert_array_equal(restored["params"]["w"], full)
    assert int(restored["step"]) == 7


def test_sharded_save_dedups_replicated_leaves(tmp_path):
    """On a mesh, fully-replicated leaves produce exactly one shard file
    (replica_id==0), not one per device."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices("cpu")[:4]
    mesh = Mesh(np.array(devs), ("dp",))
    sharded = jax.device_put(np.arange(8, dtype=np.float32),
                             NamedSharding(mesh, P("dp")))
    replicated = jax.device_put(np.ones(3, np.float32),
                                NamedSharding(mesh, P()))
    state = {"w": sharded, "scale": replicated}
    root = str(tmp_path / "sharded")
    ShardedCheckpoint(root).save(state, process_index=0)
    names = sorted(os.listdir(os.path.join(root, "process_0")))
    assert names == ["manifest.json", "scale__shard0.npy",
                     "w__shard0.npy", "w__shard1.npy",
                     "w__shard2.npy", "w__shard3.npy"]
    # restore reassembles onto a *different* layout (plain host arrays)
    out = ShardedCheckpoint(root).restore(
        {"w": np.zeros(8, np.float32), "scale": np.zeros(3, np.float32)})
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(8, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(out["scale"]),
                                  np.ones(3, np.float32))


# -------------------------------------------------------- satellite fixes


def test_to_dict_on_sharded_directory(tmp_path):
    """to_dict() on a directory with process_<i>/ subdirs flattens to
    relative-path keys instead of raising IsADirectoryError."""
    root = tmp_path / "ckpt"
    (root / "process_0").mkdir(parents=True)
    (root / "process_0" / "manifest.json").write_bytes(b"[]")
    (root / "meta.txt").write_bytes(b"hello")
    out = Checkpoint.from_directory(str(root)).to_dict()
    assert out == {"meta.txt": b"hello",
                   os.path.join("process_0", "manifest.json"): b"[]"}


def test_to_directory_crash_safe(tmp_path, monkeypatch):
    dst = str(tmp_path / "out")
    import pickle as _pickle

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(_pickle, "dump", boom)
    with pytest.raises(OSError):
        Checkpoint.from_dict({"x": 1}).to_directory(dst)
    monkeypatch.undo()
    # a failed materialization leaves nothing at the target, and no
    # staging debris in the parent
    assert not os.path.exists(dst)
    assert [n for n in os.listdir(tmp_path) if n.startswith(".out")] == []
    # success path: atomic swap, including over an existing directory
    assert Checkpoint.from_dict({"x": 1}).to_directory(dst) == dst
    Checkpoint.from_dict({"x": 2}).to_directory(dst)
    assert Checkpoint.from_directory(dst).to_dict() == {"x": 2}


def test_deterministic_shard_filenames(tmp_path):
    state = {"layer/0": {"w": np.ones((2, 2), np.float32)},
             "b": np.zeros(3, np.float32)}
    r1, r2 = str(tmp_path / "a"), str(tmp_path / "b")
    ShardedCheckpoint(r1).save(state, process_index=0)
    ShardedCheckpoint(r2).save(state, process_index=0)
    n1 = sorted(os.listdir(os.path.join(r1, "process_0")))
    n2 = sorted(os.listdir(os.path.join(r2, "process_0")))
    assert n1 == n2  # no per-process hash salt
    assert "b__full.npy" in n1 and "layer_0_w__full.npy" in n1


# --------------------------------------------------- trainer integration


@pytest.fixture(scope="module")
def ckpt_cluster():
    import ray_tpu
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                       object_store_memory=256 * 1024 * 1024)
    yield ctx
    ray_tpu.shutdown()


def test_gang_restart_resumes_from_latest_committed(ckpt_cluster, tmp_path):
    """End-to-end acceptance: checkpoints flow through session.report →
    manager staging → driver commit; a worker that stages a *partial*
    step and dies mid-save restarts the gang from the previous committed
    step, and numbering continues past it."""
    from ray_tpu.air.config import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train import DataParallelTrainer

    def train_fn(config):
        from ray_tpu.air import session
        ckpt = session.get_checkpoint()
        start = ckpt.to_dict()["i"] + 1 if ckpt else 0
        for i in range(start, 5):
            session.report({"i": i},
                           checkpoint=Checkpoint.from_dict({"i": i}))
            if i == 2 and start == 0:
                # die mid-save: the *next* step is half-staged (no commit
                # can ever happen for it), then the worker crashes
                mgr = session.get_checkpoint_manager()
                tmp = mgr.begin_step(session.next_checkpoint_step())
                with open(os.path.join(tmp, "half.npy"), "wb") as f:
                    f.write(b"\x00" * 64)
                raise RuntimeError("preempted mid-save")

    run_config = RunConfig(
        name="gang_restart_ckpt", storage_path=str(tmp_path),
        failure_config=FailureConfig(max_failures=1))
    trainer = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1),
        run_config=run_config)
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["i"] == 4
    # the committed root: steps 0..2 from attempt 1 (partial step 3
    # reaped), then the resumed attempt continues the numbering
    root = os.path.join(str(tmp_path), "gang_restart_ckpt", "checkpoints")
    mgr = CheckpointManager(root)
    latest = mgr.latest_committed()
    assert latest is not None
    assert mgr.load(latest).to_dict() == {"i": 4}
    # the final checkpoint handed back is directory-backed + committed
    assert result.checkpoint is not None
    assert result.checkpoint.to_dict() == {"i": 4}

    # a fresh trainer with the same run identity auto-resumes — and the
    # train_fn (which stops at 5) has nothing left to do
    trainer2 = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="gang_restart_ckpt",
                             storage_path=str(tmp_path)))
    r2 = trainer2.fit()
    assert r2.error is None
    assert r2.checkpoint.to_dict() == {"i": 4}


def test_async_checkpointer_through_session(ckpt_cluster, tmp_path):
    """train_fn drives an AsyncCheckpointer for sharded state; the driver
    commits the step after the round barrier and the result resolves to
    the committed directory."""
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import DataParallelTrainer

    def train_fn(config):
        import numpy as _np
        from ray_tpu.air import session
        ckpter = session.get_async_checkpointer()
        assert ckpter is not None
        for i in range(3):
            state = {"w": _np.full(64, float(i), _np.float32)}
            pending = ckpter.save(session.next_checkpoint_step(), state)
            session.report({"i": i}, checkpoint=pending)
        ckpter.finalize()

    trainer = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="async_session_ckpt",
                             storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    root = os.path.join(str(tmp_path), "async_session_ckpt", "checkpoints")
    mgr = CheckpointManager(root)
    assert mgr.latest_committed() == 2
    restored = mgr.restore_state({"w": np.zeros(64, np.float32)})
    np.testing.assert_array_equal(restored["w"],
                                  np.full(64, 2.0, np.float32))
    # result checkpoint points at the committed step dir
    assert result.checkpoint is not None
    assert os.path.basename(result.checkpoint._dir).endswith("00000002")


# ------------------------------------------------ what the loop waits for


def test_async_save_blocks_the_loop_a_quarter_of_a_sync_save(tmp_path,
                                                             monkeypatch):
    """An asynchronous save holds the train loop only for the host
    snapshot: under a quarter of what a synchronous save of the same
    16 MB pytree holds it for, over 3 saves with a 200 ms step (the
    compute an asynchronous write overlaps) after each."""
    rng = np.random.default_rng(0)
    state = {"params": {f"w{i}": rng.standard_normal(512 * 1024)
                        .astype(np.float32) for i in range(8)},
             "step": np.zeros((), np.int32)}
    saves, blocked_ms = 3, {}
    for mode in ("sync", "async"):
        monkeypatch.setenv("RTPU_CKPT_ASYNC", "1" if mode == "async" else "0")
        mgr = CheckpointManager(str(tmp_path / mode), num_to_keep=2)
        ck = AsyncCheckpointer(mgr)
        blocked = 0.0
        for s in range(saves):
            state["step"] = state["step"] + 1
            t0 = time.perf_counter()
            ck.save(s, state)
            blocked += time.perf_counter() - t0
            time.sleep(0.2)
        ck.finalize()
        assert mgr.latest_committed() == saves - 1, mode
        blocked_ms[mode] = 1e3 * blocked / saves
    assert blocked_ms["async"] < blocked_ms["sync"], blocked_ms
    assert blocked_ms["async"] / blocked_ms["sync"] < 0.25, blocked_ms
