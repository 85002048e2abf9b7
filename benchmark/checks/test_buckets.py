"""The enumeration of jitted shapes against a brute-force simulation of
the engine's admission (``LLMEngine._admit_locked``) over seeded draws
from the cells' own traffic files."""

import numpy as np
import pytest

from benchmark.harness import buckets, cells, loadgen


def traffic_and_engine(name):
    traffic = cells.load_json(f"{cells.BENCH_DIR}/traffic/{name}.json")
    engine = cells.load_json(
        f"{cells.BENCH_DIR}/configs/gpt2_large.json")["serve"]["engine"]
    return traffic, engine


def test_simulation_restates_the_engines_rule():
    # first is free; the rest must fit what is left; a prompt of the
    # whole budget ends the step; free slots bound the count
    assert buckets.simulate_admission([768, 128], 512, 16) == [768]
    assert buckets.simulate_admission([128, 128, 128, 128, 128], 512, 16) \
        == [128, 128, 128, 128]
    assert buckets.simulate_admission([300, 212, 128], 512, 16) == [300, 212]
    assert buckets.simulate_admission([300, 213], 512, 16) == [300]
    assert buckets.simulate_admission([128, 128, 128], 512, 2) == [128, 128]
    assert buckets.simulate_admission([512, 128], 512, 16) == [512]


@pytest.mark.parametrize("name", ["serve_closed32", "serve_steady"])
def test_every_simulated_group_was_enumerated(name):
    traffic, engine = traffic_and_engine(name)
    args = (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"],
            engine["max_prefill_tokens"], engine["max_running"])
    shapes = buckets.prefill_shapes(*args)
    programs = buckets.prefill_buckets(*args)
    pool = [p for p, _ in loadgen.length_pool(traffic)]
    rng = np.random.default_rng(5)
    seen = set()
    for _ in range(4000):
        waiting = [pool[i] for i in rng.integers(0, len(pool),
                                                 rng.integers(1, 9))]
        free = int(rng.integers(1, engine["max_running"] + 1))
        group = buckets.simulate_admission(
            waiting, engine["max_prefill_tokens"], free)
        shape = (len(group), buckets.pad_pow2(max(group), 8))
        seen.add(shape)
        assert shape in shapes, (waiting, free, group)
        assert (buckets.pad_pow2(shape[0]), shape[1]) in programs
    # and the enumeration is not idle: the common groups do occur
    assert {(1, 128), (1, 1024), (2, 256)} <= seen


def test_the_programs_of_the_cells_as_configured():
    traffic, engine = traffic_and_engine("serve_closed32")
    args = (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"],
            engine["max_prefill_tokens"], engine["max_running"])
    assert buckets.prefill_buckets(*args) == {
        (1, 128), (1, 256), (1, 512), (1, 1024),
        (2, 128), (2, 256), (2, 512), (4, 128), (4, 256)}
    assert buckets.decode_buckets(engine["max_running"]) == [1, 2, 4, 8, 16]


def test_lengths_never_pass_the_context():
    for name in ("serve_closed32", "serve_steady"):
        traffic, engine = traffic_and_engine(name)
        for p, o in loadgen.length_pool(traffic):
            assert p + o <= engine["max_seq_len"]
            assert traffic["prompt_len"]["min"] <= p \
                <= traffic["prompt_len"]["max"]


def test_every_seed_does_the_same_work_in_another_order():
    traffic, _ = traffic_and_engine("serve_closed32")
    pool = loadgen.length_pool(traffic)
    a = [x for x, _ in zip(loadgen.ordered(pool, 1, 1), range(len(pool)))]
    b = [x for x, _ in zip(loadgen.ordered(pool, 2**31 + 5, 1),
                           range(len(pool)))]
    assert sorted(a) == sorted(b) == sorted(pool) and a != b


def test_an_open_loops_window_holds_the_same_requests_for_every_seed():
    traffic, _ = traffic_and_engine("serve_steady")
    seconds, ramp = 45.0, traffic["ramp_seconds"]
    runs = [loadgen.open_schedule(traffic, seed, seconds)
            for seed in (1, 2, 2**31 + 5)]
    n_window = round(traffic["rate_per_s"] * seconds)
    for sched in runs:
        times = [t for t, _, _ in sched]
        assert times == sorted(times) and -ramp < times[0]
        window = [r for r in sched if 0.0 <= r[0] <= seconds]
        assert len(window) == n_window and sched[-1][0] < seconds
        assert len(sched) - len(window) == round(traffic["rate_per_s"] * ramp)

    def work(sched):
        window = [r for r in sched if r[0] >= 0.0]
        gaps = np.diff([0.0] + [t for t, _, _ in window])
        return sorted((p, o) for _, p, o in window), sorted(gaps.round(9))
    assert work(runs[0])[0] == work(runs[1])[0] == work(runs[2])[0]
    assert work(runs[0])[1] == pytest.approx(work(runs[1])[1])
    assert [r[1:] for r in runs[0]] != [r[1:] for r in runs[1]]
