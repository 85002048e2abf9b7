"""LLM serving, the engine's scheduler over the toy adapter
(docs/LLM_SERVING.md): continuous vs static batching equivalence,
cost-aware admission, KV exhaustion, shedding, drain, seeded sampling;
and the autoscaler's LLM signals. Tier-1, CPU-only."""

import numpy as np
import pytest
from llm_test_helpers import drain_stream

from ray_tpu.serve.exceptions import ReplicaOverloadedError
from ray_tpu.serve.llm import (EngineConfig, LLMEngine, SamplingParams,
                               ToyAdapter)

# --------------------------------------------------------------- engine


def test_continuous_vs_static_batching_same_tokens():
    """The headline correctness property: continuous batching changes
    WHEN sequences run, never WHAT they produce. The toy model reads
    its prefix back through the block tables, so a paging bug breaks
    this too."""
    rng = np.random.RandomState(0)
    reqs = [(list(rng.randint(0, 256, rng.randint(3, 12))),
             int(rng.randint(2, 10))) for _ in range(9)]

    def run(policy):
        eng = LLMEngine(ToyAdapter(seed=3), EngineConfig(
            max_running=4, num_blocks=64, block_size=8,
            max_seq_len=128, policy=policy))
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in reqs]
        outs = [drain_stream(eng, sid)[0] for sid in sids]
        eng.stop()
        return outs

    assert run("continuous") == run("static")


def test_cost_aware_admission_long_prefill_goes_alone():
    """A prompt over the per-step prefill budget is admitted ALONE
    (and others never behind it in the same step) — and everything
    still completes."""
    eng = LLMEngine(ToyAdapter(), EngineConfig(
        max_running=8, max_prefill_tokens=8, num_blocks=64,
        block_size=8, max_seq_len=256))
    short = eng.add_request([1] * 6, SamplingParams(max_new_tokens=3))
    long = eng.add_request([2] * 40, SamplingParams(max_new_tokens=3))
    t_short, _ = drain_stream(eng, short)
    t_long, _ = drain_stream(eng, long)
    assert len(t_short) == 3 and len(t_long) == 3
    m = eng.metrics()
    assert m["finished_total"] == 2
    assert m["kv_occupancy"] == 0.0    # all pages returned
    eng.stop()


def test_kv_exhaustion_queues_instead_of_oom():
    """A sequence that doesn't fit the pool WAITS for pages (freed by
    finishing sequences) instead of failing mid-decode."""
    # 15 usable pages * 4 tokens = 60 tokens capacity; each request
    # needs 8+24=32 tokens -> 8 pages; two can't run at once
    eng = LLMEngine(ToyAdapter(), EngineConfig(
        max_running=8, num_blocks=16, block_size=4, max_seq_len=64))
    a = eng.add_request([1] * 8, SamplingParams(max_new_tokens=24))
    b = eng.add_request([2] * 8, SamplingParams(max_new_tokens=24))
    ta, ca = drain_stream(eng, a)
    tb, cb = drain_stream(eng, b)
    assert len(ta) == 24 and len(tb) == 24
    assert ca["finish_reason"] == "length"
    assert cb["finish_reason"] == "length"
    eng.stop()


def test_engine_sheds_when_waiting_room_full():
    eng = LLMEngine(ToyAdapter(per_seq_delay_s=0.01),
                    EngineConfig(max_running=1, max_waiting=1,
                                 num_blocks=64, block_size=8,
                                 max_seq_len=128))
    sids = []
    with pytest.raises(ReplicaOverloadedError):
        for _ in range(12):  # 1 running + 1 waiting, the rest shed
            sids.append(eng.add_request(
                [1, 2, 3], SamplingParams(max_new_tokens=20)))
    assert eng.metrics()["shed_total"] >= 1
    for sid in sids:
        drain_stream(eng, sid)
    eng.stop()


def test_engine_drain_finishes_in_flight_sheds_new():
    eng = LLMEngine(ToyAdapter(per_seq_delay_s=0.005),
                    EngineConfig(max_running=4, num_blocks=64,
                                 block_size=8, max_seq_len=128))
    sid = eng.add_request([1] * 4, SamplingParams(max_new_tokens=30))
    eng.prepare_drain()
    with pytest.raises(ReplicaOverloadedError):
        eng.add_request([2] * 4, SamplingParams(max_new_tokens=2))
    toks, ch = drain_stream(eng, sid)
    assert len(toks) == 30 and ch["finish_reason"] == "length"
    assert eng.in_flight() == 0
    eng.stop()


def test_temperature_sampling_is_seeded_deterministic():
    def gen(seed):
        eng = LLMEngine(ToyAdapter(), EngineConfig(
            num_blocks=32, block_size=8, max_seq_len=128))
        # temperature high enough to actually spread the toy model's
        # peaked logits — 1.0 still collapses to the argmax token
        sid = eng.add_request(
            [5, 6, 7], SamplingParams(max_new_tokens=12,
                                      temperature=3.0, seed=seed),
            request_id="r1")
        toks, _ = drain_stream(eng, sid)
        eng.stop()
        return toks

    assert gen(7) == gen(7)
    assert gen(7) != gen(8)


# ---------------------------------------------------- autoscaler signals


def test_autoscaler_scales_on_llm_signals():
    from ray_tpu.serve._private.autoscaling import (AutoscalingConfig,
                                                    AutoscalingPolicy)
    cfg = AutoscalingConfig(min_replicas=1, max_replicas=8,
                            target_num_ongoing_requests_per_replica=100,
                            target_tokens_per_s_per_replica=50.0,
                            target_kv_occupancy=0.8,
                            upscale_delay_s=1.0, downscale_delay_s=1.0)
    p = AutoscalingPolicy(cfg)
    # queue is quiet but throughput demands 4 replicas
    assert p.get_decision(2, 0.0, now=0.0,
                          signals={"tokens_per_s": 200.0,
                                   "kv_occupancy": 0.1}) == 2  # delay
    assert p.get_decision(2, 0.0, now=2.0,
                          signals={"tokens_per_s": 200.0,
                                   "kv_occupancy": 0.1}) == 4
    # KV pressure alone scales out: 2 replicas at 100% occupancy
    # against a 0.8 target want ceil(2 * 1.0/0.8) = 3
    p2 = AutoscalingPolicy(cfg)
    p2.get_decision(2, 0.0, now=0.0, signals={"kv_occupancy": 1.0})
    assert p2.get_decision(2, 0.0, now=2.0,
                           signals={"kv_occupancy": 1.0}) == 3
    # no signals -> pure queue behavior unchanged
    p3 = AutoscalingPolicy(cfg)
    assert p3.get_decision(2, 0.0, now=0.0) == 2
