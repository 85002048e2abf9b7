"""The readers of a prefill step on a step log whose prompts' programs
were left in flight (the tiny Kimi-K2 through the program's own engine,
on the CPU): a prompt's ``runner.fetch`` comes a step after the
``runner.dispatch`` of its program and is recorded under the
``llm.step.prefill`` that dispatched it, where ``k2_views.prefill_steps``
(under the four ``*_prefill_mfu_share.serve`` and
``laguna_prefill_attn_roofline_share.serve``),
``laguna_prefill_moe_roofline_share.serve``,
``jamba_views.prompts_by_program`` and ``longcat_views.window_routing``
look for it. Each reads numbers, not ``None``, and the same numbers as of
an engine held synchronous; ``prefill_ahead_share.serve`` says which was
which."""

import importlib.util
import os
import time
import types

import pytest

from benchmark.harness import cells, jamba_views, k2_views, \
    program_spans as ps

# (prompt tokens, max_new_tokens), a prompt a step
SHAPES = ((30, 2), (9, 3), (20, 1), (12, 4), (41, 2), (17, 1))
_OBS = {}


def reader(name):
    path = os.path.join(cells.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Synchronous:
    """The adapter with its look-ahead withheld."""
    decode_ahead = False

    def __init__(self, adapter):
        self._adapter = adapter

    def __getattr__(self, name):
        return getattr(self._adapter, name)


def observed(synchronous):
    """What a runner hands the readers of one window: the engine's
    ``metrics()`` with its ``step_log``, and the window's edges."""
    if not _OBS:
        import numpy as np

        from benchmark.reference import kimi_k2_glue as glue
        from ray_tpu.models.kimi_k2 import KimiK2Config
        from ray_tpu.serve.llm import EngineConfig, LLMEngine, \
            SamplingParams
        from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
        cfg = KimiK2Config.tiny()
        adapter = FlaxModelAdapter("kimi_k2", cfg, glue.init_for(cfg, 7))
        rng = np.random.RandomState(54)
        prompts = [[int(t) for t in rng.randint(0, adapter.vocab_size, n)]
                   for n, _ in SHAPES]
        for sync in (True, False):
            t0 = time.time()
            eng = LLMEngine(Synchronous(adapter) if sync else adapter,
                            EngineConfig(max_running=4, num_blocks=96,
                                         block_size=8, max_seq_len=128,
                                         max_prefill_tokens=8))
            try:
                sids = [eng.add_request(p, SamplingParams(max_new_tokens=m))
                        for p, (_, m) in zip(prompts, SHAPES)]
                for sid in sids:
                    while not eng.poll(sid, 0, max_wait_s=60.0)["done"]:
                        pass
                while eng.in_flight() or adapter._state:
                    time.sleep(0.02)
            finally:
                eng.stop()
            _OBS[sync] = types.SimpleNamespace(
                engine_metrics=dict(eng.metrics(), step_log=eng.step_log()),
                t0=t0, t1=time.time())
    return _OBS[synchronous]


def test_the_prefill_steps_reader_reads_every_prompt_left_in_flight():
    ahead, sync = (k2_views.prefill_steps(observed(s)) for s in (False, True))
    assert ahead is not None and ahead == sync
    n = float(len(SHAPES))
    assert ahead["steps"] == n
    assert ahead["prompt_tokens"] == sum(p for p, _ in SHAPES) / n
    assert ahead["padded_tokens"] >= ahead["prompt_tokens"]
    assert ahead["assignments"] > 0


def test_prompts_by_program_reads_the_same_programs():
    ahead, sync = (jamba_views.prompts_by_program(observed(s))
                   for s in (False, True))
    assert ahead == sync
    assert sorted(t for v in ahead.values() for t, _ in v) \
        == sorted(p for p, _ in SHAPES)
    assert all(prompts == 1 for v in ahead.values() for _, prompts in v)


def test_every_fetch_of_the_window_is_summed_once():
    """A reader that sums every ``runner.fetch`` of the window
    (``longcat_views.window_routing`` does, over other keys) reads the
    synchronous engine's sum."""
    def pairs(obs):
        return sum(f["attrs"]["expert_tokens"]
                   for step in ps.window_steps(obs)
                   for f in ps.named(step, ps.RUNNER_FETCH))
    assert pairs(observed(False)) == pairs(observed(True)) > 0


@pytest.mark.parametrize("synchronous, share", ((False, 100.0), (True, 0.0)))
def test_prefill_ahead_share_says_which_steps_were_left_in_flight(
        synchronous, share):
    assert reader("prefill_ahead_share.serve").read(
        observed(synchronous)) == share
    assert reader("decode_ahead_share.serve").read(
        observed(synchronous)) == share


def test_a_program_without_the_attribute_reads_none():
    obs = observed(False)
    log = [dict(step, children=[
        dict(c, attrs={k: v for k, v in c["attrs"].items() if k != "ahead"})
        for c in step["children"]]) for step in obs.engine_metrics["step_log"]]
    old = types.SimpleNamespace(engine_metrics={"step_log": log},
                                t0=obs.t0, t1=obs.t1)
    assert reader("prefill_ahead_share.serve").read(old) is None
    assert reader("prefill_ahead_share.serve").read(
        types.SimpleNamespace(engine_metrics=None, t0=0.0, t1=1.0)) is None
