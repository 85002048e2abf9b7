"""LLM serving, a model with per-sequence state (Kimi-Linear: KDA
state slots beside a latent page pool, routed experts) held to the plain
reference's logits (docs/LLM_SERVING.md). Tier-1, CPU-only."""

import time

import numpy as np
import pytest
from llm_test_helpers import PAGE, drain_stream, flax_seq, token_prompts

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PagedKVCache,
                               SamplingParams, ToyAdapter)


# ------------------------------------------ a model with state (Kimi-Linear)
# Logits are compared, not tokens. Everything here is float32 at 'highest'
# on both sides (tests/conftest.py), so the served rows differ from the
# reference's full forward by the order of sums only: 5e-5 absolute on
# logits of spread ~0.16 (chunked prefill + up to 30 one-token updates of
# the state). A stale state, a wrong slot or a wrong page moves a row by
# 1e-2 or more (test_kimi_a_stale_state_shows).

KIMI_TOL = 5e-5
_KIMI = {}


def _kimi():
    if not _KIMI:
        from benchmark.reference import kimi_linear_glue, kimi_linear_ref
        from ray_tpu.models.kimi_linear import KimiLinearConfig
        cfg = KimiLinearConfig.tiny()
        _KIMI.update(cfg=cfg, params=kimi_linear_glue.init_for(cfg, 7),
                     sizes=kimi_linear_ref.sizes_of(cfg),
                     ref=kimi_linear_ref)
    return _KIMI


def _kimi_adapter(max_running=4):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _kimi()
    adapter = FlaxModelAdapter("kimi_linear", k["cfg"], k["params"])
    cache = PagedKVCache(num_blocks=64, block_size=PAGE)
    adapter.bind_cache(cache)
    adapter.bind_state(max_running)
    return adapter, cache


def _kimi_reference_rows(prompt, tokens):
    """The reference's logits after the prompt and after each of
    ``tokens`` but the last: what prefill and each decode returned."""
    k = _kimi()
    ids = np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
    rows = k["ref"].forward(k["params"]["params"], ids, k["sizes"])
    return np.asarray(rows[len(prompt) - 1:])


def _kimi_serve(adapter, seqs, n, rows=None):
    """Prefill (unless ``rows`` has each sequence's logits so far) and n
    greedy decode steps; every logits row that came back, a sequence."""
    if rows is None:
        rows = [[r] for r in adapter.prefill(seqs)]
    for _ in range(n):
        for s, got in zip(seqs, rows):
            s.tokens.append(int(got[-1].argmax()))
        for got, r in zip(rows, adapter.decode(seqs)):
            got.append(r)
    return rows


def _kimi_check(seq, rows):
    want = _kimi_reference_rows(
        seq.prompt, seq.tokens + [0])          # one row a logits row
    np.testing.assert_allclose(np.stack(rows), want[:len(rows)],
                               atol=KIMI_TOL)


def test_kimi_pages_and_slots_serve_the_references_logits():
    """Rows of unequal length in one batch (70, 5 and 33 tokens: a
    bucket of 4 x 128, one prompt longer than a KDA chunk, one shorter
    than a page), decode in a bucket of 4; one sequence ends and the rest go on in a bucket of 2; a new
    sequence takes the freed slot and joins them."""
    adapter, cache = _kimi_adapter()
    prompts = token_prompts(41, adapter.vocab_size, (70, 5, 33, 19))
    a, b, c = (flax_seq(cache, f"s{i}", p, budget=24)
               for i, p in enumerate(prompts[:3]))
    rows = _kimi_serve(adapter, [a, b, c], 4)
    slot_b = adapter._state["s1"]["slot"]
    adapter.release("s1")
    cache.free("s1")
    assert adapter.counters()["state_slots_in_use"] == 2
    rows_ac = _kimi_serve(adapter, [a, c], 3, rows=[rows[0], rows[2]])
    d = flax_seq(cache, "s3", prompts[3], budget=24)
    rows_d = _kimi_serve(adapter, [d], 0)
    assert adapter._state["s3"]["slot"] == slot_b      # the slot is reused
    rows_acd = _kimi_serve(adapter, [a, c, d], 3,
                           rows=rows_ac + rows_d)
    for seq, got in zip((a, b, c, d), (rows_acd[0], rows[1], rows_acd[1],
                                       rows_acd[2])):
        _kimi_check(seq, got)
    assert {k[:2] for k in adapter._fns if isinstance(k, tuple)} == {
        (4, 128), (4, 1), (2, 1), (1, 32)}
    totals = np.asarray(adapter.counters()["expert_tokens_total"])
    assert totals.shape == (3, 4) and totals.sum() > 0


def test_kimi_a_stale_state_shows(monkeypatch):
    """Without the zeroing at admission the second user of a slot starts
    from the first one's state and conv tail, and its logits are off by
    far more than the tolerance: the comparison above would see it."""
    adapter, cache = _kimi_adapter(max_running=1)
    first, second = token_prompts(43, adapter.vocab_size, (40, 21))
    a = flax_seq(cache, "a", first)
    _kimi_serve(adapter, [a], 2)
    adapter.release("a")
    cache.free("a")
    monkeypatch.setattr(adapter, "_zero_fn",
                        lambda: lambda idx, *arrays: arrays)
    b = flax_seq(cache, "b", second)
    stale = _kimi_serve(adapter, [b], 1)[0]
    want = _kimi_reference_rows(second, b.tokens + [0])
    assert float(np.abs(np.stack(stale) - want[:2]).max()) > 100 * KIMI_TOL
    monkeypatch.undo()
    adapter.release("b")
    cache.free("b")
    c = flax_seq(cache, "c", second)
    _kimi_check(c, _kimi_serve(adapter, [c], 1)[0])


def test_kimi_greedy_rows_fetch_tokens_not_logits():
    """``tokens_only``: the step's greedy tokens, found on the device,
    are the argmax of the logits the same step gives a twin adapter, in
    prefill, in a decode bucket in row order and in the full bucket in
    slot order (slots taken out of order). The engine asks for them only
    of an adapter that offers them and only when no row samples."""
    from ray_tpu.serve.llm.engine import Sequence
    pair = [_kimi_adapter(max_running=2) for _ in range(2)]
    prompts = token_prompts(53, pair[0][0].vocab_size, (20, 7, 11))
    got = []
    for (adapter, cache), only in zip(pair, (True, False)):
        assert adapter.greedy_on_device
        x = flax_seq(cache, "x", prompts[2])
        adapter.prefill([x])                        # slot 1
        seqs = [flax_seq(cache, f"g{i}", p) for i, p in
                enumerate(prompts[:2])]
        first = adapter.prefill(seqs[:1], tokens_only=only)     # slot 2
        adapter.release("x")
        cache.free("x")
        outs = [np.concatenate([first, adapter.prefill(
            seqs[1:], tokens_only=only)])]      # slot 1: not row order
        assert [adapter._state[s.seq_id]["slot"] for s in seqs] == [2, 1]
        for _ in range(2):
            toks = outs[-1] if only else outs[-1].argmax(-1)
            for s, t in zip(seqs, toks):
                s.tokens.append(int(t))
            outs.append(adapter.decode(seqs, tokens_only=only))
        outs.append(adapter.decode(seqs[:1], tokens_only=only))  # b1
        got.append(outs)
    for toks, logits in zip(*got):
        assert toks.dtype == np.int32 and toks.ndim == 1
        assert toks.tolist() == logits.argmax(-1).tolist()
    eng = LLMEngine(pair[0][0], EngineConfig(
        max_running=2, num_blocks=64, block_size=PAGE, max_seq_len=128))
    toy = LLMEngine(ToyAdapter(), EngineConfig())
    try:
        greedy = Sequence("a", None, [1], SamplingParams())
        sampled = Sequence("b", None, [1], SamplingParams(temperature=0.7),
                           rng=__import__("random").Random(0))
        assert eng._tokens_only([greedy]) == {"tokens_only": True}
        assert eng._tokens_only([greedy, sampled]) == {}
        assert toy._tokens_only([greedy]) == {}
        assert eng._sample(greedy, np.int32(7)) == 7
    finally:
        eng.stop()
        toy.stop()


def test_kimi_engine_counts_slots_experts_and_the_admit_span():
    """Through ``LLMEngine``: 6 requests on 3 slots, so slots are
    released and taken again; tokens are the reference's greedy ones;
    the step log has ``runner.state.admit`` under ``llm.step.prefill``
    and the decode fetch carries the experts the step touched."""
    adapter, _ = _kimi_adapter()
    prompts = token_prompts(47, adapter.vocab_size, (30, 9, 66, 12, 40, 5))
    eng = LLMEngine(adapter, EngineConfig(
        max_running=3, num_blocks=64, block_size=PAGE, max_seq_len=128,
        max_prefill_tokens=64))
    try:
        assert eng.metrics()["state_slots_total"] == 3
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=5))
                for p in prompts]
        served = [drain_stream(eng, sid, timeout=180.0)[0]
                  for sid in sids]
        deadline = time.time() + 10
        while eng.metrics()["state_slots_in_use"] and time.time() < deadline:
            time.sleep(0.05)
        m = eng.metrics()
        log = eng.step_log()
    finally:
        eng.stop()
    for p, toks in zip(prompts, served):
        want = _kimi_reference_rows(p, toks)
        gap = want.max(-1) - want[np.arange(5), toks]
        assert float(gap.max()) <= KIMI_TOL
    assert m["state_slots_in_use"] == 0
    assert np.sum(m["expert_tokens_total"]) > 0
    assert np.shape(m["expert_tokens_last_step"]) == (3, 4)

    def walk(span):
        yield span
        for child in span.get("children", ()):
            yield from walk(child)
    admits = [c["name"] for step in log for s in walk(step)
              if s["name"] == "llm.step.prefill" for c in walk(s)]
    assert admits.count("runner.state.admit") >= 3
    # off the chip the recurrence is the XLA step, and the spans say so
    said = {s["attrs"].get("recurrence") for step in log for d in walk(step)
            if d["name"] == "llm.step.decode" for s in walk(d)
            if s["name"] == "runner.dispatch"}
    assert said == {"xla"} and m["kda_kernel_steps_total"] == 0
    fetches = [s for step in log for d in walk(step)
               if d["name"] == "llm.step.decode" for s in walk(d)
               if s["name"] == "runner.fetch"]
    assert fetches and all(
        0 <= f["attrs"]["experts_touched"] <= 12
        and f["attrs"]["moe_max_over_mean"] >= 1.0
        for f in fetches if f["attrs"].get("expert_tokens"))


def test_kimi_decode_steps_through_the_recurrence_kernel(monkeypatch):
    """Where the chooser says ``kda_kernel`` (here: patched, the kernel
    interpreted; heads of whole 128 x 128 tiles) the adapter's decode
    steps, rows in slot order (a bucket as wide as the slots) and rows
    by ``slots`` (a narrower one), return the logits of the steps that
    gather and scatter, leave the same state in the slots, and are
    counted."""
    import functools

    from benchmark.reference import kimi_linear_glue
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    from ray_tpu.ops import linear_attention as LA
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    cfg = KimiLinearConfig.tiny(kda_num_heads=8, kda_head_dim=128)
    params = kimi_linear_glue.init_for(cfg, 9)

    def serve():
        adapter = FlaxModelAdapter("kimi_linear", cfg, params)
        cache = PagedKVCache(num_blocks=64, block_size=PAGE)
        adapter.bind_cache(cache)
        adapter.bind_state(4)
        prompts = token_prompts(43, adapter.vocab_size, (21, 5, 33))
        seqs = [flax_seq(cache, f"s{i}", p, budget=8)
                for i, p in enumerate(prompts)]
        rows = _kimi_serve(adapter, seqs, 2)             # bucket 4: in order
        adapter.release("s1")
        cache.free("s1")
        rows = _kimi_serve(adapter, [seqs[0], seqs[2]], 2,
                           rows=[rows[0], rows[2]])      # bucket 2: slots
        return adapter, np.stack([np.stack(r) for r in rows])

    plain, want = serve()
    assert plain._decode_recurrence == "xla"
    assert plain.counters()["kda_kernel_steps_total"] == 0
    monkeypatch.setattr(LA, "kda_decode_path",
                        lambda pool, S: "kda_kernel" if S == 1 else "xla")
    monkeypatch.setattr(LA, "kda_recurrent_step_in_place", functools.partial(
        LA.kda_recurrent_step_in_place, interpret=True))
    kernel, got = serve()
    assert kernel._decode_recurrence == "kda_kernel"
    assert kernel.counters()["kda_kernel_steps_total"] == 4
    np.testing.assert_allclose(got, want, atol=KIMI_TOL)
    np.testing.assert_allclose(kernel._arrays["kda_state"],
                               plain._arrays["kda_state"], atol=KIMI_TOL)
    # the null slot is as it was made
    assert float(np.abs(kernel._arrays["kda_state"][:, 0]).max()) == 0.0


@pytest.mark.parametrize("what", [
    "enable_prefix_cache", "spec_k", "decode_window", "rollback",
    "export_kv", "import_kv", "prefill_export", "adopt_request"])
def test_kimi_refuses_what_needs_a_snapshot_of_the_state(what):
    """Dropping cached tokens, sharing them by page and shipping them
    as pages each need the state as it was at that token."""
    from ray_tpu.serve.llm.model_runner import RecurrentStateError
    adapter, cache = _kimi_adapter()
    base = dict(max_running=2, num_blocks=64, block_size=PAGE,
                max_seq_len=128)
    with pytest.raises(RecurrentStateError, match="state") as err:
        if what == "enable_prefix_cache":
            LLMEngine(adapter, EngineConfig(enable_prefix_cache=True,
                                            **base))
        elif what == "spec_k":
            LLMEngine(adapter, EngineConfig(spec_k=2, **base))
        elif what in ("prefill_export", "adopt_request"):
            eng = LLMEngine(adapter, EngineConfig(**base))
            try:
                if what == "prefill_export":
                    eng.prefill_export([1, 2, 3])
                else:
                    eng.adopt_request([1, 2, 3], 4, {"kind": "x"})
            finally:
                eng.stop()
        else:
            seq = flax_seq(cache, "a", [1, 2, 3])
            adapter.prefill([seq])
            {"decode_window": lambda: adapter.decode_window([seq], [[1, 2]]),
             "rollback": lambda: adapter.rollback("a", 1),
             "export_kv": lambda: adapter.export_kv("a", 3),
             "import_kv": lambda: adapter.import_kv("a", 3, {}),
             }[what]()
    assert "snapshot" in str(err.value)
