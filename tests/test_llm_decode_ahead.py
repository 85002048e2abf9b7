"""The engine's look-ahead (docs/LLM_SERVING.md, "The decode step's
order"): with an adapter that feeds a row's greedy token on the device,
decode step n + 1 is dispatched before step n is fetched. Tier-1,
CPU-only.

What it may not change is everything a client or a later sequence can
see. So an engine that looks ahead and one held synchronous serve the
same requests, through the tiny Kimi-Linear (state slots), Kimi-K2 (one
latent pool) and Laguna (a ring a sequence) adapters, and are compared
token for token and, at every ``release``, row for row of what the
sequence left in the pools, the ring and the state. The synchronous
engine is the same engine round ``Synchronous``, a wrapper of the helpers
that withholds the adapter's ``decode_ahead``; the program has no option
for it.

Tokens are compared exactly, rows to ``TOL``. Looking ahead, a prompt
joins the decode batch one step later than it would, so a row shares some
of its steps with other neighbours, in a bucket of another width, and its
sums run in another order: 7e-7 on rows of size 1 (float32 at 'highest'
on both sides, tests/conftest.py). A row another token was fed to, or one
written at another position, differs by 1e-1 and more.

A prefill step's program is left in flight the same way ("what feeds a
prompt's rows"): the decode step behind it is dispatched before the
prompts' first tokens are fetched, its new rows fed on the device. The
cases from ``test_prompts_in_flight_...`` on hold that half.

Every engine of a kind binds the same adapter (a fresh cache, pools and
state each time), so its steps compile once for the file; a prompt is
admitted alone (``max_prefill_tokens=8``), so while one is prefilled the
others decode."""

import importlib
import time

import numpy as np
import pytest
from llm_test_helpers import (PAGE, Synchronous, drain_stream,
                              token_prompts)

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, SamplingParams,
                               ToyAdapter)

KINDS = {"kimi_linear": "KimiLinearConfig", "kimi_k2": "KimiK2Config",
         "laguna": "LagunaConfig"}
ENGINE = dict(max_running=4, num_blocks=96, block_size=PAGE,
              max_seq_len=128, max_prefill_tokens=8)
# 12 requests on 4 slots: (prompt tokens, max_new_tokens)
SHAPES = ((30, 9), (9, 14), (60, 5), (12, 1), (41, 20), (17, 3), (25, 12),
          (50, 7), (10, 16), (33, 2), (21, 10), (45, 6))
TOL = 1e-5
_ADAPTERS, _BASE = {}, {}


def _adapter(kind):
    if kind not in _ADAPTERS:
        from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
        glue = importlib.import_module(f"benchmark.reference.{kind}_glue")
        cfg = getattr(importlib.import_module(f"ray_tpu.models.{kind}"),
                      KINDS[kind]).tiny()
        _ADAPTERS[kind] = FlaxModelAdapter(kind, cfg, glue.init_for(cfg, 7))
    return _ADAPTERS[kind]


def _requests(kind, shapes=SHAPES, **sampling):
    prompts = token_prompts(53, _adapter(kind).vocab_size,
                            [n for n, _ in shapes])
    return [(p, SamplingParams(max_new_tokens=m, **sampling))
            for p, (_, m) in zip(prompts, shapes)]


def _left_behind(adapter, seq_id):
    """What a sequence leaves at its release: its cached length and
    table, the rows of every pool at its cached positions (of a window
    group: the positions a window layer would still read), its state."""
    st = adapter._state[seq_id]
    n, bs = st["len"], adapter.cache.block_size
    out = {"len": n, "table": list(st["table"])}
    for name, p in adapter._spec["pages"].items():
        pool = np.asarray(adapter._arrays[name])
        if p.get("window"):
            ring = np.asarray(st["rings"][p["window"]])
            pos = np.arange(max(0, n - p["window"]), n)
            out[name] = pool[:, ring[(pos // bs) % len(ring)], pos % bs]
        else:
            pos = np.arange(n)
            out[name] = pool[:, np.asarray(st["table"])[pos // bs], pos % bs]
    if adapter.has_state:
        out["state"] = {k: np.asarray(v)
                        for k, v in adapter.state_of(seq_id).items()}
    return out


def _serve(kind, requests, synchronous=False, before=None, skip=(),
           then=None):
    """The requests through an engine of their own. ``before(engine)``
    runs before the first is added, ``then(engine)`` after the last has
    finished; streams in ``skip`` are not read."""
    adapter = _adapter(kind)
    left = {}

    def release(seq_id):
        if seq_id in adapter._state:
            left[seq_id] = _left_behind(adapter, seq_id)
        return type(adapter).release(adapter, seq_id)
    adapter.release = release
    eng = LLMEngine(Synchronous(adapter) if synchronous else adapter,
                    EngineConfig(**ENGINE))
    try:
        if before:
            before(eng)
        sids = [eng.add_request(p, sp, request_id=f"r{i}")
                for i, (p, sp) in enumerate(requests)]
        out = [drain_stream(eng, sid, timeout=240.0) if i not in skip
               else ([], {"finish_reason": "skipped"})
               for i, sid in enumerate(sids)]
        _quiet(eng, adapter)
        more = then(eng) if then else None
        _quiet(eng, adapter)
        return {"tokens": [t for t, _ in out], "then": more,
                "reasons": [c["finish_reason"] for _, c in out],
                "chunks": [c for _, c in out],
                "left": [left.get(sid) for sid in sids],
                "metrics": eng.metrics(), "steps": eng.step_log(),
                "ledger": eng.token_ledger(), "itl": len(eng._itl),
                "free": sorted(eng.cache._free),
                "flying": eng._flying or eng._prompt}
    finally:
        eng.stop()
        for name in ("release", "decode", "prefill"):
            adapter.__dict__.pop(name, None)


def _quiet(eng, adapter):
    """Wait until the last finished sequence is released too (a stream
    is done a moment before)."""
    deadline = time.time() + 30
    while (eng.in_flight() or adapter._state) and time.time() < deadline:
        time.sleep(0.02)


def _baseline(kind):
    """The synchronous engine's answer to the 12 requests."""
    if kind not in _BASE:
        _BASE[kind] = _serve(kind, _requests(kind), synchronous=True)
    return _BASE[kind]


def _walk(span):
    yield span
    for child in span.get("children", ()):
        yield from _walk(child)


def _decode_spans(steps):
    """The spans of the decode steps that were dispatched (a step in
    flight that lands with nothing dispatched has a span without
    ``ahead``)."""
    return [s for step in steps for s in _walk(step)
            if s["name"] == "llm.step.decode" and "ahead" in s["attrs"]]


def _prefill_spans(steps):
    return [s for step in steps for s in _walk(step)
            if s["name"] == "llm.step.prefill"]


def _same_left(a, b, state=True):
    # (which pages a sequence is given depends on which step freed them)
    assert a["len"] == b["len"] and len(a["table"]) == len(b["table"])
    for name in a:
        if name in ("len", "table", "state"):
            continue
        np.testing.assert_allclose(a[name], b[name], atol=TOL, rtol=0,
                                   err_msg=name)
    for k in a.get("state", ()) if state else ():
        np.testing.assert_allclose(a["state"][k], b["state"][k], atol=TOL,
                                   rtol=0, err_msg=k)


def _same_served(ahead, sync, requests, but=()):
    """Tokens, finish reasons and what every release left, but for the
    requests in ``but``; each cached length is the prompt and all but the
    last served token."""
    for i, (prompt, _) in enumerate(requests):
        if i in but:
            continue
        assert ahead["tokens"][i] == sync["tokens"][i], i
        assert ahead["reasons"][i] == sync["reasons"][i], i
        a, s = ahead["left"][i], sync["left"][i]
        # (a row ended by its stop token is in the step dispatched before
        # the host saw it: that step's update of its state is discarded
        # with the slot)
        _same_left(a, s, state=ahead["reasons"][i] == "length")
        assert a["len"] == len(prompt) + len(ahead["tokens"][i]) - 1


def _clean(run, kind):
    """Nothing left allocated, in flight or freed twice."""
    m = run["metrics"]
    assert run["flying"] is None
    assert m["kv_blocks_used"] == 0
    assert run["free"] == list(range(1, ENGINE["num_blocks"]))
    if kind == "kimi_linear":
        assert m["state_slots_in_use"] == 0
    for group in m.get("kv_window_groups", {}).values():
        assert group["blocks_used"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_mixed_requests_are_served_as_the_synchronous_engine_serves_them(
        kind):
    """12 requests of mixed prompt and output lengths on 4 slots, one
    with ``max_new_tokens=1`` (it never decodes); a prompt is admitted
    while the others decode."""
    requests = _requests(kind)
    sync, ahead = _baseline(kind), _serve(kind, requests)
    _same_served(ahead, sync, requests)
    assert [len(t) for t in ahead["tokens"]] == [m for _, m in SHAPES]
    assert set(ahead["reasons"]) == {"length"}
    _clean(ahead, kind)
    m = ahead["metrics"]
    assert sync["metrics"]["decode_steps_ahead_total"] == 0
    assert m["decode_steps_ahead_total"] > 0
    assert m["decode_tokens_discarded_total"] \
        == sync["metrics"]["decode_tokens_discarded_total"] == 0
    assert sorted(ahead["ledger"]) == sorted(sync["ledger"])
    spans = _decode_spans(ahead["steps"])
    assert sum(s["attrs"]["ahead"] for s in spans) \
        == m["decode_steps_ahead_total"] >= 0.8 * len(spans)
    assert not any(s["attrs"]["ahead"]
                   for s in _decode_spans(sync["steps"]))
    # every prompt's program was left in flight, none of the other's
    assert m["prefill_steps_ahead_total"] == m["prefill_steps_total"] == 12
    assert sync["metrics"]["prefill_steps_ahead_total"] == 0
    assert all(s["attrs"]["ahead"] for s in _prefill_spans(ahead["steps"]))
    assert not any(s["attrs"]["ahead"]
                   for s in _prefill_spans(sync["steps"]))
    # one program a bucket, whoever fed the token
    fns = {k: fn for k, fn in _adapter(kind)._fns.items()
           if isinstance(k, tuple)}
    assert m["bucket_first_calls_total"] == len(fns)
    assert {fn._cache_size() for fn in fns.values()} == {1}


@pytest.mark.parametrize("kind", KINDS)
def test_a_stop_tokens_row_is_in_the_next_step_and_its_token_discarded(
        kind):
    """Three of the 12 requests end by a ``stop_token`` at a decode step,
    which the host learns a step late: the token of the step after is
    counted as discarded and nowhere else, and the sequences admitted
    into the freed pages, slot and ring are served as ever."""
    base = _baseline(kind)["tokens"]
    requests, want = _requests(kind), {}
    for i in (0, 4, 6):
        toks = base[i]
        k = next(k for k in range(2, len(toks) - 1)
                 if toks[k] not in toks[:k])
        requests[i] = (requests[i][0], SamplingParams(
            max_new_tokens=len(toks), stop_token=toks[k]))
        want[i] = toks[:k + 1]
    ahead = _serve(kind, requests)
    sync = _serve(kind, requests, synchronous=True)
    _same_served(ahead, sync, requests)
    for i, toks in enumerate(base):
        assert ahead["tokens"][i] == want.get(i, toks)
        assert ahead["reasons"][i] == ("stop" if i in want else "length")
    _clean(ahead, kind)
    m = ahead["metrics"]
    assert m["decode_tokens_discarded_total"] == 3
    assert sync["metrics"]["decode_tokens_discarded_total"] == 0
    served = sum(len(t) for t in ahead["tokens"])
    assert m["generated_tokens_total"] == served
    assert ahead["itl"] == served - len(requests)
    assert sorted(ahead["ledger"]) == sorted(sync["ledger"])
    # a later sequence was given pages a stopped row had been writing to
    for i in want:
        freed = set(ahead["left"][i]["table"])
        assert any(freed & set(ahead["left"][j]["table"])
                   for j in range(4, len(requests)) if j != i)


def _at_decode(kind, n, act):
    """``before`` for ``_serve``: ``act(engine)`` in the engine's thread,
    inside the adapter's n-th decode call, after its own work."""
    def before(eng):
        adapter, calls = _adapter(kind), [0]

        def decode(seqs, **kwargs):
            out = type(adapter).decode(adapter, seqs, **kwargs)
            calls[0] += 1
            if calls[0] == n:
                act(eng)
            return out
        adapter.decode = decode
    return before


@pytest.mark.parametrize("kind", KINDS)
def test_a_row_cancelled_in_the_dispatched_step_frees_its_pages_once(kind):
    """``cancel`` of the second request right after the fifth decode call
    has dispatched it (and, looking ahead, with the call before it still
    in flight): both its tokens in flight are discarded, its pages, ring
    and slot go back once, and every other request is served as ever."""
    requests = _requests(kind)
    runs = [_serve(kind, requests, synchronous=sync, skip=(1,),
                   before=_at_decode(kind, 5,
                                     lambda eng: eng.cancel("seq-2")))
            for sync in (False, True)]
    _same_served(*runs, requests, but=(1,))
    for run in runs:
        _clean(run, kind)
        assert all(r[0] != "r1" for r in run["ledger"])
    assert [r["metrics"]["decode_tokens_discarded_total"]
            for r in runs] == [2, 1]


@pytest.mark.parametrize("kind", KINDS)
def test_a_decode_call_that_raises_fails_the_step_in_flight_too(kind):
    """The fourth decode call raises with the third in flight: every
    running and waiting sequence fails with the error, nothing stays in
    flight or allocated, and the requests that follow are served as
    ever."""
    def boom(eng):
        raise RuntimeError("the chip fell over")
    picked = (0, 1, 2, 4, 5, 6)     # (none that ends at its prefill)
    requests = _requests(kind, [SHAPES[i] for i in picked])

    def then(eng):
        assert eng._flying is None and eng.metrics()["kv_blocks_used"] == 0
        sids = [eng.add_request(p, sp, request_id=f"again{i}")
                for i, (p, sp) in enumerate(requests)]
        return [drain_stream(eng, sid, timeout=240.0) for sid in sids]
    run = _serve(kind, requests, before=_at_decode(kind, 4, boom), then=then)
    assert run["reasons"] == ["error"] * 6
    _clean(run, kind)
    again = _serve(kind, requests, synchronous=True)["tokens"]
    for want, (toks, chunk) in zip(again, run["then"]):
        assert toks == want and chunk["finish_reason"] == "length"


@pytest.mark.parametrize("kind", KINDS)
def test_a_sampled_row_makes_the_steps_synchronous_while_it_runs(kind):
    """The fifth of six requests samples (temperature 0.7): the steps it
    is in fetch logits and run synchronously, those before it joins and
    after it leaves look ahead, and all six are served as by the
    synchronous engine."""
    requests = _requests(kind, ((30, 24), (9, 24), (20, 6), (12, 24),
                                (15, 5), (17, 8)))
    requests[4] = (requests[4][0], SamplingParams(
        max_new_tokens=5, temperature=0.7, seed=11))
    ahead = _serve(kind, requests)
    sync = _serve(kind, requests, synchronous=True)
    _same_served(ahead, sync, requests)
    _clean(ahead, kind)
    flags = [s["attrs"]["ahead"] for s in _decode_spans(ahead["steps"])]
    first, last = flags.index(False, 1), len(flags) - flags[::-1].index(False)
    assert any(flags[:first]) and any(flags[last:])
    assert 4 <= flags[first:last].count(False)
    # (the first step after the sampled row left has nothing in flight)
    assert flags[last - 1] is False and all(flags[last:])
    # its own prompt is fetched at once; those before it were left in
    # flight
    left = [s["attrs"]["ahead"] for s in _prefill_spans(ahead["steps"])]
    assert left[:4] == [True] * 4 and left[4] is False
    assert ahead["metrics"]["prefill_steps_ahead_total"] == sum(left) < 6



# ------------------------------------------------------ prompts in flight

# 16 requests on 4 slots, a prompt a step: answers of one to four tokens,
# so a slot is taken again as soon as it is left
BUSY = ((30, 2), (9, 3), (20, 1), (12, 4), (41, 2), (17, 1), (25, 3),
        (50, 2), (10, 4), (33, 1), (21, 2), (45, 3), (14, 1), (28, 2),
        (11, 3), (37, 2))


@pytest.mark.parametrize("kind", KINDS)
def test_prompts_in_flight_are_served_as_the_synchronous_engine_serves_them(
        kind):
    """A prompt arrives every step and slots are taken again at once:
    every prompt's program is left in flight, the decode step behind it
    feeds its rows' first tokens on the device, and tokens and what each
    release leaves are the synchronous engine's. A prompt whose budget is
    one token is in no decode step (the rows dispatched are those of the
    tokens served after the first, none discarded)."""
    requests = _requests(kind, BUSY)
    ahead = _serve(kind, requests)
    sync = _serve(kind, requests, synchronous=True)
    _same_served(ahead, sync, requests)
    assert [len(t) for t in ahead["tokens"]] == [m for _, m in BUSY]
    _clean(ahead, kind)
    m = ahead["metrics"]
    assert m["prefill_steps_ahead_total"] == m["prefill_steps_total"] \
        == len(BUSY)
    assert sync["metrics"]["prefill_steps_ahead_total"] == 0
    assert m["decode_tokens_discarded_total"] == 0
    assert m["decode_rows_total"] == sync["metrics"]["decode_rows_total"] \
        == sum(m - 1 for _, m in BUSY)
    assert sorted(ahead["ledger"]) == sorted(sync["ledger"])
    # slots were reused while prompts flew: more prompts than steps
    # without one
    steps = [[c["name"] for c in st["children"]] for st in ahead["steps"]]
    with_prompt = [names for names in steps if "llm.step.prefill" in names]
    assert len(with_prompt) == len(BUSY) > len(steps) - len(with_prompt)
    # a first token reached its client when its prompt was fetched
    assert all(r["ttft_s"] > 0 for r in ahead["chunks"])


@pytest.mark.parametrize("kind", KINDS)
def test_a_first_token_that_stops_is_in_the_next_step_and_discarded(kind):
    """Two requests whose FIRST token is their ``stop_token``: the host
    learns it when the prompt is fetched, after the decode step behind it
    was dispatched with the row in it. That step's token is discarded,
    the sequence leaves the prompt and nothing else, and whoever takes
    its pages, ring and slot is served as ever."""
    base = _baseline(kind)["tokens"]
    requests = _requests(kind)
    for i in (2, 5):
        requests[i] = (requests[i][0], SamplingParams(
            max_new_tokens=SHAPES[i][1], stop_token=base[i][0]))
    ahead = _serve(kind, requests)
    sync = _serve(kind, requests, synchronous=True)
    _same_served(ahead, sync, requests)
    for i, toks in enumerate(base):
        assert ahead["tokens"][i] == (toks[:1] if i in (2, 5) else toks)
        assert ahead["reasons"][i] == ("stop" if i in (2, 5) else "length")
    _clean(ahead, kind)
    assert ahead["metrics"]["decode_tokens_discarded_total"] == 2
    assert sync["metrics"]["decode_tokens_discarded_total"] == 0
    assert ahead["metrics"]["prefill_steps_ahead_total"] == 12
    assert sorted(ahead["ledger"]) == sorted(sync["ledger"])


@pytest.mark.parametrize("kind", KINDS)
def test_a_prompt_cancelled_in_flight_frees_its_pages_once(kind):
    """``cancel`` of the third request inside the decode call that
    follows its prefill step: its prompt's program is still in flight,
    and the decode step just dispatched holds its row. Both tokens are
    discarded, its pages, ring and slot go back once, and every other
    request is served as ever."""
    requests = _requests(kind)
    seen = []

    def cancel(eng):
        prompt = _adapter(kind)._flying_prompt
        seen.append(prompt is not None and list(prompt.at))
        eng.cancel("seq-3")
    runs = [_serve(kind, requests, synchronous=sync, skip=(2,),
                   before=_at_decode(kind, 3, cancel))
            for sync in (False, True)]
    assert seen == [["seq-3"], False]
    _same_served(*runs, requests, but=(2,))
    for run in runs:
        _clean(run, kind)
        assert all(r[0] != "r2" for r in run["ledger"])
    assert [r["metrics"]["decode_tokens_discarded_total"]
            for r in runs] == [2, 1]
    spans = _prefill_spans(runs[0]["steps"])
    assert spans[2]["attrs"]["ahead"] is True


@pytest.mark.parametrize("kind", KINDS)
def test_a_prefill_call_that_raises_fails_the_step_in_flight_too(kind):
    """The fourth prefill call raises with the decode step behind the
    third prompt in flight: every admitted, running and waiting sequence
    fails with the error, nothing stays in flight or allocated, and the
    requests that follow are served as ever."""
    picked = (0, 1, 2, 4, 5, 6)
    requests = _requests(kind, [SHAPES[i] for i in picked])

    def before(eng):
        adapter, calls = _adapter(kind), [0]

        def prefill(seqs, **kwargs):
            calls[0] += 1
            if calls[0] == 4:
                assert eng._flying is not None
                raise RuntimeError("the chip fell over")
            return type(adapter).prefill(adapter, seqs, **kwargs)
        adapter.prefill = prefill

    def then(eng):
        assert eng._flying is None and eng._prompt is None \
            and eng.metrics()["kv_blocks_used"] == 0
        sids = [eng.add_request(p, sp, request_id=f"again{i}")
                for i, (p, sp) in enumerate(requests)]
        return [drain_stream(eng, sid, timeout=240.0) for sid in sids]
    run = _serve(kind, requests, before=before, then=then)
    assert run["reasons"] == ["error"] * 6
    _clean(run, kind)
    again = _serve(kind, requests, synchronous=True)["tokens"]
    for want, (toks, chunk) in zip(again, run["then"]):
        assert toks == want and chunk["finish_reason"] == "length"


def test_a_prefill_role_prompt_is_fetched_at_once():
    """``prefill_export`` (Kimi-K2: pages and no state) beside greedy
    requests: the step that admits it fetches its prompt synchronously,
    its snapshot is the synchronous engine's, the others' prompts are
    left in flight."""
    kind = "kimi_k2"
    requests = _requests(kind, SHAPES[:3])
    prompt = _requests(kind, ((19, 4),))[0][0]

    def then(eng):
        sid = eng.prefill_export(prompt, SamplingParams(max_new_tokens=4))
        toks, _ = drain_stream(eng, sid, timeout=240.0)
        blob = eng.take_export(sid)
        return toks, blob
    ahead, sync = (_serve(kind, requests, synchronous=s, then=then)
                   for s in (False, True))
    _same_served(ahead, sync, requests)
    (toks, blob), (want, want_blob) = ahead["then"], sync["then"]
    assert toks == want and len(toks) == 1
    assert blob["first_token"] == want_blob["first_token"] == toks[0]
    for name, pages in blob["kv"]["pages"].items():
        np.testing.assert_allclose(
            np.asarray(pages, np.float32),
            np.asarray(want_blob["kv"]["pages"][name], np.float32),
            atol=TOL, rtol=0, err_msg=name)
    left = [s["attrs"]["ahead"] for s in _prefill_spans(ahead["steps"])]
    assert left == [True, True, True, False]
    assert ahead["metrics"]["prefill_steps_ahead_total"] == 3
    _clean(ahead, kind)



@pytest.mark.parametrize("case", ("logits", "sampled", "spec_k",
                                  "prefill_role"))
def test_no_prompt_is_left_in_flight_where_the_host_needs_it_at_once(case):
    """``prefill_steps_ahead_total`` stays 0, every ``llm.step.prefill``
    says ``ahead`` false and the requests are served: an adapter that
    returns logits; rows that sample; an engine that drafts (``spec_k``);
    prefill-role requests (the last three on Kimi-K2, which has
    ``decode_ahead``). What the engine observes decides, not an
    option."""
    config = dict(max_running=4, num_blocks=64, block_size=PAGE,
                  max_seq_len=64)
    adapter = ToyAdapter() if case == "logits" else _adapter("kimi_k2")
    if case == "spec_k":
        config.update(spec_k=2, draft_model="toy", draft_model_config={
            "vocab_size": adapter.vocab_size})
    sampling = SamplingParams(max_new_tokens=4, **(
        {"temperature": 0.7, "seed": 3} if case == "sampled" else {}))
    eng = LLMEngine(adapter, EngineConfig(**config))
    try:
        add = eng.prefill_export if case == "prefill_role" \
            else eng.add_request
        sids = [add(p, sampling) for p in token_prompts(
            61, adapter.vocab_size, (9, 14, 6))]
        served = [drain_stream(eng, sid, timeout=240.0)[0] for sid in sids]
        deadline = time.time() + 30
        while eng.in_flight() and time.time() < deadline:
            time.sleep(0.02)
    finally:
        eng.stop()
    # (a step's tree is logged when the step returns: read once the
    # engine's thread has ended)
    metrics, steps = eng.metrics(), eng.step_log()
    assert [len(t) for t in served] \
        == [1 if case == "prefill_role" else 4] * 3
    assert metrics["prefill_steps_total"] >= 1
    assert metrics["prefill_steps_ahead_total"] == 0
    spans = _prefill_spans(steps)
    assert spans and not any(s["attrs"]["ahead"] for s in spans)
    assert eng._prompt is None and eng._flying is None


# ------------------------------------------------------- the step's order

class RecordingAdapter:
    """A stub that says it can decode ahead and writes down the order of
    the engine's calls. A row's token is its cached length."""
    greedy_on_device = decode_ahead = True

    def __init__(self):
        self.calls, self._len = [], {}

    def bind_cache(self, cache):
        self.cache = cache

    def prefill(self, seqs, tokens_only=False, fetch=True):
        assert tokens_only and not fetch
        k = 1 + sum(1 for c in self.calls if c[0] == "prompt")
        self.calls.append(("prompt", k, [s.seq_id for s in seqs]))
        self._len.update({s.seq_id: len(s.prompt) for s in seqs})
        out = np.asarray([len(s.prompt) for s in seqs])
        step = type("Step", (), {})()
        step.fetch = lambda: self.calls.append(("prompt_fetch", k)) or out
        step.wait = lambda: self.calls.append(("prompt_wait", k))
        return step

    def decode(self, seqs, tokens_only=False, fetch=True):
        assert tokens_only and not fetch
        n = 1 + sum(1 for c in self.calls if c[0] == "dispatch")
        self.calls.append(("dispatch", n, [len(s.tokens) for s in seqs]))
        for s in seqs:
            self._len[s.seq_id] += 1
        out = np.asarray([self._len[s.seq_id] for s in seqs])
        step = type("Step", (), {})()
        step.fetch = lambda: self.calls.append(("fetch", n)) or out
        step.wait = lambda: self.calls.append(("wait", n))
        return step

    def release(self, seq_id):
        self.calls.append(("release", seq_id))


def test_step_n_plus_1_is_dispatched_before_step_n_is_fetched():
    """A steady batch of three: their prompts' program, then dispatch(1)
    before the prompts are fetched, then dispatch(n + 1) before fetch(n)
    every step, ``ahead`` on each of those; the rows whose budget the
    step in flight fills are not in the next; the last fetch has nothing
    dispatched before it."""
    adapter = RecordingAdapter()
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=64, block_size=PAGE, max_seq_len=64,
        max_prefill_tokens=64))
    try:
        sids = [eng.add_request([1] * n, SamplingParams(max_new_tokens=m))
                for n, m in ((5, 6), (9, 6), (7, 4))]
        served = [drain_stream(eng, sid)[0] for sid in sids]
        deadline = time.time() + 10
        while eng.in_flight() and time.time() < deadline:
            time.sleep(0.02)
        steps, metrics = eng.step_log(), eng.metrics()
    finally:
        eng.stop()
    assert served == [[5, 6, 7, 8, 9, 10], [9, 10, 11, 12, 13, 14],
                      [7, 8, 9, 10]]
    order = [c[:2] for c in adapter.calls if c[0] in ("dispatch", "fetch")]
    n_steps = max(n for _, n in order)
    assert n_steps >= 5
    assert order == [("dispatch", 1)] + [
        c for n in range(1, n_steps)
        for c in (("dispatch", n + 1), ("fetch", n))] + [("fetch", n_steps)]
    # the decode program after the prompts is dispatched before they are
    # fetched, and they are fetched before the one after it
    kinds = [c[:2] for c in adapter.calls
             if c[0] in ("prompt", "dispatch", "prompt_fetch")]
    assert kinds[:4] == [("prompt", 1), ("dispatch", 1),
                         ("prompt_fetch", 1), ("dispatch", 2)]
    # no row was dispatched past its budget (6, 6 and 4 tokens, the first
    # of each from its prefill)
    assert sum(len(c[2]) for c in adapter.calls
               if c[0] == "dispatch") == 5 + 5 + 3
    # (the first had the prompts in flight before it, no step)
    assert [s["attrs"]["ahead"] for s in _decode_spans(steps)] \
        == [True] * n_steps
    assert metrics["decode_steps_ahead_total"] == n_steps
    assert metrics["prefill_steps_ahead_total"] == 1
    assert metrics["decode_tokens_discarded_total"] == 0
    # a sequence is released with no program in flight: the step
    # dispatched last has been waited for, or fetched
    releases = [i for i, c in enumerate(adapter.calls) if c[0] == "release"]
    assert len(releases) == 3
    for i in releases:
        before = adapter.calls[:i]
        last = max(c[1] for c in before if c[0] == "dispatch")
        assert ("wait", last) in before or ("fetch", last) in before


def test_a_release_waits_for_the_newest_program_in_flight():
    """A prompt a step (each admitted alone) while the others decode:
    every prompt's program has the decode step behind it dispatched
    before it is fetched, and whenever a sequence is released the program
    dispatched LAST, a prompt's or a decode step's, has been waited for
    or fetched: the device runs them in order, so nothing is in flight
    beside the release."""
    adapter = RecordingAdapter()
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=64, block_size=PAGE, max_seq_len=64,
        max_prefill_tokens=8))
    try:
        sids = [eng.add_request([1] * 8, SamplingParams(max_new_tokens=m))
                for m in (3, 3, 5, 1, 2, 4)]
        served = [drain_stream(eng, sid)[0] for sid in sids]
        deadline = time.time() + 10
        while eng.in_flight() and time.time() < deadline:
            time.sleep(0.02)
        metrics = eng.metrics()
    finally:
        eng.stop()
    assert [len(t) for t in served] == [3, 3, 5, 1, 2, 4]
    assert metrics["prefill_steps_ahead_total"] == 6
    assert metrics["decode_tokens_discarded_total"] == 0
    calls = adapter.calls
    for k in range(1, 7):
        sent = calls.index(("prompt", k, [f"seq-{k}"]))
        behind = next(i for i in range(sent, len(calls))
                      if calls[i][0] == "dispatch")
        fetched = calls.index(("prompt_fetch", k))
        assert sent < behind < fetched
        # (the fourth, of one token, is in no decode step)
        assert (k != 4) == (calls[behind][2][-1] == 0)
        nxt = [i for i, c in enumerate(calls) if c[:2] == ("prompt", k + 1)]
        assert not nxt or fetched < nxt[0]
    done = {("dispatch", "wait"): "fetch", ("prompt", "prompt_wait"):
            "prompt_fetch"}
    releases = [i for i, c in enumerate(calls) if c[0] == "release"]
    assert len(releases) == 6
    for i in releases:
        before = [c[:2] for c in calls[:i]]
        what, n = [c for c in before if c[0] in ("dispatch", "prompt")][-1]
        wait = "wait" if what == "dispatch" else "prompt_wait"
        assert (wait, n) in before or (done[(what, wait)], n) in before


class RecordingToy(ToyAdapter):
    def __init__(self):
        super().__init__()
        self.calls = []

    def prefill(self, seqs, **kwargs):
        self.calls.append(("prefill", len(seqs), kwargs))
        return super().prefill(seqs, **kwargs)

    def decode(self, seqs, **kwargs):
        self.calls.append(("decode", [len(s.tokens) for s in seqs], kwargs))
        return super().decode(seqs, **kwargs)


def test_an_adapter_that_returns_logits_is_called_as_ever():
    """``decode(seqs)`` with no argument beside, once a step, each step's
    tokens committed before the next is asked for; no step is ahead and
    nothing is discarded. The step log is read once the engine's thread
    has ended: the last token reaches the client from inside its step,
    whose tree is logged when the step returns."""
    adapter = RecordingToy()
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=64, block_size=PAGE, max_seq_len=64))
    try:
        sid = eng.add_request([3, 1, 4, 1, 5], SamplingParams(
            max_new_tokens=6))
        toks = drain_stream(eng, sid)[0]
    finally:
        eng.stop()
    steps, metrics = eng.step_log(), eng.metrics()
    assert len(toks) == 6
    assert adapter.calls == [("prefill", 1, {})] + [
        ("decode", [n], {}) for n in range(1, 6)]
    assert metrics["decode_steps_ahead_total"] == 0
    assert metrics["decode_tokens_discarded_total"] == 0
    spans = [s for step in steps for s in _walk(step)
             if s["name"] == "llm.step.decode"]
    assert len(spans) == 5
    assert all(s["attrs"] == {"n": 1, "ahead": False} for s in spans)
    for step in steps:      # decode, then its commit, inside one step
        names = [c["name"] for c in step["children"]]
        if "llm.step.decode" in names:
            assert names[:2] == ["llm.step.decode", "llm.step.commit"]


class FailingToy(ToyAdapter):
    """Its third decode call raises, and so does every release after
    it until ``mended``."""
    calls, mended = 0, False

    def decode(self, seqs):
        self.calls += 1
        if self.calls == 3:
            raise RuntimeError("step failed")
        return super().decode(seqs)

    def release(self, seq_id):
        if self.calls >= 3 and not self.mended:
            raise RuntimeError("release failed too")
        return super().release(seq_id)


def test_a_release_that_raises_while_a_step_fails_leaves_the_engine_running():
    adapter = FailingToy()
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=64, block_size=PAGE, max_seq_len=64))
    try:
        sid = eng.add_request([3, 1, 4], SamplingParams(max_new_tokens=8))
        toks, chunk = drain_stream(eng, sid)
        assert chunk["finish_reason"] == "error" and len(toks) == 3
        assert "step failed" in chunk["error"] \
            and "release failed too" in chunk["error"]
        assert eng._thread.is_alive() and eng.cache.stats()[
            "kv_blocks_used"] == 0
        adapter.mended = True
        sid = eng.add_request([3, 1, 4], SamplingParams(max_new_tokens=4))
        assert len(drain_stream(eng, sid)[0]) == 4
    finally:
        eng.stop()
