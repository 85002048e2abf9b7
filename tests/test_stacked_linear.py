"""``ops.linear.stacked_linear``: a layer's product over float32 weights
stacked on a layer axis, the kernel interpreted against the plain product,
the chooser on each side of its conditions, and GPT-2's served (stacked)
form through both of the chooser's paths against the training form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as A
from ray_tpu.ops import linear as LN

_KERNEL = functools.partial(LN.stacked_linear_kernel, interpret=True)
L = 3


@functools.lru_cache(maxsize=1)
def _stack(K, N):
    rng = np.random.default_rng(K + N)
    return (jnp.asarray(rng.normal(size=(L, K, N)) * 0.02, jnp.float32),
            jnp.asarray(rng.normal(size=(L, N)) * 0.02, jnp.float32))


@pytest.mark.parametrize("layer", [1, L - 1], ids=["middle", "last"])
@pytest.mark.parametrize("M", [16, 512])
@pytest.mark.parametrize("K,N", [(1280, 3840), (1280, 1280), (1280, 5120),
                                 (5120, 1280)],
                         ids=["c_attn", "attn.c_proj", "c_fc", "mlp.c_proj"])
def test_kernel_is_the_bfloat16_product_with_float32_sums(K, N, M, layer):
    """GPT-2 large's four products: bfloat16 rows against the layer's
    float32 matrix rounded to bfloat16, summed in float32, the bias added
    to the sum, rounded once."""
    w, b = _stack(K, N)
    x = jnp.asarray(np.random.default_rng(M).normal(size=(M, K)),
                    jnp.bfloat16)
    got = _KERNEL(x, w, b, layer)
    assert got.shape == (M, N) and got.dtype == jnp.bfloat16
    want = jnp.dot(x, w[layer].astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) + b[layer]
    # (a sum carried over K tiles adds in another order than one dot:
    # float32 roundings, under the one to bfloat16)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want.astype(jnp.bfloat16), np.float32),
        rtol=2 ** -7, atol=2e-3)


@pytest.mark.parametrize("M", [5, 48, 1024])
def test_kernel_pads_rows_and_carries_sums(M):
    """Fewer rows than a sublane tile (a small decode bucket), rows that
    are whole tiles, the most rows the chooser sends; K in four tiles with
    the sum carried; the layer a loop's counter, as the model hands it."""
    w, b = _stack(5120, 256)
    x = jnp.asarray(np.random.default_rng(M).normal(size=(M, 5120)),
                    jnp.bfloat16)
    got = jax.lax.fori_loop(
        0, L, lambda i, acc: acc.at[i].set(_KERNEL(x, w, b, i)),
        jnp.zeros((L, M, 256), jnp.bfloat16))
    for layer in range(L):
        want = jnp.dot(x, w[layer].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32) + b[layer]
        np.testing.assert_allclose(
            np.asarray(got[layer], np.float32), np.asarray(want),
            rtol=2 ** -7, atol=2e-3)


def test_stacked_linear_path(monkeypatch):
    """``"kernel"`` only for bfloat16 rows, at most 1,024 of them, and a
    float32 stack of whole tiles on one chip."""
    x = jax.ShapeDtypeStruct((16, 1280), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((36, 1280, 3840), jnp.float32)
    assert LN.stacked_linear_path(x, w) == "xla"            # the CPU
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert LN.stacked_linear_path(x, w) == "kernel"
    for M in (1, 512, 1024):
        rows = jax.ShapeDtypeStruct((2, M // 2, 1280) if M > 1 else (M, 1280),
                                    jnp.bfloat16)
        assert LN.stacked_linear_path(rows, w) == "kernel"
    # more rows than a cell's longest program: XLA's product, as before
    assert LN.stacked_linear_path(
        jax.ShapeDtypeStruct((2048, 1280), jnp.bfloat16), w) == "xla"
    # float32 rows: no cast of the weights at all, and a float32 product
    assert LN.stacked_linear_path(
        jax.ShapeDtypeStruct((16, 1280), jnp.float32), w) == "xla"
    # weights that are bfloat16 already: no cast to keep out of HBM
    assert LN.stacked_linear_path(
        x, jax.ShapeDtypeStruct(w.shape, jnp.bfloat16)) == "xla"
    # no whole tiles (the tiny configurations' widths under 128)
    assert LN.stacked_linear_path(
        jax.ShapeDtypeStruct((16, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 64, 192), jnp.float32)) == "xla"
    # one matrix, not a stack
    assert LN.stacked_linear_path(
        x, jax.ShapeDtypeStruct((1280, 3840), jnp.float32)) == "xla"

    class Mesh:
        size = 4
    with A.attention_mesh(Mesh()):
        assert LN.stacked_linear_path(x, w) == "xla"


def _both_forms(dtype, vocab_size=512):
    import dataclasses

    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm.model_runner import _stack_blocks
    cfg = dataclasses.replace(gpt2.GPT2Config.tiny(vocab_size), dtype=dtype)
    plain, served = gpt2.GPT2(cfg), gpt2.GPT2(cfg, stacked=True)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    params = plain.init(jax.random.PRNGKey(0), ids)
    # biases and gains that are not their initial 0 and 1
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * np.random.default_rng(p.size).normal(
            size=p.shape).astype(p.dtype), params)
    return cfg, plain, served, params, _stack_blocks(
        params, "h", cfg.n_layer), ids


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 6e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_served_form_is_the_training_form(monkeypatch, path, dtype, tol):
    """``GPT2(stacked=True)`` over the stacked tree: the training form's
    logits and, step by step, the same rows in the caches, whichever
    product ``stacked_linear_path`` names. Its own ``init`` makes the
    tree ``_stack_blocks`` makes."""
    from ray_tpu.models import gpt2
    cfg, plain, served, params, stacked, ids = _both_forms(dtype)
    own = served.init(jax.random.PRNGKey(1), ids)
    assert jax.tree_util.tree_map(lambda p: (p.shape, p.dtype), own) \
        == jax.tree_util.tree_map(lambda p: (p.shape, p.dtype), stacked)
    ran = []
    if path == "kernel":
        monkeypatch.setattr(LN, "stacked_linear_path",
                            lambda x, w: "kernel")
        monkeypatch.setattr(
            LN, "stacked_linear_kernel",
            lambda *a, **k: ran.append(a[1].shape) or _KERNEL(*a, **k))
    want = plain.apply(params, ids)
    got = served.apply(stacked, ids)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    E = cfg.n_embd      # (flax traces a scanned block more than once)
    assert set(ran) == ({(cfg.n_layer, *kn) for kn in (
        (E, 3 * E), (E, E), (E, 4 * E), (4 * E, E))}
        if path == "kernel" else set()), ran
    # incremental over a paged pool: a prefill of 5 tokens, then 3 one
    # at a time; both forms write the same rows into their pools
    hd = cfg.n_embd // cfg.n_head
    pools = [{"k_pages": jnp.zeros((cfg.n_layer, 5, 4, cfg.n_head * hd),
                                   dtype),
              "v_pages": jnp.zeros((cfg.n_layer, 5, 4, cfg.n_head * hd),
                                   dtype),
              "block_tables": jnp.array([[1, 2], [3, 4]])}
             for _ in range(2)]
    lengths = jnp.zeros((2,), jnp.int32)
    for at, n in ((0, 5), (5, 1), (6, 1), (7, 1)):
        outs = []
        for i, (model, tree) in enumerate(((plain, params),
                                           (served, stacked))):
            logits, pools[i] = model.apply(
                tree, ids[:, at:at + n], kv_cache=pools[i],
                seq_lengths=lengths)
            outs.append(logits)
        lengths = lengths + n
        np.testing.assert_allclose(np.asarray(outs[1], np.float32),
                                   np.asarray(outs[0], np.float32), atol=tol)
    for name in ("k_pages", "v_pages"):
        assert np.any(np.asarray(pools[0][name][:, 1:], np.float32))
        np.testing.assert_allclose(
            np.asarray(pools[1][name], np.float32),
            np.asarray(pools[0][name], np.float32), atol=tol)


def test_model_says_what_its_products_run(monkeypatch):
    """``gpt2.linear_path`` puts the chooser's question to every stack of
    a block, with the configuration's dtype for the rows: one word where
    the four agree, ``"mixed"`` where one product's shape decides
    otherwise; an adapter of a model without such products has no
    chooser and says nothing."""
    import dataclasses

    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    cfg, _, _, _, stacked, _ = _both_forms(jnp.bfloat16)
    served, other = FlaxModelAdapter("gpt2", cfg), FlaxModelAdapter("llama")
    assert gpt2.linear_path(cfg, stacked, 16) == "xla"          # the CPU
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert gpt2.linear_path(cfg, stacked, 16) == "kernel"
    assert gpt2.linear_path(cfg, stacked, 2048) == "xla"
    assert gpt2.linear_path(
        dataclasses.replace(cfg, dtype=jnp.float32), stacked, 16) == "xla"
    odd = jax.tree_util.tree_map(lambda p: p, stacked)
    fc = odd["params"]["h"]["mlp"]["c_fc"]
    fc["kernel"] = fc["kernel"][:, :, :200]     # no whole lane tiles
    assert gpt2.linear_path(cfg, odd, 16) == "mixed"
    assert served._linear_path(16) == "kernel"
    assert other._linear_path(16) is None


def test_served_lookup_casts_the_rows_it_takes_not_the_tables():
    """The stacked form's program casts no whole stack and no table for
    its lookups (the tied head's product still names the token table in
    the activation's dtype, a cast the chip's compiler fuses into the
    product: tests/test_chip_compile.py); the training form's lookup is
    ``nn.Embed``'s, as it was."""
    import re
    # (a vocabulary that is no width of a block's matrices)
    cfg, plain, served, params, stacked, ids = _both_forms(jnp.bfloat16, 320)

    def casts(model, tree):
        text = jax.jit(model.apply).lower(tree, ids).as_text()
        return [m.group(1) for m in re.finditer(
            r"stablehlo\.convert %\S+ : \(tensor<([\dx]+)xf32>\) -> "
            r"tensor<[\dx]+xbf16>", text)]
    table = f"{cfg.vocab_size}x{cfg.n_embd}"
    positions = f"{cfg.n_positions}x{cfg.n_embd}"
    was = casts(plain, params)
    assert was.count(table) == 2 and was.count(positions) == 1
    got = casts(served, stacked)
    assert got.count(table) == 1 and positions not in got, got
    assert not [c for c in got
                if c.startswith(f"{cfg.n_layer}x{cfg.n_embd}x")], got


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_adapter_says_which_product_its_programs_ran(monkeypatch, path):
    """A GPT-2 adapter's dispatch spans carry ``linear`` (the model's own
    chooser, asked with the step's rows and the bound weights), the
    engine's metrics count the programs that took the kernel beside
    ``steps_total`` (0 off the chip), and the served tokens are the
    unstacked model's either way."""
    from llm_test_helpers import PAGE, drain_stream, token_prompts

    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    if path == "kernel":
        monkeypatch.setattr(LN, "stacked_linear_path",
                            lambda x, w: "kernel")
        monkeypatch.setattr(LN, "stacked_linear_kernel", _KERNEL)
    cfg = gpt2.GPT2Config.tiny()
    plain = gpt2.GPT2(cfg)
    params = plain.init(jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))
    adapter = FlaxModelAdapter("gpt2", cfg, params=params)
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=32, block_size=PAGE, max_seq_len=64))
    prompts = token_prompts(7, cfg.vocab_size, (9, 14))
    try:
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=4))
                for p in prompts]
        served = [drain_stream(eng, sid, timeout=120.0)[0] for sid in sids]
    finally:
        eng.stop()
    # (read once the engine's thread has ended: a step in flight has
    # counted its program and not yet logged its spans)
    m, log = eng.metrics(), eng.step_log()
    for p, toks in zip(prompts, served):
        ids = list(p)
        for t in toks:      # the training form's greedy tokens
            logits = plain.apply(params, jnp.asarray([ids]))
            assert t == int(logits[0, -1].argmax())
            ids.append(t)

    def walk(span):
        yield span
        for child in span.get("children", ()):
            yield from walk(child)
    said = [s["attrs"]["linear"] for step in log for s in walk(step)
            if s["name"] == "runner.dispatch"]
    assert said and set(said) == {path}
    assert m["stacked_linear_kernel_steps_total"] \
        == (len(said) if path == "kernel" else 0)
    assert m["steps_total"] >= 4
