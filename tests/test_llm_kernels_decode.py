"""LLM serving, the numerics under the engine (docs/LLM_SERVING.md):
the paged-attention kernel vs the whole-kv reference, the page
allocator's exact admission, and incremental model decode vs the full
forward. Tier-1, CPU-only."""

import numpy as np
import pytest

from ray_tpu.serve.llm import PagedKVCache
from ray_tpu.serve.llm.kv_cache import OutOfKVBlocksError


# ------------------------------------------------------ kernel numerics


# lengths against pages of 16 tokens and chunks of 128: a padding row, one
# token, a whole page, a page and one, an end mid-chunk, whole chunks, the
# full table
_LENGTHS = [0, 1, 16, 17, 100, 128, 200, 256]


@pytest.mark.parametrize("layer", [0, 2], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("H,Hkv,D", [(4, 4, 64), (8, 2, 128)],
                         ids=["mha-64", "gqa-128"])
def test_paged_attention_matches_whole_kv_reference(H, Hkv, D, dtype, tol,
                                                    layer):
    """The Pallas paged-decode kernel (interpret mode on CPU) over the
    serving pool [L, P, bs, Hkv*D], the paged gather reference, and the
    contiguous whole-kv decode path must agree on the same cache
    contents: GPT-2-like heads (two to a lane tile) and Llama-like
    (grouped, a tile each), block tables whose unused entries are the
    null page, a row with no token (finite, and nothing else)."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    rng = np.random.RandomState(0)
    L, bs, NB = 3, 16, 16
    B, C = len(_LENGTHS), Hkv * D
    P = 1 + sum(-(-n // bs) for n in _LENGTHS)
    dt = jnp.dtype(dtype)
    lengths = jnp.asarray(_LENGTHS, jnp.int32)
    k_pages = jnp.asarray(rng.randn(L, P, bs, C), dt)
    v_pages = jnp.asarray(rng.randn(L, P, bs, C), dt)
    tables = np.zeros((B, NB), np.int32)
    pages = iter(rng.permutation(np.arange(1, P)))
    for row, n in zip(tables, _LENGTHS):
        row[:-(-n // bs)] = [next(pages) for _ in range(-(-n // bs))]
    bt = jnp.asarray(tables)
    q = jnp.asarray(rng.randn(B, H, D), dt)

    ref = A.paged_attention_reference(q, k_pages, v_pages, bt, lengths,
                                      layer=layer)
    kernel = A.paged_attention_decode(q, k_pages, v_pages, bt, lengths,
                                      layer=jnp.int32(layer),
                                      interpret=True)
    assert kernel.shape == (B, H, D) and kernel.dtype == dt
    live = np.asarray(lengths) > 0
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (kernel, ref))
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    assert np.all(got[~live] == 0)

    # contiguous whole-kv path over the SAME logical cache
    def whole(pages):
        return A.paged_gather(pages, bt, layer).reshape(B, -1, Hkv, D)
    cont = A.decode_attention(q[:, :, None, :], whole(k_pages),
                              whole(v_pages), lengths)[:, :, 0, :]
    np.testing.assert_allclose(
        got[live], np.asarray(cont.astype(jnp.float32))[live],
        rtol=tol, atol=tol)


def test_cached_attention_takes_the_kernel_by_what_it_sees(monkeypatch):
    """One token a row over the serving pool on a chip: the kernel, and
    its result is the gather's; a window of tokens, a pool the kernel
    cannot take, a mesh of several devices, the CPU: the gather."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    pool = jnp.zeros((2, 9, 16, 2 * 64), jnp.float32)
    assert A.paged_decode_path(2, 64, pool, 1) == "gather"      # the CPU
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert A.paged_decode_path(2, 64, pool, 1) == "paged_kernel"
    assert A.paged_decode_path(4, 64, pool, 1) == "paged_kernel"    # GQA
    assert A.paged_decode_path(2, 64, pool, 8) == "gather"
    assert A.paged_decode_path(2, 64, pool, 1, layer=None) == "gather"
    assert A.paged_decode_path(2, 32, pool[..., :64], 1) == "gather"
    assert A.paged_decode_path(2, 64, pool[:, :, :4], 1) == "gather"
    from ray_tpu.parallel.mesh import MeshSpec
    with A.attention_mesh(MeshSpec(dp=2).build(jax.devices()[:2])):
        assert A.paged_decode_path(2, 64, pool, 1) == "gather"

    rng = np.random.RandomState(3)
    B, H, D = 3, 2, 64
    cache = {"k_pages": jnp.asarray(rng.randn(*pool.shape), jnp.float32),
             "v_pages": jnp.asarray(rng.randn(*pool.shape), jnp.float32),
             "block_tables": jnp.asarray([[1, 2, 0], [3, 0, 0], [0, 0, 0]],
                                         jnp.int32)}
    q, k, v = (jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
               for _ in range(3))
    lengths = jnp.asarray([20, 5, 0], jnp.int32)
    valid = jnp.asarray([[True], [True], [False]])
    calls = []
    kernel = A.paged_attention_decode

    def interpreted(*a, **kw):
        calls.append(kw["layer"])
        return kernel(*a, **kw, interpret=True)
    monkeypatch.setattr(A, "paged_attention_decode", interpreted)
    got, new = A.cached_attention(q, k, v, cache, lengths, valid=valid,
                                  layer=1)
    assert calls == [1]
    monkeypatch.setattr(A, "_use_pallas", lambda: False)
    want, new_ref = A.cached_attention(q, k, v, cache, lengths, valid=valid,
                                       layer=1)
    assert calls == [1]
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new["k_pages"]),
                                  np.asarray(new_ref["k_pages"]))


# ------------------------------------------- latent (MLA) decode kernel

_R, _DR, _W, _DN, _DV = 512, 64, 640, 16, 16      # a row as both Kimis'


def _latent_case(lengths, H, dtype, seed=0, tables=None, L=2):
    """A pool [L, P, 16, W] with the rows' pages scattered in it (page 0
    the null page, pages no table reaches beyond the last), queries and
    ``kv_b``; tables of 80 pages whose unused entries are the null page."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    B = len(lengths)
    need = [-(-n // 16) for n in lengths]
    P = 1 + sum(need) + 3
    dt = jnp.dtype(dtype)
    pool = rng.randn(L, P, 16, _W).astype(np.float32)
    pool[..., _R + _DR:] = 0
    if tables is None:
        tables = np.zeros((B, 80), np.int32)
        pages = iter(rng.permutation(np.arange(1, 1 + sum(need))))
        for row, n in zip(tables, need):
            row[:n] = [next(pages) for _ in range(n)]
    return {
        "pool": jnp.asarray(pool, dt), "tables": jnp.asarray(tables),
        "lengths": jnp.asarray(lengths, jnp.int32),
        "q_nope": jnp.asarray(rng.randn(B, 1, H, _DN), dt),
        "q_rope": jnp.asarray(rng.randn(B, 1, H, _DR), dt),
        "w_kvb": jnp.asarray(rng.randn(_R, H, _DN + _DV) * _R ** -0.5, dt)}


def _latent_kernel(case, layer, pool=None):
    """As ``MLAMixer`` calls it: (q_n W_uk | q_r | 0) against the pool,
    the sums through W_uv."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    B, _, H, _ = case["q_nope"].shape
    dt = case["pool"].dtype
    q_abs = jnp.concatenate([
        jnp.einsum("bhd,rhd->bhr", case["q_nope"][:, 0],
                   case["w_kvb"][..., :_DN]), case["q_rope"][:, 0],
        jnp.zeros((B, H, _W - _R - _DR), dt)], axis=-1)
    out = A.latent_attention_decode(
        q_abs, case["pool"] if pool is None else pool, case["tables"],
        case["lengths"], rank=_R, layer=layer,
        sm_scale=(_DN + _DR) ** -0.5, interpret=True)
    assert out.shape == (B, H, _R) and out.dtype == jnp.float32
    return jnp.einsum("bhr,rhd->bhd", out.astype(dt),
                      case["w_kvb"][..., _DN:]), out


def _latent_reference(case, layer):
    from ray_tpu.ops import attention as A
    latent = A.paged_gather(case["pool"], case["tables"],
                            layer)[..., :_R + _DR]
    return A.latent_attention(
        case["q_nope"], case["q_rope"], latent, case["w_kvb"],
        case["lengths"][:, None] - 1, v_dim=_DV, absorbed=True)[:, 0]


def _chunk_tokens(dtype):
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    return A._LATENT_CHUNK_BYTES // (_W * jnp.dtype(dtype).itemsize)


def _boundaries(dtype):
    # against pages of 16 tokens and a chunk of T: a padding row, one
    # token, a whole page, a page and one, a chunk less one, a chunk, a
    # chunk and one, the full table
    T = _chunk_tokens(dtype)
    return [0, 1, 16, 17, T - 1, T, T + 1, 1280]


@pytest.mark.parametrize("case", [
    "h32-bfloat16", "h64-bfloat16", "h32-float32", "h64-float32",
    "traced-layer", "descending-table", "empty-first-and-last",
    "poisoned-pages"])
def test_latent_attention_decode_matches_the_gather(case):
    """The Pallas latent-decode kernel (interpret mode on the CPU) over
    the latent pool as stored against ``paged_gather`` +
    ``latent_attention(absorbed=True)`` on the same pool."""
    import jax
    import jax.numpy as jnp
    H = 64 if case.startswith("h64") else 32
    dtype = "float32" if case.endswith("float32") else "bfloat16"
    tol = 1e-5 if dtype == "float32" else 2e-2
    lengths, tables, layers = _boundaries(dtype), None, [1]
    if case == "descending-table":
        lengths = [1280, 100]
        tables = np.zeros((2, 80), np.int32)
        tables[0] = np.arange(87, 7, -1)
        tables[1, :7] = np.arange(7, 0, -1)
    elif case == "empty-first-and-last":
        lengths = [0, 0, 300, 0, _chunk_tokens(dtype) + 1, 0]
    elif case == "traced-layer":
        lengths, layers = [0, 17, 1030], [0, 1, 2]
    c = _latent_case(lengths, H, dtype, seed=len(case), tables=tables,
                     L=max(layers) + 1)
    live = np.asarray(lengths) > 0
    if case == "traced-layer":
        # the layer a loop's counter, as a model that loops over stacked
        # blocks would hand it
        got = jax.lax.fori_loop(
            0, len(layers), lambda i, acc: acc.at[i].set(
                _latent_kernel(c, i)[0]),
            jnp.zeros((len(layers), len(lengths), H, _DV),
                      c["pool"].dtype))
    elif case == "poisoned-pages":
        # NaN in the null page and in every page no table reaches: a
        # copy past a row's live pages would end in its sums
        reached = np.zeros(c["pool"].shape[1], bool)
        for row, n in zip(np.asarray(c["tables"]), lengths):
            reached[row[:-(-n // 16)]] = True
        assert not reached[0] and (~reached).sum() >= 4
        poisoned = jnp.where(reached[None, :, None, None], c["pool"],
                             jnp.nan)
        got, raw = _latent_kernel(c, layers[0], pool=poisoned)
        assert np.all(np.isfinite(np.asarray(raw)))
        got = got[None]
    else:
        got = _latent_kernel(c, layers[0])[0][None]
    for i, layer in enumerate(layers):
        want = np.asarray(_latent_reference(c, layer).astype(jnp.float32))
        have = np.asarray(got[i].astype(jnp.float32))
        np.testing.assert_allclose(have[live], want[live], rtol=tol,
                                   atol=tol)
        assert np.all(have[~live] == 0)


def test_mla_mixer_takes_the_latent_kernel_by_what_it_sees(monkeypatch):
    """``latent_decode_path`` on each side of each of its conditions,
    and ``MLAMixer`` over pages with it: one token a row on a chip is
    the kernel, and its result is the gather's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.kimi_k2 import KimiK2Config
    from ray_tpu.models.mla import MLAMixer
    from ray_tpu.ops import attention as A
    pool = jnp.zeros((2, 9, 16, 256), jnp.bfloat16)
    assert A.latent_decode_path(pool, 128, 1) == "gather"       # the CPU
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    assert A.latent_decode_path(pool, 128, 1) == "latent_kernel"
    assert A.latent_decode_path(pool, 128, 1, jnp.int32(1)) \
        == "latent_kernel"
    assert A.latent_decode_path(pool.astype(jnp.float32)[:, :, :8], 128,
                                1) == "latent_kernel"
    assert A.latent_decode_path(None, 128, 1) == "gather"   # no pages
    assert A.latent_decode_path(pool, 128, 8) == "gather"   # a window
    assert A.latent_decode_path(pool, 128, 1, layer=None) == "gather"
    assert A.latent_decode_path(pool[..., :192], 128, 1) == "gather"
    assert A.latent_decode_path(pool, 96, 1) == "gather"
    assert A.latent_decode_path(pool[:, :, :8], 128, 1) == "gather"
    from ray_tpu.parallel.mesh import MeshSpec
    with A.attention_mesh(MeshSpec(dp=2).build(jax.devices()[:2])):
        assert A.latent_decode_path(pool, 128, 1) == "gather"

    cfg = KimiK2Config.tiny(kv_lora_rank=128, qk_rope_head_dim=8)
    mixer = MLAMixer(cfg)
    rng = np.random.RandomState(5)
    B, D = 3, cfg.hidden_size
    x = jnp.asarray(rng.randn(B, 1, D), jnp.float32)
    pages = jnp.asarray(rng.randn(2, 9, 16, 256), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 0, 0], [0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([19, 4, 0], jnp.int32)
    valid = jnp.asarray([[True], [True], [False]])
    params = mixer.init(jax.random.PRNGKey(0), x)
    calls = []
    kernel = A.latent_attention_decode

    def interpreted(*a, **kw):
        calls.append(kw["layer"])
        return kernel(*a, **kw, interpret=True)
    monkeypatch.setattr(A, "latent_attention_decode", interpreted)
    args = dict(pages=pages, block_tables=tables, seq_lengths=lengths,
                valid=valid, layer=1)
    got, new = mixer.apply(params, x, **args)
    assert calls == [1]
    monkeypatch.setattr(A, "_use_pallas", lambda: False)
    want, new_ref = mixer.apply(params, x, **args)
    assert calls == [1]
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(new_ref))
    # a window of tokens gathers, on the chip too
    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    mixer.apply(params, jnp.concatenate([x, x], 1), **dict(
        args, valid=jnp.concatenate([valid, valid], 1)))
    assert calls == [1]
    # and the adapter's dispatch spans will say what the mixer does
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    adapter = FlaxModelAdapter("kimi_k2", cfg, params={})
    adapter.bind_cache(PagedKVCache(8, 16))
    assert adapter._decode_attention == "latent_kernel"
    monkeypatch.setattr(A, "_use_pallas", lambda: False)
    adapter.bind_cache(PagedKVCache(8, 16))
    assert adapter._decode_attention == "gather"


def test_paged_kv_allocator_exact_admission():
    c = PagedKVCache(num_blocks=8, block_size=4)   # 7 usable pages
    assert c.blocks_for(9) == 3
    t1 = c.allocate("a", 9)             # 3 pages
    assert 0 not in t1                  # page 0 reserved (null page)
    assert c.can_allocate(16)           # 4 pages left
    assert not c.can_allocate(17)       # 5 needed, 4 free
    with pytest.raises(OutOfKVBlocksError):
        c.allocate("b", 17)
    assert abs(c.occupancy() - 3 / 7) < 1e-9
    assert c.free("a") == 3
    assert c.occupancy() == 0.0
    assert c.free("a") == 0             # double free is a no-op


# --------------------------------------------------- incremental decode


def test_gpt2_incremental_decode_matches_full_forward():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    cfg = gpt2.GPT2Config.tiny()
    m = gpt2.GPT2(cfg)
    ids = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 10)))
    params = m.init(jax.random.PRNGKey(0), ids)
    full = m.apply(params, ids)

    cache = gpt2.init_kv_cache(cfg, 2, 32)
    L = jnp.zeros((2,), jnp.int32)
    lg, cache = m.apply(params, ids[:, :6], kv_cache=cache,
                        seq_lengths=L)
    outs, L = [lg], L + 6
    for t in range(6, 10):
        lg, cache = m.apply(params, ids[:, t:t + 1], kv_cache=cache,
                            seq_lengths=L)
        outs.append(lg)
        L = L + 1
    inc = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


def test_llama_incremental_decode_matches_full_forward():
    """GQA + rotary offsets: the decode path must rotate each new
    token by its TRUE absolute position."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    cfg = llama.LlamaConfig.tiny()     # n_kv_heads < n_heads
    m = llama.LlamaModel(cfg)
    ids = jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (2, 9)))
    params = m.init(jax.random.PRNGKey(0), ids)
    full = m.apply(params, ids)

    cache = llama.init_kv_cache(cfg, 2, 32)
    L = jnp.zeros((2,), jnp.int32)
    lg, cache = m.apply(params, ids[:, :5], kv_cache=cache,
                        seq_lengths=L)
    outs, L = [lg], L + 5
    for t in range(5, 9):
        lg, cache = m.apply(params, ids[:, t:t + 1], kv_cache=cache,
                            seq_lengths=L)
        outs.append(lg)
        L = L + 1
    inc = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
