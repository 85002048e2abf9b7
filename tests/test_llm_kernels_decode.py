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
