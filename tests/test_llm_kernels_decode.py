"""LLM serving, the numerics under the engine (docs/LLM_SERVING.md):
the paged-attention kernel vs the whole-kv reference, the page
allocator's exact admission, and incremental model decode vs the full
forward. Tier-1, CPU-only."""

import numpy as np
import pytest

from ray_tpu.serve.llm import PagedKVCache
from ray_tpu.serve.llm.kv_cache import OutOfKVBlocksError


# ------------------------------------------------------ kernel numerics


def test_paged_attention_matches_whole_kv_reference():
    """The Pallas paged-decode kernel (interpret mode on CPU), the
    paged gather reference, and the contiguous whole-kv decode path
    must agree bit-for-bit-ish on the same cache contents."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    rng = np.random.RandomState(0)
    B, H, Hkv, D, bs, NB = 3, 8, 2, 16, 8, 4
    P = 1 + B * NB
    lengths = jnp.asarray([5, 17, 30], jnp.int32)
    k_pages = jnp.asarray(rng.randn(P, bs, Hkv, D), jnp.float32)
    v_pages = jnp.asarray(rng.randn(P, bs, Hkv, D), jnp.float32)
    bt = jnp.asarray(np.arange(1, 1 + B * NB).reshape(B, NB), jnp.int32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)

    ref = A.paged_attention_reference(q, k_pages, v_pages, bt, lengths)
    kernel = A.paged_attention_decode(q, k_pages, v_pages, bt, lengths,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # contiguous whole-kv path over the SAME logical cache
    k_cont = A.paged_gather(k_pages, bt)
    v_cont = A.paged_gather(v_pages, bt)
    whole = A.decode_attention(q[:, :, None, :], k_cont, v_cont,
                               lengths)[:, :, 0, :]
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


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
