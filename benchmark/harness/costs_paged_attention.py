"""Operations and bytes a served decode step's attention needs, from
shapes and the step's own counters (``benchmark/harness/costs.py``'s
conventions: a multiply-add is 2 FLOPs, every byte is moved once,
nothing recomputed).

The attention of one decode step reads, for every running row and every
layer, the keys and values of the tokens the row holds (the new one
among them), its one query, and writes one output. Pages are read whole
by a kernel and rows end mid-page; the tokens are what is needed, so a
share of the roofline built on this cannot pass 100% honestly.
"""

from __future__ import annotations

from typing import Dict


def paged_attention_decode_cost(n_layer: int, n_embd: int, live_tokens: float,
                                n_seqs: float, kv_bytes: int = 2
                                ) -> Dict[str, float]:
    """All layers of one step. ``live_tokens``: the rows' context
    lengths summed (what ``adapter.decode``'s span records); ``n_embd``
    = heads x head dimension = a cached row's width for multi-head
    attention. Bytes: K and V of every live token once, q in and the
    output out once a row, all in the cache's precision. FLOPs: a query
    against a key and a probability against a value, 2 x ``n_embd``
    multiply-adds a live token."""
    kv = live_tokens * 2 * n_embd * kv_bytes
    q_and_out = n_seqs * 2 * n_embd * kv_bytes
    return {"bytes": float(n_layer * (kv + q_and_out)),
            "flops": 4.0 * live_tokens * n_embd * n_layer}
