"""Which jitted (batch, length) shapes a serve cell's traffic can reach.

``FlaxModelAdapter`` pads a prefill to (pow2(count), pow2(longest, 8))
and a decode to (pow2(running), 8). Which prompts share a prefill step is
decided by the engine's admission: the first waiting prompt is always
taken; another is taken while it fits what is left of
``max_prefill_tokens``; a prompt of the whole budget or more ends the
step. The enumeration below follows that rule from the traffic's length
range; ``benchmark/checks`` holds it against a brute-force simulation.
"""

from __future__ import annotations

from typing import List, Set, Tuple


def pad_pow2(n: int, lo: int = 1) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def prefill_shapes(min_prompt: int, max_prompt: int,
                   max_prefill_tokens: int, max_running: int
                   ) -> Set[Tuple[int, int]]:
    """Every (prompts in the step, padded length) a prefill can have.
    The row count matters beside the bucket: the adapter slices the real
    rows out of the padded logits, one small program per count."""
    out: Set[Tuple[int, int]] = set()
    # a group of `count` prompts: the first is free, the rest must fit
    # the budget left after it, and each is at least min_prompt long
    count = 1
    while count <= max_running:
        if count == 1:
            longest_max = max_prompt
        else:
            # sum <= budget with count-1 others at the minimum
            longest_max = min(max_prompt,
                              max_prefill_tokens - min_prompt * (count - 1))
            if longest_max < min_prompt:
                break
        lo = pad_pow2(min_prompt, 8)
        s = lo
        while s <= pad_pow2(longest_max, 8):
            out.add((count, s))
            s *= 2
        count += 1
    return out


def prefill_buckets(min_prompt: int, max_prompt: int,
                    max_prefill_tokens: int, max_running: int
                    ) -> Set[Tuple[int, int]]:
    """The jitted (batch, length) programs behind ``prefill_shapes``."""
    return {(pad_pow2(c), s) for c, s in prefill_shapes(
        min_prompt, max_prompt, max_prefill_tokens, max_running)}


def decode_buckets(max_running: int) -> List[int]:
    out, b = [], 1
    while b <= pad_pow2(max_running):
        out.append(b)
        b *= 2
    return out


def simulate_admission(prompt_lens: List[int], max_prefill_tokens: int,
                       free_slots: int) -> List[int]:
    """The engine's rule, restated: which of the waiting prompts (FIFO)
    one step admits. Used by the check only."""
    admitted: List[int] = []
    budget = max_prefill_tokens
    for n in prompt_lens:
        if len(admitted) >= free_slots:
            break
        if admitted and n > budget:
            break
        admitted.append(n)
        budget -= n
        if n >= max_prefill_tokens:
            break
    return admitted
