"""What the ``test_llm_*`` files share: token prompts from a seed, a
sequence with its pages allocated, a poll loop over an engine's stream,
and the wrapper that holds an engine synchronous. Pages hold 8 tokens
throughout, so 12-token prompts end mid-page and every sequence crosses a
page boundary while it decodes."""

import time

import numpy as np

from ray_tpu.serve.llm import SamplingParams

PAGE = 8


class Synchronous:
    """An adapter with its look-ahead withheld: everything else is the
    adapter's own."""
    decode_ahead = False

    def __init__(self, adapter):
        self._adapter = adapter

    def __getattr__(self, name):
        return getattr(self._adapter, name)


def flax_seq(cache, sid, prompt, budget=8, shared_pages=()):
    from ray_tpu.serve.llm.engine import Sequence
    cache.allocate_with_prefix(sid, len(prompt) + budget,
                               list(shared_pages))
    return Sequence(sid, None, list(prompt),
                    SamplingParams(max_new_tokens=budget))


def token_prompts(seed, vocab, lengths):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, vocab, n)] for n in lengths]


def drain_stream(eng, sid, timeout=30.0):
    toks, cur = [], 0
    deadline = time.time() + timeout
    while time.time() < deadline:
        ch = eng.poll(sid, cur, max_wait_s=5.0)
        toks += ch["tokens"]
        cur = ch["cursor"]
        if ch["done"]:
            return toks, ch
    raise TimeoutError("stream did not finish")
