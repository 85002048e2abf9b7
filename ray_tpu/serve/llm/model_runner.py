"""Model adapters: the engine's prefill/decode contract.

An adapter owns the *storage* of the paged KV pool (the engine's
``PagedKVCache`` owns only the allocator) and exposes the compute
entry points:

    prefill(seqs) -> logits [B, V]   write the prompts' KV into their
                                     pages (skipping any cached-prefix
                                     tokens), return last-token logits
    decode(seqs)  -> logits [B, V]   append each sequence's newest
                                     sampled token, attend against the
                                     cached prefix, return next logits
    decode_window(seqs, windows)     speculative decode: append a
                                     window of tokens per sequence in
                                     ONE batched step and return the
                                     logits after every position
    rollback(seq_id, n)              retract the last n cached tokens
                                     (rejected speculative positions)
    copy_page(src, dst)              duplicate page contents (copy-on-
                                     write of a shared prefix page)
    export_kv / import_kv            serialize / rebind a prompt's KV
                                     pages for prefill→decode handoff

Two implementations:

* ``ToyAdapter`` — a dependency-free numpy language model whose next
  token is a deterministic function of the cached prefix READ BACK
  THROUGH THE BLOCK TABLES (a paging bug corrupts its output, which is
  exactly what the continuous-vs-static equivalence gate wants).
  Configurable per-step latency makes it the load-bearing workload for
  the game days, without flax in the loop.

* ``FlaxModelAdapter`` — wraps ``models/gpt2.py`` / ``models/llama.py``
  incremental-decode paths in their ``stacked`` form (the blocks'
  weights stacked, one block's program looped over them): bucketed
  (batch, length) jit shapes, one paged pool for all layers carried
  through ``ops.attention.cached_attention`` from block to block,
  padding rows parked on the null page. A decode step is a program of
  its own with one token a row (``S = 1``, ``llm_decode_b{B}``): on the
  chip its attention is the ``paged_attention_decode`` Pallas kernel,
  which reads each row's live pages where they lie in the pool; off the
  chip the same program gathers (``cached_attention`` chooses by what it
  can observe, ``ops.attention.paged_decode_path``). GPT-2's four
  products a block take the float32 stacks whole and name their layer
  (``ops.linear.stacked_linear``: on the chip a kernel that reads the
  layer's tiles where they lie and rounds them in VMEM, so no program
  casts a stack through HBM; the dispatch span's ``linear`` says which
  ran and the engine's ``metrics()`` count
  ``stacked_linear_kernel_steps_total``).
  Prefill and
  ``decode_window`` go ``paged_gather`` + ``decode_attention`` (XLA) on
  every platform, padded to a bucket of at least 8 tokens — the
  multi-token incremental step is causal at the right offsets by
  construction (``q_positions = seq_lengths[:, None] + arange(S)``), so
  batched speculative verification is numerically the plain decode loop.

A model may state what it caches instead (``kind="kimi_linear"``,
``kind="kimi_k2"``, ``kind="jamba"``, ``kind="lfm2"``: the models'
``cache_spec``): pages
for some or all layers, of the row width it names, and per-sequence
*state* for others (Kimi-K2 names one latent pool and no state; Jamba K
and V pages for one layer in fourteen and a Mamba state for the rest). A
state array that a decode step's recurrence runs over names its kind
(``"recurrence": "kda"`` | ``"mamba"``), and ``_recurrences`` holds each
kind's chooser: ``bind_state`` asks it with the pool, the dispatch span
says what it chose, and ``counters()`` counts the steps that took a kernel
(``recurrence_kernel_steps_total``); a further kind is a further entry
of that table and no further case anywhere. A state may name no
recurrence at all (LFM2: the two-row tail of its gated convolutions and
nothing else): it has its slots, its admission and its refusals like any
state, its dispatch spans name no ``recurrence`` and ``counters()`` has
none of a recurrence's keys. The
adapter then owns one pool a named page kind and one array a named state
kind with a **state slot** per running sequence (slot 0 is the null
slot, where padding rows read and write): a slot is taken and zeroed
when a sequence is prefilled (``runner.state.admit``) and freed in
``release``. Pools and state go through the same ``_run``: bucketed,
donated, written in place. Its decode step is the one-token program
too (``S = 1``: the one-token recurrence); in the bucket that is as
wide as the state has slots, the rows go in slot order (``_by_slot``)
and the state is updated where it lies. The latent (MLA) blocks of such
a step attend as GPT-2's do: on the chip the ``latent_attention_decode``
Pallas kernel reads each row's live latent pages where they lie in the
pool, off the chip (and in every prefill and verify program)
``paged_gather`` + ``latent_attention``; ``models.mla.MLAMixer`` chooses
by ``ops.attention.latent_decode_path``, and ``bind_cache`` asks the
same function what the dispatch spans will say. Such an adapter also finds
each row's greedy token on the device (``greedy_on_device``): asked with
``tokens_only=True``, ``prefill`` / ``decode`` return tokens [B] and the
logits are not fetched. It can also run a decode step **ahead**
(``decode_ahead``): ``decode(seqs, tokens_only=True, fetch=False)``
dispatches the step and returns it unfetched (``DecodeStep``); a row that
is in the step still in flight feeds that step's greedy token, taken on
the device from ``_last_tokens`` (every decode program writes its rows'
greedy tokens there and reads the rows' inputs from there where the host
says so), so the engine can dispatch step n + 1 before it fetches step n.
It is the same program a bucket either way: a synchronous ``decode`` is
that dispatch, then the fetch. A prompt's program runs ahead the same
way: ``prefill(seqs, tokens_only=True, fetch=False)`` dispatches it and
returns it unfetched (``PromptStep``). Behind every prompt program a
copy of a few integers (``llm_prefill_feed``, one program a prefill
batch width) puts its rows' greedy tokens into ``_last_tokens`` above
the rows a decode program writes (from ``_feed_base`` on), and a decode
row whose prompt is still in flight reads its input there, so no prompt
or decode program differs for it; a synchronous ``prefill`` is the same
dispatch and copy, then the fetch. What cannot work without snapshots of
the state raises ``RecurrentStateError``: ``decode_window`` /
``rollback``, ``export_kv`` / ``import_kv`` (and the engine refuses
``enable_prefix_cache`` and ``spec_k`` at construction). A model that
states pages and NO state may use all of them: every cached token of it
is a page row, written at its absolute position, so a prefix's pages
can be shared (a suffix is prefilled from a non-zero length), a window
verified in one step (``llm_verify_b{B}_s{S}``) and rolled back by
length, and a prompt's pages shipped
(tests/test_llm_kimi_k2_serving.py).

A page kind may carry a ``window`` (``kind="laguna"``,
``kind="smallthinker"``: the sliding layers' ``k_window`` /
``v_window``): the positions a layer of that kind ever reads. Such kinds
form a **page group** of their own in the allocator (``kv_cache.py``):
its pool has a stated size (the engine's ``window_blocks``; by default
``max_running`` rings of ``window / block_size + 1`` pages and a null
page), a sequence takes what it needs of a ring (``min(ring,
blocks_for(budget))`` pages) with its other pages at admission, and
``_pack`` carries one table a group (the full group's padded table, then
each window group's ring, a short ring's filled up with the group's null
page: such a sequence never wraps, so the program reads position ``p``
of it in ring page ``p // block_size`` by the same rule and no program
differs). Position ``p`` lies in ring page ``(p // block_size) % ring``,
so a page is overwritten once what it held has left the window. That is
also what
such a model cannot do, and every entry point says so
(``WindowedPagesError``): a ring page cannot be shared with another
sequence (``enable_prefix_cache``), a prompt's suffix cannot be prefilled
behind a cached prefix (its window layers would need the prefix's last
``window`` rows, which no ring holds for it), a speculative window cannot
be rolled back (``decode_window`` / ``rollback``: the rejected positions
have overwritten the rows a ring apart), and ``export_kv`` / ``import_kv``
ship whole-prompt pages, which a ring is not.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu._private import tracing


class RecurrentStateError(NotImplementedError):
    """A feature that drops, shares or ships cached tokens was asked of
    a model that keeps recurrent state per sequence. Pages can be cut at
    any token; a state can only be restored from a snapshot taken at
    that token, and nothing takes such snapshots yet (ROADMAP R1)."""


def _recurrences() -> Dict[str, Any]:
    """A state kind's ``recurrence`` -> the chooser of what a decode step
    runs over its pool (``path(pool, S)``; a choice that ends in
    ``_kernel`` updates the pool where it lies)."""
    from ray_tpu.ops.linear_attention import kda_decode_path
    from ray_tpu.ops.ssm import mamba_decode_path
    return {"kda": kda_decode_path, "mamba": mamba_decode_path}


class WindowedPagesError(NotImplementedError):
    """A feature that shares, rolls back or ships cached tokens was asked
    of a model with a windowed page group. A ring page is overwritten as
    its sequence grows, so it cannot be shared with another sequence or
    rolled back past its span."""


# the shortest token-axis bucket of a prefill or a verify step (a decode
# step is a program of its own with one token a row)
_MIN_S = 8


def _pad_pow2(n: int, lo: int = 1) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def _cached_tokens(seq) -> int:
    return int(getattr(seq, "cached_tokens", 0) or 0)


class ToyAdapter:
    """Deterministic numpy LM over the paged pool (tests, game day,
    bench). Each token's "KV" is its embedding; the next-token logits
    are ``mean(cached embeddings) @ E^T`` — prefix-dependent, exactly
    reproducible, and read through the block tables so paging bugs are
    visible as wrong tokens, not just wrong latency."""

    def __init__(self, vocab_size: int = 256, dim: int = 32,
                 seed: int = 0, step_delay_s: float = 0.0,
                 per_seq_delay_s: float = 0.0,
                 per_prefill_token_delay_s: float = 0.0):
        rng = np.random.RandomState(seed)
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.embed = rng.randn(self.vocab_size, self.dim).astype(
            np.float32)
        self.step_delay_s = float(step_delay_s)
        self.per_seq_delay_s = float(per_seq_delay_s)
        self.per_prefill_token_delay_s = float(per_prefill_token_delay_s)
        self._lock = threading.Lock()

    def bind_cache(self, cache):
        self.cache = cache
        self.pages = np.zeros(
            (cache.num_blocks, cache.block_size, self.dim), np.float32)
        # seq id -> {"table": np.ndarray pages, "len": cached tokens}
        self._state: Dict[str, Dict[str, Any]] = {}

    def device_info(self) -> Dict[str, Any]:
        return {"platform": "host", "device_kind": "numpy"}

    def cache_bytes(self) -> Dict[str, int]:
        return {"bytes": self.pages.nbytes}

    def copy_page(self, src: int, dst: int):
        self.pages[dst] = self.pages[src]

    def _write(self, st, tokens: List[int]):
        bs = self.cache.block_size
        table = st["table"]
        for i, tok in enumerate(tokens):
            pos = st["len"] + i
            self.pages[table[pos // bs], pos % bs] = self.embed[tok]
        st["len"] += len(tokens)

    def _logits(self, st) -> np.ndarray:
        bs = self.cache.block_size
        table = st["table"]
        n = st["len"]
        nb = -(-n // bs)
        flat = self.pages[table[:nb]].reshape(nb * bs, self.dim)[:n]
        h = flat.mean(axis=0)
        return (h @ self.embed.T).astype(np.float32)

    def _cow_partial_page(self, seq_id: str, st, cached: int):
        """A cached prefix ending mid-page means our first write lands
        in a shared page: take a private copy first (copy-on-extend)."""
        bs = self.cache.block_size
        if cached % bs == 0:
            return
        old, new = self.cache.copy_on_write(seq_id, cached // bs)
        if new != old:
            self.copy_page(old, new)
            st["table"] = np.asarray(
                self.cache.block_table(seq_id), np.int64)

    def prefill(self, seqs) -> np.ndarray:
        n_tok = sum(len(s.prompt) - _cached_tokens(s) for s in seqs)
        if self.step_delay_s or self.per_prefill_token_delay_s:
            time.sleep(self.step_delay_s
                       + self.per_prefill_token_delay_s * n_tok)
        out = np.zeros((len(seqs), self.vocab_size), np.float32)
        with self._lock:
            for i, s in enumerate(seqs):
                cached = _cached_tokens(s)
                st = {"table": np.asarray(
                    self.cache.block_table(s.seq_id), np.int64),
                    "len": cached}
                self._state[s.seq_id] = st
                self._cow_partial_page(s.seq_id, st, cached)
                self._write(st, s.prompt[cached:])
                out[i] = self._logits(st)
        return out

    def decode(self, seqs) -> np.ndarray:
        if self.step_delay_s or self.per_seq_delay_s:
            time.sleep(self.step_delay_s
                       + self.per_seq_delay_s * len(seqs))
        out = np.zeros((len(seqs), self.vocab_size), np.float32)
        with self._lock:
            for i, s in enumerate(seqs):
                st = self._state[s.seq_id]
                self._write(st, [s.tokens[-1]])
                out[i] = self._logits(st)
        return out

    def decode_window(self, seqs, windows) -> List[np.ndarray]:
        """Append each sequence's token window, returning logits after
        EVERY window position ([w_i, V] per sequence). The toy model is
        sequential anyway; the contract (and the flax implementation)
        is one batched step."""
        if self.step_delay_s or self.per_seq_delay_s:
            time.sleep(self.step_delay_s
                       + self.per_seq_delay_s * len(seqs))
        out = []
        with self._lock:
            for s, win in zip(seqs, windows):
                st = self._state[s.seq_id]
                rows = np.zeros((len(win), self.vocab_size), np.float32)
                for j, tok in enumerate(win):
                    self._write(st, [int(tok)])
                    rows[j] = self._logits(st)
                out.append(rows)
        return out

    def rollback(self, seq_id: str, n: int):
        with self._lock:
            st = self._state.get(seq_id)
            if st is not None and n > 0:
                st["len"] = max(0, st["len"] - int(n))

    def export_kv(self, seq_id: str, n_prompt: int) -> Dict[str, Any]:
        """Snapshot the prompt's KV pages for prefill→decode handoff."""
        bs = self.cache.block_size
        nb = -(-int(n_prompt) // bs)
        with self._lock:
            st = self._state[seq_id]
            table = np.asarray(st["table"][:nb], np.int64)
            return {"kind": "toy", "n": int(n_prompt),
                    "pages": self.pages[table].copy()}

    def import_kv(self, seq_id: str, n_prompt: int,
                  blob: Dict[str, Any]):
        """Rebind shipped prompt KV into this replica's (freshly
        allocated, private) pages."""
        if blob.get("kind") != "toy":
            raise ValueError("KV blob is not from a toy adapter")
        bs = self.cache.block_size
        nb = -(-int(n_prompt) // bs)
        with self._lock:
            table = np.asarray(
                self.cache.block_table(seq_id), np.int64)
            self.pages[table[:nb]] = blob["pages"]
            self._state[seq_id] = {"table": table, "len": int(n_prompt)}

    def release(self, seq_id: str):
        with self._lock:
            self._state.pop(seq_id, None)


def _stack_blocks(tree, name: str, n: int):
    """``{name}_0 .. {name}_{n-1}`` of a flax tree -> one entry ``name``
    whose leaves carry a leading layer axis (what the models' ``stacked``
    form takes); a tree without them (stacked already, empty, None)
    passes through."""
    import jax
    import jax.numpy as jnp
    if not tree or f"{name}_0" not in tree.get("params", ()):
        return tree
    rest = dict(tree["params"])
    blocks = [rest.pop(f"{name}_{i}") for i in range(n)]
    rest[name] = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *blocks)
    return {**tree, "params": rest}


def _device_memory(tree) -> Dict[str, int]:
    """What the device that holds ``tree`` says of its memory, for a
    set-up span's attributes: ``memory_in_use_bytes`` and
    ``memory_peak_bytes`` (a TPU says; the CPU does not: {})."""
    import jax
    leaf = next(iter(jax.tree_util.tree_leaves(tree)), None)
    if not hasattr(leaf, "devices"):
        return {}
    stats = next(iter(leaf.devices())).memory_stats() or {}
    return {f"memory_{name}_bytes": int(stats[key])
            for name, key in (("in_use", "bytes_in_use"),
                              ("peak", "peak_bytes_in_use")) if key in stats}


def bucket_name(B: int, S: int, full: bool = False) -> str:
    """The jitted step's name for one (batch, length) bucket."""
    if full:
        return f"llm_verify_b{B}_s{S}"
    return f"llm_decode_b{B}" if S == 1 else f"llm_prefill_b{B}_s{S}"


class DecodeStep:
    """A decode step the device has been given and the host has not
    fetched (``FlaxModelAdapter.decode(..., fetch=False)``). ``at`` says
    where each sequence's greedy token will lie in ``_last_tokens`` (its
    row of the padded batch): the step dispatched after it reads a row's
    input token there, on the device.
    ``fetch()`` waits for the program and returns what a synchronous
    ``decode`` would have; only then do the step's tokens count into the
    sequences' cached lengths (``_state[seq]["len"]``), so a sequence
    released with a step still in flight shows the length the host
    knows of. ``wait()`` only waits: the program has ended (and every
    program dispatched before it: the device runs them in order), and the
    device holds nothing of it but the integers ``fetch()`` will read."""

    _held_as = "_flying"    # the adapter's name for the step while it flies

    def __init__(self, adapter, at: Dict[str, int], states, take, pending):
        self.at = at
        self._adapter, self._states = adapter, states
        self._take, self._pending = take, pending

    def fetch(self) -> np.ndarray:
        out = self._take()
        for st in self._states:
            st["len"] += 1
        if getattr(self._adapter, self._held_as) is self:
            setattr(self._adapter, self._held_as, None)
        return out

    def wait(self):
        with tracing.step_span("runner.wait"):
            self._pending.block_until_ready()


class PromptStep(DecodeStep):
    """A prompt's program the device has been given and the host has not
    fetched (``FlaxModelAdapter.prefill(..., fetch=False)``). ``at`` says
    where each sequence's first token will lie in ``_last_tokens``, above
    the rows a decode program writes: the decode step dispatched behind it
    feeds those rows from there. The prompt's tokens are in the
    sequences' cached lengths since the dispatch (``states`` is empty)."""

    _held_as = "_flying_prompt"


class FlaxModelAdapter:
    """GPT-2 / Llama incremental decode over the paged pool.

    jit shapes are bucketed (batch to a power of two, prompt length to
    a power of two >= 8, a decode step one token); padding rows carry
    zero lengths and null-page block tables, so they scatter into
    scratch and attend to nothing. Pages live as two jax arrays
    [L, P, bs, Hkv*D]: heads and head dimension share the minor axis,
    which then fills whole lanes,
    so the chip keeps the pool in the order scatter and gather index it.
    The step donates both and every layer writes its B*S new rows into
    them, so the compiled program aliases the pools to its outputs and
    copies nothing of their size: tests/test_chip_compile.py,
    ``test_served_step_writes_the_pool_in_place``. ``export_kv`` /
    ``import_kv`` blobs keep heads apart: [L, nb, bs, Hkv, D].
    ``params`` may come as the models' training form (one entry a
    block); it is kept stacked (``_stack_blocks``), because the step
    loops one block's program over the layers: a bucket then compiles
    in seconds and its program does not grow with depth.
    """

    def __init__(self, kind: str = "gpt2", config=None,
                 params=None, seed: int = 0):
        import jax
        import jax.numpy as jnp
        # the constructor's own programs are traced, lowered and compiled
        # before any step: counted from here on (docs/TRACING.md, "Before a
        # process is ready")
        tracing.watch_process()
        self._jnp = jnp
        self.kind = kind
        # what the model says it caches (None: K and V pages, every
        # layer alike)
        self._spec: Optional[Dict[str, Any]] = None
        # what the model says its blocks' products run over the bound
        # weights for a step of so many rows (None: not
        # ``ops.linear.stacked_linear``'s)
        self._linear_chooser = None
        with tracing.step_span("llm.setup.adapter", kind=kind):
            self._bind_model(kind, config)
        with tracing.step_span("llm.setup.params") as span:
            if params is None:
                dummy = jnp.zeros((1, 8), jnp.int32)
                params = self.model.init(jax.random.PRNGKey(seed), dummy)
            self.params = params
            span.set(bytes=sum(
                x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(self.params)),
                **_device_memory(self.params))
        self._expert_tokens_total = self._expert_tokens_last = None
        self._zero_expert_tokens_total = None   # [routed layers]
        self._routed_tokens_total = 0           # real tokens through a router
        self._expert_products: Dict[int, Any] = {}
        self._kv_pages_live = self._kv_pages_padded = 0
        self._kv_run_pages = self._kv_table_pages = 0
        self._decode_recurrence: Optional[str] = None   # ``bind_state``
        self._recurrence_kind: Optional[str] = None     # "kda" | "mamba"
        self._recurrence_kernel_steps = 0
        self._state_admits = 0
        self._state_admit_seconds = 0.0
        self._window_pages_live = self._window_pages_padded = 0
        self._window_pages_held = self._window_pages_whole = 0
        self._fns: Dict[Any, Any] = {}     # (B, S, full?) -> jitted step
        self._flying: Optional[DecodeStep] = None   # dispatched, unfetched
        self._flying_prompt: Optional[PromptStep] = None    # the same
        # one record a bucket: the finished ``runner.dispatch`` span of
        # its first call with the ``jax.*`` events under it, wherever the
        # call came from (a step of the engine, a warm-up outside one)
        self.first_calls: List[Dict[str, Any]] = []
        # programs dispatched whose blocks' products took the kernel that
        # reads the float32 stacks where they lie (``_linear_path``)
        self.stacked_linear_kernel_steps = 0
        self._lock = threading.Lock()

    def _bind_model(self, kind: str, config):
        """The model's module, its configuration and its object, and what
        the model says of its cache (``_spec``)."""
        if kind == "gpt2":
            from ray_tpu.models import gpt2
            self.cfg = config or gpt2.GPT2Config.tiny()
            self._linear_chooser = gpt2.linear_path
            self.model, self._blocks = gpt2.GPT2(self.cfg, stacked=True), "h"
            self.n_heads = self.n_kv_heads = self.cfg.n_head
            self.head_dim = self.cfg.n_embd // self.cfg.n_head
            self.vocab_size = self.cfg.vocab_size
        elif kind == "llama":
            from ray_tpu.models import llama
            self.cfg = config or llama.LlamaConfig.tiny()
            self.model = llama.LlamaModel(self.cfg, stacked=True)
            self._blocks = "layers"
            self.n_heads = self.cfg.n_heads
            self.n_kv_heads = self.cfg.n_kv_heads
            self.head_dim = self.cfg.head_dim
            self.vocab_size = self.cfg.vocab_size
        elif kind == "kimi_linear":
            from ray_tpu.models import kimi_linear
            self.cfg = config or kimi_linear.KimiLinearConfig.tiny()
            self.model = kimi_linear.KimiLinearModel(self.cfg)
            self._blocks = None            # three block kinds: unrolled
            self.vocab_size = self.cfg.vocab_size
            self._spec = kimi_linear.cache_spec(self.cfg)
        elif kind == "kimi_k2":
            from ray_tpu.models import kimi_k2
            self.cfg = config or kimi_k2.KimiK2Config.tiny()
            self.model = kimi_k2.KimiK2Model(self.cfg)
            self._blocks = None            # a dense layer, then routed ones
            self.vocab_size = self.cfg.vocab_size
            self._spec = kimi_k2.cache_spec(self.cfg)
        elif kind == "laguna":
            from ray_tpu.models import laguna
            self.cfg = config or laguna.LagunaConfig.tiny()
            self.model = laguna.LagunaModel(self.cfg)
            self._blocks = None            # layers differ by their type
            self.vocab_size = self.cfg.vocab_size
            self._spec = laguna.cache_spec(self.cfg)
        elif kind == "smallthinker":
            from ray_tpu.models import smallthinker
            self.cfg = config or smallthinker.SmallThinkerConfig.tiny()
            self.model = smallthinker.SmallThinkerModel(self.cfg)
            self._blocks = None            # layers differ by their layout
            self.vocab_size = self.cfg.vocab_size
            self._spec = smallthinker.cache_spec(self.cfg)
        elif kind == "longcat_flash":
            from ray_tpu.models import longcat_flash
            self.cfg = config or longcat_flash.LongcatFlashConfig.tiny()
            self.model = longcat_flash.LongcatFlashModel(self.cfg)
            self._blocks = None            # two cached sublayers a layer
            self.vocab_size = self.cfg.vocab_size
            self._spec = longcat_flash.cache_spec(self.cfg)
        elif kind == "jamba":
            from ray_tpu.models import jamba
            self.cfg = config or jamba.JambaConfig.tiny()
            self.model = jamba.JambaModel(self.cfg)
            self._blocks = None            # the model stacks its own runs
            self.vocab_size = self.cfg.vocab_size
            self._spec = jamba.cache_spec(self.cfg)
        elif kind == "lfm2":
            from ray_tpu.models import lfm2
            self.cfg = config or lfm2.Lfm2Config.tiny()
            self.model = lfm2.Lfm2Model(self.cfg)
            self._blocks = None            # operator and feed-forward vary
            self.vocab_size = self.cfg.vocab_size
            self._spec = lfm2.cache_spec(self.cfg)
        else:
            raise ValueError(f"unknown model kind {kind!r}")

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, tree):
        # accepted as the models' training form (one entry a block) too
        self._params = _stack_blocks(tree, self._blocks, self.n_layers)
        # ``_linear_path`` by rows a step, asked anew of these weights
        self._linear_paths: Dict[int, str] = {}

    @property
    def bucket_first_calls(self) -> int:
        """``_fns`` misses: the (batch, length) buckets met so far, each
        a step that traced, lowered and compiled its program."""
        return sum(1 for key in self._fns if isinstance(key, tuple))

    @property
    def has_state(self) -> bool:
        """The model keeps per-sequence recurrent state beside pages."""
        return bool(self._spec and self._spec.get("state"))

    @property
    def page_windows(self) -> tuple:
        """The windows of the model's windowed page kinds (each a page
        group of its own in the allocator); () for every other model."""
        pages = (self._spec or {}).get("pages", {})
        return tuple(sorted({p["window"] for p in pages.values()
                             if p.get("window")}))

    @property
    def greedy_on_device(self) -> bool:
        """``prefill`` / ``decode`` take ``tokens_only=True`` and then
        return each row's greedy token [B] in place of its logits
        [B, V] (64 rows of 40,960 logits are 10.5 MB a step to fetch
        and to search on the host: my chip runs, PR 28)."""
        return self._spec is not None

    @property
    def decode_ahead(self) -> bool:
        """``decode`` takes ``fetch=False``: it dispatches the step and
        returns it unfetched (``DecodeStep``), and a row that is in the
        step still in flight feeds that step's greedy token without the
        host having seen it."""
        return self._spec is not None

    @property
    def n_layers(self) -> int:
        return getattr(self.cfg, "n_layer",
                       getattr(self.cfg, "n_layers", 0))

    def device_info(self) -> Dict[str, Any]:
        """The device that holds ``params`` (what ``__llm_metrics__``
        reports: a replica that was granted no chip serves from the CPU
        and must say so)."""
        import jax
        dev = next(iter(jax.tree_util.tree_leaves(self.params)[0].devices()))
        stats = dev.memory_stats() or {}
        return {"platform": dev.platform, "device_kind": dev.device_kind,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}

    def bind_cache(self, cache):
        jnp = self._jnp
        self.cache = cache
        self._flying = self._flying_prompt = None
        dtype = self.cfg.dtype
        if set(self.page_windows) != set(getattr(cache, "windows", ())):
            raise ValueError(
                f"the model's page windows {list(self.page_windows)} are "
                f"not the cache's groups {list(cache.windows)}: build it "
                "with PagedKVCache(..., windows=adapter.page_windows, "
                "max_sequences=...)")
        # the window groups' rings, behind the padded table in ``_pack``
        self._rings = {w: cache.ring_blocks(w) for w in self.page_windows}
        # what a decode step's attention runs (its dispatch span says
        # it): the path the model's own code will choose, asked of the
        # same function with the same pool
        from ray_tpu.ops.attention import latent_decode_path, \
            paged_decode_path
        self._decode_attention = "gather"
        if self._spec is None:
            shape = (self.n_layers, cache.num_blocks, cache.block_size,
                     self.n_kv_heads * self.head_dim)
            self.k_pages = jnp.zeros(shape, dtype)
            self.v_pages = jnp.zeros(shape, dtype)
            self._decode_attention = paged_decode_path(
                self.n_heads, self.head_dim, self.k_pages, 1)
        else:
            # one pool a page kind the model names; state arrays come
            # with ``bind_state``
            # (a kind with a window lies in its window group's pool)
            self._arrays: Dict[str, Any] = {
                name: jnp.zeros((p["layers"],
                                 cache.group_blocks(p["window"])
                                 if p.get("window") else cache.num_blocks,
                                 cache.block_size, p["row"]), p["dtype"])
                for name, p in self._spec["pages"].items()}
            for name, p in self._spec["pages"].items():
                if "latent_rank" in p:      # MLAMixer's pool
                    self._decode_attention = latent_decode_path(
                        self._arrays[name], p["latent_rank"], 1)
                elif "q_heads" in p:        # K and V pages of grouped heads
                    self._decode_attention = paged_decode_path(
                        p["q_heads"], p["head_dim"], self._arrays[name], 1)
            self._free_slots: List[int] = []
            self.state_slots = 0
            # the last decode program's greedy tokens by row of its padded
            # batch (no batch has more rows than the pool has pages, and
            # one shape serves every bucket's program); above the widest
            # batch, from ``_feed_base``, the last prompt program's
            # (``_feed_fn``)
            rows = min(cache.max_sequences or cache.num_blocks - 1,
                       cache.num_blocks - 1)
            self._feed_base = _pad_pow2(rows)
            self._last_tokens = jnp.zeros(
                (max(_pad_pow2(cache.num_blocks), 2 * self._feed_base),),
                jnp.int32)
        # NB: every block table is padded to the worst-case blocks per
        # sequence so decode jits once per batch bucket
        self.nb_max = cache.blocks_for(
            getattr(self.cfg, "n_positions",
                    getattr(self.cfg, "max_seq_len", 2048)))
        self._state: Dict[str, Dict[str, Any]] = {}

    def bind_state(self, max_running: int):
        """One state slot a running sequence (and the null slot 0), for
        each array the model's ``cache_spec`` names under ``state``:
        [layers, slots, ...]."""
        jnp = self._jnp
        self.state_slots = int(max_running)
        for name, p in self._spec["state"].items():
            layers, *rest = p["shape"]
            self._arrays[name] = jnp.zeros(
                (layers, self.state_slots + 1, *rest), p["dtype"])
            if p.get("recurrence"):
                # what a decode step's recurrence runs (its dispatch
                # span says it): the model's own chooser, asked with
                # the same pool, as ``_decode_attention`` is
                self._recurrence_kind = p["recurrence"]
                self._decode_recurrence = _recurrences()[p["recurrence"]](
                    self._arrays[name], 1)
        self._free_slots = list(range(self.state_slots, 0, -1))

    def cache_bytes(self) -> Dict[str, int]:
        """What ``bind_cache`` and ``bind_state`` made, for the engine's
        ``llm.setup.cache`` span: the pools' and state arrays' ``bytes``
        and, where the device says, its memory with them in it."""
        arrays = [self.k_pages, self.v_pages] if self._spec is None \
            else [*self._arrays.values(), self._last_tokens]
        return {"bytes": sum(a.size * a.dtype.itemsize for a in arrays),
                **_device_memory(arrays)}

    def counters(self) -> Dict[str, Any]:
        """What ``engine.metrics()`` adds of the model step: the pages
        its decode steps' attention had to read and the pages of their
        padded tables; for a model with state or routed experts, its
        slots and its experts' tokens (docs/TRACING.md)."""
        out = {"kv_pages_live_total": self._kv_pages_live,
               "kv_pages_padded_total": self._kv_pages_padded,
               # over the decode steps: the pages of their rows' tables
               # (every page group) that lie in runs the kernel copies at
               # once, and those tables' pages
               "kv_run_pages_total": self._kv_run_pages,
               "kv_table_pages_total": self._kv_table_pages}
        if self._spec is None:
            return out
        if self._rings:
            out.update(
                kv_window_pages_live_total=self._window_pages_live,
                kv_window_pages_padded_total=self._window_pages_padded,
                # over the decode steps: the window pages their rows held,
                # and what whole rings would have been
                kv_window_pages_held_total=self._window_pages_held,
                kv_window_pages_whole_rings_total=self._window_pages_whole)
        out.update(state_slots_total=self.state_slots,
                   state_slots_in_use=self.state_slots
                   - len(self._free_slots))
        if self.has_state:
            # the sequences given a zeroed slot, and the host seconds
            # inside ``runner.state.admit``
            out.update(state_admits_total=self._state_admits,
                       state_admit_seconds_total=self._state_admit_seconds)
        if self._decode_recurrence is not None:
            out["recurrence_kernel_steps_total"] = \
                self._recurrence_kernel_steps
            if self._recurrence_kind == "kda":
                # (the name from before a second recurrence: a benchmark
                # file reads it)
                out["kda_kernel_steps_total"] = self._recurrence_kernel_steps
        if self._expert_tokens_total is not None:
            out["expert_tokens_total"] = self._expert_tokens_total.tolist()
            out["expert_tokens_last_step"] = self._expert_tokens_last.tolist()
            out["routed_tokens_total"] = self._routed_tokens_total
        if self._zero_expert_tokens_total is not None:
            out["zero_expert_tokens_total"] = \
                self._zero_expert_tokens_total.tolist()
        return out

    def _take_slots(self, seq_ids: List[str]) -> List[int]:
        """A zeroed state slot for each newly prefilled sequence."""
        jnp = self._jnp
        if len(self._free_slots) < len(seq_ids):
            raise RuntimeError(
                f"{len(seq_ids)} sequences admitted with "
                f"{len(self._free_slots)} state slots free: the engine's "
                "max_running exceeds what bind_state was given")
        slots = [self._free_slots.pop() for _ in seq_ids]
        t0 = time.perf_counter()
        with tracing.step_span("runner.state.admit", n=len(slots)):
            idx = np.zeros((_pad_pow2(len(slots)),), np.int32)
            idx[:len(slots)] = slots
            names = list(self._spec["state"])
            with self._lock:
                cleared = self._zero_fn()(
                    jnp.asarray(idx), *(self._arrays[n] for n in names))
                self._arrays.update(zip(names, cleared))
        self._state_admits += len(slots)
        self._state_admit_seconds += time.perf_counter() - t0
        return slots

    def _zero_fn(self):
        fn = self._fns.get("zero_slots")
        if fn is None:
            import jax

            def llm_state_admit(idx, *arrays):
                return tuple(a.at[:, idx].set(0) for a in arrays)
            donate = tuple(range(1, 1 + len(self._spec["state"]))) \
                if jax.devices()[0].platform == "tpu" else ()
            fn = self._fns["zero_slots"] = jax.jit(
                llm_state_admit, donate_argnums=donate)
        return fn

    def _feed_fn(self):
        """The copy behind every prompt program: its rows' greedy tokens
        (``small[:B]``) into ``_last_tokens`` from ``_feed_base`` on,
        where the decode program dispatched next reads the input of a
        row whose token comes as ``-1 - (_feed_base + i)``. A program of
        its own, one a prefill batch width, so that no prompt or decode
        program differs for it; a synchronous ``prefill`` runs it too
        (whoever warms a bucket compiles it)."""
        fn = self._fns.get("feed_prompts")
        if fn is None:
            import jax
            base = self._feed_base

            def llm_prefill_feed(last, small, B):
                return jax.lax.dynamic_update_slice(last, small[:B], (base,))
            donate = (0,) if jax.devices()[0].platform == "tpu" else ()
            fn = self._fns["feed_prompts"] = jax.jit(
                llm_prefill_feed, static_argnums=(2,), donate_argnums=donate)
        return fn

    def state_of(self, seq_id: str) -> Dict[str, Any]:
        """A copy of the sequence's recurrent state as it lies in its
        slot: one device array a state kind, [layers, ...] (a check or a
        debugger reads it; the step never does)."""
        fn = self._fns.get("read_slot")
        if fn is None:
            import jax
            fn = self._fns["read_slot"] = jax.jit(
                lambda slot, *arrays: tuple(a[:, slot] for a in arrays))
        names = list(self._spec["state"])
        slot = np.int32(self._state[seq_id]["slot"])
        with self._lock:
            return dict(zip(names, fn(
                slot, *(self._arrays[n] for n in names))))

    def copy_page(self, src: int, dst: int):
        if self._spec is not None:
            with self._lock:
                for name in self._spec["pages"]:
                    a = self._arrays[name]
                    self._arrays[name] = a.at[:, dst].set(a[:, src])
            return
        with self._lock:
            self.k_pages = self.k_pages.at[:, dst].set(
                self.k_pages[:, src])
            self.v_pages = self.v_pages.at[:, dst].set(
                self.v_pages[:, src])

    def _step_fn(self, B: int, S: int, full: bool = False):
        key = (B, S, full)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        import jax
        jnp = self._jnp
        if self._spec is not None:
            fn = self._fns[key] = self._spec_step_fn(B, S, full)
            return fn

        def step(params, tokens, k_pages, v_pages, block_tables,
                 seq_lengths, valid):
            logits, pool = self.model.apply(
                params, tokens, seq_lengths=seq_lengths, valid=valid,
                kv_cache={"k_pages": k_pages, "v_pages": v_pages,
                          "block_tables": block_tables})
            if not full:    # speculative verify reads EVERY position
                # last REAL token's logits per row
                idx = jnp.maximum(
                    jnp.sum(valid.astype(jnp.int32), axis=1) - 1, 0)
                logits = jnp.take_along_axis(
                    logits, idx[:, None, None], axis=1)[:, 0]
            return logits, pool["k_pages"], pool["v_pages"]

        # one name per bucket, so a device trace's XLA Modules line says
        # which program ran (jit names the module after the function)
        step.__name__ = step.__qualname__ = bucket_name(B, S, full)
        # donate the pools on TPU (in-place page update, zero copy);
        # CPU ignores donation and would warn on every compile
        donate = (2, 3) if jax.devices()[0].platform == "tpu" else ()
        fn = jax.jit(step, donate_argnums=donate)
        self._fns[key] = fn
        return fn

    def _by_slot(self, B: int, S: int) -> bool:
        """A decode bucket as wide as the state has slots runs with its
        rows in slot order (row r is slot r + 1, free slots are padding
        rows): the program then updates the state where it lies. (Rows
        gathered from and scattered back to their slots cost a 64-row
        step 9 of its 29 ms on the device: my chip run, PR 28.)"""
        return self.has_state and S == 1 and B == self.state_slots

    def _spec_step_fn(self, B: int, S: int, full: bool = False):
        """The step of a model that states its cache: every pool and
        state array is an argument, donated, and comes back written in
        place. The rows' integers come as ONE array (``_pack``): each
        upload is a place where the engine's thread gives way to the
        threads that answer the streams. Beside the logits the program
        returns ``small``: each row's greedy token, then the routed
        layers' per-expert token counts of the step (then, of a router
        with zero-compute outputs, each layer's assignments to them), so
        that a step whose rows all sample greedily fetches B + layers x
        experts integers and leaves the logits on the device. ``full`` (a
        speculative window's verify step) returns the logits after every
        position, [B, S, V]. A decode program (``S == 1``) also takes
        ``_last_tokens`` before the arrays, donated like them: a row whose
        token comes as ``-1 - r`` feeds row ``r``'s greedy token of the
        decode program before it, and the program leaves its own rows'
        there; a row with a token from the host runs the same program."""
        import jax
        jnp = self._jnp
        names = list(self._arrays)
        by_slot = self._by_slot(B, S)
        rings = dict(self._rings)
        feeds = S == 1

        def step(params, packed, *arrays):
            tokens, n_new = packed[:, :S], packed[:, S]
            if feeds:
                last, *arrays = arrays
                tokens = jnp.where(
                    tokens < 0, last[jnp.maximum(-1 - tokens, 0)], tokens)
            seq_lengths, slots = packed[:, S + 1], packed[:, S + 2]
            cache = dict(zip(names, arrays), block_tables=packed[:, S + 3:])
            if rings:       # one table a page group: the padded, the rings
                at = S + 3 + self.nb_max
                cache["block_tables"] = packed[:, S + 3:at]
                cache["window_tables"] = {}
                for w, ring in rings.items():
                    cache["window_tables"][w] = packed[:, at:at + ring]
                    at += ring
            if not by_slot:
                cache["slots"] = slots
            valid = jnp.arange(S)[None, :] < n_new[:, None]
            logits, cache, *counts = self.model.apply(
                params, tokens, cache=cache, seq_lengths=seq_lengths,
                valid=valid,
                logits_at=None if full else jnp.maximum(n_new - 1, 0))
            rows = logits[:, 0]     # (a verify step's are not asked for)
            greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)
            small = jnp.concatenate([greedy] + [
                c.reshape(-1).astype(jnp.int32) for c in counts])
            return (logits if full else rows, small,
                    *([last.at[:B].set(greedy)] if feeds else []),
                    *(cache[n] for n in names))

        step.__name__ = step.__qualname__ = bucket_name(B, S, full)
        donate = tuple(range(2, 2 + feeds + len(names))) \
            if jax.devices()[0].platform == "tpu" else ()
        return jax.jit(step, donate_argnums=donate)

    def _run(self, rows: List[Dict[str, Any]], op: str,
             tokens_only: bool = False) -> np.ndarray:
        """``_dispatch``, then the fetch."""
        _, fetch, _ = self._dispatch(rows, op, tokens_only)
        return fetch()

    def _dispatch(self, rows: List[Dict[str, Any]], op: str,
                  tokens_only: bool = False):
        """rows: [{tokens: [ints], len: cache length, table: [pages]}];
        builds the padded batch and gives the device its program. Returns
        where each row lies in the padded batch, the output ``fetch()``
        will read (ready when the program has ended), and ``fetch()``,
        which waits for the program:
        -> last-token logits [B, V] (or full [B, S, V] when ``op`` is
        ``verify``) for the real rows; with ``tokens_only`` (a model
        that states its cache) each row's greedy token [B] instead.
        ``op`` (prefill | decode | verify) labels the step spans."""
        jnp = self._jnp
        full = op == "verify"
        B = _pad_pow2(len(rows))
        # a decode step is the one-token program; a prefill or a window
        # is padded to a bucket
        S = 1 if op == "decode" else _pad_pow2(
            max(len(r["tokens"]) for r in rows), _MIN_S)
        # where each row goes in the padded batch: in order, or (a full
        # decode bucket of a model with state) to its slot's place
        at = [r["slot"] - 1 for r in rows] if self._by_slot(B, S) \
            else list(range(len(rows)))
        with tracing.step_span("runner.build_inputs", op=op, B=B, S=S):
            if self._spec is not None:
                packed = self._pack(rows, at, B, S)
            else:
                tokens = np.zeros((B, S), np.int32)
                lengths = np.zeros((B,), np.int32)
                valid = np.zeros((B, S), bool)
                tables = np.zeros((B, self.nb_max), np.int32)
                for i, r in enumerate(rows):
                    n = len(r["tokens"])
                    tokens[i, :n] = r["tokens"]
                    lengths[i] = r["len"]
                    valid[i, :n] = True
                    t = r["table"][:self.nb_max]
                    tables[i, :len(t)] = t
        # which product the routed experts of this many rows will run:
        # the layer's own chooser, asked with the same shapes
        product = self._expert_product(B * S)
        linear = self._linear_path(B * S)
        self.stacked_linear_kernel_steps += linear == "kernel"
        first = (B, S, full) not in self._fns
        with tracing.step_span("runner.dispatch", B=B, S=S, first_call=first,
                               **({"linear": linear} if linear else {}),
                               **({"expert_product": product.name}
                                  if product else {}),
                               **(self._count_pages(rows, B)
                                  if op == "decode" else
                                  {"prompt_tokens": sum(len(r["tokens"])
                                                        for r in rows),
                                   "padded_tokens": B * S})) as dispatch:
            fn = self._step_fn(B, S, full)
            with self._lock:
                if self._spec is None:
                    logits, self.k_pages, self.v_pages = fn(
                        self.params, jnp.asarray(tokens), self.k_pages,
                        self.v_pages, jnp.asarray(tables),
                        jnp.asarray(lengths), jnp.asarray(valid))
                else:
                    fed = (self._last_tokens,) if S == 1 else ()
                    logits, small, *arrays = fn(
                        self.params, jnp.asarray(packed), *fed,
                        *self._arrays.values())
                    if fed:
                        self._last_tokens, *arrays = arrays
                    self._arrays = dict(zip(self._arrays, arrays))
                    if op == "prefill":
                        self._last_tokens = self._feed_fn()(
                            self._last_tokens, small, B)
                    if tokens_only:
                        # nobody will read them: the device frees them
                        # when the program ends (64 x 100,352 float32 a
                        # step in flight are 25.7 MB)
                        logits = None
        if first and dispatch.rec is not None:
            self.first_calls.append(dict(dispatch.rec, children=[
                c for c in dispatch.rec["children"]
                if c["name"].startswith("jax.")]))

        def fetch() -> np.ndarray:
            with tracing.step_span("runner.fetch") as span:
                if self._spec is None:
                    # one call waits and copies: this path's host is
                    # exposed and its stream threads contend for the
                    # interpreter, so a return to Python between the wait
                    # and the copy costs a step ~0.25 ms (PERF.md, PR 39)
                    out = np.asarray(logits[:len(rows)], np.float32)
                else:
                    # the wait for the program apart from the copy and
                    # the cutting on the host
                    t0 = time.perf_counter()
                    small.block_until_ready()
                    span.set(wait_ms=(time.perf_counter() - t0) * 1e3)
                    # whole arrays, cut on the host: a slice on the device
                    # is a program a row count
                    counts = np.asarray(small)
                    out = counts[:B][at] if tokens_only \
                        else np.asarray(logits, np.float32)[at]
                    if counts.size > B:
                        held = B + int(np.prod(self._spec["expert_counts"]))
                        span.set(**self._count_experts(
                            counts[B:held].reshape(
                                self._spec["expert_counts"]), product,
                            sum(len(r["tokens"]) for r in rows),
                            counts[held:]))
                span.set(bytes=out.nbytes)
            return out
        return at, fetch, (logits if self._spec is None else small)

    def _pack(self, rows, at, B: int, S: int) -> np.ndarray:
        """The rows' integers as one int32 array [B, S + 3 + nb_max
        (+ each window group's ring)]: a row's new tokens, how many of
        them are real, the tokens cached before them, its state slot, its
        block table, then its ring in each window group (the pages it
        holds of it, the rest the group's null page). A padding row is
        zeros: nothing real, the null slot, the null pages."""
        packed = np.zeros(
            (B, S + 3 + self.nb_max + sum(self._rings.values())), np.int32)
        for i, r in zip(at, rows):
            n = len(r["tokens"])
            packed[i, :n] = r["tokens"]
            packed[i, S:S + 3] = n, r["len"], r.get("slot", 0)
            t = r["table"][:self.nb_max]
            packed[i, S + 3:S + 3 + len(t)] = t
            col = S + 3 + self.nb_max
            for w, ring in self._rings.items():
                t = r["rings"][w]
                packed[i, col:col + len(t)] = t
                col += ring
        return packed

    def _count_pages(self, rows, B: int) -> Dict[str, Any]:
        """What a decode step's attention reads, for its dispatch span
        and ``counters()``: which path it takes (the kernel over the
        rows' live pages, or the gather to every row's padded table; for
        a model with a recurrent state, which path its recurrence takes),
        the pages that hold one of the rows' tokens, the pages of the
        ``B`` padded tables, and of the pages the rows' tables hold
        (every page group) those in runs of consecutive pages
        (``PagedKVCache.table_run_pages``, counted at admission)."""
        bs = self.cache.block_size
        lens = [r["len"] + 1 for r in rows]
        live = sum(-(-n // bs) for n in lens)
        padded = B * self.nb_max
        run = sum(r["run_pages"][0] for r in rows)
        held = sum(r["run_pages"][1] for r in rows)
        self._kv_pages_live += live
        self._kv_pages_padded += padded
        self._kv_run_pages += run
        self._kv_table_pages += held
        out = {"attention": self._decode_attention,
               "live_tokens": sum(lens),
               "kv_pages_live": live, "kv_pages_padded": padded,
               "kv_run_pages": run, "kv_table_pages": held}
        if self._decode_recurrence is not None:
            out["recurrence"] = self._decode_recurrence
            self._recurrence_kernel_steps += \
                self._decode_recurrence.endswith("_kernel")
        for w, ring in self._rings.items():
            # a window layer reads a row's last ``w`` positions: the ring
            # pages that hold one of them, of the ``ring`` a row holds
            w_live = sum(-(-n // bs) - max(n - w, 0) // bs for n in lens)
            held = sum(len(r["rings"][w]) for r in rows)
            self._window_pages_live += w_live
            self._window_pages_padded += B * ring
            self._window_pages_held += held
            self._window_pages_whole += len(rows) * ring
            out.update(window_tokens=sum(min(n, w) for n in lens),
                       kv_window_pages_live=w_live,
                       kv_window_pages_held=held,
                       kv_window_pages_whole_rings=len(rows) * ring,
                       kv_window_pages_padded=B * ring)
        return out

    def _linear_path(self, T: int) -> Optional[str]:
        """What a block's products run for a step of ``T`` rows: the
        model's own answer (``_linear_chooser``: its chooser, asked of
        every stack with the same shapes, on the thread that traces the
        step). None: the model has no such products."""
        if self._linear_chooser is None:
            return None
        if T not in self._linear_paths:         # a bucket's first call
            self._linear_paths[T] = self._linear_chooser(
                self.cfg, self.params, T)
        return self._linear_paths[T]

    def _expert_product(self, T: int):
        """``moe.expert_product`` for a step of ``T`` rows (None: the
        model has no routed experts)."""
        shapes = (self._spec or {}).get("routed_experts")
        if shapes is None:
            return None
        if T not in self._expert_products:      # a bucket's first call
            from ray_tpu.parallel.moe import expert_product
            self._expert_products[T] = expert_product(T, *shapes)
        return self._expert_products[T]

    def _count_experts(self, counts: np.ndarray, product, tokens: int,
                       zeros: np.ndarray) -> Dict[str, Any]:
        """counts [routed layers, experts held]: the step's tokens per
        expert (real experts held here); ``tokens`` the step's real
        tokens, each through every router; ``zeros`` [routed layers] (or
        empty: the router has no such output) its assignments to a
        zero-compute expert, which cost nothing. Kept cumulatively for
        ``counters()``; the step span gets how many experts the step
        touched, how uneven the load was (largest over mean, the median
        of the layers) and the rows that went through an expert (the
        live blocks' of ``product``)."""
        if self._expert_tokens_total is None:
            self._expert_tokens_total = np.zeros(counts.shape, np.int64)
        self._expert_tokens_total += counts
        self._expert_tokens_last = counts
        self._routed_tokens_total += tokens
        mean = counts.mean(axis=1)
        out = {"experts_touched": int((counts > 0).sum()),
               "expert_tokens": int(counts.sum()),
               "expert_rows_multiplied": product.rows_multiplied(counts),
               "moe_max_over_mean": float(np.median(
                   counts.max(axis=1) / np.maximum(mean, 1e-9)))}
        if zeros.size:
            if self._zero_expert_tokens_total is None:
                self._zero_expert_tokens_total = np.zeros(zeros.shape,
                                                          np.int64)
            self._zero_expert_tokens_total += zeros
            # of the step's tokens x routed layers x experts a token
            out.update(zero_expert_tokens=int(zeros.sum()),
                       routed_tokens=tokens,
                       routed_assignments=tokens * zeros.size
                       * self._spec["routed_experts"][0])
        return out

    def prefill(self, seqs, tokens_only: bool = False, fetch: bool = True):
        """The prompts' tokens into their pages (and state). ``fetch=False``
        (``decode_ahead``) returns the dispatched ``PromptStep`` in place
        of its result: the decode step dispatched behind it feeds these
        sequences' first tokens on the device. At most one prompt program
        may be in flight when the next is dispatched."""
        rows = []
        slots = self._take_slots([s.seq_id for s in seqs]) \
            if self.has_state else [0] * len(seqs)
        for s, slot in zip(seqs, slots):
            cached = _cached_tokens(s)
            if cached:
                self._refuse_with_window(
                    "prefill from a non-zero length", "the suffix's window "
                    "layers would read the prefix's last rows, which no "
                    "ring holds for this sequence")
            if cached % self.cache.block_size:
                # copy-on-extend: the suffix write lands in the last
                # shared prefix page — privatize it first
                old, new = self.cache.copy_on_write(
                    s.seq_id, cached // self.cache.block_size)
                if new != old:
                    self.copy_page(old, new)
            table = self.cache.block_table(s.seq_id)
            st = self._state[s.seq_id] = {
                "table": table, "len": len(s.prompt), "slot": slot,
                "run_pages": self.cache.table_run_pages(s.seq_id)}
            if self._rings:
                st["rings"] = {w: self.cache.ring_table(s.seq_id, w)
                               for w in self._rings}
            rows.append(dict(st, tokens=s.prompt[cached:], len=cached))
        if self._spec is None:
            return self._run(rows, "prefill", tokens_only)
        at, take, pending = self._dispatch(rows, "prefill", tokens_only)
        step = self._flying_prompt = PromptStep(
            self, {s.seq_id: self._feed_base + i for s, i in zip(seqs, at)},
            (), take, pending)
        return step.fetch() if fetch else step

    def decode(self, seqs, tokens_only: bool = False, fetch: bool = True):
        """One token a sequence. ``fetch=False`` (``decode_ahead``)
        returns the dispatched ``DecodeStep`` in place of its result. A
        sequence that is in the step still in flight has no
        ``s.tokens[-1]`` for this step yet: its row feeds that step's
        greedy token on the device and lies one token further on; one
        whose prompt's program is in flight feeds its first token the same
        way, at the length the prompt left. At most one decode step (and
        one prompt program, dispatched after it) may be in flight when
        the next is dispatched, and they are fetched before any other."""
        flying, prompt = self._flying, self._flying_prompt
        states = [self._state[s.seq_id] for s in seqs]
        rows = []
        for s, st in zip(seqs, states):
            src = flying.at.get(s.seq_id) if flying else None
            further = src is not None
            if src is None and prompt:
                src = prompt.at.get(s.seq_id)
            rows.append(dict(
                st, slot=st.get("slot", 0),
                tokens=[s.tokens[-1] if src is None else -1 - src],
                len=st["len"] + further))
        at, take, pending = self._dispatch(rows, "decode", tokens_only)
        step = self._flying = DecodeStep(
            self, dict(zip((s.seq_id for s in seqs), at)), states, take,
            pending)
        return step.fetch() if fetch else step

    def decode_window(self, seqs, windows) -> List[np.ndarray]:
        """One batched multi-token incremental step; causal masking at
        the right offsets comes from ``cached_attention``'s
        ``q_positions``, so position j's logits condition on exactly
        window[:j+1] — the speculative verify contract."""
        self._refuse_with_state("decode_window", "a rejected position "
                                "cannot be taken out of the state again")
        self._refuse_with_window(
            "decode_window", "a window's positions overwrite the ring rows "
            "a ring before them, which a rejected position cannot restore")
        rows = []
        for s, win in zip(seqs, windows):
            st = self._state[s.seq_id]
            rows.append({"tokens": list(win), "len": st["len"],
                         "table": st["table"]})
            st["len"] += len(win)
        full = self._run(rows, "verify")       # [B, S, V]
        return [full[i, :len(win)] for i, win in enumerate(windows)]

    def _refuse_with_state(self, what: str, why: str):
        if self.has_state:
            raise RecurrentStateError(
                f"{what}: model kind {self.kind!r} keeps recurrent state "
                f"per sequence, and {why}; it needs a snapshot of the "
                "state at the token in question, which nothing takes yet")

    def _refuse_with_window(self, what: str, why: str):
        if self._spec is not None and self.page_windows:
            raise WindowedPagesError(
                f"{what}: model kind {self.kind!r} has a windowed page "
                f"group (a ring of pages a sequence), and {why}")

    def rollback(self, seq_id: str, n: int):
        self._refuse_with_state("rollback", "the last n tokens cannot be "
                                "taken out of the state again")
        self._refuse_with_window(
            "rollback", "the rows the last n positions overwrote in the "
            "ring are gone")
        st = self._state.get(seq_id)
        if st is not None and n > 0:
            st["len"] = max(0, st["len"] - int(n))

    def export_kv(self, seq_id: str, n_prompt: int) -> Dict[str, Any]:
        self._refuse_with_state("export_kv", "the pages alone do not "
                                "carry a prompt to another replica")
        self._refuse_with_window(
            "export_kv", "a blob holds a prompt's whole pages in order, "
            "which a ring is not")
        jnp = self._jnp
        bs = self.cache.block_size
        nb = -(-int(n_prompt) // bs)
        st = self._state[seq_id]
        idx = jnp.asarray(np.asarray(st["table"][:nb], np.int32))
        if self._spec is not None:      # the pools the model names
            with self._lock:
                pages = {name: np.asarray(self._arrays[name][:, idx])
                         for name in self._spec["pages"]}
            return {"kind": f"flax:{self.kind}", "n": int(n_prompt),
                    "pages": pages}
        heads = (self.n_layers, nb, bs, self.n_kv_heads, self.head_dim)
        with self._lock:
            k = np.asarray(self.k_pages[:, idx]).reshape(heads)
            v = np.asarray(self.v_pages[:, idx]).reshape(heads)
        return {"kind": f"flax:{self.kind}", "n": int(n_prompt),
                "k": k, "v": v}

    def import_kv(self, seq_id: str, n_prompt: int,
                  blob: Dict[str, Any]):
        self._refuse_with_state("import_kv", "a blob of pages alone does "
                                "not restore a prompt")
        self._refuse_with_window(
            "import_kv", "a blob holds a prompt's whole pages in order, "
            "which a ring is not")
        jnp = self._jnp
        if blob.get("kind") != f"flax:{self.kind}":
            raise ValueError(
                f"KV blob kind {blob.get('kind')!r} does not match "
                f"adapter flax:{self.kind}")
        bs = self.cache.block_size
        nb = -(-int(n_prompt) // bs)
        table = self.cache.block_table(seq_id)
        idx = jnp.asarray(np.asarray(table[:nb], np.int32))
        if self._spec is not None:
            with self._lock:
                for name in self._spec["pages"]:
                    a = self._arrays[name]
                    self._arrays[name] = a.at[:, idx].set(
                        jnp.asarray(blob["pages"][name], a.dtype))
            self._state[seq_id] = {
                "table": table, "len": int(n_prompt),
                "run_pages": self.cache.table_run_pages(seq_id)}
            return
        merged = (self.n_layers, nb, bs, -1)
        with self._lock:
            self.k_pages = self.k_pages.at[:, idx].set(jnp.asarray(
                blob["k"], self.k_pages.dtype).reshape(merged))
            self.v_pages = self.v_pages.at[:, idx].set(jnp.asarray(
                blob["v"], self.v_pages.dtype).reshape(merged))
        self._state[seq_id] = {
            "table": table, "len": int(n_prompt),
            "run_pages": self.cache.table_run_pages(seq_id)}

    def release(self, seq_id: str):
        st = self._state.pop(seq_id, None)
        if st is not None and st.get("slot"):
            self._free_slots.append(st["slot"])


# the kinds ``FlaxModelAdapter`` builds (tests/test_llm_lfm2_serving.py
# holds that each of them does)
FLAX_KINDS = ("gpt2", "llama", "kimi_linear", "kimi_k2", "laguna",
              "longcat_flash", "smallthinker", "jamba", "lfm2")


def make_adapter(model: str = "toy",
                 model_config: Optional[Dict[str, Any]] = None):
    """Deployment-facing factory: ``model`` is ``toy`` or one of
    ``FLAX_KINDS`` (tiny test configs unless ``model_config``
    overrides)."""
    model_config = dict(model_config or {})
    if model == "toy":
        return ToyAdapter(**model_config)
    if model in FLAX_KINDS:
        return FlaxModelAdapter(kind=model, **model_config)
    raise ValueError(
        f"unknown model {model!r} ({' | '.join(('toy',) + FLAX_KINDS)})")
