"""Paged (block) KV cache: the allocator behind continuous batching.

Reference analogue: vLLM's BlockSpaceManager (PAPERS.md "Fine-Tuning
and Serving Gemma 4 31B on Google Cloud TPU" serves through the same
design). The cache is a fixed pool of fixed-size pages; each sequence
owns a *block table* mapping its logical token positions to physical
pages. Growing a sequence by one token allocates at most one page;
finishing a sequence returns all its pages to the free intervals at once.
Admission control is therefore exact: a prompt of L tokens with a
budget of G generated tokens needs ``ceil((L + G) / block_size)``
pages, and the engine refuses to admit what it cannot finish —
sequences never deadlock mid-decode waiting for pages.

Page 0 is reserved as the *null page*: batch-padding rows point every
block-table entry at it, so padded jit steps scatter their garbage
into scratch instead of a live sequence's memory.

Pages are handed out as **ascending runs**: the free pages of a pool are
a set of intervals (``_FreeRuns``), a need is taken from as few of them
as hold it (the smallest interval that holds it all, the lowest among
equals, so a fresh pool gives 1, 2, 3, ...; else the longest whole and
then the smallest that holds the rest), a table lists each interval's
pages ascending, and pages that come back join the free intervals beside
them. The decode kernels are why (``ops.attention.paged_attention_decode``):
a page's copy from HBM costs about as much to start as its 16 KB take to
arrive, and where ``RUN_PAGES`` consecutive entries of a table name
consecutive pages one copy brings them all. A free stack handed a
released table back in reverse, cut where needs differ: descending
pieces after one turn of the callers. Contiguity is best effort and
changes nothing that is exact: admission is by the count of free pages
in every group, and an allocation the counts allow never fails.
``stats()`` reports ``kv_run_pages_share``: of the pages the sequences'
tables hold, those in whole runs, counted when a table is handed out.

Pages are *refcounted* so the radix prefix cache (prefix_cache.py) can
share read-only prompt pages across sequences: ``allocate_with_prefix``
maps a cached prefix into a new sequence's block table by bumping the
shared pages' refcounts, ``copy_on_write`` gives a sequence a private
copy of a shared page before it writes into it, and a page returns to
the free list only when its last reference (sequence table or cache
branch) drops.

A model may cache some layers over a *window*: a layer that only ever
reads the last ``window`` positions (a sliding-attention layer). Those
layers' pages form a **page group** of their own (``windows=``): a
sequence's *ring* there is ``window // block_size + 1`` pages (position
``p`` lies in ring page ``(p // block_size) % ring``, so a page is
overwritten once every position it held has left the window), and the
sequence takes **what it needs of it** with its other pages at admission:
``min(ring, blocks_for(num_tokens))`` pages, returned with them at
release. A sequence whose whole budget fits in fewer pages than a ring
never wraps (position ``p`` lies in its page ``p // block_size``), so the
rest of its ring's table is the group's null page and nothing reads or
writes it. The group's pool has a stated size as the full group's has
(``window_blocks=``, its null page included; default ``max_sequences``
whole rings and the null page); admission counts every group by the need
above, so it stays exact on each: a request that any group cannot hold is
refused (``OutOfKVBlocksError``, whose ``group`` names the group that was
short) and waits. A ring page is overwritten in place: it is never
refcounted, shared or copied on write. Not done here: pages behind the
window are not given back while the sequence runs, a ring does not grow
on demand, nothing is preempted, and a table does not slide (ROADMAP R3,
R8).

The pool itself is storage-agnostic (``make_pages`` builds numpy or
jax arrays per layer on demand) — the allocator tracks only indices,
so the same bookkeeping serves the numpy toy adapter and the jitted
flax adapters.
"""

from __future__ import annotations

import bisect
import threading
from typing import Dict, Iterable, List, Optional, Tuple

# table entries the decode kernels fetch with one copy where they name
# consecutive pages (``ops.attention.PAGED_RUN_PAGES``: the same number,
# held equal by tests/test_llm_kernels_decode.py)
RUN_PAGES = 8


class OutOfKVBlocksError(Exception):
    """The pool cannot satisfy an allocation — the engine keeps the
    sequence WAITING (or sheds it) rather than admitting work it
    cannot finish. ``group``: the page group that was short (``"full"``,
    or a window)."""

    def __init__(self, message: str, group="full"):
        super().__init__(message)
        self.group = group


def run_pages(table: List[int]) -> int:
    """Pages of ``table`` that lie in a group of ``RUN_PAGES`` consecutive
    ones (table positions 0..7, 8..15, ...): what a decode kernel brings
    with one copy a group."""
    return RUN_PAGES * sum(
        all(table[j + 1] - table[j] == 1 for j in range(i, i + RUN_PAGES - 1))
        for i in range(0, len(table) - RUN_PAGES + 1, RUN_PAGES))


class _FreeRuns:
    """The free pages ``first..last`` of one pool as intervals: sorted
    starts with their lengths beside them, neighbours joined when pages
    come back, the count of free pages kept (``len``). A need is taken
    from as few intervals as hold it, each piece ascending, so a table is
    a few runs of consecutive pages (the allocator's lock guards it)."""

    def __init__(self, first: int, last: int):
        self._starts: List[int] = [first]
        self._lens: List[int] = [last - first + 1]
        self._count = last - first + 1

    def __len__(self) -> int:
        return self._count

    def _fit(self, need: int) -> int:
        """The smallest interval that holds ``need`` pages, the lowest
        among equals (-1: none does)."""
        best = -1
        for i, n in enumerate(self._lens):
            if n >= need and (best < 0 or n < self._lens[best]):
                best = i
        return best

    def take(self, need: int) -> List[int]:
        """``need`` pages (the caller has checked the count): from one
        interval where one holds them (the smallest that does, so an
        exact fit goes whole and the long intervals wait for the long
        needs), else the longest intervals whole until one holds the
        rest. Each interval's pages ascending, the intervals by
        address."""
        assert 0 <= need <= self._count, (need, self._count)
        pieces: List[Tuple[int, int]] = []
        while need:
            i = self._fit(need)
            if i >= 0:
                start, n = self._starts[i], need
                if n == self._lens[i]:
                    del self._starts[i], self._lens[i]
                else:
                    self._starts[i] += n
                    self._lens[i] -= n
            else:
                i = max(range(len(self._lens)),
                        key=lambda j: (self._lens[j], -j))
                start, n = self._starts.pop(i), self._lens.pop(i)
            pieces.append((start, n))
            self._count -= n
            need -= n
        pieces.sort()
        return [p for start, n in pieces for p in range(start, start + n)]

    def give(self, pages: Iterable[int]) -> None:
        """Pages come back, in any order; each run joins the free
        intervals beside it."""
        pages = sorted(pages)
        i = 0
        while i < len(pages):
            j = i
            while j + 1 < len(pages) and pages[j + 1] == pages[j] + 1:
                j += 1
            self._give_run(pages[i], j - i + 1)
            i = j + 1

    def _give_run(self, start: int, n: int) -> None:
        self._count += n
        at = bisect.bisect_left(self._starts, start)
        if at and self._starts[at - 1] + self._lens[at - 1] == start:
            at -= 1
            self._lens[at] += n
        else:
            self._starts.insert(at, start)
            self._lens.insert(at, n)
        if at + 1 < len(self._starts) \
                and self._starts[at] + self._lens[at] == self._starts[at + 1]:
            self._lens[at] += self._lens.pop(at + 1)
            del self._starts[at + 1]

    def __iter__(self):
        for start, n in zip(self._starts, self._lens):
            yield from range(start, start + n)


class _Tables(dict):
    """seq id -> the pages it holds of one page group, with the pages
    that lie in whole runs (``run_pages``) counted once, when a table is
    handed out, and summed beside the tables' pages."""

    def __init__(self):
        super().__init__()
        self.runs: Dict[str, int] = {}
        self.run_total = self.page_total = 0

    def hand_out(self, seq_id: str, pages: List[int]) -> None:
        """``pages`` become (or replace) the sequence's table."""
        self.take_back(seq_id)
        self[seq_id] = pages
        self.runs[seq_id] = run_pages(pages)
        self.run_total += self.runs[seq_id]
        self.page_total += len(pages)

    def take_back(self, seq_id: str) -> Optional[List[int]]:
        pages = self.pop(seq_id, None)
        if pages is not None:
            self.run_total -= self.runs.pop(seq_id)
            self.page_total -= len(pages)
        return pages

    def run_share(self) -> float:
        return self.run_total / max(1, self.page_total)


class PagedKVCache:
    """Block allocator + occupancy accounting for one replica's pool.

    Thread-safe: the engine thread allocates/frees while actor threads
    read occupancy for admission and telemetry.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 windows: Iterable[int] = (), max_sequences: int = 0,
                 window_blocks: Optional[int] = None):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (page 0 is reserved)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # the sequences that may hold pages at once (0: not said)
        self.max_sequences = int(max_sequences)
        # page 0 reserved as the null/scratch page for padding rows
        self._free = _FreeRuns(1, self.num_blocks - 1)
        self._tables = _Tables()                  # seq id -> pages
        self._refs: Dict[int, int] = {}           # page -> reference count
        # window -> its page group: a ring (or what a sequence needs of
        # one) a sequence, page 0 null
        self._rings: Dict[int, _RingGroup] = {
            int(w): _RingGroup(int(w) // self.block_size + 1,
                               int(max_sequences), window_blocks)
            for w in sorted(set(windows))}
        self._lock = threading.Lock()

    # ---- sizing ----

    def blocks_for(self, num_tokens: int) -> int:
        return max(1, -(-int(num_tokens) // self.block_size))

    def can_allocate(self, num_tokens: int) -> bool:
        with self._lock:
            return len(self._free) >= self.blocks_for(num_tokens) \
                and not self._ring_short_locked(num_tokens)

    # ---- window groups ----

    @property
    def windows(self) -> Tuple[int, ...]:
        return tuple(self._rings)

    def ring_blocks(self, window: int) -> int:
        """Pages of a whole ring of the window's group: the width of a
        sequence's table there, and the most it holds."""
        return self._rings[window].ring

    def ring_need(self, window: int, num_tokens: int) -> int:
        """Pages a sequence of ``num_tokens`` (prompt + budget) takes of
        the window's group: a whole ring, or the pages of its tokens where
        they are fewer (it then never wraps)."""
        return min(self._rings[window].ring, self.blocks_for(num_tokens))

    def group_blocks(self, window: int) -> int:
        """Pages of the window group's pool, its null page included."""
        return self._rings[window].num_blocks

    def ring_table(self, seq_id: str, window: int) -> Optional[List[int]]:
        with self._lock:
            t = self._rings[window].tables.get(seq_id)
            return list(t) if t else None

    def _ring_short_locked(self, num_tokens: int
                           ) -> Optional[OutOfKVBlocksError]:
        """The first window group that cannot give a sequence of
        ``num_tokens`` its pages."""
        for w, g in self._rings.items():
            need = self.ring_need(w, num_tokens)
            if len(g.free) < need:
                return OutOfKVBlocksError(
                    f"need {need} pages of the window-{w} group, "
                    f"{len(g.free)} free (pool {g.num_blocks - 1})", group=w)
        return None

    def _take_rings_locked(self, seq_id: str, num_tokens: int):
        for w, g in self._rings.items():
            g.tables.hand_out(
                seq_id, g.free.take(self.ring_need(w, num_tokens)))

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    # ---- allocation ----

    def allocate(self, seq_id: str, num_tokens: int) -> List[int]:
        """Reserve every page a sequence will ever need (prompt +
        generation budget) up front — exact admission, no mid-decode
        OOM."""
        need = self.blocks_for(num_tokens)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id!r} already allocated")
            if len(self._free) < need:
                raise OutOfKVBlocksError(
                    f"need {need} KV blocks, {len(self._free)} free "
                    f"(pool {self.num_blocks - 1})")
            short = self._ring_short_locked(num_tokens)
            if short:
                raise short
            pages = self._free.take(need)
            for p in pages:
                self._refs[p] = 1
            self._tables.hand_out(seq_id, pages)
            self._take_rings_locked(seq_id, num_tokens)
            return list(pages)

    def allocate_with_prefix(self, seq_id: str, num_tokens: int,
                             shared_pages: List[int]) -> List[int]:
        """Admit a sequence whose leading pages are already resident:
        the shared (read-only) pages are mapped into the new block
        table by refcount, and only the remainder comes from the free
        list.  The caller must not write into a shared page without
        ``copy_on_write`` first."""
        if self._rings and shared_pages:
            raise ValueError(
                "a shared prefix cannot be mapped into a sequence of a "
                "model with a windowed page group: a ring page is "
                "overwritten as the sequence grows, so what it held of "
                "the prefix cannot be shared")
        need = self.blocks_for(num_tokens)
        n_shared = len(shared_pages)
        if n_shared > need:
            raise ValueError(
                f"prefix covers {n_shared} pages but sequence needs {need}")
        fresh_need = need - n_shared
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id!r} already allocated")
            for p in shared_pages:
                if self._refs.get(p, 0) <= 0:
                    raise ValueError(f"shared page {p} is not live")
            if len(self._free) < fresh_need:
                raise OutOfKVBlocksError(
                    f"need {fresh_need} fresh KV blocks "
                    f"({n_shared} shared), {len(self._free)} free")
            short = self._ring_short_locked(num_tokens)
            if short:
                raise short
            for p in shared_pages:
                self._refs[p] += 1
            fresh = self._free.take(fresh_need)
            for p in fresh:
                self._refs[p] = 1
            pages = list(shared_pages) + fresh
            self._tables.hand_out(seq_id, pages)
            self._take_rings_locked(seq_id, num_tokens)
            return list(pages)

    def incref(self, pages: Iterable[int]) -> None:
        """Take an extra reference on live pages (prefix-cache branch
        adoption)."""
        with self._lock:
            for p in pages:
                if self._refs.get(p, 0) <= 0:
                    raise ValueError(f"page {p} is not live")
                self._refs[p] += 1

    def decref(self, pages: Iterable[int]) -> int:
        """Drop one reference per page; pages whose count hits zero go
        back to the free list.  Returns how many were actually freed."""
        with self._lock:
            return self._decref_locked(pages)

    def _decref_locked(self, pages: Iterable[int]) -> int:
        freed = []
        for p in pages:
            n = self._refs.get(p, 0)
            if n <= 0:
                continue
            if n == 1:
                del self._refs[p]
                freed.append(p)
            else:
                self._refs[p] = n - 1
        self._free.give(freed)
        return len(freed)

    def table_run_pages(self, seq_id: str) -> Tuple[int, int]:
        """(pages in whole runs of ``RUN_PAGES``, pages) of the tables the
        sequence holds, all page groups together, as counted when they
        were handed out."""
        with self._lock:
            tables = [self._tables] + [g.tables for g in
                                       self._rings.values()]
            return (sum(t.runs.get(seq_id, 0) for t in tables),
                    sum(len(t.get(seq_id, ())) for t in tables))

    def copy_on_write(self, seq_id: str, index: int) -> Tuple[int, int]:
        """Give ``seq_id`` a private copy of block-table entry ``index``
        before it writes into it.  Returns ``(old_page, new_page)`` —
        equal when the page was already private (nothing to do); the
        caller copies the page *contents* old→new when they differ."""
        with self._lock:
            table = self._tables.get(seq_id)
            if table is None or index >= len(table):
                raise ValueError(f"no block {index} for {seq_id!r}")
            old = table[index]
            if self._refs.get(old, 0) <= 1:
                return (old, old)
            if not self._free:
                raise OutOfKVBlocksError(
                    "copy-on-write needs 1 free KV block, 0 free")
            new, = self._free.take(1)
            self._refs[new] = 1
            self._refs[old] -= 1
            table[index] = new
            self._tables.hand_out(seq_id, table)
            return (old, new)

    def ref_count(self, page: int) -> int:
        with self._lock:
            return self._refs.get(page, 0)

    def free(self, seq_id: str) -> int:
        """Drop a finished sequence's references; pages still shared
        with the prefix cache or other sequences stay resident, the
        rest are admittable on the very next engine step."""
        with self._lock:
            for g in self._rings.values():
                g.free.give(g.tables.take_back(seq_id) or ())
            pages = self._tables.take_back(seq_id)
            if not pages:
                return 0
            return self._decref_locked(pages)

    def block_table(self, seq_id: str) -> Optional[List[int]]:
        with self._lock:
            t = self._tables.get(seq_id)
            return list(t) if t else None

    # ---- telemetry (autoscaler signal: docs/LLM_SERVING.md) ----

    def occupancy(self) -> float:
        """Fraction of the usable pool currently owned by sequences."""
        with self._lock:
            usable = self.num_blocks - 1
            return (usable - len(self._free)) / max(1, usable)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            usable = self.num_blocks - 1
            used = usable - len(self._free)
            out = {"kv_blocks_total": usable,
                   "kv_blocks_used": used,
                   "kv_block_size": self.block_size,
                   "kv_occupancy": used / max(1, usable),
                   "kv_sequences": len(self._tables),
                   # of the pages the sequences' tables hold, those in
                   # groups of RUN_PAGES consecutive ones
                   "kv_run_pages_share": self._tables.run_share()}
            if self._rings:
                out["kv_window_groups"] = {
                    w: g.stats() for w, g in self._rings.items()}
            return out


class _RingGroup:
    """One window's pages: ``num_blocks`` pages (default: ``max_sequences``
    rings of ``ring`` pages) with the null page 0 among them; a sequence's
    table holds a ring's pages or fewer (the allocator's lock guards
    it)."""

    def __init__(self, ring: int, max_sequences: int,
                 num_blocks: Optional[int] = None):
        if num_blocks is None:
            if max_sequences < 1:
                raise ValueError(
                    "a windowed page group is sized by the sequences that "
                    "may run at once (max_sequences >= 1) or by its "
                    "stated pages (window_blocks)")
            num_blocks = max_sequences * ring + 1
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (page 0 is reserved)")
        self.ring = ring
        self.num_blocks = int(num_blocks)
        self.free = _FreeRuns(1, self.num_blocks - 1)
        self.tables = _Tables()

    def stats(self) -> Dict[str, float]:
        usable = self.num_blocks - 1
        used = usable - len(self.free)
        return {"ring_blocks": self.ring, "blocks_total": usable,
                "blocks_used": used, "occupancy": used / max(1, usable),
                "sequences": len(self.tables),
                "run_pages_share": self.tables.run_share(),
                # what the running sequences' whole rings would be
                "blocks_whole_rings": len(self.tables) * self.ring}
