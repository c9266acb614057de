"""Block-paged KV cache pool: fixed-size pages, per-sequence page tables.

The memory manager half of the iterative decode engine (ISSUE 11,
vLLM-style). Device state is the columnar pool from
``models.generation.init_paged_kv`` — int8 k/v page-major ``[num_pages,
layers, page_size, heads*head_dim]`` plus f32 per-position-per-head
scales ``[num_pages, layers, page_size, SCALE_LANES]`` (head ``h`` in
lane ``h``), the ONE layout the pool is resident in, written in and read
by the attention kernel in — so the pool IS a set of frame columns with
pages as rows (:meth:`as_frame` materializes the TensorFrame view;
ROADMAP #3's data plane can later back these columns with its block
store). This class owns the HOST side:
the free list, per-sequence page ownership, the page tables the step
functions gather through, and (ISSUE 19) the two extra page lifecycles
of the serving KV memory hierarchy:

* **shared prefix pages** — read-only pages published into a
  content-addressed index (hash chain over page-granular token
  prefixes) with per-page refcounts. A sequence whose prompt prefix
  matches a published chain references those pages instead of
  re-prefilling them; a page whose refcount drops to 0 stays cached
  (LRU) until :meth:`alloc` reclaims it under demand.
* **host-swapped sequences** — :meth:`swap_out_seq` moves one evicted
  sequence's page payloads into a
  :class:`~tensorframes_tpu.blockstore.BlockStore` segment (CRC +
  quarantine machinery included) and :meth:`swap_in_seq` brings them
  back, so preemption resume restores pages instead of recomputing.

Accounting contract (property-swept in tests/test_decode.py): every
page except the reserved null page 0 is at all times in EXACTLY ONE of
three states — free, exclusively owned by one sequence, or shared with
a refcount — and :meth:`check` asserts the three-way partition after
any interleaving of join/extend/evict/share/copy-on-extend/swap. Page 0
belongs to nobody: padding slots and masked prefill positions write
their garbage there, and the attention masks guarantee it is never read
unmasked.
"""

from __future__ import annotations

import collections
import hashlib
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["PagedKVPool", "PoolAccountingError", "PoolExhaustedError"]


class PoolAccountingError(RuntimeError):
    """A page alloc/free invariant was violated (double free, freeing a
    page the sequence does not own, or a corrupted free list) — always
    a bug in the caller or the pool, never load-dependent."""


class PoolExhaustedError(RuntimeError):
    """``alloc`` asked for more pages than are free. The decode engine
    turns this into preemption (evict a victim, retry), never an
    unbounded wait."""


class _KindPages:
    """Free list and exclusive ownership of one page kind
    (``models/served.PageKind``), and the gauge its free pages show on.
    A sequence's pages of a kind are its own, in the order it got them;
    sharing and swap are the pool's, over its first kind alone."""

    def __init__(self, kind, num_pages: int, gauge):
        if num_pages < 1 + kind.entries:
            # the null page plus one sequence's whole table is the floor:
            # below it the OLDEST running sequence could page-fault with
            # nothing left to evict — the livelock the forward-progress
            # guarantee exists to rule out
            raise ValueError(
                f"num_pages={num_pages} cannot hold the null page plus "
                f"one full sequence ({kind.entries} {kind.name} pages) — "
                "an undersized pool could stall its own oldest sequence; "
                "raise num_pages or lower the decode horizon"
            )
        self.kind = kind
        self.num_pages = int(num_pages)
        self.gauge = gauge
        self.free: collections.deque = collections.deque(
            range(1, self.num_pages)
        )
        self.owned: Dict[int, List[int]] = {}


def _chain_key(prev: bytes, tokens: np.ndarray) -> bytes:
    """One link of the page-granular content address: the hash of a
    page's tokens chained onto the hash of everything before it, so a
    key identifies the page's tokens AND its whole prefix lineage."""
    h = hashlib.sha1(prev)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


class PagedKVPool:
    """Fixed-size KV pages + per-sequence page tables over the columnar
    pool state. ``columns`` holds the device arrays. The donation
    contract: every engine program that returns the pool takes
    ``columns`` donated and writes it in place, so the arrays passed in
    are DELETED by the call and the engine rebinds ``columns`` to what
    came back — never keep a reference to a column across a step; read
    through ``pool.columns`` on the engine thread (or on a stopped
    engine). Everything else is host-side bookkeeping under the
    engine's scheduling thread (single-threaded by design — the pool is
    not itself locked)."""

    def __init__(self, model, num_pages: int, page_size: int,
                 extra_pages: Optional[Dict[str, int]] = None):
        """``model`` is a ``models.served.ServedModel`` (a configuration
        gives one: ``cfg.served_model(page_size, horizon)``). Its first
        page kind is the one ``num_pages`` sizes, prefixes are shared in
        and sequences are swapped from; ``extra_pages`` gives the page
        count of each further kind by name."""
        from . import metrics as m

        self.model = model
        self.kinds = tuple(model.kinds)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_seq = int(self.kinds[0].entries)
        counts = {self.kinds[0].name: self.num_pages, **(extra_pages or {})}
        # every kind its own free list and ownership, in kind order
        self._pages: Dict[str, _KindPages] = {
            k.name: _KindPages(k, counts[k.name], gauge)
            for k, gauge in zip(self.kinds, m.page_kind_gauges(
                [k.name for k in self.kinds]))
        }
        self._kv = self._pages[self.kinds[0].name]
        # (sequence, kind) -> (pages held, the table built from them)
        self._tables: Dict[Tuple[int, str], Tuple[int, np.ndarray]] = {}
        self.columns: Dict[str, object] = model.init_pool(counts)
        # -- prefix-cache state (shared read-only pages, ISSUE 19) ----------
        # a sequence's table is refs (shared prefix chain) + owned
        # (exclusive pages), in position order
        self._refs: Dict[int, List[int]] = {}
        self._shared_ref: Dict[int, int] = {}        # page -> refcount
        self._shared_lru: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict()                # refcount-0 pages
        )
        self._prefix_index: Dict[bytes, int] = {}    # chain key -> page
        # page -> (parent chain key, own chain key, page tokens)
        self._prefix_meta: Dict[int, Tuple[bytes, bytes, bytes]] = {}
        self._prefix_children: Dict[bytes, List[int]] = {}
        # the free-pages gauges aggregate by DELTA across live pools
        # (several decode endpoints share one process-wide series; a
        # set() here would clobber the siblings)
        self._closed = True
        self.reopen()

    # the first kind's lists, under the names the sharing and swap code
    # below has always used
    @property
    def _free(self) -> collections.deque:
        return self._kv.free

    @property
    def _owned(self) -> Dict[int, List[int]]:
        return self._kv.owned

    # -- page kinds ---------------------------------------------------------

    def demand(self, n_positions: int) -> Dict[str, int]:
        """Pages of each kind a sequence of ``n_positions`` KV slots
        holds (a ring kind never more than its entries)."""
        return {k.name: k.pages_for(n_positions, self.page_size)
                for k in self.kinds}

    def allocatable(self, kind: str) -> int:
        """Pages of ``kind`` an alloc can satisfy right now."""
        if kind == self._kv.kind.name:
            return self.num_allocatable
        return len(self._pages[kind].free)

    def held(self, seq: int, kind: str) -> int:
        """Pages of ``kind`` sequence ``seq`` holds."""
        seq = int(seq)
        if kind == self._kv.kind.name:
            return len(self._refs.get(seq, ())) \
                + len(self._owned.get(seq, ()))
        return len(self._pages[kind].owned.get(seq, ()))

    def tables(self, seq: int) -> List[np.ndarray]:
        """The sequence's page table of every kind, in kind order."""
        return [self.table(seq, k.name) for k in self.kinds]

    def null_tables(self) -> List[np.ndarray]:
        return [np.zeros(k.entries, np.int32) for k in self.kinds]

    # -- capacity -----------------------------------------------------------

    @property
    def usable_pages(self) -> int:
        """Allocatable pages (everything but the null page)."""
        return self.num_pages - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocatable(self) -> int:
        """Pages :meth:`alloc` can satisfy right now: the free list plus
        cached shared pages nobody references (reclaimable on demand).
        The engine's admission budget and preemption trigger read this —
        a cache full of refcount-0 pages must not starve admissions."""
        return len(self._free) + len(self._shared_lru)

    @property
    def num_shared(self) -> int:
        """Pages currently in the shared prefix cache (any refcount)."""
        return len(self._shared_ref)

    def pages_needed(self, n_positions: int) -> int:
        """Pages covering ``n_positions`` KV slots."""
        return -(-int(n_positions) // self.page_size)

    # -- alloc / free -------------------------------------------------------

    def alloc(self, seq: int, n: int,
              kind: Optional[str] = None) -> List[int]:
        """Give ``n`` exclusive pages of ``kind`` (default: the first)
        to sequence ``seq``, appended to its table (in the first kind
        after any shared prefix). Reclaims refcount-0 shared pages
        LRU-first when the first kind's free list alone cannot cover
        ``n``; raises :class:`PoolExhaustedError` when even that cannot
        (nothing is partially allocated)."""
        n, seq = int(n), int(seq)
        if n < 0:
            raise ValueError(f"alloc of {n} pages")
        pages = self._pages[kind] if kind is not None else self._kv
        name = "" if pages is self._kv else pages.kind.name + " "
        held = pages.owned.setdefault(seq, [])
        total = len(held) + n
        if pages is self._kv:
            total += len(self._refs.get(seq, ()))
        if total > pages.kind.entries:
            raise PoolAccountingError(
                f"sequence {seq} would hold {total} {name}pages, over "
                f"the {pages.kind.entries} table entries a sequence has "
                "(max_pages_per_seq)"
            )
        if pages is self._kv and n > len(pages.free):
            self._reclaim_shared(n - len(pages.free))
        if n > len(pages.free):
            raise PoolExhaustedError(
                f"need {n} {name}pages, {len(pages.free)} free"
                + (f" + {len(self._shared_lru)} reclaimable"
                   if pages is self._kv else "")
                + f" (of {pages.num_pages - 1} usable)"
            )
        got = [pages.free.popleft() for _ in range(n)]
        held.extend(got)
        if not self._closed:
            pages.gauge.dec(n)
        return got

    def free_seq(self, seq: int) -> int:
        """Return every exclusive page owned by ``seq``, of every kind,
        to its free list and drop its references on shared prefix pages
        (a shared page at refcount 0 stays cached until reclaimed).
        Returns the count of first-kind pages freed (0 for a sequence
        holding none). Double frees and corrupted ownership raise
        :class:`PoolAccountingError`."""
        seq = int(seq)
        self._release_refs(seq)
        freed = 0
        for pages in self._pages.values():
            self._tables.pop((seq, pages.kind.name), None)
            got = pages.owned.pop(seq, None)
            if got is None:
                continue
            free_set = set(pages.free)
            for p in got:
                if p in free_set or p == 0 or (
                        pages is self._kv and p in self._shared_ref):
                    pages.owned[seq] = got  # restore for postmortem
                    raise PoolAccountingError(
                        f"double free: {pages.kind.name} page {p} of "
                        f"sequence {seq} is already free, shared, or the "
                        "null page"
                    )
            pages.free.extend(got)
            if not self._closed:
                pages.gauge.inc(len(got))
            if pages is self._kv:
                freed = len(got)
        return freed

    def owned(self, seq: int) -> List[int]:
        return list(self._owned.get(int(seq), ()))

    def seq_pages(self, seq: int) -> List[int]:
        """The sequence's full table in position order: shared prefix
        pages first, then its exclusive pages."""
        return (list(self._refs.get(int(seq), ()))
                + list(self._owned.get(int(seq), ())))

    def table(self, seq: int, kind: Optional[str] = None) -> np.ndarray:
        """The sequence's page table of ``kind`` (default: the first) as
        the step functions expect it: int32 ``[the kind's entries]``,
        unused tail entries = null page 0; the ``i``-th page a sequence
        got at entry ``i`` (a ring kind gets one as its context enters
        each new page, until the ring is whole)."""
        seq = int(seq)
        if kind is None:
            kind = self._kv.kind.name
        # a sequence's table changes only by growing (alloc appends; a
        # published page keeps its place) until free_seq drops it, and
        # the engine asks for every running sequence's table every
        # step: the last one built is kept beside the page count it was
        # built from, and handed out again (as a copy) while that holds
        n = self.held(seq, kind)
        kept = self._tables.get((seq, kind))
        if kept is not None and kept[0] == n:
            return kept[1].copy()
        if kind == self._kv.kind.name:
            t = np.zeros(self.max_pages_per_seq, np.int32)
            pages = self.seq_pages(seq)
        else:
            more = self._pages[kind]
            t = np.zeros(more.kind.entries, np.int32)
            pages = more.owned.get(seq, ())
        t[:len(pages)] = pages
        if n:
            self._tables[(seq, kind)] = (n, t)
        return t.copy()

    def null_table(self) -> np.ndarray:
        """An all-null page table — what padding slots carry."""
        return np.zeros(self.max_pages_per_seq, np.int32)

    def close(self) -> None:
        """Withdraw this pool's contribution from the process-wide
        gauges (the engine calls it at stop). Accounting and ``check()``
        keep working; only the gauges stop tracking."""
        if not self._closed:
            self._closed = True
            from . import metrics as m

            for pages in self._pages.values():
                pages.gauge.dec(len(pages.free))
            m.PREFIX_SHARED_PAGES.dec(len(self._shared_ref))

    def reopen(self) -> None:
        """Re-enroll in the process-wide gauges (engine restart)."""
        if self._closed:
            self._closed = False
            from . import metrics as m

            for pages in self._pages.values():
                pages.gauge.inc(len(pages.free))
            m.PREFIX_SHARED_PAGES.inc(len(self._shared_ref))

    # -- content-addressed prefix cache (ISSUE 19) --------------------------

    def prefix_match(
        self, tokens: np.ndarray
    ) -> Tuple[List[int], int, Optional[int], int]:
        """Longest published chain matching ``tokens``' page-granular
        prefix. Returns ``(pages, covered, cow_page, cow_tokens)``:
        ``pages`` are the matched shared pages (covering ``covered``
        tokens), capped so at least one token is always left to compute
        (the engine needs the logits at the last prompt position, and
        computing them writes KV — never into a shared page).

        ``cow_page``, when not None, is a published page whose first
        ``cow_tokens`` tokens equal the ENTIRE remaining prompt tail —
        the copy-on-extend candidate: the caller copies it into a fresh
        exclusive page (:meth:`copy_on_extend`) and teacher-forces only
        the final token, instead of prefilling the tail."""
        tokens = np.asarray(tokens, np.int32)
        plen = int(tokens.shape[0])
        ps = self.page_size
        limit = max(0, (plen - 1) // ps)
        pages: List[int] = []
        key = b""
        for i in range(limit):
            nxt = _chain_key(key, tokens[i * ps:(i + 1) * ps])
            page = self._prefix_index.get(nxt)
            if page is None:
                break
            pages.append(page)
            key = nxt
        covered = len(pages) * ps
        tail = tokens[covered:]
        r = plen - covered
        cow = None
        if 0 < r <= ps:
            want = np.ascontiguousarray(tail, np.int32).tobytes()
            for cand in self._prefix_children.get(key, ()):
                if self._prefix_meta[cand][2][:len(want)] == want:
                    cow = cand
                    break
        return pages, covered, cow, r

    def prefix_acquire(self, seq: int, pages: List[int]) -> None:
        """Reference ``pages`` (a matched chain, in position order) as
        sequence ``seq``'s shared prefix. Must run before the sequence
        allocates any exclusive page (the table is refs-then-owned)."""
        seq = int(seq)
        if self._refs.get(seq) or self._owned.get(seq):
            raise PoolAccountingError(
                f"sequence {seq} already holds pages; a shared prefix "
                "must be acquired before any alloc"
            )
        if len(pages) > self.max_pages_per_seq:
            raise PoolAccountingError(
                f"prefix of {len(pages)} pages exceeds "
                f"max_pages_per_seq={self.max_pages_per_seq}"
            )
        for p in pages:
            if p not in self._shared_ref:
                raise PoolAccountingError(
                    f"page {p} is not in the shared prefix cache"
                )
            if self._shared_ref[p] == 0:
                self._shared_lru.pop(p, None)
            self._shared_ref[p] += 1
        self._refs[seq] = list(pages)

    def _release_refs(self, seq: int) -> None:
        for p in self._refs.pop(seq, ()):
            c = self._shared_ref.get(p)
            if c is None or c < 1:
                raise PoolAccountingError(
                    f"sequence {seq} released shared page {p} with "
                    f"refcount {c}"
                )
            self._shared_ref[p] = c - 1
            if c == 1:
                # unreferenced but still cached: future prompts can hit
                # it until alloc pressure reclaims LRU-first
                self._shared_lru[p] = None

    def publish_prefix(self, seq: int, tokens: np.ndarray) -> int:
        """Convert sequence ``seq``'s freshly prefilled FULL prompt
        pages into shared prefix-cache pages (the sequence keeps
        referencing them; its ragged tail page — decode writes land
        there — stays exclusive). Publishing stops at the first chain
        key already indexed by another lineage: the shared prefix must
        stay contiguous at the head of the table. Returns the number of
        pages published."""
        seq = int(seq)
        tokens = np.asarray(tokens, np.int32)
        ps = self.page_size
        refs = self._refs.setdefault(seq, [])
        owned = self._owned.get(seq, [])
        full = int(tokens.shape[0]) // ps
        key = b""
        for i in range(len(refs)):
            key = _chain_key(key, tokens[i * ps:(i + 1) * ps])
        published = 0
        for i in range(len(refs), full):
            if not owned:
                break
            page_toks = tokens[i * ps:(i + 1) * ps]
            nxt = _chain_key(key, page_toks)
            if nxt in self._prefix_index:
                break
            page = owned.pop(0)
            refs.append(page)
            self._shared_ref[page] = 1
            self._prefix_index[nxt] = page
            self._prefix_meta[page] = (
                key, nxt,
                np.ascontiguousarray(page_toks, np.int32).tobytes(),
            )
            self._prefix_children.setdefault(key, []).append(page)
            key = nxt
            published += 1
        if published and not self._closed:
            from . import metrics as m

            m.PREFIX_SHARED_PAGES.inc(published)
        return published

    def copy_on_extend(self, seq: int, src: int) -> int:
        """Allocate a fresh exclusive page for ``seq`` as the copy
        target of shared page ``src`` (the ragged-tail copy-on-extend:
        the caller copies the device payload, then writes freely into
        the copy). Pure accounting here — returns the destination page."""
        if src not in self._shared_ref:
            raise PoolAccountingError(
                f"copy-on-extend source page {src} is not shared"
            )
        return self.alloc(seq, 1)[0]

    def _reclaim_shared(self, n: int) -> int:
        """Evict up to ``n`` refcount-0 shared pages (LRU-first) back to
        the free list, unpublishing them from the content index."""
        evicted = 0
        while evicted < n and self._shared_lru:
            page, _ = self._shared_lru.popitem(last=False)
            if self._shared_ref.pop(page, 0) != 0:
                raise PoolAccountingError(
                    f"shared page {page} on the LRU with a live refcount"
                )
            parent, key, _toks = self._prefix_meta.pop(page)
            self._prefix_index.pop(key, None)
            kids = self._prefix_children.get(parent)
            if kids:
                try:
                    kids.remove(page)
                except ValueError:
                    pass
                if not kids:
                    del self._prefix_children[parent]
            self._free.append(page)
            evicted += 1
        if evicted and not self._closed:
            from . import metrics as m

            m.PREFIX_EVICTIONS.inc(evicted)
            m.PREFIX_SHARED_PAGES.dec(evicted)
            self._kv.gauge.inc(evicted)
        return evicted

    # -- invariants ---------------------------------------------------------

    def check(self) -> None:
        """Assert the accounting partition: free ∪ exclusively-owned ∪
        shared-with-refcount = pages 1..P-1, with no page in two states,
        refcounts exactly matching the per-sequence references, and the
        content index bijective with the shared set. Cheap; the property
        sweep calls it after every mutation."""
        for more in self._pages.values():
            if more is self._kv:
                continue  # below, with the shared pages
            name, got = more.kind.name, list(more.free)
            for seq, pages in more.owned.items():
                if len(pages) > more.kind.entries:
                    raise PoolAccountingError(
                        f"sequence {seq} holds {len(pages)} {name} pages "
                        f"> the kind's {more.kind.entries} entries"
                    )
                got.extend(pages)
            if sorted(got) != list(range(1, more.num_pages)):
                raise PoolAccountingError(
                    f"{name} pages are not partitioned into free and "
                    "owned: a page is leaked, or in two places"
                )
        free = list(self._free)
        free_set = set(free)
        if len(free) != len(free_set):
            raise PoolAccountingError("free list holds a duplicate page")
        owned_all: List[int] = []
        for seq, pages in self._owned.items():
            held = len(pages) + len(self._refs.get(seq, ()))
            if held > self.max_pages_per_seq:
                raise PoolAccountingError(
                    f"sequence {seq} holds {held} pages > "
                    f"max_pages_per_seq={self.max_pages_per_seq}"
                )
            owned_all.extend(pages)
        owned_set = set(owned_all)
        if len(owned_all) != len(owned_set):
            raise PoolAccountingError(
                "a page is owned by two sequences (or twice by one)"
            )
        shared_set = set(self._shared_ref)
        counts: Dict[int, int] = {p: 0 for p in shared_set}
        for seq, pages in self._refs.items():
            for p in pages:
                if p not in shared_set:
                    raise PoolAccountingError(
                        f"sequence {seq} references page {p} which is "
                        "not in the shared set"
                    )
                counts[p] += 1
        for p, want in counts.items():
            if self._shared_ref[p] != want:
                raise PoolAccountingError(
                    f"shared page {p} refcount {self._shared_ref[p]} != "
                    f"{want} references held"
                )
        lru_set = set(self._shared_lru)
        zero_set = {p for p, c in self._shared_ref.items() if c == 0}
        if lru_set != zero_set:
            raise PoolAccountingError(
                f"LRU set {sorted(lru_set)} != refcount-0 shared pages "
                f"{sorted(zero_set)}"
            )
        index_pages = sorted(self._prefix_index.values())
        if index_pages != sorted(set(index_pages)):
            raise PoolAccountingError(
                "the prefix index maps two keys to one page"
            )
        if set(index_pages) != shared_set or set(
            self._prefix_meta
        ) != shared_set:
            raise PoolAccountingError(
                "prefix index/meta out of step with the shared set"
            )
        overlaps = (free_set & owned_set) | (free_set & shared_set) | (
            owned_set & shared_set
        )
        if overlaps:
            raise PoolAccountingError(
                f"pages in two partition states: {sorted(overlaps)}"
            )
        want = set(range(1, self.num_pages))
        have = free_set | owned_set | shared_set
        if have != want:
            raise PoolAccountingError(
                f"leaked pages: {sorted(want - have)}; "
                f"phantom pages: {sorted(have - want)}"
            )

    # -- host-swap tier (blockstore-backed, ISSUE 15 + 19) -------------------

    def page_shapes(self) -> Dict[str, List[int]]:
        """Each column's per-page shape (everything after the page
        axis). Every snapshot and swap segment carries it, and a
        snapshot whose shapes differ — one written by a build with
        another pool layout — is refused, never reinterpreted."""
        if len(self._pages) > 1:
            raise PoolAccountingError(
                "snapshots and swap segments hold one page kind; this "
                f"pool has {[k.name for k in self.kinds]}"
            )
        return {k: [int(d) for d in v.shape[1:]]
                for k, v in self.columns.items()}

    def _same_page_shapes(self, snapshot: Dict[str, object]) -> bool:
        got = snapshot.get("page_shapes")
        return got is not None and {
            k: list(v) for k, v in dict(got).items()
        } == self.page_shapes()

    def _check_page_shapes(self, snapshot: Dict[str, object],
                           what: str) -> None:
        if not self._same_page_shapes(snapshot):
            raise PoolAccountingError(
                f"{what}: snapshot page shapes "
                f"{snapshot.get('page_shapes')} != this pool's "
                f"{self.page_shapes()} — it was written by another pool "
                "layout and cannot be reinterpreted; recompute the "
                "sequences instead"
            )

    def spill(self, store, swaps: Optional[Dict[str, Dict]] = None,
              swap_store=None) -> Dict[str, object]:
        """Snapshot the whole pool into a
        :class:`~tensorframes_tpu.blockstore.BlockStore`: the device
        columns land as ONE spilled block (explicitly pushed to disk —
        a pool snapshot is cold by definition, it must not consume the
        store's resident budget) plus the host bookkeeping (free list,
        ownership) in the returned snapshot dict. This is the KV pool's
        whole-pool host-swap tier: a served model's KV state survives an
        engine restart through the same CRC-checked segments frame
        blocks spill to, and :meth:`restore` brings it back
        bit-identically. Per-sequence swap is :meth:`swap_out_seq`.

        ``swaps`` (PR 18 follow-up) folds per-sequence host-swap
        segments into the snapshot so they no longer die with the
        engine: a mapping of cross-restart identity (the request's
        trace id) → :meth:`swap_out_seq` snapshot. Each segment is
        CRC-check read from ``swap_store`` and re-published into
        ``store``; the manifest rides the snapshot's ``"swapped"`` key
        and :meth:`adopt_swapped` re-homes it into a fresh engine's
        swap store. A segment that comes back corrupt here is skipped
        (quarantined + counted by the store) — the sequence degrades
        to recompute-replay on redrive, never a wrong answer."""
        swapped: Dict[str, Dict] = {}
        if swaps and swap_store is not None:
            for tid, snap in dict(swaps).items():
                try:
                    seg = swap_store.get(snap["ref"])
                except Exception:
                    continue
                entry = {k: v for k, v in snap.items() if k != "ref"}
                entry["ref"] = store.put_spilled(seg)
                swapped[str(tid)] = entry
        block = {k: np.asarray(v) for k, v in self.columns.items()}
        ref = store.put(block)
        store.spill(ref)
        return {
            "swapped": swapped,
            "ref": ref,
            "free": list(self._free),
            "owned": {int(s): list(p) for s, p in self._owned.items()},
            # prefix-cache state rides the snapshot too — a restored
            # pool must keep every published page addressable
            "refs": {int(s): list(p) for s, p in self._refs.items()},
            "shared_ref": dict(self._shared_ref),
            "shared_lru": list(self._shared_lru),
            "prefix_index": dict(self._prefix_index),
            "prefix_meta": dict(self._prefix_meta),
            "prefix_children": {
                k: list(v) for k, v in self._prefix_children.items()
            },
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "max_pages_per_seq": self.max_pages_per_seq,
            "page_shapes": self.page_shapes(),
        }

    def restore(self, store, snapshot: Dict[str, object],
                swap_store=None) -> Dict[str, Dict]:
        """Rehydrate pool state from a :meth:`spill` snapshot:
        CRC-checked reload of the column block (corruption raises
        ``BlockCorruptionError`` — counted + quarantined by the store,
        never silently served), ``device_put`` back to the default
        device, and the page accounting restored exactly. Geometry
        mismatches raise before anything is touched. When the snapshot
        carries folded per-sequence swap segments and ``swap_store``
        is given, they are re-homed via :meth:`adopt_swapped` and the
        manifest is returned (``{}`` otherwise)."""
        import jax

        for field in ("num_pages", "page_size", "max_pages_per_seq"):
            if int(snapshot[field]) != int(getattr(self, field)):
                raise PoolAccountingError(
                    f"restore into a pool with different {field}: "
                    f"snapshot {snapshot[field]}, pool {getattr(self, field)}"
                )
        self._check_page_shapes(snapshot, "restore")
        block = store.get(snapshot["ref"])
        if set(block) != set(self.columns):
            raise PoolAccountingError(
                f"snapshot columns {sorted(block)} != pool columns "
                f"{sorted(self.columns)}"
            )
        new_cols = {
            k: jax.device_put(np.asarray(v)) for k, v in block.items()
        }
        old_free = len(self._free)
        old_shared = len(self._shared_ref)
        self.columns = new_cols
        self._kv.free = collections.deque(int(p) for p in snapshot["free"])
        self._kv.owned = {
            int(s): [int(p) for p in pages]
            for s, pages in dict(snapshot["owned"]).items()
        }
        self._refs = {
            int(s): [int(p) for p in pages]
            for s, pages in dict(snapshot.get("refs", {})).items()
        }
        self._shared_ref = {
            int(p): int(c)
            for p, c in dict(snapshot.get("shared_ref", {})).items()
        }
        self._shared_lru = collections.OrderedDict(
            (int(p), None) for p in snapshot.get("shared_lru", ())
        )
        self._prefix_index = dict(snapshot.get("prefix_index", {}))
        self._prefix_meta = {
            int(p): tuple(v)
            for p, v in dict(snapshot.get("prefix_meta", {})).items()
        }
        self._prefix_children = {
            k: list(v)
            for k, v in dict(snapshot.get("prefix_children", {})).items()
        }
        self.check()
        if not self._closed:
            from . import metrics as m

            self._kv.gauge.inc(len(self._free) - old_free)
            m.PREFIX_SHARED_PAGES.inc(len(self._shared_ref) - old_shared)
        return self.adopt_swapped(store, snapshot, swap_store)

    def adopt_swapped(self, store, snapshot: Dict[str, object],
                      swap_store) -> Dict[str, Dict]:
        """Re-home a :meth:`spill` snapshot's folded per-sequence swap
        segments into a live swap store WITHOUT touching pool page
        state: swapped sequences hold no pages (``swap_out_seq``
        released them), so they are the one part of an engine's KV
        state that is self-contained enough to move between engines.
        Returns ``{trace_id: swap-in snapshot}`` — the restored
        engine's parking manifest, consumed when each request is
        redriven. Corrupt segments are skipped (quarantined + counted
        by the store; the redrive degrades to recompute-replay)."""
        manifest: Dict[str, Dict] = {}
        if swap_store is None:
            return manifest
        for tid, entry in dict(snapshot.get("swapped", {})).items():
            try:
                seg = store.get(entry["ref"])
            except Exception:
                continue
            new = {k: v for k, v in entry.items() if k != "ref"}
            if not self._same_page_shapes(new):
                continue  # another layout's segment: recompute on redrive
            new["ref"] = swap_store.put_spilled(seg)
            manifest[str(tid)] = new
        return manifest

    def swap_out_seq(self, store, seq: int,
                     block: Dict[str, np.ndarray]) -> Dict[str, object]:
        """Per-sequence host-swap out (ISSUE 19): publish ``block`` —
        the sequence's page payloads in table order, sliced by the
        engine's warmed extract executable — straight to a CRC-checked
        disk segment (``put_spilled``: a swap segment is cold by
        definition), then release every page the sequence holds (shared
        refs drop, exclusive pages free). Returns the snapshot the
        matching :meth:`swap_in_seq` needs; ``freed`` carries the
        exclusive-page count for the caller's eviction accounting."""
        seq = int(seq)
        pages = self.seq_pages(seq)
        if not pages:
            raise PoolAccountingError(
                f"swap_out_seq: sequence {seq} holds no pages"
            )
        ref = store.put_spilled(block)
        freed = self.free_seq(seq)
        return {
            "ref": ref,
            "pages": len(pages),
            "freed": freed,
            "page_shapes": self.page_shapes(),
        }

    def swap_in_seq(self, store, snapshot: Dict[str, object],
                    seq: int) -> Tuple[List[int], Dict[str, object]]:
        """Per-sequence host-swap in: CRC-checked reload of the swap
        segment (corruption quarantines + raises ``BlockCorruptionError``
        AFTER the snapshot's ref is dropped, so the caller's counted
        fallback to recompute-replay starts clean), fresh exclusive
        pages allocated to ``seq``, segment dropped. Returns
        ``(pages, block)`` — the caller scatters the payloads into the
        pages with its warmed restore executable. The restored sequence
        owns everything exclusively (shared-prefix references are not
        re-acquired; re-sharing would need a content re-proof)."""
        from ..blockstore.store import BlockCorruptionError

        self._check_page_shapes(snapshot, "swap_in_seq")
        try:
            block = store.get(snapshot["ref"])
        except BlockCorruptionError:
            store.drop(snapshot["ref"])
            raise
        pages = self.alloc(int(seq), int(snapshot["pages"]))
        store.drop(snapshot["ref"])
        return pages, block

    # -- frame view ---------------------------------------------------------

    def as_frame(self):
        """The pool as a TensorFrame (one row per page, one column per
        pool array) — a materialized snapshot view for the data plane /
        debugging, not a live alias. Call it on the engine thread or on
        a stopped engine: a running engine's next step deletes the
        arrays this reads (the donation contract)."""
        from ..frame import frame_from_arrays

        return frame_from_arrays(
            {k: np.asarray(v) for k, v in self.columns.items()},
            num_blocks=1,
        )

    def __repr__(self) -> str:  # pragma: no cover - convenience
        return (
            f"PagedKVPool(pages={self.num_pages}, "
            f"page_size={self.page_size}, free={self.num_free}, "
            f"shared={self.num_shared}, seqs={len(self._owned)})"
        )
