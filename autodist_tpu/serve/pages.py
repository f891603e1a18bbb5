"""Paged KV-cache bookkeeping: the ONE page-table/pool allocator home.

The serving engine's decode state is a single fixed-size pool of KV pages
(device arrays ``[n_pages, page_len, heads * head_dim]`` a layer, owned by
:class:`~autodist_tpu.serve.engine.InferenceEngine`); WHICH pages belong
to WHICH request is pure host arithmetic, and it all lives here — the
same single-home pattern as ``kernel/bucketing.py`` (gradient collectives)
and ``utils/retry.py`` (backoff): ``tools/check_patterns.py`` rule 8 bans
page-pool/page-table construction anywhere else, so the admission math,
the analyzer's HBM accounting, the obs gauges and the chaos injector all
share one source of truth for "how many tokens fit".

Page 0 is a reserved **scratch page** that is never allocated: page
tables are padded to a static length with it, so a request's pad entries
(and idle decode rows) scatter/gather against scratch instead of a live
request's pages — static shapes everywhere with zero masking in the
kernel's index math.

Chaos seam: :data:`~autodist_tpu.chaos.hooks.SEAM_SERVE_PAGES` fires on
every allocation; a planted ``"exhaust"`` directive makes the pool report
exhaustion (the ``page_exhaustion`` fault class — a burst past pool
capacity must shed typed, never hang or OOM; docs/chaos.md).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from autodist_tpu.chaos import hooks as chaos_hooks

__all__ = [
    "DEFAULT_PAGE_LEN",
    "SCRATCH_PAGE",
    "CacheFeatureRefused",
    "CacheLayout",
    "PagePool",
    "PageTable",
    "build_pool",
    "pages_for_tokens",
]

DEFAULT_PAGE_LEN = 16
#: Reserved page index — never allocated, pads every page table.
SCRATCH_PAGE = 0


def pages_for_tokens(n_tokens: int, page_len: int) -> int:
    """Pages needed to hold ``n_tokens`` timeline tokens (ceil division)."""
    return max(1, -(-int(n_tokens) // int(page_len)))


class CacheFeatureRefused(ValueError):
    """A feature of the serving stack was asked for over a cache that
    cannot carry it (prefix sharing, int8 pages or speculative verification
    over a window ring or beside per-slot recurrent state; int8 pages or
    speculative verification over latent pages). Raised when the engine is
    built, never under load."""


@dataclass(frozen=True)
class CacheLayout:
    """What a model states about how its timeline lies on pages; the
    engine derives table width, reservation, accounting and the prefill
    chunk from it and knows no model by name.

    ``window`` None is the plain paged timeline: position ``p`` lives on
    table entry ``p // page_len``, for as long as the request does.

    With a ``window`` the table is two segments, ``[ring | summaries]``.
    The ring is ``window // page_len`` pages that the open window's exact
    keys are written round (position ``p`` on entry ``(p % window) //
    page_len``, overwriting what lay a window back); behind it every
    ``page_len`` positions leave one summary row, ``page_len`` rows a page
    (chunk ``j`` on entry ``ring_pages + j // page_len``, row ``j %
    page_len``). The programs work out from ``positions`` alone which
    entries a query sees, so the host does nothing when a window closes.

    ``prefill_chunk`` is the chunk the model asks for; any chunk has to be
    a multiple of ``page_len`` and, with a window, divide it (no chunk
    straddles a window). ``page_axis`` is where the cache's leaves carry
    the page dim: 0 for a leaf a layer (every model family here), 1 for a
    pool stacked over layers.

    ``latent`` says that a page has no head axis: one row a position for
    all heads, key and value the same bytes (one leaf a layer, ``[n_pages,
    page_len, width]``), read by a kernel of the model's own. The timeline
    is plain, so tables, reservation and prefix sharing are what they are
    for any plain timeline, and a page is priced from the leaf's shape like
    any other; the engine counts no page groups of ``paged_attention``'s
    blocking for it, and int8 pages and speculative verification over it
    are refused.
    """

    page_len: int = DEFAULT_PAGE_LEN
    window: Optional[int] = None
    prefill_chunk: Optional[int] = None
    page_axis: int = 0
    latent: bool = False

    def __post_init__(self):
        if self.window is not None and self.window % self.page_len:
            raise ValueError(f"window {self.window} is not a whole number "
                             f"of {self.page_len}-position pages")
        if self.prefill_chunk is not None:
            self.check_chunk(self.prefill_chunk)

    @property
    def ring_pages(self) -> int:
        """Table entries of the ring segment (0 on a plain timeline)."""
        return self.window // self.page_len if self.window else 0

    @property
    def summary_span(self) -> int:
        """Positions one summary page stands for."""
        return self.page_len * self.page_len

    def check_chunk(self, chunk: int) -> None:
        if chunk % self.page_len or (self.window and self.window % chunk):
            raise ValueError(
                f"prefill chunk {chunk} has to be a multiple of the page "
                f"({self.page_len})" + (f" that divides the window "
                                        f"({self.window})" if self.window else ""))

    def split(self, n_tokens: int):
        """``(exact pages, summary pages)`` a timeline of ``n_tokens``
        needs."""
        exact = pages_for_tokens(n_tokens, self.page_len)
        if not self.window:
            return exact, 0
        return (min(self.ring_pages, exact),
                pages_for_tokens(n_tokens, self.summary_span))

    def pages_for(self, n_tokens: int) -> int:
        return sum(self.split(n_tokens))

    def table_width(self, max_len: int) -> int:
        """Static table entries a row of ``max_len`` positions needs."""
        if not self.window:
            return max_len // self.page_len
        return self.ring_pages + -(-max_len // self.summary_span)

    def resident_rows(self, n_tokens: int) -> int:
        """Rows of reserved pages that ``n_tokens`` written positions
        fill: the positions themselves, or what the ring holds of them
        plus their finished summaries."""
        if not self.window:
            return n_tokens
        return min(n_tokens, self.window) + n_tokens // self.page_len

    def rolls(self, start: int, end: int):
        """``(window boundaries, chunk ends)`` that writing positions
        ``[start, end)`` passes: a boundary is a multiple of the window
        reached (the window behind it closes), a chunk end a multiple of
        the page (one summary made)."""
        if not self.window:
            return 0, 0
        return (end // self.window - start // self.window,
                end // self.page_len - start // self.page_len)


class PageTable:
    """One request's page list: ``capacity`` timeline tokens of KV rows.

    Token position ``p`` lives at device page ``pages[p // page_len]``,
    offset ``p % page_len``. :meth:`padded` renders the static-shape int32
    row the compiled programs consume (pad entries point at scratch).

    Under a :class:`CacheLayout` with a window the first ``n_exact`` pages
    are the ring's and the rest hold summaries; :meth:`padded` then puts
    the two runs at the start of their segments.
    """

    __slots__ = ("pages", "page_len", "n_exact", "ring_pages")

    def __init__(self, pages: List[int], page_len: int,
                 n_exact: Optional[int] = None, ring_pages: int = 0):
        self.pages = list(pages)
        self.page_len = int(page_len)
        self.n_exact = len(self.pages) if n_exact is None else int(n_exact)
        self.ring_pages = int(ring_pages)

    @property
    def capacity(self) -> int:
        """Timeline tokens these pages can hold."""
        return len(self.pages) * self.page_len

    def padded(self, max_pages: int) -> np.ndarray:
        """Static ``[max_pages]`` int32 row, padded with the scratch page."""
        row = np.full(max_pages, SCRATCH_PAGE, np.int32)
        if not self.ring_pages:
            row[: len(self.pages)] = self.pages
            return row
        row[: self.n_exact] = self.pages[: self.n_exact]
        rest = self.pages[self.n_exact:]
        row[self.ring_pages: self.ring_pages + len(rest)] = rest
        return row

    def rewind(self, n_tokens: int) -> List[int]:
        """Truncate to the pages an ``n_tokens`` timeline needs, returning
        the freed tail page ids (caller hands them to
        :meth:`PagePool.reclaim` — or use :meth:`PagePool.rewind`, which
        does both under the pool lock). The speculative-decode rollback
        path: a rejected draft rewinds the slot's timeline, and the pages
        reserved past the accepted length go straight back to the pool —
        a rejection never leaks pages (docs/serving.md § speculative
        decode). ``n_tokens <= 0`` frees everything."""
        keep = 0 if n_tokens <= 0 else pages_for_tokens(n_tokens, self.page_len)
        keep = min(keep, len(self.pages))
        freed, self.pages = self.pages[keep:], self.pages[:keep]
        return freed

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"PageTable(pages={self.pages}, page_len={self.page_len})"


class PagePool:
    """Fixed pool of KV pages with LIFO recycling.

    Thread-safe (``alloc``/``release`` may race between a scheduler thread
    and a draining controller); allocation is all-or-nothing — a request
    either gets every page its ``prompt + max_new_tokens`` timeline needs
    or ``None`` (the batcher keeps it queued until retirement recycles
    pages). Page 0 (scratch) is never handed out.
    """

    def __init__(self, n_pages: int, page_len: int,
                 quantized: bool = False,
                 bytes_per_page: float = 0.0,
                 fp_equiv_bytes_per_page: float = 0.0):
        if n_pages < 2:
            raise ValueError(f"pool needs >=2 pages (1 scratch + >=1 "
                             f"allocatable), got {n_pages}")
        self.n_pages = int(n_pages)
        self.page_len = int(page_len)
        # Quantized pool mode (int8 pages + f32 scale planes, PR 20): the
        # device arrays hold the scales; the pool carries the byte split so
        # the obs gauges and the analyzer can account physical vs
        # fp-equivalent capacity from one place. bytes_per_page is the
        # PHYSICAL page (int8 + scales when quantized); fp_equiv is what
        # the same page would cost at the model's fp cache dtype.
        self.quantized = bool(quantized)
        self.bytes_per_page = float(bytes_per_page)
        self.fp_equiv_bytes_per_page = float(fp_equiv_bytes_per_page)
        self._lock = threading.Lock()
        # LIFO free list: recycled pages are reused first (warm HBM rows).
        self._free = list(range(self.n_pages - 1, SCRATCH_PAGE, -1))
        self._allocated: set = set()

    # ------------------------------------------------------------- accounting
    @property
    def physical_bytes(self) -> float:
        """Pool HBM footprint as allocated (0 when bytes not stamped)."""
        return self.bytes_per_page * self.n_pages

    @property
    def fp_equiv_bytes(self) -> float:
        """What the pool's KV capacity would cost in fp pages — the
        quantization win's numerator (== physical when not quantized)."""
        return self.fp_equiv_bytes_per_page * self.n_pages

    @property
    def quant_capacity_x(self) -> float:
        """Effective-capacity multiplier from quantization: fp-equivalent
        bytes per physical byte (1.0 when fp or bytes unstamped)."""
        if self.bytes_per_page <= 0.0 or not self.quantized:
            return 1.0
        return self.fp_equiv_bytes_per_page / self.bytes_per_page

    @property
    def usable_pages(self) -> int:
        """Allocatable pages (total minus the scratch page)."""
        return self.n_pages - 1

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        with self._lock:
            return len(self._allocated)

    @property
    def utilization(self) -> float:
        """Allocated fraction of the usable pool, 0..1."""
        return self.used_pages / max(self.usable_pages, 1)

    @property
    def allocated_tokens(self) -> int:
        """Timeline capacity currently reserved (pages * page_len) — the
        admission budget's currency."""
        return self.used_pages * self.page_len

    def fragmentation(self, written_tokens: int) -> float:
        """Internal fragmentation: the fraction of reserved timeline slots
        not (yet) holding a real token — tail waste inside part-filled
        pages plus capacity reserved for tokens not yet decoded."""
        alloc = self.allocated_tokens
        if alloc <= 0:
            return 0.0
        return max(0.0, 1.0 - float(written_tokens) / alloc)

    # ------------------------------------------------------------- allocation
    def alloc(self, n_tokens: int,
              layout: Optional[CacheLayout] = None) -> Optional[PageTable]:
        """Reserve pages for an ``n_tokens`` timeline, or None when the
        pool cannot cover it (all-or-nothing; the chaos seam may force
        the None path to exercise the exhaustion contract). ``layout``
        says how many pages such a timeline needs where that is not one
        per ``page_len`` positions."""
        n_exact, n_summary = (layout or CacheLayout(self.page_len)).split(
            n_tokens)
        need = n_exact + n_summary
        if chaos_hooks.fire(chaos_hooks.SEAM_SERVE_PAGES,
                            need=need, tokens=int(n_tokens)) == "exhaust":
            return None
        with self._lock:
            if need > len(self._free):
                return None
            got = [self._free.pop() for _ in range(need)]
            self._allocated.update(got)
        return PageTable(got, self.page_len, n_exact=n_exact,
                         ring_pages=layout.ring_pages if layout else 0)

    def extend(self, table: PageTable, n_tokens: int) -> bool:
        """Grow ``table`` so it covers an ``n_tokens`` timeline.

        All-or-nothing like :meth:`alloc` (and rides the same chaos seam,
        so ``page_exhaustion`` windows starve extensions too). Returns
        True when the table already covers ``n_tokens`` or the extension
        landed; False when the pool cannot supply the extra pages — the
        caller degrades (speculative drafting shortens or stops) rather
        than blocks: extension is a *best-effort* growth path, never part
        of the admission liveness contract."""
        need = pages_for_tokens(n_tokens, self.page_len) - len(table.pages)
        if need <= 0:
            return True
        if chaos_hooks.fire(chaos_hooks.SEAM_SERVE_PAGES,
                            need=need, tokens=int(n_tokens)) == "exhaust":
            return False
        with self._lock:
            if need > len(self._free):
                return False
            got = [self._free.pop() for _ in range(need)]
            self._allocated.update(got)
        table.pages.extend(got)
        return True

    def reclaim(self, pages: List[int]) -> None:
        """Return specific page ids to the free list (the
        :meth:`PageTable.rewind` tail). Validates each was allocated —
        the same double-free refusal :meth:`release` keeps."""
        with self._lock:
            for p in pages:
                if p not in self._allocated:
                    raise ValueError(f"reclaim of unallocated page {p}")
                self._allocated.discard(p)
                self._free.append(p)

    def rewind(self, table: PageTable, n_tokens: int) -> int:
        """Truncate ``table`` to an ``n_tokens`` timeline and reclaim the
        freed tail in one step. Returns how many pages were freed."""
        freed = table.rewind(n_tokens)
        if freed:
            self.reclaim(freed)
        return len(freed)

    def release(self, table: PageTable) -> None:
        """Recycle a table's pages; immediately reallocatable."""
        with self._lock:
            for p in table.pages:
                if p not in self._allocated:
                    raise ValueError(f"double free of page {p}")
                self._allocated.discard(p)
                self._free.append(p)
        table.pages = []


def build_pool(n_pages: int, page_len: int = DEFAULT_PAGE_LEN,
               quantized: bool = False,
               bytes_per_page: float = 0.0,
               fp_equiv_bytes_per_page: float = 0.0) -> PagePool:
    """The one constructor call sites use (check_patterns rule 8 bans
    direct pool/table construction outside this module)."""
    return PagePool(n_pages, page_len, quantized=quantized,
                    bytes_per_page=bytes_per_page,
                    fp_equiv_bytes_per_page=fp_equiv_bytes_per_page)


def pool_size_from_spec(
    resource_spec,
    bytes_per_page: float,
    params_bytes: float = 0.0,
    state_bytes: float = 0.0,
    headroom: float = 0.8,
    serve_frac: float = 0.5,
    shard_degree: int = 1,
    max_useful_pages: Optional[int] = None,
    min_useful_pages: int = 1,
    sharing_factor: float = 1.0,
) -> int:
    """Page count (INCLUDING the scratch page) from per-chip HBM headroom.

    ``serve_frac`` of the usable HBM left after the resident params funds
    the KV pool — the same capacity/headroom vocabulary as the analyzer's
    SLM passes (``analysis/passes.py::hbm_budget``), so what the engine
    allocates and what shardlint accounts are one formula.
    ``bytes_per_page`` is the FULL logical bytes of one page;
    ``shard_degree`` is how many chips the pool's page dim shards over —
    the per-chip budget funds ``degree`` times more logical pages than it
    could hold replicated (``params_bytes`` stays the conservative full
    logical size: exact for replicated-param serving, an under-estimate
    of headroom for model-parallel plans — never an overcommit).
    ``state_bytes`` is what a chip holds of the model's per-slot state (a
    recurrent layer's, beside the pages): placed before the pool, it is
    taken off the headroom with the parameters, so that pool and state
    never overcommit the chip together.
    ``max_useful_pages`` caps at the point more pages cannot help (every
    decode row at the full ``max_len`` timeline); ``min_useful_pages``
    floors at a functioning pool — an overcommit is the analyzer's SLM
    finding to report, not a constructor crash.

    ``sharing_factor`` relaxes that cap for COW prefix sharing
    (``serve/prefix.py``): the every-row-at-max-timeline bound assumes
    1 table = exclusive pages, but a refcounted pool also earns from
    pages holding COLD cached prefixes (each turns a future admission
    into a page-table copy instead of a prefill) and live tables
    double-count shared pages — so "more pages cannot help" moves out
    by the expected logical/physical sharing ratio. 1.0 (default)
    keeps the exclusive-pages arithmetic; the engine passes 2.0 when a
    prefix cache is attached.
    """
    capacity = float(resource_spec.tpu.hbm_bytes) if resource_spec else 0.0
    budget = max(0.0, capacity * headroom - float(params_bytes)
                 - float(state_bytes)) * serve_frac
    budget *= max(int(shard_degree), 1)
    n = int(budget // max(float(bytes_per_page), 1.0))
    if max_useful_pages is not None:
        n = min(n, int(int(max_useful_pages)
                       * max(float(sharing_factor), 1.0)))
    n = max(n, int(min_useful_pages))
    return n + 1  # + the reserved scratch page
