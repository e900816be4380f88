"""Page allocation + prefill bucketing for the paged serving engine.

The paged KV cache (``models.attention.PagedKVCache``) replaces the
worst-case per-slot rectangle with a pool of fixed-size pages shared by
every slot; what makes that safe at the serving layer is a strict
free-list discipline over ONE page-id space (the same id indexes every
attention layer's pool):

* page id 0 is the reserved *scratch* page -- never handed out; unused
  page-table entries point at it and retired slots dump dead decode
  tokens into it;
* a page is owned by at most one slot at a time (no double allocation);
* every allocated page is eventually freed exactly once -- the free list
  is conserved across admit/retire storms.

Each layer's pools are head-major, ``(n_kv, n_pages, page_size, hd)``.
On a TPU with the cache on one device, decode attention reads each live
slot's pages straight from the pool through the Pallas paged-attention
kernel (bf16, only up to the slot's length; idle slots are skipped).
Elsewhere -- the CPU, a cache sharded over several devices -- it gathers
the pool into the rectangle a slot cache would hold; only that path is
bitwise identical to the rectangular engine (``models.attention``).

:class:`PageAllocator` is deliberately a plain-Python free list (ids are
engine-side bookkeeping; only the page *tables* live on device), which
keeps the invariants directly property-testable (tests/test_properties.py).

Bucketed prefill rides along: prompts are right-padded to a small
geometric grid of lengths (:func:`default_buckets`) so the engine
compiles at most one prefill trace per bucket instead of one per
distinct prompt length. Right-padding is semantically inert because the
chunked-attention kv reduction is shape-stable (see
``models.attention.chunked_attention``).
"""

from __future__ import annotations


class PageAllocator:
    """Free-list allocator over page ids ``1 .. n_pages-1`` (0 = scratch).

    Pages are handed out lowest-id-first from the free list; ``free``
    raises on a double free, on the scratch page, and on out-of-range
    ids. ``peak_in_use`` records the high-water mark -- the number that,
    times the per-page bytes, is the run's true resident KV footprint.

    Invariant (property-pinned): ``n_free + n_in_use == n_pages - 1``.
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(
                f"need at least 2 pages (scratch + 1 usable), got {n_pages}"
            )
        self.n_pages = int(n_pages)
        self._free = list(range(self.n_pages - 1, 0, -1))  # pop() -> 1, 2, ..
        self._in_use: set[int] = set()
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_in_use(self) -> int:
        return len(self._in_use)

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` pages off the free list. Raises if fewer remain."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)} "
                f"free of {self.n_pages - 1}"
            )
        out = [self._free.pop() for _ in range(n)]
        self._in_use.update(out)
        self.peak_in_use = max(self.peak_in_use, len(self._in_use))
        return out

    def free(self, pages) -> None:
        """Return pages to the free list. Each must be currently in use."""
        for p in pages:
            p = int(p)
            if p not in self._in_use:
                raise ValueError(
                    f"page {p} is not allocated "
                    "(double free, scratch page, or out of range)"
                )
            self._in_use.remove(p)
            self._free.append(p)


def default_buckets(s_max: int, base: int = 32) -> tuple[int, ...]:
    """Geometric prefill-length grid: ``base * 2^k`` capped at ``s_max``.

    ``s_max`` itself is always the last bucket, so every admissible prompt
    (length <= s_max) has a bucket and the jit prefill trace count is
    bounded by ``len(buckets)`` -- O(log(s_max/base)) -- instead of by the
    number of distinct prompt lengths in the traffic.
    """
    if s_max < 1:
        raise ValueError(f"s_max must be >= 1, got {s_max}")
    if base < 1:
        raise ValueError(f"bucket base must be >= 1, got {base}")
    out = []
    b = base
    while b < s_max:
        out.append(b)
        b *= 2
    out.append(s_max)
    return tuple(out)


def bucket_for(length: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket >= ``length`` (prompts are right-padded up to it)."""
    for b in sorted(buckets):
        if length <= b:
            return b
    raise ValueError(
        f"prompt length {length} exceeds the largest prefill bucket "
        f"{max(buckets)}"
    )
