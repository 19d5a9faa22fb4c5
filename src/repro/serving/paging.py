"""Paged KV-cache bookkeeping: page pool allocator + per-request tables.

The device side of the paged cache is a flat pool of fixed-size pages per
attention layer (``(num_pages, page_size, Hkv, Dh)``); which physical
page holds which request's tokens is decided *here*, on the host, by a
free-list allocator.  A request's page table is a list of physical page
ids; position ``t`` of the request lives at
``(table[t // page_size], t % page_size)``.

Two conventions the device code relies on:

* **Page 0 is the trash page.**  The allocator never hands it out.
  Padded page-table lanes (inactive decode lanes, short prompts in a
  padded prefill bucket) point at page 0, so out-of-range *writes* land
  in the trash page and out-of-range *reads* are masked by the per-slot
  length — no cross-request corruption either way.
* Tables handed to the device are padded to a power-of-two page count
  (:func:`PageTable.padded`) so the jitted decode step retraces only on
  bucket changes, not on every length change.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

__all__ = ["TRASH_PAGE", "PageAllocator", "PageTable", "pages_needed",
           "pad_pow2", "kv_page_bytes", "slots_per_gib"]

TRASH_PAGE = 0


def pages_needed(length: int, page_size: int) -> int:
    """Pages required to hold ``length`` tokens (ceil division)."""
    return max(0, (length + page_size - 1) // page_size)


def kv_page_bytes(page_size: int, n_kv_heads: int, head_dim: int,
                  kv_format: str = "fp", dtype_bytes: int = 4) -> int:
    """Device bytes one physical page costs per attention layer (K and V
    together), including the parallel scale / residual pools a
    compressed format carries alongside the code pages.

    * ``"fp"``   — two float pools: ``2 * page * Hkv * Dh * dtype_bytes``.
    * ``"int8"`` — int8 code pages plus one f32 scale per (position,
      head): ``2 * (page*Hkv*Dh + page*Hkv*4)``.
    * ``"sc"``   — int8 coarse codes + int8 residual pages + f32 scales:
      ``2 * (2*page*Hkv*Dh + page*Hkv*4)``.
    """
    elems = page_size * n_kv_heads * head_dim
    scales = page_size * n_kv_heads * 4            # f32 per-position-per-head
    if kv_format == "fp":
        return 2 * elems * dtype_bytes
    if kv_format == "int8":
        return 2 * (elems + scales)
    if kv_format == "sc":
        return 2 * (2 * elems + scales)
    raise ValueError(f"unknown kv_format {kv_format!r}")


def slots_per_gib(max_len: int, page_size: int, n_kv_heads: int,
                  head_dim: int, kv_format: str = "fp",
                  dtype_bytes: int = 4, n_layers: int = 1) -> float:
    """Full-length request slots one GiB of KV pool can hold.

    Pure accounting over :func:`kv_page_bytes` — the capacity headline
    BENCH_serving.json records per format (int8 >= 2x fp at any shape
    with Dh >= 8, since codes are 4x smaller and scales amortize over
    ``head_dim``)."""
    per_slot = (pages_needed(max_len, page_size)
                * kv_page_bytes(page_size, n_kv_heads, head_dim,
                                kv_format, dtype_bytes) * n_layers)
    return (1 << 30) / per_slot


def _pow2_up(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def pad_pow2(n: int, lo: int = 1, hi: int | None = None) -> int:
    """Round ``n`` up to a power-of-two bucket size in ``[lo, hi]``.

    The result is ALWAYS a power of two >= n (the jit-bucket contract:
    non-pow2 buckets would mint a fresh trace per odd size).  ``lo`` is
    rounded up to a power of two; ``hi`` is clamped *down* to one (a
    non-pow2 cap like 6 cannot name a pow2 bucket).  ``hi`` is a soft
    cap: when no power of two <= hi can hold ``n`` (e.g. n=6, hi=6) the
    next power of two above ``n`` is returned anyway, so buffers sized
    by the bucket never under-allocate.
    """
    b = max(_pow2_up(lo), _pow2_up(n))
    if hi is not None:
        hi_pow = 1 << max(hi, 1).bit_length() - 1       # pow2 floor of hi
        b = min(b, max(hi_pow, _pow2_up(n)))
    return b


class PageAllocator:
    """Free-list allocator over ``num_pages`` physical pages.

    Page 0 (``TRASH_PAGE``) is reserved at construction and never
    allocated.  ``alloc`` is all-or-nothing: it either returns ``n``
    distinct pages or ``None`` (so admission can fall back to waiting /
    preemption without partial bookkeeping).  ``capacity`` (pages it can
    hand out, the trash page excluded) and ``peak_in_use`` (the most
    ever held at once) are the memory manager's readings.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        # LIFO free list: recently-freed pages are reused first, which
        # keeps the hot working set of physical pages small
        self._free = list(range(num_pages - 1, 0, -1))
        self._allocated: set[int] = set()
        self.peak_in_use = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    def alloc(self, n: int) -> list[int] | None:
        if n < 0:
            raise ValueError(n)
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._allocated.update(pages)
        self.peak_in_use = max(self.peak_in_use, len(self._allocated))
        return pages

    def free(self, pages: list[int]) -> None:
        for p in pages:
            if p not in self._allocated:
                raise ValueError(f"double free / foreign page {p}")
            self._allocated.discard(p)
            self._free.append(p)


@dataclass
class PageTable:
    """One request's logical->physical page mapping."""
    page_size: int
    pages: list[int] = field(default_factory=list)

    @property
    def capacity(self) -> int:
        return len(self.pages) * self.page_size

    def ensure(self, length: int, allocator: PageAllocator) -> bool:
        """Grow the table to hold ``length`` tokens.  Returns False (table
        unchanged) when the pool can't supply the missing pages."""
        need = pages_needed(length, self.page_size) - len(self.pages)
        if need <= 0:
            return True
        got = allocator.alloc(need)
        if got is None:
            return False
        self.pages.extend(got)
        return True

    def release(self, allocator: PageAllocator) -> None:
        allocator.free(self.pages)
        self.pages = []

    def padded(self, width: int) -> np.ndarray:
        """Physical ids padded with the trash page to ``width`` entries."""
        if len(self.pages) > width:
            raise ValueError(f"table has {len(self.pages)} pages > "
                             f"bucket width {width}")
        out = np.full((width,), TRASH_PAGE, np.int32)
        out[:len(self.pages)] = self.pages
        return out
