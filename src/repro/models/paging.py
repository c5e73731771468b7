"""Block-paged KV-cache storage: fixed-size pages + per-slot page tables.

The dense decode caches (``lm.init_decode_caches``) pay ``B * s_max``
tokens of memory per layer no matter how long each slot's sequence
actually is.  Paged storage replaces the per-slot ``s_max`` axis with a
shared *page pool*: every attention-cache tensor stores
``num_pages * page_size`` token positions, and each serving slot maps its
logical positions onto physical pages through a small int32 page table.
Short sequences hold few pages, long ones hold many, and the pool is
sized to the expected *total* live tokens across slots — not to
``slots x s_max``.

Three parties cooperate:

  - :class:`PagedConfig` fixes the geometry (page size, pool size, table
    width) shared by host and device;
  - :class:`PageAllocator` is the HOST-side bookkeeper: a free list plus
    per-slot page lists; the continuous-batching scheduler
    (``runtime.server``) allocates on admission/growth, frees on slot
    recycle, and ships the resulting ``[B, pages_per_slot]`` tables to
    the device as plain arrays;
  - :func:`gather_pages` / :func:`append_tokens` are the DEVICE-side
    accessors (pure jax, run inside shard_map): attention reads only the
    pages a slot has mapped, and cache writes scatter tokens through the
    table.

Physical page 0 is reserved as the *garbage page*: unmapped table entries
point at it, so inactive slots and padded chunk tails scatter there
harmlessly (every read is masked by the slot's length before softmax).

Only O(s) caches are paged — attention K/V and MLA's compressed-KV
latents.  Mamba/xLSTM recurrent state is O(1) per slot and lives in
per-slot state rows alongside the pools (see ``lm.init_paged_caches``).

**Copy-on-write prefix sharing** (``prefix_cache=True``): the allocator
keeps per-page refcounts plus a radix index over *page contents* — each
node is keyed by (parent page, the page_size token ids written into it),
so a chain of index hits proves the full token prefix matches and the
cached KV values are exactly what prefill would recompute.  Admission
(``runtime.server``) adopts the matched pages read-only into the new
slot's table and skips their prefill chunks entirely; pages are freed
only when their refcount drops to zero, and the index itself pins
completed prompts' full pages (evicted leaf-first under pool pressure).
Shared pages are never written: adopters only append at positions past
the matched (page-aligned) prefix, i.e. strictly later pages.  The
quantized (int8/fp8) value+scale pools ride the same page tables, so
they share identically for free.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

#: physical page reserved for unmapped table entries / padded writes
GARBAGE_PAGE = 0

#: storage dtypes a page pool supports; "bf16" means "the model dtype"
#: (no quantization), the narrow ones store 1 byte/elem plus an fp16
#: per-position scale
PAGE_DTYPES = ("bf16", "int8", "fp8")

_INT8_QMAX = 127.0
_FP8_QMAX = 448.0  # float8_e4m3fn finite max
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Page-pool geometry shared by the scheduler and the compiled steps.

    ``num_pages`` INCLUDES the reserved garbage page 0, so the pool holds
    ``(num_pages - 1) * page_size`` usable token positions.
    ``pages_per_slot`` is the page-table width — the per-slot sequence
    ceiling is ``pages_per_slot * page_size`` (the paged analogue of
    ``s_max``, but it bounds only the *table*, not the memory: unmapped
    entries cost nothing).

    ``page_dtype`` picks the pool storage format: "bf16" stores the model
    dtype verbatim; "int8"/"fp8" store 1 byte per element plus an fp16
    per-position scale pool (symmetric, one scale per position and KV
    bank — see :func:`quantize_tokens`).  Quant/dequant happens at the pool
    boundary (:func:`append_tokens_q` / :func:`gather_pages_q`); attention
    itself always runs on dequantized full-width values.
    """

    page_size: int = 8
    num_pages: int = 64
    pages_per_slot: int = 8
    page_dtype: str = "bf16"

    def __post_init__(self):
        if self.page_size < 1 or self.num_pages < 2 or self.pages_per_slot < 1:
            raise ValueError(f"degenerate page geometry: {self}")
        if self.page_dtype not in PAGE_DTYPES:
            raise ValueError(f"page_dtype must be one of {PAGE_DTYPES}, "
                             f"got {self.page_dtype!r}")

    @property
    def quantized(self) -> bool:
        return self.page_dtype != "bf16"

    @property
    def max_seq(self) -> int:
        """Per-slot sequence ceiling (page-table width x page size)."""
        return self.pages_per_slot * self.page_size

    @property
    def capacity_tokens(self) -> int:
        """Usable pool capacity (excludes the garbage page)."""
        return (self.num_pages - 1) * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` positions."""
        return -(-n_tokens // self.page_size)


class PageAllocator:
    """Host-side page bookkeeping for one pool (numpy only, no jax).

    Not thread-safe; the scheduler owns it.  ``False`` returns mean the
    pool is exhausted — the caller defers (backpressure) rather than
    raising, because a continuous-batching scheduler can simply keep
    decoding its live slots until pages free up.

    With ``prefix_cache=True`` the allocator additionally maintains
    per-page refcounts and a radix index over page contents (copy-on-
    write prefix sharing — see the module docstring): ``match_prefix``
    walks the index, ``adopt`` maps shared pages into a slot, and
    ``register_prefix`` pins a completed prompt's full pages for future
    admissions.  ``release`` decrements refcounts and frees only at
    zero.  Without the flag every page has exactly one owner and the
    behavior is the seed allocator's, bit for bit.
    """

    def __init__(self, cfg: PagedConfig, slots: int,
                 prefix_cache: bool = False):
        self.cfg = cfg
        self.slots = slots
        self.prefix_cache = prefix_cache
        self._free = list(range(cfg.num_pages - 1, GARBAGE_PAGE, -1))
        self._owned: list[list[int]] = [[] for _ in range(slots)]
        #: page -> mapping count (slot mappings + 1 if pinned by the index)
        self._refs: dict[int, int] = {}
        #: radix node: (parent page id or -1, page-content tokens) -> page
        self._radix: dict[tuple[int, tuple[int, ...]], int] = {}
        self._radix_rev: dict[int, tuple[int, tuple[int, ...]]] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def slot_pages(self, slot: int) -> tuple[int, ...]:
        return tuple(self._owned[slot])

    @property
    def live_pages(self) -> int:
        """Distinct pages mapped by at least one slot (shared counted once)."""
        return len({p for owned in self._owned for p in owned})

    @property
    def pages_shared(self) -> int:
        """Slot-mapped page references beyond each page's first mapping —
        the device pages copy-on-write sharing is currently saving."""
        counts: dict[int, int] = {}
        for owned in self._owned:
            for p in owned:
                counts[p] = counts.get(p, 0) + 1
        return sum(c - 1 for c in counts.values() if c > 1)

    @property
    def pinned_pages(self) -> int:
        """Pages held (only) by the prefix index, reusable or evictable."""
        return len(self._radix_rev)

    @property
    def held_pages(self) -> int:
        """Distinct non-free pages — slot-mapped or index-pinned, each
        counted once regardless of refcount (what honest cache-bytes
        accounting bills)."""
        return len({p for owned in self._owned for p in owned}
                   | set(self._radix_rev))

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot``'s mapping to cover ``n_tokens`` positions.

        Returns False (allocating nothing) when the pool cannot satisfy
        the request — transient backpressure the caller retries.  A
        request exceeding the page-table WIDTH raises instead: no amount
        of waiting can map more than ``pages_per_slot`` pages, so the
        scheduler must reject it at submit time (``Server.submit``).
        Under pool pressure, index-pinned pages no slot maps are evicted
        (leaf-first, so the radix never strands unreachable children).
        """
        need = self.cfg.pages_for(n_tokens)
        if need > self.cfg.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens need {need} pages > "
                f"pages_per_slot={self.cfg.pages_per_slot}")
        grow = need - len(self._owned[slot])
        if grow <= 0:
            return True
        if grow > len(self._free):
            self._evict(grow - len(self._free))
        if grow > len(self._free):
            return False
        for _ in range(grow):
            p = self._free.pop()
            self._refs[p] = 1
            self._owned[slot].append(p)
        return True

    def release(self, slot: int) -> None:
        """Unmap all of ``slot``'s pages (slot recycle): refcounts drop by
        one and only pages nobody else maps (and the prefix index does
        not pin) return to the free list."""
        pages = self._owned[slot]
        for p in reversed(pages):
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
        self._owned[slot] = []

    # -- copy-on-write prefix sharing (radix index over page contents) ----

    def match_prefix(self, tokens) -> tuple[int, ...]:
        """Longest chain of cached full pages covering a prefix of
        ``tokens``.  Each hop matches one page's exact contents under its
        parent, so a k-page hit proves tokens[:k*page_size] equality."""
        if not self.prefix_cache:
            return ()
        ps = self.cfg.page_size
        toks = [int(t) for t in tokens]
        out: list[int] = []
        parent = -1
        for j in range(len(toks) // ps):
            page = self._radix.get((parent, tuple(toks[j * ps:(j + 1) * ps])))
            if page is None:
                break
            out.append(page)
            parent = page
        return tuple(out)

    def adopt(self, slot: int, pages) -> None:
        """Map shared (prefix-cache) pages read-only into an empty slot.

        The pages come first in the slot's table — the caller must adopt
        before any private ``ensure`` growth, and must only write
        positions past the adopted prefix (COW: shared pages are never
        mutated; a diverging suffix lands in later, private pages)."""
        if self._owned[slot]:
            raise ValueError(
                f"slot {slot}: adopt() must precede private page growth "
                f"(owns {len(self._owned[slot])} pages)")
        for p in pages:
            self._refs[p] = self._refs.get(p, 0) + 1
            self._owned[slot].append(p)

    def register_prefix(self, slot: int, tokens) -> int:
        """Index ``slot``'s fully-written prompt pages for future reuse.

        Called when a prompt's prefill completes: every page whose
        page_size positions are all covered by prompt tokens becomes a
        radix node (+1 pin ref).  Pages already indexed under the same
        content chain are walked, not re-registered, so concurrent
        identical prompts converge on one physical copy.  Returns the
        number of newly indexed pages."""
        if not self.prefix_cache:
            return 0
        ps = self.cfg.page_size
        toks = [int(t) for t in tokens]
        owned = self._owned[slot]
        parent = -1
        added = 0
        for j in range(len(toks) // ps):
            if j >= len(owned):
                break
            key = (parent, tuple(toks[j * ps:(j + 1) * ps]))
            hit = self._radix.get(key)
            if hit is not None:
                parent = hit
                continue
            page = owned[j]
            if page in self._radix_rev:
                # already indexed under a different chain — re-keying
                # would corrupt both chains; stop here
                break
            self._radix[key] = page
            self._radix_rev[page] = key
            self._refs[page] = self._refs.get(page, 0) + 1
            parent = page
            added += 1
        return added

    def drop_prefix_index(self) -> int:
        """Unpin the whole prefix index (operator reset); pages nobody
        maps return to the free list.  Returns pages freed."""
        freed = 0
        for page in list(self._radix_rev):
            self._unpin(page)
            if self._refs.get(page) is None:
                freed += 1
        return freed

    def _unpin(self, page: int) -> None:
        key = self._radix_rev.pop(page)
        del self._radix[key]
        self._refs[page] -= 1
        if self._refs[page] == 0:
            del self._refs[page]
            self._free.append(page)

    def evict_pinned(self, n: int) -> int:
        """Pressure-eviction hook: free up to ``n`` index-only pages.

        The degradation ladder (``runtime.server``) calls this *before*
        pool exhaustion forces reactive eviction inside ``ensure`` — the
        same leaf-first, refcount-safe walk, surfaced so a scheduler can
        shed cache weight on a low-water-mark signal instead of on the
        first failed allocation.  Returns the number of pages freed
        (less than ``n`` when only slot-mapped or interior pages remain).
        """
        return self._evict(n)

    def _evict(self, n: int) -> int:
        """Free up to ``n`` pages held only by the prefix index —
        leaf-first (never a node with indexed children, so surviving
        chains stay reachable), newest-registered first.  Returns pages
        freed."""
        freed = 0
        while freed < n and self._radix:
            mapped = {p for owned in self._owned for p in owned}
            parents = {k[0] for k in self._radix}
            victim = None
            for page in reversed(list(self._radix_rev)):
                if page not in parents and page not in mapped:
                    victim = page
                    break
            if victim is None:
                return freed
            self._unpin(victim)
            freed += 1
        return freed

    def table(self) -> np.ndarray:
        """The ``[slots, pages_per_slot]`` int32 device table; unmapped
        entries point at the garbage page."""
        t = np.full((self.slots, self.cfg.pages_per_slot), GARBAGE_PAGE,
                    np.int32)
        for s, pages in enumerate(self._owned):
            t[s, : len(pages)] = pages
        return t


# ---------------------------------------------------------------------------
# Device-side accessors (pure jax; run inside shard_map on local shards).
# ---------------------------------------------------------------------------


def gather_pages(pages, table, layer=None):
    """Materialize each slot's mapped positions from the pool.

    pages [num_pages, page, ...feat] (or [count, num_pages, page, ...feat]
    read at ``layer``); table [B, mp] -> [B, mp * page, ...feat].
    Unmapped entries read the garbage page; callers mask those positions
    by the slot's length (exactly like the dense cache masks positions
    beyond ``len``), so the values never reach a softmax unmasked.

    A stacked pool is read with one gather over the layers' pages laid
    end to end (``[count * num_pages, page, ...]``, a bitcast, not a
    slice), the table offset to ``layer``'s rows: so a layer scan can
    carry the pool and update it in place, where slicing one layer out
    and restacking it would copy it on every call.
    """
    if layer is not None:
        table = table + layer * pages.shape[1]
        pages = pages.reshape((-1,) + pages.shape[2:])
    g = jnp.take(pages, table, axis=0)            # [B, mp, page, ...]
    return g.reshape((table.shape[0], table.shape[1] * pages.shape[1])
                     + pages.shape[2:])


def append_tokens(pages, table, start, values, layer=None):
    """Scatter per-slot token runs into the pool through the page table.

    pages [num_pages, page, ...feat] (or [count, num_pages, page, ...feat]
    written at ``layer``, in place); table [B, mp]; start [B] (each
    slot's first logical position for this run); values [B, s, ...feat].
    Position p of slot b lands in physical page ``table[b, p // page]``
    at offset ``p % page``.  Writes beyond a slot's valid length (padded
    chunk tails, inactive decode slots whose table rows are unmapped)
    land on pages that are either overwritten by the very next tokens of
    the same slot or are the garbage page — never read unmasked.
    """
    B, s = values.shape[:2]
    page = pages.shape[1 if layer is None else 2]
    pos = start[:, None] + jnp.arange(s, dtype=start.dtype)[None, :]  # [B,s]
    logical = pos // page
    # clamp: positions past the table width scatter to the garbage page
    # (cannot happen for well-formed schedules; defensive for padding)
    ok = logical < table.shape[1]
    phys = jnp.where(
        ok, jnp.take_along_axis(table, jnp.minimum(
            logical, table.shape[1] - 1), axis=1), GARBAGE_PAGE)
    off = pos % page
    values = values.astype(pages.dtype)
    if layer is None:
        return pages.at[phys, off].set(values)
    return pages.at[layer, phys, off].set(values)


# ---------------------------------------------------------------------------
# Quantized pools: int8/fp8 storage + fp16 per-position scales.
# ---------------------------------------------------------------------------


def page_store_dtype(page_dtype: str):
    """The jnp storage dtype for a quantized pool (None = model dtype).

    "fp8" falls back to int8 storage on jax builds without
    ``float8_e4m3fn`` — same byte count, slightly different grid.
    """
    if page_dtype == "int8":
        return jnp.int8
    if page_dtype == "fp8":
        return _FP8_DTYPE if _FP8_DTYPE is not None else jnp.int8
    if page_dtype == "bf16":
        return None
    raise ValueError(f"page_dtype must be one of {PAGE_DTYPES}, "
                     f"got {page_dtype!r}")


def pool_page_dtype(pages) -> str:
    """Recover the PAGE_DTYPES tag from a pool tensor's storage dtype.

    The compiled step sees only the cache tree, not the PagedConfig, so
    the quant path keys off the pool dtype itself (fp8-fallback pools
    stored as int8 correctly report "int8" — their grid)."""
    if pages.dtype == jnp.int8:
        return "int8"
    if _FP8_DTYPE is not None and pages.dtype == _FP8_DTYPE:
        return "fp8"
    return "bf16"


def quantize_tokens(values, page_dtype: str):
    """values [..., feat] -> (quantized [..., feat], fp16 scales [...]).

    Symmetric per-position quantization: one scale per token position
    (and per KV bank: :func:`append_tokens_q` puts the bank before the
    trailing dim), shared over the trailing feature dim, so the scale
    pool is a parallel paged tensor with the feature dim dropped and rides
    the same page tables through :func:`append_tokens` /
    :func:`gather_pages`.
    Scales are stored fp16 — at head_dim >= 32 an f32 scale alone would
    eat the margin below a 1.8x pool-byte reduction.
    """
    vf = values.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=-1)
    fp8 = page_dtype == "fp8" and _FP8_DTYPE is not None
    qmax = _FP8_QMAX if fp8 else _INT8_QMAX
    scale = jnp.maximum(amax / qmax, 1e-12)
    q = vf / scale[..., None]
    if fp8:
        q = q.astype(_FP8_DTYPE)
    else:
        q = jnp.clip(jnp.round(q), -_INT8_QMAX, _INT8_QMAX).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def append_tokens_q(pages, scales, table, start, values, page_dtype: str,
                    layer=None):
    """Quant-aware :func:`append_tokens`: returns (new_pages, new_scales).

    ``scales is None`` means the pool is full-width — plain append, scale
    pool untouched.  Otherwise the values are quantized with one scale
    per element of the scale pool's position (a lane-dense attention
    pool ``[.., banks * hd]`` has scales ``[.., banks]``: one per bank),
    and both the value pool and the parallel scale pool are scattered
    through the same table."""
    if scales is None:
        return append_tokens(pages, table, start, values, layer), None
    feat = scales.shape[2 if layer is None else 3:]
    q, s = quantize_tokens(values.reshape(values.shape[:2] + feat + (-1,)),
                           page_dtype)
    return (append_tokens(pages, table, start, q.reshape(values.shape),
                          layer),
            append_tokens(scales, table, start, s, layer))


def gather_pages_q(pages, scales, table, out_dtype=jnp.bfloat16, layer=None):
    """Quant-aware :func:`gather_pages`: dequantize at the pool boundary.

    ``scales is None`` -> plain gather.  Otherwise gathers values and
    scales through the same table and returns ``values * scale`` (each
    scale over its run of the feature dim) in ``out_dtype`` (attention
    always runs full-width)."""
    if scales is None:
        return gather_pages(pages, table, layer)
    v = gather_pages(pages, table, layer).astype(jnp.float32)
    s = gather_pages(scales, table, layer).astype(jnp.float32)
    return (v.reshape(s.shape + (-1,)) * s[..., None]).reshape(
        v.shape).astype(out_dtype)
