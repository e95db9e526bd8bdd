"""Paged KV cache: device page pools viewed through a block-pool manager.

Storage layout (vLLM-style paging adapted to the scan-over-superblocks
cache pytrees):

* Attention / MLA cache leaves become batchless *page pools* of shape
  ``(reps, num_pages, ...)`` with the page-line axis (logical ``kv_seq``)
  somewhere after the page axis — ``(reps, P, page, r)`` for the MLA
  latent, head-major ``(reps, P, KV, page, hd)`` for GQA K/V — one pool
  per stacked cache leaf, all layers addressed through the same per-slot
  block table.
* O(1) recurrent states (mamba ``h``/``conv``, mLSTM ``C/n/m``, sLSTM
  ``c/n/h/m``) stay per-slot rows ``(reps, num_slots, ...)`` — a recurrent
  "page" is just the slot row.

A *slot* is one position in the packed decode batch.  ``block_tables``
(num_slots, blocks_per_slot) maps a slot's logical block index to a
physical page; physical page 0 is reserved as a trash page that idle slots
harmlessly write to, so the jitted decode step has shapes independent of
which slots are live and compiles exactly once.

Page accounting lives in :class:`repro.serve.block_pool.BlockPool` —
ref-counted physical pages with a content-hash prefix index.  This class
is the *view*: it owns the device arrays, maps slots to pages, performs
the device-side copies the pool's copy-on-write decisions require, and
keeps the trash-page / ``margin_tokens`` semantics the speculative engine
relies on (table entries past a slot's allocation stay 0, so budget-edge
verify writes land harmlessly and never alias live pages).

Allocation is *on demand*: :meth:`alloc` backs only the tokens a request
arrives with (its prompt), and :meth:`ensure_writable` grows a slot one
page at a time as its write frontier crosses page boundaries — instead of
reserving the full ``prompt + max_new_tokens`` budget at admission.  With
``prefix_cache=True`` full pages are frozen under chain hashes as their
content finalizes, later admissions alias matching prefix pages
(``N`` requests over one shared system prompt hold ~1 copy of it), and a
write into a shared or frozen page copies it first (copy-on-write), so
divergence — including speculative-rollback scribbles — can never leak
between requests.  When the pool runs dry the scheduler preempts:
:meth:`swap_out` / :meth:`swap_in` round-trip a slot's pages through host
memory (re-deduplicating against the prefix index on the way back in).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import quantize as kvq
from repro.models.common import ModelConfig
from repro.models import transformer as tfm
from repro.parallel.sharding import ParamDef, tree_instantiate

from .block_pool import BlockPool, chain_hash, token_chain_hashes


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


_PAGED_MIXERS = ("attn", "mla")
_RECURRENT_MIXERS = ("mamba", "mlstm", "slstm")


def supports_paging(cfg: ModelConfig) -> bool:
    """True iff every mixer in the model has a paged decode path
    (decoder-only archs; enc-dec / VLM cross-attention is static-engine
    territory)."""
    if cfg.is_encoder_decoder or cfg.n_image_tokens:
        return False
    return all(b.mixer in _PAGED_MIXERS + _RECURRENT_MIXERS
               for b in cfg.block_pattern)


def supports_prefix_cache(cfg: ModelConfig) -> bool:
    """Prefix sharing needs (a) all state to live in pages — a recurrent
    mixer's O(1) state is position-dependent and per-slot, so aliasing its
    "pages" is meaningless — and (b) prefill of a suffix chunk to be
    mathematically identical to whole-prompt prefill, which an MoE FFN's
    tokens-per-call capacity cutoff breaks."""
    return (supports_paging(cfg)
            and all(b.mixer in _PAGED_MIXERS for b in cfg.block_pattern)
            and all(b.ffn != "moe" for b in cfg.block_pattern))


@dataclasses.dataclass
class _SlotMeta:
    """Host bookkeeping for one allocated slot."""
    n_blocks: int                    # leading table entries backed by pages
    budget: int                      # admission token ceiling for this slot
    cached_tokens: int = 0           # prefix-cache tokens skipped at alloc
    frozen_blocks: int = 0           # leading blocks registered in the index
    hash_chain: List[int] = dataclasses.field(default_factory=list)
    # blocks [exempt_lo, exempt_hi) are this slot's OWN eagerly-frozen
    # prompt pages, registered at alloc but written by this slot's prefill:
    # that canonical write is the registration's promise, not divergence,
    # so it is exempt from copy-on-write.  Decode/verify writes can never
    # reach these blocks (positions only grow past the full prompt pages).
    exempt_lo: int = 0
    exempt_hi: int = 0


@dataclasses.dataclass
class SwapSnapshot:
    """A preempted slot's cache, parked in host memory.

    ``data`` mirrors the cache pytree: paged leaves hold the slot's pages
    gathered to ``(reps, n_blocks, page, ...)`` numpy arrays, recurrent
    leaves hold the slot's state row.  ``hash_chain`` keeps the frozen
    prefix's chain hashes so swap-in can re-alias any page still living in
    the prefix index instead of copying it back (swap resume
    re-deduplicates)."""
    n_blocks: int
    budget: int
    frozen_blocks: int
    hash_chain: List[int]
    cached_tokens: int
    data: List[Any]

    @property
    def nbytes(self) -> int:
        return int(sum(x.nbytes for seg in self.data
                       for x in jax.tree.leaves(seg)))


class PagedKVCache:
    """Page pools for every cache leaf of the model, viewed through a
    ref-counted :class:`BlockPool`."""

    def __init__(self, cfg: ModelConfig, num_slots: int, page_size: int,
                 max_len: int, num_pages: Optional[int] = None,
                 key: Optional[jax.Array] = None, margin_tokens: int = 0,
                 prefix_cache: bool = False, eager_freeze: bool = True):
        """``margin_tokens`` widens every block table past the ``max_len``
        admission ceiling WITHOUT backing pages: speculative verification
        writes up to k draft lines beyond a request's committed context,
        and near the end of its budget those positions must still resolve
        to a legal table entry.  Margin entries stay 0 (the trash page),
        so overflow writes land harmlessly and never alias live pages."""
        if not supports_paging(cfg):
            raise NotImplementedError(
                f"{cfg.name}: paged KV cache supports decoder-only archs "
                f"(mixers {_PAGED_MIXERS + _RECURRENT_MIXERS})")
        if prefix_cache and not supports_prefix_cache(cfg):
            raise NotImplementedError(
                f"{cfg.name}: prefix sharing needs attention/MLA mixers "
                "throughout and no MoE FFN (chunked-prefill identity)")
        self.cfg = cfg
        self.num_slots = num_slots
        self.page_size = page_size
        self.prefix_cache = prefix_cache
        # eager (alloc-time) registration of a request's own full prompt
        # pages: lets requests admitted in the SAME step share them, since
        # prefill order follows admission order (the first owner writes a
        # page before any aliasing request reads it).  Only sound when a
        # prompt prefills whole within its admission step — the engine
        # turns this off under chunked prefill.
        self.eager_freeze = eager_freeze
        admit_blocks = max(1, math.ceil(max_len / page_size))
        self.blocks_per_slot = admit_blocks + math.ceil(
            margin_tokens / page_size)
        self.max_len = admit_blocks * page_size
        if num_pages is None:
            # full backing store + the reserved trash page (margin blocks
            # are never backed — they always point at the trash page)
            num_pages = 1 + num_slots * admit_blocks
        self.num_pages = num_pages
        self.pool = BlockPool(num_pages, page_size)

        defs = tfm.paged_cache_defs(cfg, num_slots, num_pages, page_size)
        self.pools = tree_instantiate(defs, key if key is not None
                                      else jax.random.key(0))
        # leaf -> is it a page pool (vs a per-slot state row)?  Pool leaves
        # carry "kv_seq" but no "batch" logical axis after stacking; the
        # position of "kv_seq" is the leaf's page-line axis.
        self._paged = jax.tree.map(
            lambda d: "kv_seq" in d.logical and "batch" not in d.logical,
            defs, is_leaf=_is_def)
        self._line_axis = jax.tree.map(
            lambda d: d.logical.index("kv_seq") if "kv_seq" in d.logical
            else 0, defs, is_leaf=_is_def)

        self.block_tables = np.zeros((num_slots, self.blocks_per_slot),
                                     np.int32)
        self._free_slots: List[int] = list(range(num_slots - 1, -1, -1))
        self._meta: Dict[int, _SlotMeta] = {}

    # -- allocator ---------------------------------------------------------

    def pages_needed(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.page_size))

    @property
    def free_page_count(self) -> int:
        return self.pool.free_page_count

    @property
    def available_page_count(self) -> int:
        """Pages obtainable right now: free + evictable cached."""
        return self.pool.available_page_count

    @property
    def free_slot_count(self) -> int:
        return len(self._free_slots)

    @property
    def page_bytes(self) -> int:
        """HBM bytes of ONE physical page summed over every paged leaf —
        the unit of the capacity axis."""
        total = 0
        for seg_pool, seg_flag in zip(self.pools, self._paged):
            for leaf, paged in zip(jax.tree.leaves(seg_pool),
                                   jax.tree.leaves(seg_flag)):
                if paged:
                    total += leaf.size // self.num_pages * leaf.dtype.itemsize
        return total

    def prefix_match_pages(self, tokens: np.ndarray) -> int:
        """Admission-time peek: how many of ``tokens``'s full pages are in
        the prefix index (no references taken)."""
        if not self.prefix_cache:
            return 0
        m = 0
        for h in token_chain_hashes(np.asarray(tokens), self.page_size):
            if self.pool.peek(h) is None:
                break
            m += 1
        return m

    def pages_needed_for(self, tokens: np.ndarray) -> int:
        """Fresh pages an ``alloc(tokens=...)`` would consume after prefix
        dedup."""
        return self.pages_needed(len(tokens)) - self.prefix_match_pages(
            tokens)

    def can_admit(self, n_tokens: int, reserve_pages: int = 0) -> bool:
        return (n_tokens <= self.max_len
                and bool(self._free_slots)
                and self.pages_needed(n_tokens) + reserve_pages
                <= self.available_page_count)

    def can_admit_tokens(self, tokens: np.ndarray,
                         reserve_pages: int = 0) -> bool:
        """Like :meth:`can_admit` but priced AFTER prefix-cache dedup —
        pages the index already holds cost the admission nothing."""
        return (len(tokens) <= self.max_len
                and bool(self._free_slots)
                and self.pages_needed_for(tokens) + reserve_pages
                <= self.available_page_count)

    def alloc(self, n_tokens: int, slot: Optional[int] = None,
              budget: Optional[int] = None,
              tokens: Optional[np.ndarray] = None) -> Optional[int]:
        """Reserve a slot plus pages backing an ``n_tokens`` context NOW
        (growth past it is on demand via :meth:`ensure_writable`, up to
        ``budget`` tokens — default ``n_tokens``).  Returns the slot id,
        or None if slots/pages are exhausted.  ``slot`` pins a specific
        free slot — a draft-model cache mirroring the target engine must
        pack its batch by the target's slot indices.  ``tokens`` (the
        context ids) enables prefix-cache lookup: matching leading full
        pages are aliased instead of allocated, and
        :meth:`prefix_cached_tokens` reports how many tokens the caller
        may skip prefilling."""
        budget = n_tokens if budget is None else budget
        if max(n_tokens, budget) > self.max_len:
            raise ValueError(f"request needs {max(n_tokens, budget)} tokens "
                             f"> max_len {self.max_len}")
        n_pages = self.pages_needed(n_tokens)
        if not self._free_slots:
            return None

        # prefix-cache: alias every indexed full page of the context; at
        # least one trailing token is always recomputed (the engine needs
        # its logits), so a fully-aligned full match leaves the final page
        # aliased-but-about-to-be-written — the copy-on-write case.
        matched: List[int] = []
        hashes: List[int] = []
        if self.prefix_cache and tokens is not None and n_tokens > 1:
            for h in token_chain_hashes(np.asarray(tokens)[:n_tokens],
                                        self.page_size):
                page = self.pool.lookup(h)
                if page is None:
                    break
                matched.append(page)
                hashes.append(h)
        fresh: List[int] = []
        for _ in range(n_pages - len(matched)):
            page = self.pool.acquire()
            if page is None:
                for p in fresh + matched:
                    self.pool.release(p)
                return None
            fresh.append(page)

        if slot is None:
            slot = self._free_slots.pop()
        else:
            try:
                self._free_slots.remove(slot)
            except ValueError:
                for p in fresh + matched:
                    self.pool.release(p)
                raise ValueError(f"slot {slot} is not free")
        row = np.zeros((self.blocks_per_slot,), np.int32)
        pages = matched + fresh
        row[: n_pages] = pages
        self.block_tables[slot] = row
        cached = min(len(matched) * self.page_size, n_tokens - 1) \
            if matched else 0
        self._meta[slot] = _SlotMeta(
            n_blocks=n_pages, budget=budget, cached_tokens=cached,
            frozen_blocks=len(matched), hash_chain=hashes)
        self._zero_slot_state(slot)
        if (self.prefix_cache and self.eager_freeze and tokens is not None):
            # register this context's remaining full pages NOW — their
            # canonical content lands during this admission's prefill,
            # before any same-step aliasing request reads them
            meta = self._meta[slot]
            meta.exempt_lo = len(matched)
            self.freeze_committed(slot, np.asarray(tokens)[:n_tokens],
                                  n_tokens)
            meta.exempt_hi = meta.frozen_blocks
        return slot

    def prefix_cached_tokens(self, slot: int) -> int:
        """Tokens of this slot's context that admission found in the
        prefix cache — the prefill work the scheduler may skip."""
        return self._meta[slot].cached_tokens

    def slot_budget(self, slot: int) -> int:
        return self._meta[slot].budget

    def slot_pages(self, slot: int) -> int:
        return self._meta[slot].n_blocks

    def ensure_writable(self, slot: int, start: int, end: int) -> bool:
        """Make token positions ``[start, end)`` safely writable by this
        slot before a device step runs: acquire pages on demand as the
        write frontier crosses page boundaries, and copy-on-write any page
        in the span that is shared or frozen.  Positions at or past the
        slot's budget are clipped — they resolve to margin/trash entries
        and may be scribbled on freely (the speculative rollback
        contract).  Returns False when the pool is dry (caller preempts);
        the slot is left consistent either way."""
        meta = self._meta[slot]
        end = min(end, meta.budget)
        if start >= end:
            return True
        row = self.block_tables[slot]
        for b in range(start // self.page_size,
                       (end - 1) // self.page_size + 1):
            if b >= meta.n_blocks:
                assert b == meta.n_blocks, (
                    f"write frontier skipped block {meta.n_blocks} -> {b}")
                page = self.pool.acquire()
                if page is None:
                    return False
                row[b] = page
                meta.n_blocks += 1
            elif (self.pool.cow_needed(int(row[b]))
                  and not meta.exempt_lo <= b < meta.exempt_hi):
                src = int(row[b])
                dst = self.pool.acquire()
                if dst is None:
                    return False
                self._copy_page(src, dst)
                self.pool.note_cow()
                self.pool.release(src)
                row[b] = dst
                # the copy diverges from the indexed content: this slot's
                # chain is only trusted up to the copied block
                meta.frozen_blocks = min(meta.frozen_blocks, b)
                del meta.hash_chain[b:]
        return True

    def freeze_committed(self, slot: int, tokens: np.ndarray,
                         final_len: int) -> None:
        """Register every full page whose content is final — all
        positions' canonical tokens fed through the model, i.e. positions
        ``< final_len`` — under its chain hash, making it aliasable by
        later admissions.  No-op unless ``prefix_cache`` is on."""
        if not self.prefix_cache:
            return
        meta = self._meta[slot]
        row = self.block_tables[slot]
        n_final = min(final_len // self.page_size, meta.n_blocks)
        tokens = np.asarray(tokens)
        for b in range(meta.frozen_blocks, n_final):
            parent = meta.hash_chain[b - 1] if b else None
            h = chain_hash(parent, tokens[b * self.page_size:
                                          (b + 1) * self.page_size])
            meta.hash_chain.append(h)
            self.pool.freeze(int(row[b]), h)
            meta.frozen_blocks = b + 1

    def free(self, slot: int) -> None:
        """Release every page the slot references (shared pages survive
        via their other references; frozen pages park in the reuse cache)
        and recycle the slot.  Freeing a slot that is not allocated is the
        double-free that used to corrupt the free list — it raises."""
        meta = self._meta.pop(slot, None)
        if meta is None:
            raise ValueError(f"double free: slot {slot} is not allocated")
        row = self.block_tables[slot]
        for b in range(meta.n_blocks):
            self.pool.release(int(row[b]))
        self._free_slots.append(slot)
        self.block_tables[slot] = 0

    def table_refs(self) -> Dict[int, int]:
        """Per-page reference counts implied by the block tables — feeds
        :meth:`BlockPool.check` in tests."""
        refs: Dict[int, int] = {}
        for slot, meta in self._meta.items():
            for b in range(meta.n_blocks):
                p = int(self.block_tables[slot][b])
                refs[p] = refs.get(p, 0) + 1
        return refs

    # -- preemption / swap -------------------------------------------------

    def swap_out(self, slot: int) -> SwapSnapshot:
        """Copy the slot's pages (and recurrent rows) to host memory and
        free them — LRU preemption's swap path.  The snapshot remembers
        the frozen prefix's chain hashes so :meth:`swap_in` can re-alias
        any page still in the prefix index instead of copying it back.

        Swap-out compaction: the per-page gathers of EVERY cache leaf
        (2 x layers for GQA, more for MLA/hybrid trees) are flattened to
        bytes on device and concatenated, so the whole swap crosses
        device->host as ONE contiguous DMA instead of one transfer per
        leaf; :class:`PoolStats` records the transfers saved.  The
        snapshot still holds the original per-leaf numpy layout —
        :meth:`swap_in` is unchanged."""
        meta = self._meta[slot]
        row = self.block_tables[slot]
        phys = jnp.asarray(row[: meta.n_blocks])

        def gather(pool, paged):
            if paged:
                return pool[:, phys]
            return jax.lax.dynamic_slice_in_dim(pool, slot, 1, axis=1)

        dev = [jax.tree.map(gather, seg_pool, seg_flag)
               for seg_pool, seg_flag in zip(self.pools, self._paged)]
        data = self._pack_to_host(dev)
        snap = SwapSnapshot(
            n_blocks=meta.n_blocks, budget=meta.budget,
            frozen_blocks=meta.frozen_blocks,
            hash_chain=list(meta.hash_chain),
            cached_tokens=meta.cached_tokens, data=data)
        self.free(slot)
        return snap

    def swap_in_pages_needed(self, snap: SwapSnapshot) -> int:
        """Fresh pages a :meth:`swap_in` would consume after re-aliasing
        whatever survived in the prefix index."""
        hits = sum(1 for h in snap.hash_chain[: snap.frozen_blocks]
                   if self.pool.peek(h) is not None)
        return snap.n_blocks - hits

    def swap_in(self, snap: SwapSnapshot,
                slot: Optional[int] = None) -> Optional[int]:
        """Restore a swapped-out slot: frozen-prefix pages still in the
        index are aliased (no copy — swap resume re-deduplicates), the
        rest are re-acquired and scattered back from host.  Returns the
        slot, or None if slots/pages are exhausted."""
        if not self._free_slots:
            return None
        pages: List[int] = []
        restore: List[int] = []             # block indices needing data
        frozen = 0
        for b in range(snap.n_blocks):
            page = None
            if b < snap.frozen_blocks:
                page = self.pool.lookup(snap.hash_chain[b])
            if page is None:
                page = self.pool.acquire()
                if page is None:
                    for p in pages:
                        self.pool.release(p)
                    return None
                restore.append(b)
            elif frozen == b:
                frozen = b + 1
            pages.append(page)

        if slot is None:
            slot = self._free_slots.pop()
        else:
            self._free_slots.remove(slot)
        row = np.zeros((self.blocks_per_slot,), np.int32)
        row[: snap.n_blocks] = pages
        self.block_tables[slot] = row
        self._meta[slot] = _SlotMeta(
            n_blocks=snap.n_blocks, budget=snap.budget,
            cached_tokens=snap.cached_tokens, frozen_blocks=frozen,
            hash_chain=list(snap.hash_chain[:frozen]))
        dst = jnp.asarray(np.asarray(pages, np.int32)[restore]) \
            if restore else None
        src = np.asarray(restore)

        def put(pool, host, paged):
            if paged:
                if not restore:
                    return pool
                return pool.at[:, dst].set(
                    jnp.asarray(host[:, src]).astype(pool.dtype))
            return jax.lax.dynamic_update_slice_in_dim(
                pool, jnp.asarray(host).astype(pool.dtype), slot, axis=1)

        for i, (seg_pool, seg_host) in enumerate(zip(self.pools, snap.data)):
            self.pools[i] = jax.tree.map(put, seg_pool, seg_host,
                                         self._paged[i])
        # pages re-frozen lazily by freeze_committed; aliased ones already
        # carry their index entries
        return slot

    def _pack_to_host(self, dev: List[Any]) -> List[Any]:
        """One device->host transfer for a whole pytree of device arrays:
        bitcast every leaf to bytes, concatenate, pull the single flat
        buffer across, and re-view the per-leaf numpy arrays out of it
        (zero-copy slicing on the host side)."""
        leaves, treedef = jax.tree.flatten(dev)
        flat = [jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)
                for x in leaves]
        packed = np.asarray(jnp.concatenate(flat))      # the one DMA
        out, off = [], 0
        for x in leaves:
            n = x.size * x.dtype.itemsize
            out.append(packed[off:off + n].view(x.dtype).reshape(x.shape))
            off += n
        self.pool.stats.swap_dmas += 1
        self.pool.stats.swap_transfers_saved += max(len(leaves) - 1, 0)
        return jax.tree.unflatten(treedef, out)

    # -- device page ops ---------------------------------------------------

    def _copy_page(self, src: int, dst: int) -> None:
        """Device-side page copy across every paged leaf — the
        copy-on-write data move."""
        def f(pool, paged):
            if not paged:
                return pool
            return pool.at[:, dst].set(pool[:, src])
        self.pools = jax.tree.map(f, self.pools, self._paged)

    def _zero_slot_state(self, slot: int) -> None:
        """Fresh requests start from zero recurrent state; attention pages
        need no reset (masked by position)."""
        def f(pool, paged):
            if paged:
                return pool
            zeros = jnp.zeros(pool.shape[:1] + (1,) + pool.shape[2:],
                              pool.dtype)
            return jax.lax.dynamic_update_slice_in_dim(pool, zeros, slot,
                                                       axis=1)
        self.pools = jax.tree.map(f, self.pools, self._paged)

    # -- views -------------------------------------------------------------

    def block_tables_for(self, slots: Optional[List[int]] = None) -> jax.Array:
        """Device block tables; rows not in ``slots`` are pointed at the
        trash page so masked/idle lanes cannot clobber live pages."""
        if slots is None:
            return jnp.asarray(self.block_tables)
        bt = np.zeros_like(self.block_tables)
        for s in slots:
            bt[s] = self.block_tables[s]
        return jnp.asarray(bt)

    def write_prefill_states(self, slot: int, states: List[Any],
                             prompt_len: int, start: int = 0) -> None:
        """Scatter full-prefill collected states into this slot's pages.

        ``states`` come from ``models.prefill(collect_state=True)`` with
        batch 1: attention-family leaves are (reps, 1, S, ...) per-token
        streams -> paged scatter (S may exceed ``prompt_len`` when the
        prefill was length-bucketed/padded; only tokens in
        ``[start, prompt_len)`` are written — ``start`` skips positions a
        prefix-cache hit already holds); recurrent leaves are (reps, 1,
        ...) final states -> slot rows.
        """
        row = self.block_tables[slot]
        idx = np.arange(start, prompt_len)
        phys = jnp.asarray(row[idx // self.page_size])
        off = jnp.asarray(idx % self.page_size)
        states = self._quantize_states(states)

        def f(pool, state, paged, a):
            if not paged:
                return jax.lax.dynamic_update_slice_in_dim(
                    pool, state.astype(pool.dtype), slot, axis=1)
            # state (reps, 1, S, *line) -> the (reps, n, *rest) values of
            # pool[:, phys, ..., off (axis a), ...]; with the two indexed
            # axes apart, numpy indexing puts the token axis first
            rest = pool.shape[2:a] + pool.shape[a + 1:]
            val = state[:, 0, start:prompt_len].astype(pool.dtype)
            val = val.reshape(val.shape[:2] + rest)
            if a > 2:
                val = jnp.moveaxis(val, 1, 0)
            idx = [slice(None)] * pool.ndim
            idx[1], idx[a] = phys, off
            return pool.at[tuple(idx)].set(val)

        for i, (seg_pool, seg_state) in enumerate(zip(self.pools, states)):
            self.pools[i] = jax.tree.map(f, seg_pool, seg_state,
                                         self._paged[i], self._line_axis[i])

    def _quantize_states(self, states: List[Any]) -> List[Any]:
        """Quantized pools (cfg.kv_dtype != bf16) carry per-line scale
        leaves the collected prefill states don't have: quantize each
        value stream over its line axis (the same kernels/quantize.py op
        the decode commit path uses) and add the matching ``*_scale``
        state, so the paged scatter is a plain tree.map over identical
        structures — and the ``astype(pool.dtype)`` on the already-
        quantized values is a no-op, never a raw cast."""
        if not kvq.is_quantized(self.cfg.kv_dtype):
            return states
        out: List[Any] = []
        for seg_pool, seg_state in zip(self.pools, states):
            new_seg = {}
            for bname, blk_pool in seg_pool.items():
                blk = dict(seg_state[bname])
                for name in blk_pool:
                    if not name.endswith("_scale"):
                        continue
                    base = name[: -len("_scale")]
                    q, s = kvq.quantize(blk[base], self.cfg.kv_dtype, -1)
                    blk[base] = q
                    blk[name] = s
                new_seg[bname] = blk
            out.append(new_seg)
        return out

    def dense_view(self, slot: int) -> List[Any]:
        """Gather one slot's cache back into the dense ``init_cache`` layout
        (batch 1): paged leaves -> (reps, 1, max_len, ...), state leaves ->
        (reps, 1, ...).  Quantized pools are dequantized back to the model
        dtype and their scale leaves dropped, so the view matches the
        dense layout regardless of ``kv_dtype``.  For tests and debugging.
        """
        row = jnp.asarray(self.block_tables[slot])

        def f(pool, paged, a):
            if paged:
                g = jnp.moveaxis(pool[:, row], a, 2)   # (reps, nb, page, ...)
                return g.reshape(g.shape[0], 1,
                                 self.blocks_per_slot * self.page_size,
                                 *g.shape[3:])[:, :, : self.max_len]
            return jax.lax.dynamic_slice_in_dim(pool, slot, 1, axis=1)

        dense = [jax.tree.map(f, seg, flag, axes)
                 for seg, flag, axes in zip(self.pools, self._paged,
                                            self._line_axis)]
        if kvq.is_quantized(self.cfg.kv_dtype):
            for seg in dense:
                for blk in seg.values():
                    for name in [n for n in blk if n.endswith("_scale")]:
                        base = name[: -len("_scale")]
                        blk[base] = kvq.dequantize(
                            blk[base], blk.pop(name)).astype(self.cfg.dtype)
        return dense
