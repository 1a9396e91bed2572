"""KV-cache pools for continuous batching: slotted (fixed row per
request) and paged (vLLM-style block tables over a global arena).

One preallocated cache — per layer ``{"k": [num_slots, max_len, Hkv, Dh],
"v": ...}`` (or the int8 ``k_q/k_s/v_q/v_s`` quartet from the existing
KV-quant path, models/llama.py:init_cache) — shared by every in-flight
request. A request owns one slot (one batch row) from admission to
completion; slot positions are host-side state (the per-layer ``pos``
scalar of the single-sequence cache does not apply: every row is at its
own position, passed to the batched step as a ``[num_slots]`` vector).

Freeing a slot is O(1) bookkeeping: the stale rows are never zeroed —
chunked prefill overwrites from position 0 and the attention validity
mask (k_idx <= row position) makes unwritten/stale tail entries
unattendable, the same invariant bucketed prefill relies on
(infer/generate.py:prefill).

The LAST cache position of every slot is reserved as the junk-write
target for free/prefilling rows riding the fixed-shape decode step
(batch_step.decode_step writes ALL rows each iteration), so usable
sequence length is ``max_len - 1``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from ..analysis.sync_runtime import check_owner
from ..models import llama
from .prefix_cache import PrefixCache, chain_keys


@dataclass
class KVExport:
    """A pinned, immutable view of one request's full KV blocks.

    Produced by ``PagedKVPool.export_blocks``: every listed block carries
    an extra refcount (it cannot be recycled or evicted while the export
    is live) and ``cache`` snapshots the arena array refs — jax arrays
    are immutable, so the snapshot stays byte-consistent even while the
    engine keeps decoding into NEW arena arrays. Callers read KV bytes
    from ``cache`` (off the engine thread if they like), then MUST call
    ``release_export`` exactly once."""

    keys: List[bytes]          # chain keys, one per exported full block
    blocks: List[int]          # pinned physical block ids, chain order
    cache: list = field(repr=False, default_factory=list)
    released: bool = False


def _place_cache(cache, mesh, num_kv_heads):
    """Device-put a pool's buffers into the serving mesh's NamedSharding
    (head dim over ``tp``, see batch_step.kv_cache_pspec) so the very first
    dispatch runs partitioned instead of paying a lazy reshard. Identity
    without a mesh. Block tables stay host numpy — replicated by virtue of
    being passed as plain arrays."""
    if mesh is None:
        return cache
    import jax
    from jax.sharding import NamedSharding

    from .batch_step import kv_cache_pspec

    s = NamedSharding(mesh, kv_cache_pspec(mesh, num_kv_heads))
    return [{k: jax.device_put(v, s) for k, v in layer.items()}
            for layer in cache]


class SlotKVPool:  # graftsync: owner=engine-thread
    """Fixed pool of KV-cache slots with per-slot length state.

    Bookkeeping is engine-thread-owned (no locks): every mutator runs on
    the engine loop, and cross-thread callers must ride
    ``BatchEngine.call_in_loop``. ``check_owner`` asserts this under
    ``GRAFTSYNC_RUNTIME=1`` and is a no-op otherwise."""

    kind = "slotted"

    def __init__(self, args: llama.LlamaArgs, num_slots: int, max_len: int,
                 dtype=None, quantize: bool = False, mesh=None):
        import jax.numpy as jnp

        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.args = args
        self.num_slots = num_slots
        self.max_len = max_len
        self.quantize = quantize
        self.cache = llama.init_cache(args, num_slots, max_len=max_len,
                                      dtype=dtype or jnp.float32,
                                      quantize=quantize)
        # Slot positions live pool-side, not per layer.
        for layer in self.cache:
            layer.pop("pos", None)
        self.cache = _place_cache(self.cache, mesh, args.num_kv_heads)
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        # Written length per slot (== next write position). Free slots keep
        # their stale value; allocate() resets it.
        self.lengths: List[int] = [0] * num_slots

    # -- capacity ------------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Longest sequence a slot can hold (last position is the junk-write
        target for masked rows of the fixed-shape decode step)."""
        return self.max_len - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_slots - len(self._free)

    def occupancy(self) -> float:
        return self.num_used / self.num_slots

    # -- slot lifecycle ------------------------------------------------------
    def allocate(self, need_tokens: int = 0,
                 token_ids: Optional[Sequence[int]] = None) -> Optional[int]:
        """Claim a free slot (resets its length); None when the pool is full.
        ``need_tokens``/``token_ids`` are part of the shared pool interface
        — a slot always holds ``capacity`` tokens and has no prefix cache,
        so both are ignored here."""
        check_owner("engine-thread")
        if not self._free:
            return None
        slot = self._free.pop()
        self.lengths[slot] = 0
        return slot

    def ensure_capacity(self, slot: int, length: int) -> bool:
        """Shared pool interface: a slot's full extent is preallocated."""
        return length <= self.max_len

    def free(self, slot: int) -> None:
        check_owner("engine-thread")
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range 0..{self.num_slots - 1}")
        if slot in self._free:
            raise ValueError(f"slot {slot} double-freed")
        self._free.append(slot)

    def reset(self) -> None:
        """Free every slot (buffers are NOT zeroed — see module docstring)."""
        self._free = list(range(self.num_slots - 1, -1, -1))
        self.lengths = [0] * self.num_slots

    def max_active_len(self, slots) -> int:
        """Longest written length among ``slots`` — drives the attend bucket
        of the next batched decode step."""
        return max((self.lengths[s] for s in slots), default=0)


class PagedKVPool:  # graftsync: owner=engine-thread
    """Paged KV pool (PagedAttention, Kwon et al. 2023): one global arena of
    fixed-size blocks per layer shared by every sequence, addressed through
    per-sequence block tables.

    The slotted pool sizes HBM for ``num_slots x max_len`` worst-case rows;
    here a sequence only holds the blocks covering its *written* length, so
    the same KV budget admits as many concurrent sequences as their actual
    lengths fit. Admission is gated on free *blocks* (plus a free batch
    row), and blocks are mapped on demand as decode advances.

    Layout and invariants:

    - arena: per layer ``{"k": [num_blocks+1, block_size, Hkv, Dh], "v"}``
      (or the int8 ``k_q/k_s/v_q/v_s`` quartet) from
      ``llama.init_paged_cache``. Logical position ``p`` of sequence ``s``
      lives at ``(tables[s][p // block_size], p % block_size)``.
    - physical block 0 is a reserved shared junk block, never allocated:
      unmapped table entries point at it, and rows a step is not handed
      (``tables_for``; the fixed-shape batched step still writes them every
      iteration) scatter their junk there. This replaces the slotted pool's
      reserved-last-position trick, so usable length is the full table
      extent minus the one position needed to write the final emitted
      token's successor.
    - alloc/free are O(1) list ops on ``_free_blocks``; freeing never zeroes
      data — the validity mask (k_idx <= row position) makes stale entries
      unattendable, exactly as in the slotted pool.
    - ``fragmentation()`` is internal waste: 1 - used_tokens / (blocks_in_use
      * block_size). ``free_watermark`` tracks the minimum free-block count
      since the last ``read_watermark()`` — the headroom metric that says
      how close the arena came to exhaustion.

    Automatic prefix caching (``prefix_cache=True``): every physical block
    carries a refcount, full blocks become content-addressable through a
    ``PrefixCache`` (key = hash(parent_key, token_ids); see
    prefix_cache.py), and ``allocate(token_ids=...)`` adopts the longest
    cached block-chain for the prompt — block tables point at SHARED
    physical blocks (zero copy, refcount++) and ``lengths[seq]`` starts at
    the adopted token count so chunked prefill skips the hit prefix.
    Freed refcount-0 blocks with published keys retire to an LRU list
    instead of the free list (their bytes stay adoptable); allocation and
    ``ensure_capacity`` growth evict from the LRU end only when the plain
    free list runs dry. ``prefix_cache=False`` (default) is bit-for-bit
    the pre-cache pool.
    """

    kind = "paged"

    def __init__(self, args: llama.LlamaArgs, num_seqs: int, max_len: int,
                 block_size: int = 32, num_blocks: int = 0,
                 dtype=None, quantize: bool = False,
                 prefix_cache: bool = False, min_hit_blocks: int = 1,
                 mesh=None):
        import jax.numpy as jnp
        import numpy as np

        if num_seqs < 1:
            raise ValueError(f"num_seqs must be >= 1, got {num_seqs}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if block_size < 1 or (block_size & (block_size - 1)) != 0:
            raise ValueError(
                f"block_size must be a power of two, got {block_size}")
        if max_len % block_size != 0:
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of block_size "
                f"({block_size}) so attend buckets align to block bounds")
        self.args = args
        self.num_slots = num_seqs  # batch rows; name shared with SlotKVPool
        self.max_len = max_len
        self.block_size = block_size
        self.max_blocks = max_len // block_size  # table width per sequence
        if num_blocks <= 0:
            # Default: same token capacity as the slotted pool would have.
            num_blocks = num_seqs * self.max_blocks
        self.num_blocks = num_blocks
        self.quantize = quantize
        # +1: physical block 0 is the reserved junk block.
        self.cache = _place_cache(
            llama.init_paged_cache(
                args, num_blocks + 1, block_size,
                dtype=dtype or jnp.float32, quantize=quantize),
            mesh, args.num_kv_heads)
        self.tables = np.zeros((num_seqs, self.max_blocks), dtype=np.int32)
        self.lengths: List[int] = [0] * num_seqs
        self._mapped: List[int] = [0] * num_seqs  # blocks mapped per row
        self._free_rows: List[int] = list(range(num_seqs - 1, -1, -1))
        self._free_blocks: List[int] = list(range(num_blocks, 0, -1))
        self._watermark = num_blocks
        # Prefix cache: per-block refcounts + content-hash bookkeeping.
        # Block 0 (junk) is never allocated, registered, or refcounted.
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(block_size, min_hit_blocks) if prefix_cache else None)
        self._ref: List[int] = [0] * (num_blocks + 1)
        # per row: leading full blocks already published + chain parent key
        self._registered: List[int] = [0] * num_seqs
        self._chain_key: List[Optional[bytes]] = [None] * num_seqs

    # -- capacity ------------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Longest sequence a row's table can address, leaving one position
        for the successor of the final emitted token (whose KV is written by
        the decode step that samples the next token)."""
        return self.max_len - 1

    @property
    def num_free(self) -> int:
        """Free batch rows (the admission gate also checks free blocks)."""
        return len(self._free_rows)

    @property
    def num_used(self) -> int:
        return self.num_slots - len(self._free_rows)

    def occupancy(self) -> float:
        return self.num_used / self.num_slots

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: the plain free list plus retired (refcount
        0, still content-addressable) cached blocks — both satisfy an
        allocation, retired ones via LRU eviction."""
        free = len(self._free_blocks)
        if self.prefix is not None:
            free += self.prefix.retired_blocks
        return free

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - self.free_blocks

    def blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.block_size) if tokens > 0 else 0

    def fragmentation(self) -> float:
        """Internal fragmentation: fraction of mapped KV positions holding no
        live token (0.0 = every mapped block full)."""
        mapped_tokens = self.blocks_in_use * self.block_size
        if mapped_tokens == 0:
            return 0.0
        used = sum(self.lengths[s] for s in range(self.num_slots)
                   if s not in self._free_rows)
        return 1.0 - min(used, mapped_tokens) / mapped_tokens

    def read_watermark(self) -> int:
        """Minimum free-block count since the previous call (then reset)."""
        w = self._watermark
        self._watermark = self.free_blocks
        return w

    def _note_free_level(self) -> None:
        free = self.free_blocks
        if free < self._watermark:
            self._watermark = free

    # -- block supply --------------------------------------------------------
    def _take_block(self) -> Optional[int]:
        """One allocatable block: the plain free list first, then — with
        the prefix cache on — evict the least-recently-retired cached
        block (refcount-0 only by construction; its key is unpublished
        before reuse, so a stale chain can never match recycled bytes)."""
        if self._free_blocks:
            return self._free_blocks.pop()
        if self.prefix is not None:
            return self.prefix.evict_lru()
        return None

    def _release_block(self, block: int) -> None:
        """Refcount-- ; at zero a registered block retires to the prefix
        LRU (bytes stay adoptable), an unregistered one frees outright."""
        self._ref[block] -= 1
        if self._ref[block] > 0:
            return
        if self.prefix is None or not self.prefix.retire(block):
            self._free_blocks.append(block)

    # -- sequence lifecycle --------------------------------------------------
    def allocate(self, need_tokens: int = 0,
                 token_ids: Optional[Sequence[int]] = None) -> Optional[int]:
        """Claim a batch row and map enough blocks for ``need_tokens``
        (the prompt). None when no row is free OR the arena cannot cover
        the request — admission is gated on actual free blocks.

        With the prefix cache on and ``token_ids`` given, the longest
        cached block-chain covering the prompt is ADOPTED instead of
        allocated: those table entries point at shared physical blocks
        (refcount++, zero copy) and ``lengths[seq]`` starts at the
        adopted token count — the engine's chunked prefill resumes there.
        At least the final prompt token is always recomputed (its logits
        seed sampling), and nothing is mutated on refusal."""
        check_owner("engine-thread")
        adopted: List[int] = []
        adopted_key: Optional[bytes] = None
        if self.prefix is not None and token_ids is not None \
                and need_tokens > 0:
            adopted, adopted_key = self.prefix.match(
                token_ids, max_blocks=self.max_blocks)
        need = self.blocks_for(need_tokens)
        fresh = need - len(adopted)
        # Retired blocks about to be adopted are NOT allocatable supply:
        # revival pulls them off the LRU, so exclude them from the gate.
        adopting_retired = sum(1 for b in adopted if self._ref[b] == 0)
        if not self._free_rows or fresh > self.free_blocks - adopting_retired:
            return None
        seq = self._free_rows.pop()
        self.tables[seq, :] = 0
        for i, b in enumerate(adopted):
            self.tables[seq, i] = b
            self._ref[b] += 1
            if self._ref[b] == 1:
                self.prefix.revive(b)
        for i in range(len(adopted), need):
            b = self._take_block()
            self.tables[seq, i] = b
            self._ref[b] = 1
        self._mapped[seq] = need
        cached = len(adopted) * self.block_size
        self.lengths[seq] = cached
        self._registered[seq] = len(adopted)
        self._chain_key[seq] = adopted_key
        if self.prefix is not None and need_tokens > 0:
            self.prefix.note_lookup(need_tokens, cached)
        self._note_free_level()
        return seq

    def ensure_capacity(self, seq: int, length: int) -> bool:
        """Map blocks on demand so positions ``[0, length)`` are addressable.
        False (no state change) when the arena is exhausted — the caller
        decides whether to preempt."""
        if length > self.max_len:
            return False
        need = self.blocks_for(length)
        grow = need - self._mapped[seq]
        if grow <= 0:
            return True
        if grow > self.free_blocks:
            return False
        for i in range(self._mapped[seq], need):
            b = self._take_block()
            self.tables[seq, i] = b
            self._ref[b] = 1
        self._mapped[seq] = need
        self._note_free_level()
        return True

    def register_upto(self, seq: int, token_ids: Sequence[int]) -> None:
        """Publish content-hash keys for this row's newly-FULL blocks
        (``lengths[seq] // block_size`` leading blocks hold immutable,
        fully-written KV; the tail block is still mutable and never
        published). ``token_ids`` must be the fed-token sequence whose KV
        the row holds — prompt plus generated — so generated blocks are
        adoptable too (RadixAttention-style). Idempotent per block: each
        row tracks how far its chain has been published."""
        if self.prefix is None:
            return
        full = min(self.lengths[seq] // self.block_size, self._mapped[seq])
        if full <= self._registered[seq]:
            return
        keys = chain_keys(token_ids[:full * self.block_size],
                          self.block_size,
                          parent_key=self._chain_key[seq],
                          start_block=self._registered[seq])
        for i, key in zip(range(self._registered[seq], full), keys):
            self.prefix.register(key, int(self.tables[seq, i]))
            self._chain_key[seq] = key
        self._registered[seq] = full

    def free(self, seq: int) -> None:
        """Return the row; each mapped block's refcount drops, and blocks
        reaching zero either retire to the prefix LRU (registered) or
        rejoin the free list. O(mapped) list ops."""
        check_owner("engine-thread")
        if not 0 <= seq < self.num_slots:
            raise ValueError(f"seq {seq} out of range 0..{self.num_slots - 1}")
        if seq in self._free_rows:
            raise ValueError(f"seq {seq} double-freed")
        for i in range(self._mapped[seq]):
            self._release_block(int(self.tables[seq, i]))
        self.tables[seq, :] = 0  # unmapped rows scatter to the junk block
        self._mapped[seq] = 0
        self._registered[seq] = 0
        self._chain_key[seq] = None
        self._free_rows.append(seq)

    def reset(self) -> None:
        """Free every row and block (buffers are NOT zeroed)."""
        self.tables[:, :] = 0
        self.lengths = [0] * self.num_slots
        self._mapped = [0] * self.num_slots
        self._free_rows = list(range(self.num_slots - 1, -1, -1))
        self._free_blocks = list(range(self.num_blocks, 0, -1))
        self._watermark = self.num_blocks
        self._ref = [0] * (self.num_blocks + 1)
        self._registered = [0] * self.num_slots
        self._chain_key = [None] * self.num_slots
        if self.prefix is not None:
            self.prefix.clear()

    def max_active_len(self, seqs) -> int:
        """Longest written length among ``seqs`` — drives the attend bucket
        of the next batched decode step."""
        return max((self.lengths[s] for s in seqs), default=0)

    def tables_for(self, seqs):
        """Block tables for a batched step in which only ``seqs`` take part:
        a fresh host array of ``tables``' shape and dtype whose other rows
        are all zeros, the junk block. A row that holds blocks and sits the
        step out (still prefilling, between two chunks) is written there and
        not into its own, possibly shared, first block."""
        import numpy as np

        rows = list(seqs)
        out = np.zeros_like(self.tables)
        out[rows] = self.tables[rows]
        return out

    # -- KV transfer (public API) --------------------------------------------
    # The disaggregated-serving handoff (serve/kv_transfer.py) moves KV
    # between replicas through these three calls. Both sides must run with
    # the prefix cache on: content-hash chain keys are the wire addresses,
    # which is what makes shared prefixes transfer at most once.

    def export_blocks(self, token_ids: Sequence[int]) -> KVExport:
        """Pin and return the cached block-chain covering ``token_ids``.

        ``token_ids`` is the fed-token sequence a request wrote (prompt
        plus generated) — the same sequence ``register_upto`` published.
        Every full block whose chain key is published gets refcount++
        (revived off the LRU if retired), so the bytes cannot be recycled
        while the export is live. The chain stops at the first
        unpublished key; a short prompt (< one full block) exports empty.
        Overlapping exports of the same blocks are fine — pins nest via
        the refcount. Call on the engine thread (``call_in_loop``); read
        ``cache`` wherever; release on the engine thread again."""
        check_owner("engine-thread")
        if self.prefix is None:
            raise ValueError("export_blocks requires prefix_cache=True "
                             "(chain keys are the transfer addresses)")
        full = len(token_ids) // self.block_size
        keys: List[bytes] = []
        blocks: List[int] = []
        for key in chain_keys(token_ids[:full * self.block_size],
                              self.block_size):
            b = self.prefix.lookup(key)
            if b is None:
                break
            keys.append(key)
            blocks.append(b)
        for b in blocks:
            if self._ref[b] == 0:
                self.prefix.revive(b)
            self._ref[b] += 1
        self._note_free_level()
        return KVExport(keys=keys, blocks=blocks,
                        cache=[dict(layer) for layer in self.cache])

    def release_export(self, export: KVExport) -> None:
        """Unpin an export's blocks (refcount--; zero retires registered
        blocks to the prefix LRU). Exactly once per export — a double
        release would corrupt refcounts, so it raises instead."""
        check_owner("engine-thread")
        if export.released:
            raise ValueError("KV export already released (double release "
                             "would double-decrement block refcounts)")
        for b in export.blocks:
            if self._ref[b] <= 0:
                raise RuntimeError(
                    f"refcount invariant violated: exported block {b} has "
                    f"refcount {self._ref[b]} at release")
        export.released = True
        export.cache = []
        for b in export.blocks:
            self._release_block(b)

    def adopt_blocks(self, keys: Sequence[bytes],
                     blocks_data: Sequence[Sequence[Dict[str, "object"]]],
                     ) -> Dict[str, int]:
        """Install transferred KV blocks into this arena under their chain
        keys — the receiving half of the handoff.

        ``keys[i]`` is the chain key of block ``i``; ``blocks_data[i]`` is
        its payload, a per-layer list of ``{name: ndarray[block_size, Hkv,
        Dh]}`` dicts whose names/shapes/dtypes must match this arena's
        layout exactly (fp or int8 quartet — a mismatch raises, nothing is
        mutated). Keys must arrive in chain order.

        A key already published here is skipped (``reused`` — that block
        transferred at most once, ever). Fresh keys take a free block,
        write the bytes, register, and retire to the prefix LRU: refcount
        0, adoptable by the next ``allocate(token_ids=...)`` and evictable
        under pressure like any cached block — which is exactly what makes
        adopt-after-evict safe: a re-transfer simply re-installs. Runs out
        of arena space → stops at a chain prefix (``skipped`` counts the
        rest). Engine-thread only."""
        check_owner("engine-thread")
        import numpy as np

        if self.prefix is None:
            raise ValueError("adopt_blocks requires prefix_cache=True")
        if len(keys) != len(blocks_data):
            raise ValueError(f"{len(keys)} keys but {len(blocks_data)} "
                             "block payloads")
        layout = [{name: (tuple(arr.shape[1:]), np.dtype(arr.dtype))
                   for name, arr in layer.items()} for layer in self.cache]
        for i, data in enumerate(blocks_data):
            if len(data) != len(layout):
                raise ValueError(f"block {i}: {len(data)} layers, arena "
                                 f"has {len(layout)}")
            for li, layer in enumerate(data):
                if set(layer) != set(layout[li]):
                    raise ValueError(
                        f"block {i} layer {li}: names {sorted(layer)} != "
                        f"arena {sorted(layout[li])} (fp/int8 mismatch?)")
                for name, arr in layer.items():
                    want_shape, want_dtype = layout[li][name]
                    got = np.asarray(arr)
                    if tuple(got.shape) != want_shape \
                            or np.dtype(got.dtype) != want_dtype:
                        raise ValueError(
                            f"block {i} layer {li} '{name}': "
                            f"{got.shape}/{got.dtype} != arena "
                            f"{want_shape}/{want_dtype}")
        reused = adopted = 0
        staged: List[int] = []   # fresh blocks, pinned until bytes land
        staged_data: List[Sequence[Dict[str, "object"]]] = []
        for key, data in zip(keys, blocks_data):
            if self.prefix.lookup(key) is not None:
                reused += 1
                continue
            b = self._take_block()
            if b is None:
                break  # arena full of live data; keep the chain prefix
            if self._ref[b] != 0:
                raise RuntimeError(
                    f"refcount invariant violated: free block {b} has "
                    f"refcount {self._ref[b]}")
            # Pin while staging so a later _take_block in THIS loop can
            # never evict a block we just adopted (chain stays contiguous).
            self._ref[b] = 1
            self.prefix.register(key, b)
            staged.append(b)
            staged_data.append(data)
            adopted += 1
        if staged:
            self._write_blocks(staged, staged_data)
        for b in staged:
            self._release_block(b)  # refcount 0 -> retires to the LRU
        self._note_free_level()
        return {"adopted": adopted, "reused": reused,
                "skipped": len(keys) - adopted - reused}

    def quarantine(self, keys: Sequence[bytes]) -> int:
        """Unpublish suspect chain keys (graftchaos degradation ladder):
        a refused/corrupt KV transfer must not leave its keys adoptable.

        Each published key is dropped from the prefix index; a retired
        (refcount-0) block rejoins the free list immediately, while a
        block still referenced by live rows merely loses its key — those
        rows keep decoding on their own bytes and the block frees
        normally when they release it (unregistered blocks free outright
        in ``_release_block``). Unknown keys are ignored: quarantine is
        idempotent and safe to call on a chain that never adopted.
        Returns the number of keys actually dropped. Engine-thread only."""
        check_owner("engine-thread")
        if self.prefix is None:
            return 0
        dropped = 0
        for key in keys:
            b = self.prefix.lookup(key)
            if b is None:
                continue
            self.prefix.drop(b)
            if self._ref[b] == 0:
                # Was retired on the LRU: drop() removed it from the LRU
                # and key maps, so it must rejoin the allocatable supply
                # here or the block leaks.
                self._free_blocks.append(b)
            dropped += 1
        return dropped

    def _write_blocks(self, block_ids: Sequence[int], blocks_data) -> None:
        """Scatter transferred bytes into the arena: one batched
        ``.at[ids].set`` per layer tensor (a single device write each, not
        one per block)."""
        import numpy as np

        idx = np.asarray(block_ids, dtype=np.int32)
        new_cache = []
        for li, layer in enumerate(self.cache):
            new_layer = {}
            for name, arr in layer.items():
                stack = np.stack([np.asarray(d[li][name])
                                  for d in blocks_data])
                new_layer[name] = arr.at[idx].set(stack)
            new_cache.append(new_layer)
        self.cache = new_cache
