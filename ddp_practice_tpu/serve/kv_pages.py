"""Paged KV-cache pool: block-granular memory for continuous batching.

A flat decode cache (inference.make_cache, the one-shot generator's)
keeps one write cursor for the whole batch, and decode attention scans
the whole `[0, max_len)` span every step, whatever the rows hold: its
cost follows its allocated span, not the live context. A server's
requests come and go one at a time, so this module gives every slot
positions of its own, with vLLM-style paging:

- the flax "cache" collection of a decode-mode model is allocated as a
  POOL of fixed-size blocks: every `cached_key`/`cached_value` leaf is
  `(num_blocks, block_size, h*hd)` (the flat cache's minor layout —
  in-place TPU updates, ops/decode_attention.py); an int8 cache
  model (kv_cache_dtype="int8", models/vit.py) additionally pools its
  per-(head, position) fp32 scales as `(num_blocks, h, block_size)`
  leaves — the per-BLOCK scale pages that halve KV bytes/token; a
  latent-attention model (models/mla_lm.py) declares ONE leaf a layer,
  `cached_latent`, a token's normalised latent and rotated key in one
  row, pooled `(num_blocks, block_size, row)` like a K leaf (scatter,
  copy-on-write and the radix cache are by the leaf's rank, not its
  name);
- each slot owns a host-side list of blocks plus a device-side PAGE
  TABLE row (`[max_slots, max_blocks_per_slot]` int32): position `p` of
  a slot lives in pool block `page_table[slot, p // block_size]` at row
  `p % block_size`. Positions are SLOT-LOCAL, starting at 0 — there is
  no shared clock, so nothing drains and nothing rewinds;
- admission scatters the bucketed scratch prefill into freshly allocated
  blocks (`scatter_prompt_blocks`), decode appends at each slot's own
  write position, release returns the slot's blocks to the free list
  individually, and a request's context can outgrow the model's
  `max_len` as long as blocks exist.

Blocks are REFCOUNTED (PR 6): a block may be referenced by several
slots at once (shared prompt prefix, forked sampling siblings) and by
the radix prefix cache below; `free` is a deref and the block returns
to the free list only at refcount zero. Copy-on-write keeps sharing
sound: a slot about to WRITE into a block with refcount > 1 first
copies it into a private block (`copy_block`, serve/engine.py
`_ensure_writable`).

`RadixPrefixCache` is a block-granular radix tree over the pool: each
node is one FULL block of `block_size` prompt tokens at canonical
slot-local positions (node depth i covers positions [i*bs, (i+1)*bs)).
Admission walks the tree with the new prompt (`match`) and re-uses the
matched blocks outright — those prefill chunks are never recomputed —
then inserts its own full prompt blocks (`insert`) so later requests
hit them. The tree holds one reference per cached block; eviction
(`evict`) walks unreferenced LEAF nodes in LRU order, so a block is
never reclaimed while any slot still attends through it
(evict-while-referenced is structurally impossible — pinned in
tests/test_kv_pages.py). Sharing requires canonical positions, so the
prefix-cache admission path right-pads (attn_start 0) instead of the
plain path's left-padding — RoPE makes both layouts equivalent.

Block 0 is the pool's designated GARBAGE block: it is never handed out
by the allocator, never refcounted, never a copy-on-write source or
target, and retired slots' page-table rows point at it, so the batched
decode step can keep scattering for every batch row (static shapes,
zero recompiles) without a freed slot ever touching a live request's
pages. Stale K/V inside a reused block is never visible: a new
occupant's attention is masked to `[attn_start, length]` in its own
slot-local coordinates, and every position it does attend was written by
its own prefill/decode — or by the SAME tokens' prefill under a cache
hit (tests/test_kv_pages.py pins both).
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ddp_practice_tpu.inference import make_cache

# pool block index reserved as the write target of retired slots; the
# allocator never hands it out, refcounts it, or copies into it
GARBAGE_BLOCK = 0


class BlockAllocator:
    """Host-side refcounted free-list over the pool's block indices.

    Pure bookkeeping, same idiom as SlotAllocator below: freed blocks
    go to the BACK of the free list, so allocation order is deterministic
    and reuse is observable in tests. `alloc(n)` is all-or-nothing —
    a request either gets its blocks or None (the scheduler's admission
    gate turns None into queueing, never a crash).

    Blocks carry a REFCOUNT: `alloc` hands them out at 1, `ref` adds a
    holder (another slot sharing the block, the radix prefix cache),
    `free` drops one — the block returns to the free list only when the
    last holder lets go. A never-shared pool behaves exactly like the
    PR-3 allocator. Block 0 (GARBAGE_BLOCK) is outside the economy
    entirely: alloc never returns it and ref/free refuse it loudly (the
    retired-slot DMA convention must never alias a live/shared block).
    """

    def __init__(self, num_blocks: int) -> None:
        if num_blocks <= 1:
            raise ValueError(
                f"need at least 2 blocks (block {GARBAGE_BLOCK} is the "
                f"garbage block), got {num_blocks}"
            )
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(1, num_blocks))
        self._refs: Dict[int, int] = {}
        # optional refcount-transition hook: called as on_refcount(block,
        # count) after every ref/free. The radix prefix cache subscribes
        # to keep its evictable-blocks counter O(1) — a cached leaf flips
        # between evictable and pinned exactly when its refcount crosses
        # the 1 <-> 2 boundary, which only the allocator can see.
        self.on_refcount = None

    def alloc(self, n: int = 1) -> Optional[List[int]]:
        """n blocks at refcount 1, or None if fewer than n are free
        (all-or-nothing)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        blocks = self._free[:n]
        del self._free[:n]
        for b in blocks:
            assert b != GARBAGE_BLOCK, "garbage block leaked into free list"
            self._refs[b] = 1
        return blocks

    def ref(self, blocks: Sequence[int]) -> None:
        """Add one holder to each block (prefix-cache hit, fork)."""
        for b in blocks:
            if b == GARBAGE_BLOCK:
                raise ValueError(
                    f"block {GARBAGE_BLOCK} is the garbage block — it can "
                    f"never be shared or refcounted"
                )
            if b not in self._refs:
                raise ValueError(f"block {b} is not allocated")
            self._refs[b] += 1
            if self.on_refcount is not None:
                self.on_refcount(b, self._refs[b])

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one holder per block; a block with no holders left
        returns to the BACK of the free list."""
        for b in blocks:
            if b == GARBAGE_BLOCK:
                raise ValueError(
                    f"block {GARBAGE_BLOCK} is the garbage block — retired "
                    f"page-table rows point at it, it is never allocated "
                    f"or freed"
                )
            if b not in self._refs:
                raise ValueError(f"block {b} is not allocated")
            self._refs[b] -= 1
            count = self._refs[b]
            if count == 0:
                del self._refs[b]
                self._free.append(b)
            if self.on_refcount is not None:
                self.on_refcount(b, count)

    def refcount(self, block: int) -> int:
        """Current holder count (0 = free; garbage block reads 0)."""
        return self._refs.get(block, 0)

    @property
    def num_used(self) -> int:
        return len(self._refs)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_shared(self) -> int:
        """Blocks held by more than one holder — the sharing observable
        behind the `kv_blocks_shared` gauge."""
        return sum(1 for c in self._refs.values() if c > 1)


class SlotAllocator:
    """Host-side free-list over the engine's slot (batch row) indices.

    Pure bookkeeping — no device state. Freed slots go to the BACK of the
    free list so reuse is observable in tests (a released slot is handed
    out again once the older free slots are consumed) and allocation
    order is deterministic.
    """

    def __init__(self, max_slots: int) -> None:
        if max_slots <= 0:
            raise ValueError("max_slots must be positive")
        self.max_slots = max_slots
        self._free: List[int] = list(range(max_slots))
        self._used: set = set()

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop(0)
        self._used.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._used:
            raise ValueError(f"slot {slot} is not allocated")
        self._used.remove(slot)
        self._free.append(slot)

    @property
    def num_used(self) -> int:
        return len(self._used)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def used_slots(self) -> List[int]:
        return sorted(self._used)


class _RadixNode:
    """One full block of the radix tree: `tokens` is the block_size-token
    edge label, `block` the pool block holding those positions' K/V."""

    __slots__ = ("tokens", "block", "children", "parent", "last_use")

    def __init__(self, tokens: Tuple[int, ...], block: int, parent) -> None:
        self.tokens = tokens
        self.block = block
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.parent = parent
        self.last_use = 0


class RadixPrefixCache:
    """Block-granular radix tree mapping prompt prefixes to pool blocks.

    Depth-i nodes hold slot-local positions [i*block_size, (i+1)*bs) of
    some previously served prompt; only FULL blocks are cached (a
    partial tail block is private to its request — it would otherwise
    be written by that request's decode while shared). The tree holds
    one allocator reference per node, so cached blocks survive their
    original request's release; `evict` drops LRU leaves whose blocks
    have no other holder, leaf-first, so nothing a slot still attends
    through can ever be reclaimed.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int) -> None:
        self.allocator = allocator
        self.block_size = block_size
        self._root = _RadixNode((), GARBAGE_BLOCK, None)
        self._clock = 0          # LRU tick, bumped per touch
        self._nodes = 0
        self.hit_tokens = 0      # cumulative matched / recomputed token
        self.miss_tokens = 0     # counters (ServeMetrics exports deltas)
        # structural-change counter (insert/evict edges only): the
        # prefix-digest publisher (serve/affinity.py) rebuilds its
        # fingerprint exactly when this moves, so idle heartbeats never
        # re-walk a warm tree
        self.edit_seq = 0
        # O(1) evictable accounting: `_leaf_index` maps block -> its LEAF
        # node (a block appears at most once in the tree — insert only
        # ever refs a freshly allocated, caller-owned block), and
        # `_evictable` is the subset whose allocator refcount is exactly
        # 1 (the tree is the only holder). admit_gate probes evictable()
        # on EVERY blocked admission; before this counter each probe
        # walked the whole tree — linear in a big warm cache. Structural
        # transitions (insert/evict) are maintained here; refcount
        # transitions (a slot attaching to or releasing a cached block)
        # arrive through the allocator's on_refcount hook.
        self._leaf_index: Dict[int, _RadixNode] = {}
        self._evictable: set = set()
        allocator.on_refcount = self._on_refcount

    def __len__(self) -> int:
        return self._nodes

    # ------------------------------------------ evictable bookkeeping
    def _on_refcount(self, block: int, count: int) -> None:
        """Allocator hook: a leaf's block crossed a refcount boundary.
        count == 1 with the tree holding the block means evictable;
        anything else (a slot still attends through it, or the block
        is not a leaf/not cached) means not."""
        if block in self._leaf_index:
            if count == 1:
                self._evictable.add(block)
            else:
                self._evictable.discard(block)

    def _leaf_gained(self, node: "_RadixNode") -> None:
        """`node` just became a leaf (inserted, or its last child was
        evicted): index it and classify its evictability."""
        if node is self._root:
            return
        self._leaf_index[node.block] = node
        if self.allocator.refcount(node.block) == 1:
            self._evictable.add(node.block)

    def _leaf_lost(self, node: "_RadixNode") -> None:
        """`node` is no longer a leaf (gained a child) or no longer in
        the tree (evicted): drop it from the evictable accounting."""
        self._leaf_index.pop(node.block, None)
        self._evictable.discard(node.block)

    def _chunks(self, tokens: Sequence[int]):
        bs = self.block_size
        for i in range(len(tokens) // bs):
            yield tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])

    def _walk(self, tokens: Sequence[int]) -> list:
        """Nodes along the longest cached block-chunk prefix, in order.
        Side-effect free — `match` stamps LRU ticks and takes refs on
        top of this, `peek` deliberately does neither."""
        node = self._root
        out: list = []
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            node = child
            out.append(node)
        return out

    def _clamp_full(self, items: list, tokens: Sequence[int]) -> list:
        """Drop trailing matched items until at least ONE token of
        `tokens` is left to prefill — admission must produce the last
        prompt token's logits, which no cache holds. THE one clamp
        shared by `match` / `peek` / `ref_prefix`: the gate, the
        admission, and the room-making pin must agree on matched
        length or a feasible admission desynchronizes from its gate."""
        while items and len(items) * self.block_size >= len(tokens):
            items.pop()
        return items

    def peek(self, tokens: Sequence[int]) -> int:
        """Read-only longest-cached-prefix length in TOKENS, with
        `match`'s always-leave-one-to-prefill clamp — the admission
        gate's probe: no LRU stamp, no refs, no hit/miss accounting, so
        gating a request never perturbs cache state."""
        clamped = self._clamp_full(self._walk(tokens), tokens)
        return len(clamped) * self.block_size

    def ref_prefix(self, tokens: Sequence[int]) -> List[int]:
        """Temporarily PIN the cached prefix chain of `tokens`: refs
        every matched block (same walk + leave-one-to-prefill clamp as
        `match`, but no LRU stamp and no hit/miss accounting) and
        returns them — the caller MUST `allocator.free()` the list to
        drop the pins. `make_room` uses this to spare the blocked
        request's own prefix while aging out the rest of the cache."""
        blocks = self._clamp_full(
            [n.block for n in self._walk(tokens)], tokens)
        self.allocator.ref(blocks)
        return blocks

    def match(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of `tokens`: (blocks, matched_tokens).

        Matching is block-granular and always leaves at least ONE prompt
        token uncached — the admission prefill must produce the last
        prompt token's logits, which no cache holds. The caller owns a
        reference on each returned block (`allocator.ref` applied here),
        so a concurrent eviction can never pull a matched block out from
        under the admission that is about to attend through it.
        """
        self._clock += 1
        nodes = self._walk(tokens)
        blocks: List[int] = []
        for node in nodes:
            node.last_use = self._clock
            blocks.append(node.block)
        # never match the WHOLE prompt (`_clamp_full`): at least one
        # token is left to prefill
        blocks = self._clamp_full(blocks, tokens)
        matched = len(blocks) * self.block_size
        self.allocator.ref(blocks)
        self.hit_tokens += matched
        self.miss_tokens += len(tokens) - matched
        return blocks, matched

    def insert(self, tokens: Sequence[int], blocks: Sequence[int]) -> int:
        """Cache `tokens`' full blocks, where `blocks[i]` holds positions
        [i*bs, (i+1)*bs). Chunks already present keep their EXISTING
        block (the caller's duplicate stays private to its slot); new
        nodes take one tree reference on the caller's block. Returns the
        number of nodes added."""
        self._clock += 1
        node = self._root
        added = 0
        for i, chunk in enumerate(self._chunks(tokens)):
            child = node.children.get(chunk)
            if child is None:
                b = int(blocks[i])
                if b == GARBAGE_BLOCK:
                    raise ValueError(
                        "garbage block can never enter the prefix cache"
                    )
                self.allocator.ref([b])
                if not node.children:
                    self._leaf_lost(node)  # interior now, not evictable
                child = _RadixNode(chunk, b, node)
                node.children[chunk] = child
                self._nodes += 1
                added += 1
                self._leaf_gained(child)
            child.last_use = self._clock
            node = child
        if added:
            self.edit_seq += 1
        return added

    def evictable(self) -> int:
        """Blocks `evict` could free right now: leaf-reachable nodes
        whose block has no holder beyond the tree. Admission gates count
        these as available — evicting them is make_room's first move.
        O(1): the counter is maintained incrementally (insert/evict
        structural edges here, slot ref/deref edges via the allocator's
        on_refcount hook) instead of walking the tree per probe."""
        return len(self._evictable)

    def _evictable_walk(self) -> int:
        """The full-tree definition of `evictable()` — O(nodes). Kept as
        the oracle the incremental counter is pinned against
        (tests/test_kv_pages.py randomized op sequence)."""
        return sum(
            1 for n in self._iter_nodes()
            if not n.children and self.allocator.refcount(n.block) == 1
        )

    def _iter_nodes(self):
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())

    def evict(self, n_blocks: int) -> int:
        """Drop up to `n_blocks` LRU unreferenced LEAF nodes (repeatedly
        — an evicted leaf may expose its parent). Returns blocks freed.
        Nodes whose block another holder (a slot) still references are
        skipped: evict-while-referenced cannot happen by construction.
        """
        freed = 0
        while freed < n_blocks and self._evictable:
            # snapshot this round's victims from the incremental set (an
            # eviction below may expose a parent — it joins the NEXT
            # round, same order the full-walk loop gave)
            victims = sorted(
                (self._leaf_index[b] for b in self._evictable),
                key=lambda n: n.last_use,
            )
            for v in victims:
                if freed >= n_blocks:
                    break
                del v.parent.children[v.tokens]
                self._leaf_lost(v)
                if not v.parent.children:
                    self._leaf_gained(v.parent)
                self.allocator.free([v.block])
                self._nodes -= 1
                freed += 1
        if freed:
            self.edit_seq += 1
        return freed

    def clear(self) -> int:
        """Evict everything evictable (engine reset); returns blocks
        freed. Nodes pinned by live slots stay."""
        return self.evict(self._nodes)


# Cache leaves that are NOT pages, by the variable's name in the model's
# "cache" collection: a recurrent layer's fixed-size state a sequence
# (models/hybrid_lm.py Mamba2Mixer) is pooled a SLOT, and an expert
# layer's counters (ops/moe.py LatentMoE `moe_stats`, and `moe_rows`: the
# rows its layout moved and those the whole layout holds) are one small
# vector each a layer.
STATE_LEAVES = ("ssm_state", "conv_state")
# a block-sparse attention layer's per-slot counts (models/hybrid_lm.py
# SparseAttention: pages its walk read, pages a dense walk would have), one
# row a slot like a state; its compressed keys (`INDEX_LEAF`) are pages like
# any K leaf, `block / stride` rows a block where a K leaf has `block`
SLOT_STATS_LEAF = "sparse_stats"
INDEX_LEAF = "cached_index"
# a window attention layer's per-slot counts (models/vit.py SelfAttention
# with `window`: pages its decode walks read, pages whole walks would have)
WINDOW_STATS_LEAF = "window_stats"
STATS_LEAF = "moe_stats"
ROWS_LEAF = "moe_rows"
# a latent-attention layer's one page leaf (models/mla_lm.py): pages like
# any K or V leaf; named so that the engine can size its gauge
LATENT_LEAF = "cached_latent"


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def leaf_kind(path) -> str:
    """"state" | "slots" | "stats" | "rows" | "pages": which pool a cache
    leaf belongs to (scalars, the flat layout's cursors, are told by their
    rank). "state" and "slots" hold one row a SLOT."""
    name = leaf_name(path)
    if name in STATE_LEAVES:
        return "state"
    return {STATS_LEAF: "stats", ROWS_LEAF: "rows", SLOT_STATS_LEAF: "slots",
            WINDOW_STATS_LEAF: "slots"}.get(name, "pages")


def per_slot(path) -> bool:
    return leaf_kind(path) in ("state", "slots")


class CacheSpec(NamedTuple):
    """What a model says of its paged leaves beyond their names: the page
    GROUP of each layer. Every paged leaf is in the group "global" (a slot
    keeps a page for every position it has written) but those of the
    modules named in `window_layers`, which are in "window": a query there
    attends the `window` latest keys alone, so the pages behind a slot's
    window are dead and go back to the group's own allocator
    (`PageGroup.trim`). A group has its own pool lead dimension
    (`make_paged_cache`), its own allocator and its own table row a slot;
    positions address both tables alike, column `p // block_size`. A model
    states the fields as `cache_spec()` (models/hybrid_lm.py); one without
    the method has the global group alone. The state, latent and compressed-key
    kinds are still told by leaf NAME (`leaf_kind`)."""

    window: int = 0
    window_layers: Tuple[str, ...] = ()

    def group(self, path) -> str:
        keys = {str(getattr(k, "key", k)) for k in path}
        return "window" if keys & set(self.window_layers) else "global"

    def window_pages(self, block_size: int, chunk: int) -> int:
        """The most pages of the window group one slot ever holds: the keys
        a chunk of `chunk` tokens attends (the window behind its first row,
        and the chunk), wherever in a page they begin."""
        return -(-(self.window + chunk) // block_size) + 1


def cache_spec(model) -> CacheSpec:
    """`model.cache_spec()` (the spec's fields, as a dict: models/ imports
    nothing of serve/) as a `CacheSpec`; the global group alone without."""
    return CacheSpec(**model.cache_spec()) if hasattr(model, "cache_spec") \
        else CacheSpec()


def make_paged_cache(model, num_blocks: int, block_size: int,
                     max_slots: int = 1, window_blocks: int = 0) -> Any:
    """Block-pool cache collection for `model` (decode mode): a cache
    spec a layer, by what the layer declares (`leaf_kind`, by name) and by
    what the model says of it (`cache_spec`: a window layer's pages are a
    pool of `window_blocks` of their own).

    Mirrors the tree structure of `inference.make_cache` — same variable
    names per attention block, so `decode_apply` threads it unchanged —
    but every K/V leaf is `(num_blocks, block_size, kv_heads*hd)` instead
    of `(batch, max_len, kv_heads*hd)`. An int8 cache model's per-(head,
    position) scale leaves pool the same way: `(1, h, block_size)` becomes
    `(num_blocks, h, block_size)` — per-block scale pages riding the
    same page table as the K/V they dequantize. A recurrent layer's
    leaves (`STATE_LEAVES`) are a per-SLOT state pool, `(max_slots, ...)`:
    admission overwrites a slot's row from the prefill's final state,
    decode updates every row in place, and release leaves the row for
    the next owner to overwrite; a sparse attention layer's per-slot counts
    pool the same way and its compressed keys as pages of their own row
    count. Scalar leaves (the flat layout's write cursors) and an expert
    layer's counters stay as they are.
    """
    shapes = jax.eval_shape(lambda: make_cache(model, 1, block_size))
    spec = cache_spec(model)

    def per_leaf(path, a):
        if a.ndim == 0 or leaf_kind(path) in ("stats", "rows"):
            return jnp.zeros(a.shape, a.dtype)
        if per_slot(path):
            lead = max_slots
        elif spec.group(path) == "window":
            lead = window_blocks
        else:
            lead = num_blocks
        return jnp.zeros((lead,) + a.shape[1:], a.dtype)

    return jax.tree_util.tree_map_with_path(per_leaf, shapes)


class PageGroup:
    """Host side of one page group beside the engine's first (serve/
    engine.py keeps the global group's table and allocator as it always
    did): an allocator over the group's own pool and a page-table row a
    slot whose columns are POSITIONS (`p // block_size`, as the global
    table's), of which a slot holds the run `[first, end)`: every column
    before `first` was given back (`trim`) and points at the garbage block
    again, so a read behind the window finds block 0 and never another
    slot's page."""

    def __init__(self, num_blocks: int, max_slots: int, columns: int):
        self.blocks = BlockAllocator(num_blocks)
        self.table = np.zeros((max_slots, columns), np.int32)
        self.first = np.zeros((max_slots,), np.int64)
        self.end = np.zeros((max_slots,), np.int64)
        self.freed = 0       # pages given back behind a window, cumulative

    def held(self, slot: int) -> int:
        return int(self.end[slot] - self.first[slot])

    def trim(self, slot: int, live_from: int) -> int:
        """Give back the slot's pages in the columns before `live_from`
        (never past what it holds: a window is at least the query's own
        position); how many went."""
        lo, hi = int(self.first[slot]), min(int(self.end[slot]), live_from)
        if hi <= lo:
            return 0
        self.blocks.free([int(b) for b in self.table[slot, lo:hi]])
        self.table[slot, lo:hi] = GARBAGE_BLOCK
        self.first[slot] = hi
        self.freed += hi - lo
        return hi - lo

    def extend(self, slot: int, ids) -> None:
        """The pages `ids` in the columns from the slot's end on."""
        end = int(self.end[slot])
        self.table[slot, end:end + len(ids)] = ids
        self.end[slot] = end + len(ids)

    def clear(self, slot: int) -> None:
        lo, hi = int(self.first[slot]), int(self.end[slot])
        if hi > lo:
            self.blocks.free([int(b) for b in self.table[slot, lo:hi]])
        self.table[slot, :] = GARBAGE_BLOCK
        self.first[slot] = self.end[slot] = 0


def _is_scale_leaf(path) -> bool:
    """Scale-pool leaves ((nb, h, bs) — positions on axis 2) vs K/V
    leaves ((nb, bs, h*hd) — positions on axis 1), told apart by the
    cache variable NAME (`cached_key_scale` / `cached_value_scale`,
    models/vit.py) rather than shape heuristics."""
    return any(
        "scale" in str(getattr(k, "key", k)) for k in path
    )


def scatter_prompt_blocks(pool: Any, scratch: Any, block_ids,
                          width: int, block_size: int, slot=None) -> Any:
    """Scatter a batch-1 contiguous scratch cache into pool blocks.

    `scratch` holds a freshly prefilled prompt at positions `[0, width)`
    of a `(1, width, h*hd)` flat cache; `block_ids` is the
    `(ceil(width / block_size),)` int32 list of destination blocks (may
    be traced — admission happens inside jit). Chunk `i` of the scratch
    lands in pool block `block_ids[i]`; a trailing partial chunk writes
    only its real rows, so whatever the rest of that block held stays —
    and stays invisible, because attention is masked to the slot's own
    positions. int8 scale leaves ((1, h, width) -> (nb, h, block_size))
    chunk along their position axis (2) the same way. Scalar leaves
    keep the POOL's value (no global clock), as do an expert layer's
    counters; the rows this prefill's expert layers moved join the
    pool's (the next burst reads them back). A recurrent layer's state
    leaves are not pages: the scratch's batch-1 final state overwrites
    row `slot` of the state pool.
    """
    def per_leaf(path, p, s):
        kind = leaf_kind(path)
        if p.ndim == 0 or kind in ("stats", "slots"):
            return p
        if kind == "rows":
            return p + s
        if kind == "state":
            return lax.dynamic_update_slice(
                p, s.astype(p.dtype), (slot,) + (0,) * (p.ndim - 1))
        # rows a block of THIS leaf holds (a compressed-key leaf has fewer
        # a block than K and V) and those `width` positions fill of it
        pos_axis = 2 if _is_scale_leaf(path) else 1
        per = p.shape[pos_axis]
        filled = -(-width * per // block_size)
        for i in range(-(-filled // per)):
            lo = i * per
            rows = min(per, filled - lo)
            if pos_axis == 1:
                chunk = lax.dynamic_slice(
                    s, (0, lo, 0), (1, rows, s.shape[2])
                ).astype(p.dtype)
                p = lax.dynamic_update_slice(p, chunk, (block_ids[i], 0, 0))
            else:
                chunk = lax.dynamic_slice(
                    s, (0, 0, lo), (1, s.shape[1], rows)
                ).astype(p.dtype)
                p = lax.dynamic_update_slice(p, chunk, (block_ids[i], 0, 0))
        return p

    return jax.tree_util.tree_map_with_path(per_leaf, pool, scratch)


def rewind_block_tail(blocks: BlockAllocator, table_row, nblk: int,
                      floor: int) -> int:
    """Return a page-table row's tail blocks [floor, nblk) to the pool —
    the block half of a length rewind. Speculative verify
    (serve/engine.py step_verify) grows every slot for the worst case
    (`spec_k + 1` positions) before it knows how much of the draft the
    model accepts; after acceptance the rejected tail's positions no
    longer exist, so the blocks grown ONLY for them come straight back.
    The caller picks `floor` so it never dips below the pre-grow table
    (freed blocks are then provably this dispatch's own fresh
    refcount-1 allocations — a shared prefix/fork block can never be in
    the tail). Freed table entries are pointed back at the garbage
    block, keeping the batched dispatch's static shapes safe. Returns
    the new block count (== max(floor, min(nblk, floor)) — i.e. floor,
    or nblk unchanged when there is no tail)."""
    if nblk <= floor:
        return nblk
    tail = [int(b) for b in table_row[floor:nblk]]
    assert GARBAGE_BLOCK not in tail, "garbage block in a live tail"
    blocks.free(tail)
    table_row[floor:nblk] = GARBAGE_BLOCK
    return floor


def copy_block(pool: Any, src, dst) -> Any:
    """Copy one pool block (every non-scalar leaf row `src` -> `dst`) —
    the copy-on-write primitive: a slot about to write into a SHARED
    block first duplicates it into a private one. `src`/`dst` may be
    traced scalars (the engine jits one copy program, reused for every
    split). Copying from/into the garbage block is a caller bug; the
    engine asserts it host-side before dispatch."""

    def per_leaf(path, p):
        if p.ndim == 0 or leaf_kind(path) != "pages":
            return p
        row = lax.dynamic_slice(
            p, (src,) + (0,) * (p.ndim - 1), (1,) + p.shape[1:]
        )
        return lax.dynamic_update_slice(
            p, row, (dst,) + (0,) * (p.ndim - 1)
        )

    return jax.tree_util.tree_map_with_path(per_leaf, pool)
