"""Block-granular prompt-prefix KV reuse for the paged engine.

Reference: vLLM's automatic prefix caching (block hashing + refcounted
copy-on-read KV blocks) and the reference's ``ray.llm``
``routing_policies/kv_aware`` prefix-aware routing. A prompt is chunked
into KV-block-sized runs of token ids; each FULL block gets a chain hash
(its tokens mixed with the previous block's hash, so a block's key pins
the entire prefix behind it). After a request prefills, its full prompt
blocks are registered here; a later request whose prompt shares the
prefix matches the longest cached chain and prefills only its suffix.

Ownership model (host-side bookkeeping only — the blocks themselves live
in the engine's device pool):

- a cached block is REFCOUNTED: every admitted request using it holds one
  ref; the engine's release path decrefs instead of freeing.
- refs can drop to zero without eviction: the block stays cached (a warm
  prefix survives between conversation turns) but becomes *evictable* —
  the engine reclaims LRU zero-ref blocks when the free list runs short,
  so caching never deadlocks admission.
- eviction takes only blocks with no cached chain-child (evicting a
  parent would leave unreachable children holding pool blocks forever),
  the least recently used first, and a parent is next in line, at its own
  age, the moment its last child goes: the oldest idle chain goes whole,
  tail to head, before a more recent one loses a block. The order is kept
  (a heap of childless zero-ref entries, at most one item an entry, its
  age put right when it surfaces), so an eviction costs what it frees and
  not a pass over the cache.

**State snapshots** (`num_snapshots` > 0; a model with recurrent layers,
`llm/_engine`'s `SNAPSHOT_STATE`). A matched run of blocks resumes such a
sequence only from a copy of its slot's state at or before the run's end. The
copies live in a device pool of fixed size whose entry numbers this cache
owns: a snapshot belongs to the cached block that *ends* at its position
(`attach_snapshot`), `deepest_snapshot` finds the one to resume from, and it
is evicted with that block, so it never outlives the blocks up to its
position. When every entry is taken the least recently used snapshot gives
its own up (`reserve_snapshot`), unless an admitted request is still to
start from it (`pin_snapshot`). A snapshot that is gone shortens what a match
can resume; it fails nothing. Where a prompt leaves snapshots and which of
its own it keeps is a `SnapshotPolicy`'s to say (below the cache): the
family's step set names the class, the engine builds one on its cache and
asks it.

Pure host-side data structure: no asyncio, no JAX — unit-testable alone.
All mutation happens from the engine's single admission/step context.
"""

from __future__ import annotations

import hashlib
import heapq
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["PrefixCache", "SnapshotPolicy", "SnapshotsAtChunks",
           "SnapshotsAtMatch", "chain_keys"]


def chain_keys(prompt_ids: Sequence[int], block_size: int) -> List[bytes]:
    """Chain hash per FULL block of the prompt: key_i commits to tokens
    [0, (i+1)*block_size) — equal keys mean equal whole prefixes, so a
    match can splice the cached blocks in without comparing tokens. The
    ids are hashed as the bytes of one int64 array: a list, a tuple and an
    integer array of the same ids give the same keys."""
    raw = np.asarray(prompt_ids, np.int64).tobytes()
    step = 8 * block_size
    keys: List[bytes] = []
    prev = b""
    for end in range(step, len(raw) + 1, step):
        prev = hashlib.blake2b(
            prev + raw[end - step:end], digest_size=16).digest()
        keys.append(prev)
    return keys


@dataclass
class _Entry:
    block: int                   # physical block id in the engine pool
    refs: int = 0                # admitted requests currently using it
    parent: Optional[bytes] = None
    depth: int = 0               # blocks before it on its chain
    children: int = 0            # cached entries whose parent it is
    last_use: int = 0            # LRU tick
    queued: bool = False         # has its one item in the eviction heap
    snap: int = -1               # entry of the snapshot pool, -1: none
    snap_use: int = 0            # the snapshot's own LRU tick
    snap_by: int = 0             # the request whose prompt left it


class PrefixCache:
    def __init__(self, block_size: int, num_snapshots: int = 0):
        self.block_size = int(block_size)
        self.num_snapshots = int(num_snapshots)
        self._free_snaps = list(range(self.num_snapshots))
        self._snap_key: Dict[int, bytes] = {}   # pool entry -> its block's key
        self._snap_pins: Dict[int, int] = {}    # pool entry -> admissions
        self.snapshots_taken = 0
        self.snapshots_restored = 0
        self.snapshots_evicted = 0   # with their blocks, or displaced (LRU)
        self._entries: Dict[bytes, _Entry] = {}
        self._by_block: Dict[int, bytes] = {}
        # every cached entry's `refs` by its block's number, for
        # `shared_blocks`: grown as blocks are seen
        self._block_refs = np.zeros((1024,), np.int32)
        self._tick = 0
        # the eviction order: (last_use, -depth, key) of entries that had no
        # request and no cached child when they were put in (`_offer`), at
        # most one item an entry; `evict` drops or re-files a stale one
        self._heap: List[Tuple[int, int, bytes]] = []
        # cached blocks no request holds, kept as a count: `stats()` reads
        # it from the engine's loop while an admission, in its thread,
        # changes the entries
        self._idle = 0
        # counters surfaced through engine stats / the metrics plane
        self.hits = 0            # match() calls that reused >= 1 block
        self.block_hits = 0      # total blocks served from cache
        self.misses = 0
        self.evictions = 0
        self.evict_calls = 0
        self.evict_examined = 0  # heap items popped, stale ones included

    # -- lookup -----------------------------------------------------------

    def match(self, keys: List[bytes]) -> List[int]:
        """Blocks for the longest cached prefix of ``keys``, INCREF'd —
        the caller owns one ref per returned block and must decref via
        :meth:`decref_block` (the engine's release path) or
        :meth:`cancel_match` on admission failure."""
        self._tick += 1
        out: List[int] = []
        for k in keys:
            e = self._entries.get(k)
            if e is None:
                break
            self._idle -= e.refs == 0
            e.refs += 1
            self._block_refs[e.block] += 1
            e.last_use = self._tick
            out.append(e.block)
        if out:
            self.hits += 1
            self.block_hits += len(out)
        else:
            self.misses += 1
        return out

    def cancel_match(self, blocks: List[int]):
        for b in blocks:
            self.decref_block(b)

    # -- state snapshots --------------------------------------------------

    def deepest_snapshot(self, keys: List[bytes], n_blocks: int,
                         run: bool = True):
        """(blocks, pool entry) of the deepest snapshot at or before the end
        of the first `n_blocks` matched blocks of `keys`: the sequence can
        resume at position `blocks * block_size` from that entry. (0, -1)
        where none is left. Counts as a use of every snapshot on the run
        (`run`): while a document is asked about, the shallower ones a
        trimmed tail would fall back to stay as recent as the one that
        serves. Without `run`, of the one that serves alone."""
        self._tick += 1
        blocks, entry, found = 0, -1, None
        for i in range(n_blocks):
            e = self._entries.get(keys[i])
            if e is not None and e.snap >= 0:
                if run:
                    e.snap_use = self._tick
                blocks, entry, found = i + 1, e.snap, e
        if found is not None:
            found.snap_use = self._tick
            self.snapshots_restored += 1
        return blocks, entry

    def snapshot_owner(self, entry: int) -> int:
        """The request whose prompt left the snapshot in pool entry `entry`
        (what `attach_snapshot` was told; 0 where it was told nothing)."""
        return self._entries[self._snap_key[entry]].snap_by

    def pin_snapshot(self, entry: int, pinned: bool = True):
        """An admitted request will start from `entry` in a coming step:
        until then (`pinned=False`) it is not displaced."""
        n = self._snap_pins.get(entry, 0) + (1 if pinned else -1)
        if n > 0:
            self._snap_pins[entry] = n
        else:
            self._snap_pins.pop(entry, None)

    def has_snapshot(self, key: bytes) -> bool:
        e = self._entries.get(key)
        return e is not None and e.snap >= 0

    def reserve_snapshot(self) -> int:
        """A pool entry to write a snapshot to: a free one, else the least
        recently used snapshot's (never one an admission is pinned to).
        -1 where the pool has none to give."""
        if self._free_snaps:
            return self._free_snaps.pop()
        used = [(self._entries[k].snap_use, i)
                for i, k in self._snap_key.items() if i not in self._snap_pins]
        if not used:
            return -1
        entry = min(used)[1]
        self._drop(self._snap_key[entry])
        self.snapshots_evicted += 1
        return self._free_snaps.pop()

    def attach_snapshot(self, key: bytes, entry: int, rid: int = 0) -> bool:
        """Pool entry `entry` (from `reserve_snapshot`) now holds the state
        at the end of the cached block `key`, left by request `rid`. False,
        and the entry is free again, where that block is not cached or
        already has one."""
        e = self._entries.get(key)
        if e is None or e.snap >= 0:
            self._free_snaps.append(entry)
            return False
        self._tick += 1
        e.snap, e.snap_use, e.snap_by = entry, self._tick, rid
        self._snap_key[entry] = key
        self.snapshots_taken += 1
        return True

    def drop_snapshot(self, key: bytes):
        """Give up the snapshot of block `key`, if it has one (a request
        recycling its own older ones). One that an admitted request is still
        to start from stays, for `reserve_snapshot` or its block's eviction
        to take later."""
        e = self._entries.get(key)
        if e is not None and e.snap >= 0 and e.snap not in self._snap_pins:
            self._drop(key)

    def _drop(self, key: bytes):
        e = self._entries[key]
        self._snap_key.pop(e.snap, None)
        self._free_snaps.append(e.snap)
        e.snap = -1

    # -- registration -----------------------------------------------------

    def register(self, keys: List[bytes], blocks: List[int]) -> None:
        """Cache a freshly prefilled prompt's full blocks. ``blocks[i]``
        holds the KV for chain key ``keys[i]``. Entries that already exist
        (the matched prefix, already ref'd by this request via match) are
        left alone; new tails are inserted with refs=1 — the registering
        request's own ref. An entry is a block of the engine's pool, so the
        pool's size is the only cap there is."""
        self._tick += 1
        prev: Optional[bytes] = None
        for depth, (k, b) in enumerate(zip(keys, blocks)):
            e = self._entries.get(k)
            if e is not None:
                # already cached (this request matched it, or an identical
                # cold request registered first) — never double-insert; if
                # the existing entry maps a DIFFERENT physical block, this
                # request's private copy stays uncached and frees normally
                e.last_use = self._tick
                prev = k
                continue
            if int(b) in self._by_block:
                # this physical block already backs another chain (should
                # not happen with disjoint allocation, but never corrupt
                # the block->key map)
                prev = None
                continue
            self._entries[k] = _Entry(block=int(b), refs=1, parent=prev,
                                      depth=depth, last_use=self._tick)
            self._by_block[int(b)] = k
            if int(b) >= len(self._block_refs):
                self._block_refs = np.pad(
                    self._block_refs, (0, max(len(self._block_refs), int(b))))
            self._block_refs[int(b)] = 1
            if prev is not None:
                self._entries[prev].children += 1
            prev = k

    # -- release / eviction ----------------------------------------------

    def decref_block(self, block: int) -> bool:
        """True if the block is cache-owned (it stays resident, evictable
        once refs hit zero); False = not ours, caller frees it."""
        k = self._by_block.get(int(block))
        if k is None:
            return False
        e = self._entries[k]
        if e.refs == 1:
            self._idle += 1
            self._offer(k, e)
        e.refs = max(0, e.refs - 1)
        self._block_refs[e.block] = e.refs
        return True

    def shared_blocks(self, tables: np.ndarray) -> int:
        """How many entries of `tables` (block numbers; 0 is no block) are
        cached blocks that more than one admitted request holds."""
        refs = self._block_refs
        known = tables[tables < len(refs)]
        return int(np.count_nonzero(refs[known] > 1))

    def owns_block(self, block: int) -> bool:
        return int(block) in self._by_block

    def _offer(self, k: bytes, e: _Entry) -> None:
        """File `e` for eviction unless its item is in the heap already or a
        cached child pins it. Called where the last request lets go of it
        and where its last child is evicted; a match takes it back by its
        refs alone, and `evict` sorts that out when the item surfaces."""
        if not e.queued and not e.children:
            e.queued = True
            heapq.heappush(self._heap, (e.last_use, -e.depth, k))

    def evict(self, want: int) -> List[int]:
        """Free up to ``want`` blocks that no request holds and no cached
        child hangs on: least recently used first, the deeper of two of one
        age first, and a parent in its turn, at its own age, once its last
        child is gone (so the oldest idle chain goes whole before the next
        loses a block). A block's snapshot goes with it. Returns the
        physical blocks for the engine's free list. Costs the items it
        pops: those it frees and the stale ones above them."""
        self.evict_calls += 1
        freed: List[int] = []
        while len(freed) < want and self._heap:
            last_use, _, k = heapq.heappop(self._heap)
            self.evict_examined += 1
            e = self._entries[k]
            e.queued = False
            if e.refs or e.children:
                continue        # taken back since: filed again when let go
            if e.last_use != last_use:
                self._offer(k, e)   # used since it was filed: to its place
                continue
            if e.snap >= 0:
                # a pinned one has a request on its block: refs > 0
                self._drop(k)
                self.snapshots_evicted += 1
            del self._entries[k]
            self._idle -= 1
            del self._by_block[e.block]
            parent = self._entries.get(e.parent)
            if parent is not None:
                parent.children -= 1
                if not parent.refs:
                    self._offer(e.parent, parent)
            freed.append(e.block)
            self.evictions += 1
        return freed

    def clear(self) -> List[int]:
        """Drop everything (device pool was rebuilt — the cached blocks no
        longer hold valid KV). Returns all previously cached blocks."""
        blocks = [e.block for e in self._entries.values()]
        self._entries.clear()
        self._by_block.clear()
        self._heap.clear()
        self._block_refs[:] = 0
        self._idle = 0
        self._free_snaps = list(range(self.num_snapshots))
        self._snap_key.clear()
        self._snap_pins.clear()
        return blocks

    # -- introspection ----------------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def evictable_blocks(self) -> int:
        """Blocks reclaimable RIGHT NOW plus those pinned only by cached
        children — i.e. every cached block no active request holds. The
        engine counts these as available capacity (one `evict` call walks
        a zero-ref subtree from its leaves to its root)."""
        return self._idle

    def stats(self) -> dict:
        out = {
            "entries": len(self._entries),
            "evictable": self.evictable_blocks(),
            "hits": self.hits,
            "block_hits": self.block_hits,
            "misses": self.misses,
            "evictions": self.evictions,
            # evict_examined / evictions near 1: an eviction costs what it
            # frees
            "evict_calls": self.evict_calls,
            "evict_examined": self.evict_examined,
        }
        if self.num_snapshots:
            out["snapshots"] = len(self._snap_key)
        return out


class SnapshotPolicy:
    """Where a prompt leaves snapshots of its slot's state in `cache`'s pool
    and which of its own it keeps: the four questions the engine's loop asks
    of a family whose step set has `SNAPSHOT_STATE`, in the order of a
    request's life (at admission `waits`, then `resume`; `cut`; `take`,
    which asks `here`; `attached`). `widest` is the widest prompt chunk of the engine's
    ladder. A request is the engine's: a policy reads its `cursor` (the
    prompt positions run so far), `block_keys` and `slot`, and owns its
    `take_at` (as `resume` gave it) and `kept`. A subclass says `resume`;
    as the other answers stand, a prompt ends a chunk and leaves a snapshot
    at its `take_at` and nowhere else, and keeps what it took."""

    def __init__(self, cache: PrefixCache, widest: int):
        self.cache, self.bs, self.widest = cache, cache.block_size, widest

    def waits(self, keys: List[bytes], in_chunks) -> bool:
        """At admission, before the match: does the prompt of `keys` wait at
        the queue's head, given the admitted requests still `in_chunks`?"""
        return False

    def resume(self, keys: List[bytes], n_blocks: int) -> Tuple[int, int, int]:
        """At admission, with the first `n_blocks` blocks of `keys` matched
        and room found: (the position the prompt resumes at, the pool entry
        it resumes from or -1, the position at which it is to end a chunk
        and leave a snapshot or 0)."""
        raise NotImplementedError

    def cut(self, req, n: int) -> int:
        """When the next chunk of `req`'s prompt is cut: of the `n` tokens
        it could hold, how many it does (it ends early at `take_at`)."""
        if req.cursor < req.take_at:
            n = min(n, req.take_at - req.cursor)
        return n

    def here(self, req, end: int) -> bool:
        """Is `end`, where a chunk of `req`'s prompt ends, a place for a
        snapshot?"""
        return end == req.take_at

    def take(self, req, end: int) -> int:
        """When a chunk that ends at `end` is dispatched: the pool entry the
        step copies the slot's state to, or -1. A block has one snapshot."""
        if self.here(req, end) and not self.cache.has_snapshot(
                req.block_keys[end // self.bs - 1]):
            return self.cache.reserve_snapshot()
        return -1

    def attached(self, req, key: bytes) -> None:
        """`req`'s snapshot at the end of block `key` is in the cache: drop
        those of its own that it no longer keeps."""


class SnapshotsAtChunks(SnapshotPolicy):
    """An entry is small beside a prompt's blocks (Solar: 12.7 MB), so a
    prompt leaves one wherever a chunk ends on a multiple of the widest
    chunk inside its full blocks: a later prompt that shares the blocks runs
    at most one chunk of matched tokens again. Of its own a request keeps
    the two deepest (one of them lies at most one widest chunk before any
    later prompt's shared blocks end) and the deepest on a multiple of
    `SNAPSHOT_FAR` widest chunks, which a tail trimmed by eviction falls back
    to: eviction takes an idle document's blocks from the tail, so one idle
    between two questions can lose its last few hundred tokens and both deep
    snapshots with them, and the next question then reran the whole
    document (12k tokens, 2-4 times in a 51 s window of the long-document
    cell on a v5e, PERF.md section 6, PR 46). `kept`: (the two deepest's
    keys, oldest first; the far one's)."""

    SNAPSHOT_FAR = 8

    def resume(self, keys, n_blocks):
        # a match is a use of every snapshot on the run
        covered, restore = self.cache.deepest_snapshot(keys, n_blocks)
        return covered * self.bs, restore, 0

    def here(self, req, end):
        return (end % self.widest == 0
                and end // self.bs <= len(req.block_keys))

    def attached(self, req, key):
        deep, far = req.kept or ((), None)
        had = deep + (far,)
        deep = deep[-1:] + (key,)
        if req.cursor % (self.SNAPSHOT_FAR * self.widest) == 0:
            far = key
        for k in had:
            if k is not None and k not in deep + (far,):
                self.cache.drop_snapshot(k)
        req.kept = deep, far


class SnapshotsAtMatch(SnapshotPolicy):
    """An entry is the whole of a sequence's memory (Brumby: 214 MB, what 8k
    positions of keys and values would cost), so the pool holds a few and a
    prompt leaves one only where prompts were seen to part: where its match
    ended with no snapshot within a widest chunk of the end. It runs the
    matched tokens behind the deepest one again, ends a chunk exactly on the
    match's end and leaves the one snapshot there; every later prompt behind
    the same blocks resumes from it with nothing to run again (a resumed
    prompt's chunks are then cut elsewhere than a cold run's, so its logits
    are the cold run's to rounding and not bit for bit). A prompt in flight
    holds at most that one entry, and a match counts as a use of the
    snapshot it resumes from alone, so what no prompt asks for again is the
    first to be displaced."""

    def waits(self, keys, in_chunks):
        """A prompt that shares blocks with one still in chunks, which has
        not run them all yet, waits (a second of a closed loop's caller at
        most): admitted now it would match what is registered so far, run
        the rest of the shared blocks itself and leave a snapshot where
        nobody parts. Once they are run it matches the whole of what they
        share, and nothing is run a third time or snapshotted half way."""
        for r in in_chunks:
            if r.slot < 0:
                continue        # released by the abort sweep
            shared = next(
                (i for i, (a, b) in enumerate(zip(keys, r.block_keys))
                 if a != b), min(len(keys), len(r.block_keys)))
            if shared * self.bs > r.cursor:
                return True
        return False

    def resume(self, keys, n_blocks):
        covered, restore = self.cache.deepest_snapshot(
            keys, n_blocks, run=False)
        # prompts part at the match's end; with no snapshot within a widest
        # chunk of it this one pays the rerun and leaves one there
        far = (n_blocks - covered) * self.bs > self.widest
        return covered * self.bs, restore, n_blocks * self.bs if far else 0
