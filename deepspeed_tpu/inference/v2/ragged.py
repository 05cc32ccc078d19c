"""Ragged batching state: blocked KV allocator, sequence manager, batch builder.

TPU-native redesign of the reference FastGen ragged layer
(ref inference/v2/ragged/: ``BlockedAllocator`` blocked_allocator.py:11,
``BlockedKVCache`` kv_cache.py:40, ``DSSequenceDescriptor``/``DSStateManager``
ragged_manager.py:19, ``RaggedBatchWrapper`` ragged_wrapper.py:31).

Differences forced by XLA (fixed shapes, no host pointers on device):

* The device never sees Python sequence objects — each engine step receives a
  ``RaggedBatch`` of FIXED-shape int32 arrays (token ids, per-token sequence
  slot / position / KV-cache destination, block tables, sequence lengths),
  padded up to a (token bucket, max_seqs, block bucket). One executable a
  bucket serves every prefill/decode mix — the padding discipline replaces
  the reference's variable-size CUDA launches.  The arrays are views into
  ONE host buffer (``PackedIndex``), so a step costs one transfer.
* KV "pages" are rows of one flat device array per layer; the block table is
  data, not pointers, and paged attention is a gather over it.
* Block 0 is reserved as a garbage page: padded tokens scatter their KV
  there and padded table entries point at it, so no masking is needed on the
  write path.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np


class KVCacheExhausted(RuntimeError):
    """Allocation failed for want of free KV pages.

    A typed subclass so the serving layer can tell "preempt someone and
    retry" (this) apart from genuine config errors (plain RuntimeError,
    e.g. a sequence exceeding max_blocks_per_seq)."""


class BlockedAllocator:
    """Refcounted free-list page allocator (ref blocked_allocator.py:11).

    Block 0 is reserved (garbage page for padding); valid handles are
    1..num_blocks-1.  ``free()`` rejects double-frees and out-of-range
    handles — a double-freed page would be handed to two live sequences
    and silently cross-write their KV.

    Pages are **refcounted** so the serving layer's paged prefix cache
    can share read-only KV pages between sequences: ``allocate`` hands a
    page out at refcount 1, ``acquire`` adds an owner, and ``free``
    drops one owner — the page returns to the free list only when the
    LAST owner releases it.  A caller that never shares pages sees the
    pre-refcount semantics unchanged.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}        # handle -> owner count
        self.num_blocks = num_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        """Current owner count (0 = on the free list)."""
        return self._refs.get(block, 0)

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise KVCacheExhausted(f"KV cache exhausted: want {n} blocks, "
                                   f"have {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def _validate(self, blocks: Sequence[int], op: str) -> None:
        # Validate the whole batch before mutating: a partially-applied
        # free()/acquire() would leave the caller unable to retry safely.
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate handles in {op}(): {list(blocks)}")
        for b in blocks:
            if b == 0:
                raise ValueError("block 0 is reserved")
            if not (0 < b < self.num_blocks):
                raise ValueError(f"block {b} out of range "
                                 f"(1..{self.num_blocks - 1})")
            if b not in self._refs:
                raise ValueError(f"block {b} is not allocated "
                                 f"({op} of a free page"
                                 f"{' — double free?' if op == 'free' else ''})")

    def acquire(self, blocks: Sequence[int]) -> None:
        """Add one owner to each live page (prefix-cache sharing: a
        sequence adopting cached pages, or the cache pinning a donor's
        pages past the donor's flush)."""
        self._validate(blocks, "acquire")
        for b in blocks:
            self._refs[b] += 1

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one owner per handle; pages return to the free list at
        owner count zero."""
        self._validate(blocks, "free")
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)


# the token id of a sequence's sampled token while the step that samples it
# is still on the device (``InferenceEngineV2.launch``): the next step's row
# reads the token where the device left it (``model.ragged_step_sampled``:
# any negative id), and the fetch writes it over this.  A self-drafting
# engine's ``SequenceDescriptor.draft`` holds it too, for the draft that
# step makes
IN_FLIGHT = -1


@dataclass
class SequenceDescriptor:
    """Host-side state of one in-flight sequence (ref ragged_manager.py:19)."""
    uid: int
    slot: int                       # row in the device block table
    tokens: List[int] = field(default_factory=list)   # full known token ids
    num_cached: int = 0             # tokens whose KV is already in cache
    blocks: List[int] = field(default_factory=list)
    # a self-drafting engine's (engine_v2: ``self_draft``): the module's
    # guess at the token after the pending one, which the next step
    # verifies; whether the module's cache rows cover every cached
    # position (a step that ran without the module leaves a hole, and
    # the sequence drafts no more)
    draft: Optional[int] = None
    draftable: bool = True
    # a model with window layers in a pool of their own (``DSStateManager
    # .window``): that pool's pages by the same index as ``blocks`` (the
    # page of position p is entry p // block_size), 0 where the page went
    # back to the free list behind the window; ``window_freed`` leading
    # entries have
    window_blocks: List[int] = field(default_factory=list)
    window_freed: int = 0

    @property
    def uncached(self) -> int:
        return len(self.tokens) - self.num_cached

    def settle(self, burst: List[int], verified: bool) -> None:
        """Take in the ``burst`` a self-drafting step delivers: one
        token, or two where the draft it verified stood.  Where the
        caller kept the place of the token in flight (``IN_FLIGHT``, the
        last of ``tokens``) they are written over it; else the last of
        them is left to the caller's ``extend``, as after any step, and
        the one before it is appended here.  A ``verified`` run ran one
        position past the tokens known: a refused draft's is given back
        (its cache rows are rewritten by the next step before any row
        reads them), unless ``DSStateManager.keep_place`` gave it back
        already for a step launched ahead, which then ran a position on
        where the draft stood: bookkeeping alone, no device copy."""
        if self.tokens[-1] == IN_FLIGHT:
            self.tokens[-1:] = burst
        else:
            self.tokens.extend(burst[:-1])
        if verified:
            self.num_cached += len(burst) - 1 - (self.draft != IN_FLIGHT)


@jax.tree_util.register_pytree_node_class
class PackedIndex:
    """One step's seven int32 index arrays in ONE flat buffer, so that
    they reach the device as one transfer: ``token_ids``, ``token_slot``,
    ``token_pos``, ``token_dest`` (``rows`` each), ``block_tables``
    (``slots`` x ``blocks``), ``ctx_lens``, ``logits_idx`` (``slots``
    each), in that order.

    ``buf`` is a numpy array on the host, where ``build_ragged_batch``
    writes through :meth:`arrays`' views, and a device array (or a
    tracer) inside a step program, which cuts it apart by the same static
    slices.  A pytree of one leaf: the three sizes are static, so a
    jitted step is compiled once per (``rows``, ``blocks``) bucket, as it
    was per shape of the separate arrays."""

    def __init__(self, buf, rows: int, slots: int, blocks: int,
                 draft: bool = False, window: bool = False):
        self.buf, self.rows, self.slots, self.blocks = buf, rows, slots, blocks
        self.draft, self.window = draft, window

    @staticmethod
    def size(rows: int, slots: int, blocks: int, draft: bool = False,
             window: bool = False) -> int:
        return (4 * rows + slots * (blocks + 2) + (rows + slots) * draft
                + (rows + slots * blocks) * window)

    def window_arrays(self) -> Optional[Tuple]:
        """The two further arrays of a model whose window layers keep
        their rows in a pool of their own, after the seven (such a model
        drafts nothing): ``window_dest`` [T], each row's flat row of THAT
        pool, and ``window_tables`` [max_seqs+1, NB], its pages by the
        same index as ``block_tables``, 0 (the garbage page) where a page
        was freed behind the window.  None for any other model."""
        if not self.window:
            return None
        at = self.size(self.rows, self.slots, self.blocks, self.draft)
        return (self.buf[at:at + self.rows],
                self.buf[at + self.rows:].reshape(self.slots, self.blocks))

    def draft_arrays(self) -> Tuple:
        """A self-drafting step's two further arrays, after the seven:
        ``token_next`` [T], the token that follows each row where the
        host knows it (-1: the step's own argmax at that row), and
        ``verify`` [max_seqs+1], 1 where the sequence's last row is a
        draft to be verified against the argmax of the row before, and 2
        beside it where the run was launched ahead of the fetch of the
        step before (its tokens are that step's, on the device, and its
        positions one on where that step's draft stood)."""
        at = self.size(self.rows, self.slots, self.blocks)
        return (self.buf[at:at + self.rows],
                self.buf[at + self.rows:at + self.rows + self.slots])

    def arrays(self) -> Tuple:
        """The seven arrays, in the step programs' argument order:
        ``token_ids`` [T] (0-padded), ``token_slot`` [T] (``max_seqs`` =
        padding slot), ``token_pos`` [T] (absolute position),
        ``token_dest`` [T] (flat KV-cache row, 0 = garbage),
        ``block_tables`` [max_seqs+1, NB], ``ctx_lens`` [max_seqs+1]
        (tokens in cache AFTER the step), ``logits_idx`` [max_seqs+1]
        (row in T of a sequence's final token)."""
        t, s, nb = self.rows, self.slots, self.blocks
        b, tables_end = self.buf, 4 * t + s * nb
        return (b[:t], b[t:2 * t], b[2 * t:3 * t], b[3 * t:4 * t],
                b[4 * t:tables_end].reshape(s, nb),
                b[tables_end:tables_end + s],
                b[tables_end + s:tables_end + 2 * s])

    def tree_flatten(self):
        return (self.buf,), (self.rows, self.slots, self.blocks, self.draft,
                             self.window)

    @classmethod
    def tree_unflatten(cls, sizes, leaves):
        return cls(leaves[0], *sizes)


class DSStateManager:
    """Tracks live sequences, their slots and KV pages (ref ragged_manager.py).

    ``max_seqs`` bounds concurrent sequences (device block-table rows);
    ``max_blocks_per_seq`` bounds context length per sequence;
    ``min_blocks_bucket`` is the narrowest block table a step is cut to
    (the context buckets are it times a power of two).

    A sequence holds two kinds of state: its KV pages (``blocks``, from
    the allocator) and its ``slot``, which besides a block-table row is
    the index of its recurrent state where the model has an SSM mixer
    (``engine.state``; row ``max_seqs`` is the padding rows' garbage
    slot, as block 0 is the garbage page).  A slot needs no clearing when
    it is handed on: a sequence's first row is at position 0, and a run
    that starts there starts from zero state inside the step.  So
    ``flush`` frees both with no device work, and a preempted sequence
    recomputes from zeros.  ``open(num_cached > 0)`` would start a
    sequence past position 0: the engine refuses it for such a model.

    It also keeps the host side of a step's index arrays: two
    ``PackedIndex`` buffers a (token bucket, block bucket), made at the
    bucket's first steps and rewritten in turn by the later ones.

    **Two kinds of layer** (``window`` > 0: a model that mixes window and
    full attention by layer).  The full layers' rows live in the pool
    above.  The window layers' rows live in a SECOND pool of
    ``window_blocks`` pages with an allocator of its own
    (``window_allocator``) and a table of its own a sequence
    (``SequenceDescriptor.window_blocks``).  A row of a window layer sees
    the ``window`` last positions only, so a page whose every row lies
    more than ``window`` below the sequence's next position will never be
    read again: ``free_behind_window`` returns it at the start of the
    next step's build and points its entry at page 0.  A sequence so
    holds at most ``ceil((window + token budget) / block_size) + 1`` pages
    of that pool whatever its context.  What would need such a page again
    (prefix adoption, rewind, hand-off) the engine refuses, by name.
    """

    def __init__(self, max_seqs: int, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int, min_blocks_bucket: int = 1,
                 window: int = 0, window_blocks: int = 0):
        if not 1 <= min_blocks_bucket <= max_blocks_per_seq:
            raise ValueError(
                f"min_context_blocks={min_blocks_bucket}: expected 1 .. "
                f"{max_blocks_per_seq} (max_context / block_size)")
        self.max_seqs = max_seqs
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.min_blocks_bucket = min_blocks_bucket
        self.allocator = BlockedAllocator(num_blocks)
        self._seqs: Dict[int, SequenceDescriptor] = {}
        self._free_slots = list(range(max_seqs - 1, -1, -1))
        self._index: Dict[Tuple[int, int, int], PackedIndex] = {}
        self._turn = 0              # which of a bucket's two buffers is next
        # a self-drafting engine's index buffers carry two more arrays
        self.drafting = False
        self.window = int(window)
        self.window_allocator = (BlockedAllocator(window_blocks)
                                 if window else None)
        # window pages the last step's build returned, pages of either
        # pool live sequences hold: the v2.schedule span's counts
        self.pages_freed = 0

    def __contains__(self, uid: int) -> bool:
        return uid in self._seqs

    def get(self, uid: int) -> SequenceDescriptor:
        return self._seqs[uid]

    @property
    def n_active(self) -> int:
        return len(self._seqs)

    def open(self, uid: int, tokens: Sequence[int],
             cached_blocks: Sequence[int] = (),
             num_cached: int = 0) -> SequenceDescriptor:
        """Open a sequence, optionally seeded with **pre-owned** KV pages.

        ``cached_blocks`` are prefix-cache pages whose KV already holds
        the first ``num_cached`` tokens (the caller must have ``acquire``d
        one owner per page for this sequence — ownership transfers here,
        and ``flush`` releases it).  ``num_cached`` must be block-aligned
        and strictly smaller than ``len(tokens)`` so at least one token
        remains to prefill (the step that samples needs a real row).
        Adopted pages are never written: the first uncached token lands
        at position ``num_cached``, which block-aligns to a FRESH page.
        """
        if uid in self._seqs:
            raise ValueError(f"uid {uid} already active")
        if not self._free_slots:
            raise RuntimeError("no free sequence slots")
        if num_cached:
            if num_cached % self.block_size != 0:
                raise ValueError(
                    f"uid {uid}: num_cached {num_cached} not aligned to "
                    f"block_size {self.block_size} — a partially-filled "
                    "shared page would be appended into by this sequence")
            if num_cached >= len(tokens):
                raise ValueError(
                    f"uid {uid}: num_cached {num_cached} >= prompt length "
                    f"{len(tokens)}; at least one token must prefill")
            if len(cached_blocks) * self.block_size != num_cached:
                raise ValueError(
                    f"uid {uid}: {len(cached_blocks)} cached blocks cover "
                    f"{len(cached_blocks) * self.block_size} tokens, "
                    f"num_cached says {num_cached}")
        elif cached_blocks:
            raise ValueError(f"uid {uid}: cached_blocks without num_cached")
        seq = SequenceDescriptor(uid=uid, slot=self._free_slots.pop(),
                                 tokens=list(tokens),
                                 num_cached=int(num_cached),
                                 blocks=list(cached_blocks))
        self._seqs[uid] = seq
        return seq

    def extend(self, uid: int, token: int) -> None:
        self._seqs[uid].tokens.append(token)

    def keep_place(self, seq: SequenceDescriptor, verified: bool) -> None:
        """A self-drafting engine's ``extend(uid, IN_FLIGHT)`` for a
        sequence the unfetched step samples: the place of the token in
        flight, and the sequence as the NEXT step is to find it with that
        step's outcome unknown.  Its base is the outcome in which the
        draft in flight is refused (``verified``: the step runs a verify
        run for it): the position run for the draft is given back now,
        the token in flight is the pending one, the next draft is in
        flight too (the device moves the run one position on where the
        draft stands: ``model.ragged_draft_step``).  The run needs its
        pages and its block-table bucket whichever way that goes: where
        the further position would take a page that is not to be had, a
        wider table or more than the table holds, the sequence keeps its
        place and sits the launch out (nothing of it is uncached; the
        fetch settles it as after any step)."""
        at = len(seq.tokens)
        seq.tokens.append(IN_FLIGHT)
        room = self.max_blocks_per_seq * self.block_size
        if verified:
            # pending token at ``at`` and draft behind it, or one on each
            need = -(-(at + 3) // self.block_size)
            buckets = self.min_blocks_bucket, self.max_blocks_per_seq
            if at + 3 > room or _bucket(need, *buckets) != _bucket(
                    -(-(at + 2) // self.block_size), *buckets) or (
                        need > len(seq.blocks) + self.allocator.free_blocks):
                seq.draft = None        # verified; the fetch brings the next
                return
            self.ensure_capacity(seq, at + 3)
            seq.num_cached -= 1
        seq.draft = (IN_FLIGHT if seq.draftable and at + 1 < room else None)

    def ensure_capacity(self, seq: SequenceDescriptor, upto_tokens: int) -> None:
        """Allocate pages so the first ``upto_tokens`` tokens fit."""
        need = -(-upto_tokens // self.block_size)  # ceil
        if need > self.max_blocks_per_seq:
            raise RuntimeError(
                f"sequence {seq.uid} needs {need} blocks > "
                f"max_blocks_per_seq {self.max_blocks_per_seq}")
        if need > len(seq.blocks):
            seq.blocks.extend(self.allocator.allocate(need - len(seq.blocks)))
        if self.window and need > len(seq.window_blocks):
            seq.window_blocks.extend(self.window_allocator.allocate(
                need - len(seq.window_blocks)))

    def free_behind_window(self, seq: SequenceDescriptor) -> int:
        """Return the window pool's pages of ``seq`` that no later row can
        see: every row of such a page lies more than ``window`` below the
        sequence's next position (``num_cached``: the row there sees
        positions ``num_cached - window + 1`` on).  Their entries point at
        page 0 from now on.  Returns how many were returned."""
        upto = min(max(0, (seq.num_cached - self.window) // self.block_size),
                   len(seq.window_blocks))
        gone = seq.window_blocks[seq.window_freed:upto]
        if gone:
            self.window_allocator.free(gone)
            seq.window_blocks[seq.window_freed:upto] = [0] * len(gone)
            seq.window_freed = upto
        return len(gone)

    def pages_held(self) -> Tuple[int, int]:
        """Pages handed out, (full pool, window pool): what live
        sequences hold (such a model shares no page with a prefix cache)."""
        return tuple(a.num_blocks - 1 - a.free_blocks
                     for a in (self.allocator, self.window_allocator))

    def flush(self, uid: int) -> None:
        """Release a finished sequence (ref ragged_manager flush path)."""
        seq = self._seqs.pop(uid)
        if seq.blocks:
            self.allocator.free(seq.blocks)
        if seq.window_blocks[seq.window_freed:]:
            self.window_allocator.free(seq.window_blocks[seq.window_freed:])
        self._free_slots.append(seq.slot)

    def step_index(self, rows: int, blocks: int) -> PackedIndex:
        """A host index buffer of a (``rows``, ``blocks``) bucket,
        cleared to a step's padding: token ids, positions and
        destinations 0 (the garbage page), slot ``max_seqs``, empty
        tables.  Two buffers a bucket, handed out in turn whatever the
        bucket: a step may be built while the one before it is still on
        the device (its transfer may not have read the buffer yet), and
        the caller has fetched the step before THAT one (the engine runs
        one step ahead at most), so nothing still reads what is
        overwritten."""
        self._turn ^= 1
        index = self._index.get((rows, blocks, self._turn))
        if index is None:
            slots = self.max_seqs + 1
            index = self._index[rows, blocks, self._turn] = PackedIndex(
                np.empty((PackedIndex.size(rows, slots, blocks, self.drafting,
                                           bool(self.window)),), np.int32),
                rows, slots, blocks, self.drafting, bool(self.window))
        index.buf[:] = 0
        index.buf[rows:2 * rows] = self.max_seqs
        return index


@dataclass
class RaggedBatch:
    """Fixed-shape device inputs for one engine step
    (ref RaggedBatchWrapper, ragged_wrapper.py:31).

    ``index`` holds the seven host arrays (``index.arrays()``), cut to the
    step's buckets; the engine ships its one buffer to the device
    unchanged, so shapes vary only with the bucket and XLA compiles the
    step once a bucket.
    """
    index: PackedIndex
    n_tokens: int               # real (unpadded) token count
    uids_by_slot: Dict[int, int]  # slot → uid for sampled slots
    # a self-drafting step's verify runs: the sequences whose last row
    # is their draft
    verified: Tuple[SequenceDescriptor, ...] = ()
    # once shipped (``InferenceEngineV2._ship``): the step program's key,
    # and whether this was the key's first dispatch (the one that compiles)
    key: Tuple = ()
    compiled: bool = False


def _bucket(n: int, floor: int, cap: int) -> int:
    """``n`` rounded up to ``floor`` times a power of two, at most ``cap``:
    a handful of shapes, so a handful of compiled programs."""
    b = floor
    while b < n:
        b *= 2
    return min(b, cap)


def build_ragged_batch(schedule: "List[tuple]", mgr: DSStateManager,
                       token_budget: int) -> RaggedBatch:
    """Assemble device arrays from (seq, n_new_tokens) work items, in place
    in the manager's buffer for the step's buckets.

    ``schedule`` holds (SequenceDescriptor, n_tokens) pairs; the last
    scheduled token of a sequence is sampled only if it is the sequence's
    final known token (i.e. the prompt chunk completes the prompt).  An
    item one token longer than what the sequence has uncached is a
    self-drafting engine's VERIFY RUN: its last row is ``seq.draft``, at
    the position after the last known token (``num_cached`` runs past
    it; ``SequenceDescriptor.settle`` gives it back if it is refused).

    Shapes are bucketed (power-of-two token count and context width) so
    decode-heavy steps don't pay the full prefill budget: a 16-seq decode
    step runs [16, ctx] work, not [budget, max_ctx] (the shape discipline
    the reference gets from its CUDA kernels' ragged launch geometry).
    """
    bs = mgr.block_size
    total = sum(n_new for _, n_new in schedule)
    if total > token_budget:
        raise RuntimeError(f"schedule ({total} tokens) exceeds budget "
                           f"{token_budget}")

    # Reserve all pages up front so an allocator failure leaves every
    # sequence untouched (no num_cached advance without a KV write).  A
    # window page nobody can see any more goes back first (gone whether
    # or not the step then runs): the step after its last row left the
    # window.
    if mgr.window:
        mgr.pages_freed = sum(mgr.free_behind_window(seq)
                              for seq, _ in schedule)
    for seq, n_new in schedule:
        mgr.ensure_capacity(seq, seq.num_cached + n_new)

    ctx_max = max((seq.num_cached + n_new for seq, n_new in schedule),
                  default=0)
    nb = _bucket(-(-ctx_max // bs), mgr.min_blocks_bucket,
                 mgr.max_blocks_per_seq)
    index = mgr.step_index(_bucket(total, 16, token_budget), nb)
    (token_ids, token_slot, token_pos, token_dest, block_tables, ctx_lens,
     logits_idx) = index.arrays()
    uids_by_slot: Dict[int, int] = {}
    verified = []
    if index.draft:
        token_next, verify = index.draft_arrays()
        token_next[:] = -1
    if index.window:
        window_dest, window_tables = index.window_arrays()

    slots, first_pos, counts = [], [], []
    cursor = 0
    for seq, n_new in schedule:
        start = seq.num_cached
        end = start + n_new
        sl = seq.slot
        known = seq.tokens[start:end]
        token_ids[cursor:cursor + len(known)] = known
        if index.draft:
            follows = seq.tokens[start + 1:end + 1]
            token_next[cursor:cursor + len(follows)] = follows
            if end > len(seq.tokens):
                token_ids[cursor + n_new - 1] = seq.draft
                verify[sl] = 1
                verified.append(seq)
            if known[0] == IN_FLIGHT:
                verify[sl] |= 2         # launched ahead of that token
        # a sequence may hold pages past this step's context (a fused
        # decode's horizon, a rewound draft): the bucket cuts them off
        held = seq.blocks[:nb]
        block_tables[sl, :len(held)] = held
        if index.window:
            held = seq.window_blocks[:nb]
            window_tables[sl, :len(held)] = held
        ctx_lens[sl] = end
        logits_idx[sl] = cursor + n_new - 1
        if end >= len(seq.tokens):
            uids_by_slot[sl] = seq.uid
        slots.append(sl)
        first_pos.append(start - cursor)
        counts.append(n_new)
        cursor += n_new
        seq.num_cached = end

    if cursor:
        # every sequence's rows at once: slot, position, KV destination
        rows_slot = np.repeat(slots, counts)
        rows_pos = np.arange(cursor) + np.repeat(first_pos, counts)
        token_slot[:cursor] = rows_slot
        token_pos[:cursor] = rows_pos
        token_dest[:cursor] = (block_tables[rows_slot, rows_pos // bs] * bs
                               + rows_pos % bs)
        if index.window:
            window_dest[:cursor] = (
                window_tables[rows_slot, rows_pos // bs] * bs + rows_pos % bs)

    return RaggedBatch(index=index, n_tokens=cursor,
                       uids_by_slot=uids_by_slot, verified=tuple(verified))
