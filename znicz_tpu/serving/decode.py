"""Autoregressive decode serving: KV-cache + prefill/decode AOT split
+ continuous token batching.

The round-8 engine scores fixed-shape one-shot requests; this module
is the *generation* half of the serving story (ROADMAP item 2 — the
heaviest-traffic scenario a millions-of-users deployment runs).  It
converts any exported causal LM bundle (``manifest["kind"] == "lm"``:
token-first chain of embedding / pos_encoding / causal attention /
LSTM, a position-independent head) into a continuous-batching token
server, built from three pieces:

1. **KV cache** (:class:`KVCache`) — per-replica device buffers
   preallocated at :meth:`DecodeModel.warmup`: one (S+1, maxT, H, Dh)
   K and V page array per attention layer and one (S+1, H) carry pair
   per LSTM layer, where S is ``max_slots`` sequence slots (+1 scratch
   row that absorbs padded decode lanes).  Pages are *functionally*
   updated by the decode program and donated back, so on
   donation-capable platforms a warmed decode loop mutates HBM in
   place and allocates nothing per token.

2. **Prefill / decode AOT split** (:class:`DecodeModel`) — two
   separate program families, both real ``jit().lower().compile()``
   AOT like the round-8 ladder:

   - *prefill*, bucketed on **prompt length** via the same
     ``serving/buckets.py`` ladder math applied to the T axis
     (``prompt_align·2^k``): runs the full causal forward over the
     padded prompt, writes every position's K/V (or the masked LSTM
     carry) into the request's slot, and returns the last real
     position's logits — the first token;
   - *decode*, bucketed on **live-batch size**: one token for every
     in-flight sequence per dispatch — embedding gather → positional
     offset add → per-layer cached step
     (``MultiHeadAttention.xla_decode_step`` /
     ``LSTM.xla_decode_step``) → head logits — with ragged per-lane
     position indices, so sequences at different depths share one
     program.

   Warmed, the token loop performs ZERO XLA compiles
   (``znicz_xla_compiles_total{site=serving-prefill|serving-decode}``
   stays flat — pinned by tests/test_retrace_guard.py).

3. **Continuous token batching** (:class:`DecodeEngine`) — the Orca
   iteration-level insight applied to generation: the scheduler
   admits queued prompts into the *in-flight* decode batch between
   token steps (``admission="continuous"``; ``"static"`` keeps the
   run-to-completion behavior as the measured A/B arm in
   serve_bench), and evicts slots the moment a sequence finishes
   (EOS, token budget, or the bucketed max-T page boundary) so a
   long straggler never holds the batch hostage.

Round 15 rebuilds the decode *data plane* around a **paged KV-cache**
(``engine.paged_kv``, default on; the flat per-slot layout above stays
as the measured A/B arm), the vLLM PagedAttention idea (Kwon et al.
2023) expressed in XLA terms:

4. **Paged KV-cache** (:class:`PagedKVCache`) — K/V live in a shared
   page *pool* of fixed ``kv_page_tokens``-token blocks addressed
   through a per-sequence block table, so a sequence holds exactly the
   pages its length needs instead of reserving ``max_t`` rows, live
   capacity is bounded by **tokens** (``pool_tokens``), not slots, and
   attention programs are bucketed on the **block count** — a short
   sequence's decode step reads only the pages it occupies, not the
   full ``max_t`` reservation the flat layout gathers every token.

5. **Prefix sharing** (:class:`PrefixCache`) — prompts are hashed
   block-by-block into a radix trie at admission; requests with a
   common prompt prefix (the dominant system-prompt traffic shape)
   *share* the prefix's full pages by reference (refcounted), a
   partially-matched boundary block is **copied on write** before the
   divergent tail lands, and the tail alone pays prefill.  Pages are
   pinned by the trie, evicted LRU under pool pressure, and the whole
   cache invalidates on a weight swap (cached K/V are a function of
   the weights).

6. **Speculative decoding** — a small *drafter* bundle (a population
   member trained by the round-14 engine and published through the
   round-13 pipeline) proposes ``spec_draft_k`` greedy tokens per
   step; the big model verifies the whole window in ONE batched
   forward (:meth:`DecodeModel.run_verify`) and accepts per
   Leviathan's rule — greedy arms stay token-identical to
   non-speculative decoding by construction, temperature arms use the
   exact rejection-sampling correction.

Telemetry splits decode latency into its two canonical halves —
``znicz_serving_ttft_seconds`` (queue + prefill + first sample) and
``znicz_serving_token_seconds`` (steady-state cadence) — because the
two move independently: admission policy moves TTFT, cache residency
moves per-token.  TTFT clocks stamp from **admission-eligible** time:
a swap drain's admission pause (accumulated in
``znicz_swap_pause_seconds_total``) is excluded, so soak histograms
measure serving, not the drain policy.  Paged state rides
``znicz_kv_pages_{total,used}``, ``znicz_prefix_cache_total{hit|miss}``
and ``znicz_spec_tokens_total{accepted|rejected}``.  Resilience
(round 11 carried forward): ``deadline_ms`` applies to **TTFT** — a
prompt still queued past its deadline is evicted before prefill and
never occupies a slot — and the circuit breaker sheds *new prompts*
with fast :class:`Overloaded` replies while in-flight decodes drain to
completion; **page-pool exhaustion** trips the same breaker, so a
token-capacity overload sheds exactly like a failure-rate overload
while draining lanes release their pages.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import recorder as _recorder
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.resilience import faults as _faults
from znicz_tpu.serving.batcher import (_CLOSED, _HALF_OPEN, _OPEN,
                                       _STATE_CODE, DeadlineExceeded,
                                       Overloaded, PriorityQueue,
                                       QueueFull)
from znicz_tpu.serving.buckets import bucket_for, ladder, next_pow2
from znicz_tpu.utils.logger import Logger

__all__ = ["DecodeModel", "DecodeEngine", "KVCache", "PagedKVCache",
           "PrefixCache", "PoolExhausted"]


class PoolExhausted(RuntimeError):
    """The paged KV pool has no free page for a required block.  The
    engine translates this into breaker load-shedding (queued prompts)
    or a graceful force-finish (an in-flight lane crossing a block
    boundary) — it never kills neighbors."""

#: distinguishes same-named engines in the registry's labels
_DECODE_SEQ = itertools.count()

#: layer kinds the decode planner knows how to step incrementally
_SEQ_KINDS = ("embedding", "pos_encoding", "attention", "lstm")
_HEAD_KINDS = ("all2all", "all2all_tanh", "all2all_relu",
               "all2all_str", "all2all_sigmoid", "softmax")


class _Op:
    """One planned chain step: the unit (config carrier), the export
    KEYS of its weight leaves, and — for stateful layers — its cache
    array indices.  Weights themselves are NOT baked into the op: the
    traced programs take them as a call-time operand pytree, which is
    what lets :meth:`DecodeModel.swap_weights` replace them without a
    single recompile."""

    __slots__ = ("kind", "unit", "wkeys", "aux", "table")

    def __init__(self, kind, unit, wkeys=(), aux=None, table=None):
        self.kind = kind
        self.unit = unit
        self.wkeys = tuple(wkeys)  # export keys (layer<i>_<attr>)
        self.aux = aux or {}       # cache indices etc.
        self.table = table         # pos_encoding: baked (maxT, D) table


def _dq_leaves(w):
    """Dequantize one op's weight-leaf tuple inside a traced body:
    ``(q int8, scale f32)`` pairs (round-21 int8 bundles) expand to
    f32 on load — exact arithmetic, so the program matches the
    host-side dequantized oracle bitwise; plain leaves pass through."""
    import jax.numpy as jnp
    return tuple(
        leaf[0].astype(jnp.float32) * leaf[1]
        if isinstance(leaf, tuple) else leaf
        for leaf in w)


class KVCache:
    """The preallocated decode state for one replica: the page/carry
    arrays (functionally threaded through every program call) plus the
    host-side slot free list.

    Slot reuse needs no zeroing: prefill overwrites ``[0, t_bucket)``
    of a reused slot, and every attention step masks positions
    ``> pos``, so a prior tenant's rows beyond the new sequence's live
    prefix are unreachable by construction (pinned by
    tests/test_decode.py's eviction-reuse case).
    """

    def __init__(self, specs: list[tuple[str, tuple]], max_slots: int,
                 dtype=np.float32) -> None:
        import jax.numpy as jnp
        self.max_slots = int(max_slots)
        #: scratch row absorbing padded decode lanes (their scattered
        #: writes must land somewhere that is never a live sequence)
        self.trash_slot = self.max_slots
        self.specs = list(specs)
        self.arrays: tuple = tuple(
            jnp.zeros((self.max_slots + 1,) + tuple(shape), dtype)
            for _name, shape in specs)
        self._free = list(range(self.max_slots))

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def acquire(self) -> int:
        return self._free.pop()

    def release(self, slot: int) -> None:
        self._free.append(slot)

    def nbytes(self) -> int:
        return int(sum(a.size * a.dtype.itemsize for a in self.arrays))


class PagedKVCache:
    """Paged decode state: per-attention-layer K/V page POOLS plus the
    host-side block tables, refcounts and free lists.

    Geometry: each pool array is ``(pool_pages + 1, page_tokens, H,
    Dh)`` — the last row is the **trash page** where padded lanes and
    padded window positions scatter their garbage writes.  A sequence
    in slot ``s`` owns ``tables[s]``: one page id per
    ``page_tokens``-token block of its positions, ``trash_page`` where
    no block is allocated.  LSTM carries (``kind="slot"`` specs) stay
    slot-indexed exactly like the flat cache — they are O(H) per
    sequence, not O(T), so paging buys them nothing.

    Sharing: a page's ``ref`` counts every holder — each sequence
    whose table maps a block to it, plus the prefix trie's pin.  Pages
    free when the count hits zero.  Shared pages (``ref > 1`` or
    trie-pinned) are never written: writes always land at a
    sequence's *append* position, past every shared full block, and
    the boundary block of a partial prefix match is copied
    (:meth:`DecodeModel.copy_page`) before the divergent tail lands —
    the copy-on-write contract tests/test_paged_decode.py pins.

    All mutating calls happen on the scheduler thread (same
    single-writer discipline as the flat cache); the gauges read
    integers racily, which is fine for telemetry.
    """

    def __init__(self, specs: list[tuple],
                 max_slots: int, page_tokens: int, max_blocks: int,
                 pool_pages: int, dtype=np.float32) -> None:
        # specs: (name, kind, shape) or (name, kind, shape, dtype) —
        # the 4-tuple form (round 21) gives one pool its own dtype, so
        # int8 K/V pages and their f32 per-(token, head) scale pools
        # coexist in the same cache and share page ids / COW / trash
        # semantics
        import jax.numpy as jnp
        self.max_slots = int(max_slots)
        self.trash_slot = self.max_slots
        self.page_tokens = int(page_tokens)
        self.max_blocks = int(max_blocks)
        self.pool_pages = int(pool_pages)
        self.trash_page = self.pool_pages
        self.specs = list(specs)
        arrays = []
        for spec in specs:
            kind, shape = spec[1], spec[2]
            sdtype = spec[3] if len(spec) > 3 else dtype
            if kind == "page":
                arrays.append(jnp.zeros(
                    (self.pool_pages + 1, self.page_tokens)
                    + tuple(shape), sdtype))
            else:  # slot-indexed (LSTM carries)
                arrays.append(jnp.zeros(
                    (self.max_slots + 1,) + tuple(shape), sdtype))
        self.arrays: tuple = tuple(arrays)
        #: indices (into ``arrays``) of the page pools — the leaves
        #: :meth:`DecodeModel.copy_page` must copy on a COW
        self.pool_indices = tuple(i for i, s in enumerate(specs)
                                  if s[1] == "page")
        #: slot-indexed leaves (LSTM carries) — the rows a
        #: prefill→decode handoff must carry alongside the pages
        self.slot_indices = tuple(i for i, s in enumerate(specs)
                                  if s[1] != "page")
        self.tables = np.full((self.max_slots + 1, self.max_blocks),
                              self.trash_page, np.int32)
        self.ref = np.zeros(self.pool_pages, np.int64)
        self._free_pages = list(range(self.pool_pages - 1, -1, -1))
        self._free = list(range(self.max_slots))

    # -- slots (same protocol as the flat cache) -----------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    def acquire(self) -> int:
        return self._free.pop()

    def release(self, slot: int) -> None:
        self._free.append(slot)

    # -- pages ---------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    def pages_used(self) -> int:
        return self.pool_pages - len(self._free_pages)

    def alloc_page(self) -> int:
        if not self._free_pages:
            raise PoolExhausted(
                f"KV page pool exhausted ({self.pool_pages} pages "
                f"x {self.page_tokens} tokens all held)")
        pid = self._free_pages.pop()
        self.ref[pid] = 1
        return pid

    def free_page(self, pid: int) -> None:
        self._free_pages.append(pid)

    def ref_dec(self, pid: int) -> None:
        self.ref[pid] -= 1
        if self.ref[pid] == 0:
            self.free_page(pid)

    def share_block(self, slot: int, block: int, pid: int) -> None:
        """Map ``block`` of ``slot`` to an EXISTING page by reference
        (prefix sharing).  The page must be live (ref > 0): a
        zero-ref page sits on the free list, and re-refing it here
        without unlinking it would let ``alloc_page`` hand the same
        page to another sequence — callers must pin matched pages
        before anything (eviction) can drop their last holder."""
        assert int(self.ref[pid]) > 0, \
            f"share_block: page {pid} is on the free list"
        self.tables[slot, block] = pid
        self.ref[pid] += 1

    def new_block(self, slot: int, block: int) -> int:
        """Allocate a fresh private page for ``block`` of ``slot``."""
        pid = self.alloc_page()
        self.tables[slot, block] = pid
        return pid

    def blocks_of(self, slot: int) -> list[int]:
        return [int(p) for p in self.tables[slot]
                if p != self.trash_page]

    def writable(self, slot: int, block: int) -> bool:
        """May ``slot`` write into ``block``'s page?  True iff the
        page is private (ref exactly 1 — this sequence, no sharers,
        no trie pin)."""
        pid = int(self.tables[slot, block])
        return pid != self.trash_page and int(self.ref[pid]) == 1

    def release_slot_pages(self, slot: int) -> None:
        """Drop every page reference ``slot`` holds (pages free when
        their last holder lets go) and reset its table row."""
        for block in range(self.max_blocks):
            pid = int(self.tables[slot, block])
            if pid != self.trash_page:
                self.ref_dec(pid)
        self.tables[slot] = self.trash_page

    def table_operand(self, slot: int, nb: int) -> np.ndarray:
        """The (nb+1,) int32 table row a program dispatch reads: the
        first ``nb`` block entries plus the trash page as the padded
        write sink."""
        out = np.empty(nb + 1, np.int32)
        out[:nb] = self.tables[slot, :nb]
        out[nb] = self.trash_page
        return out

    def trash_operand(self, nb: int) -> np.ndarray:
        return np.full(nb + 1, self.trash_page, np.int32)

    def nbytes(self) -> int:
        return int(sum(a.size * a.dtype.itemsize for a in self.arrays))


class _TrieNode:
    __slots__ = ("key", "page", "host", "children", "parent",
                 "last_use")

    def __init__(self, key, page, parent) -> None:
        self.key = key          # the block's token ids (bytes key)
        self.page = page        # HBM page id, or None while spilled
        self.host = None        # host-tier frame id, or None (round
        #                         22: a block lives in EXACTLY one
        #                         tier — page XOR host)
        self.children: dict = {}
        self.parent = parent
        self.last_use = 0


class PrefixCache:
    """Radix trie over block-aligned prompt prefixes.

    Keys are the raw token ids of one full ``page_tokens`` block
    (hashed by dict machinery); a path root→node spells a block-aligned
    prompt prefix and carries one page id per block.  Matching at
    admission walks full blocks, then refines into the boundary block:
    the longest token-level common prefix with any child selects a
    copy-on-write donor, so divergence mid-block still reuses the
    shared positions' K/V.  Matches are capped at ``len(prompt) - 1``
    tokens — the last prompt position is always recomputed, because
    the first sampled token needs its logits.

    Every node pins its page with one refcount; :meth:`evict` walks
    leaves in LRU order under pool pressure, and :meth:`clear` drops
    everything (a weight swap invalidates all cached K/V)."""

    def __init__(self, page_tokens: int) -> None:
        self.page_tokens = int(page_tokens)
        self.root = _TrieNode(None, None, None)
        self.nodes = 0
        self._clock = 0

    def _tick(self, node: _TrieNode) -> None:
        self._clock += 1
        node.last_use = self._clock

    @staticmethod
    def _key(tokens: np.ndarray) -> bytes:
        return np.ascontiguousarray(tokens, np.int32).tobytes()

    def match_nodes(self, tokens: np.ndarray
                    ) -> tuple[list, int, tuple | None]:
        """Longest cached prefix of ``tokens`` (capped at ``n-1``):
        returns ``(full_block_nodes, matched_tokens, cow)`` where
        ``cow`` is ``(donor_node, extra_tokens)`` for a partial
        boundary-block match (``matched_tokens`` already includes
        ``extra_tokens``) or ``None``.  Nodes — not bare page ids —
        because a matched block may be SPILLED to the host tier
        (``node.page is None``): the caller restores it before
        sharing (round 22)."""
        n = int(tokens.shape[0])
        ptok = self.page_tokens
        node = self.root
        nodes: list[_TrieNode] = []
        matched = 0
        while matched + ptok <= n - 1:
            child = node.children.get(
                self._key(tokens[matched:matched + ptok]))
            if child is None:
                break
            node = child
            self._tick(node)
            nodes.append(node)
            matched += ptok
        # boundary refinement: the longest token-level common prefix
        # with any child of the last matched node
        tail = tokens[matched:min(n - 1, matched + ptok)]
        best, best_common = None, 0
        if len(tail) > 0:
            for child in node.children.values():
                key = np.frombuffer(child.key, np.int32)
                m = int(np.argmin(np.equal(
                    key[:len(tail)], tail).astype(np.int8))) \
                    if not np.array_equal(key[:len(tail)], tail) \
                    else len(tail)
                if m > best_common:
                    best, best_common = child, m
        if best is not None and best_common > 0:
            self._tick(best)
            return nodes, matched + best_common, (best, best_common)
        return nodes, matched, None

    def match(self, tokens: np.ndarray
              ) -> tuple[list[int], int, tuple | None]:
        """Page-id view of :meth:`match_nodes` for HBM-only callers
        (no spill tier: every matched node is resident)."""
        nodes, matched, cow = self.match_nodes(tokens)
        return ([node.page for node in nodes], matched,
                None if cow is None else (cow[0].page, cow[1]))

    def insert(self, tokens: np.ndarray, table_row: np.ndarray,
               cache: PagedKVCache) -> int:
        """Register every FULL prompt block of ``tokens`` (pages from
        the sequence's ``table_row``); new nodes pin their page with
        one extra refcount.  Returns nodes added."""
        n = int(tokens.shape[0])
        ptok = self.page_tokens
        node = self.root
        added = 0
        for block in range(n // ptok):
            key = self._key(tokens[block * ptok:(block + 1) * ptok])
            child = node.children.get(key)
            if child is None:
                pid = int(table_row[block])
                if pid == cache.trash_page:
                    break  # not materialized (shouldn't happen)
                child = _TrieNode(key, pid, node)
                node.children[key] = child
                cache.ref[pid] += 1  # the trie's pin
                self.nodes += 1
                added += 1
            node = child
            self._tick(node)
        return added

    def _leaves(self) -> list[_TrieNode]:
        out, stack = [], [self.root]
        while stack:
            node = stack.pop()
            if node is not self.root and not node.children:
                out.append(node)
            stack.extend(node.children.values())
        return out

    def spill_candidate(self, cache: PagedKVCache):
        """The LRU HBM-resident node held by NOTHING but the trie pin
        (``ref == 1`` — no live sequence maps its page), or None.
        Safe to demote: the node STAYS in the trie, so the block is
        still matchable from the host tier — unlike eviction, a spill
        loses residency, not the hit (round 22).  Interior nodes
        qualify too: demotion never orphans children."""
        best = None
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.page is not None \
                    and int(cache.ref[node.page]) == 1 \
                    and (best is None or node.last_use < best.last_use):
                best = node
        return best

    def evict(self, cache: PagedKVCache, pages_needed: int) -> int:
        """Unpin LRU leaf blocks until ``pages_needed`` pages are
        free (or no HBM-resident leaf remains).  An unpinned page
        frees immediately when no live sequence still references it.
        Host-resident leaves are skipped — they hold no HBM page, so
        dropping them frees nothing here.  Returns nodes evicted."""
        evicted = 0
        while cache.free_pages < pages_needed:
            leaves = [lf for lf in self._leaves()
                      if lf.page is not None]
            if not leaves:
                break
            victim = min(leaves, key=lambda n: n.last_use)
            victim.parent.children.pop(victim.key)
            cache.ref_dec(victim.page)
            self.nodes -= 1
            evicted += 1
        return evicted

    def spilled_nodes(self) -> int:
        """Host-tier residents (telemetry + accounting tests)."""
        count, stack = 0, list(self.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.host is not None:
                count += 1
        return count

    def clear(self, cache: PagedKVCache, tier=None) -> int:
        """Drop the whole trie (weight swap: cached K/V are functions
        of the OLD weights) — BOTH tiers: spilled frames free too.
        Returns nodes dropped."""
        dropped = 0
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.page is not None:
                cache.ref_dec(node.page)
            elif tier is not None and node.host is not None:
                tier.free(node.host)
            dropped += 1
        self.root.children.clear()
        self.nodes = 0
        return dropped


class DecodeModel(Logger):
    """Prefill/decode program families + KV cache over an exported LM.

    ``model`` is an :class:`~znicz_tpu.export.ExportedModel` (or a
    bundle path); its manifest must describe a causal LM
    (``kind == "lm"`` — legacy pre-round-12 bundles re-derive the
    kind from their layer table, so any previously exported LM
    decodes without re-export).

    Geometry knobs:

    - ``max_slots`` — concurrent sequences (KV pages preallocated);
    - ``max_t`` — cache page length, rounded up to a power of two
      (a sequence reaching it is force-finished);
    - ``max_prompt`` / ``prompt_align`` — the prompt-length ladder:
      prefill programs exist for ``prompt_align·2^k ≤ max_prompt``.

    Paged knobs (round 15; every default reads the manifest's
    ``decode`` section first, then ``root.common.engine``):

    - ``paged`` — page the KV-cache (``engine.paged_kv``, default on;
      ``False`` = the flat per-slot A/B arm, greedy token-identical);
    - ``page_tokens`` — tokens per page (``engine.kv_page_tokens``,
      default 16; power of two dividing ``max_t``);
    - ``pool_tokens`` — the pool's token capacity
      (default ``max_slots · max_t`` — the flat cache's exact byte
      budget, so the paged arm never wins by spending more memory);
    - ``spec_k`` — compile the speculative-verification family for
      ``spec_k``-token draft windows (0 = off).

    Quantization knobs (round 21):

    - ``kv_quant`` — int8 K/V pages with one f32 scale per
      (token, head) row (``engine.kv_quant``, default off; paged
      cache only — the flat A/B arm stays the bitwise greedy-identity
      baseline).  At a fixed pool byte budget the pool holds roughly
      ``2 / (1 + 4/Dh)`` × the bf16 arm's tokens;
    - ``kv_dtype`` — the page pools' dtype when NOT quantizing
      (default f32; ``"bfloat16"`` is the byte-budget baseline arm the
      quant benchmark compares lanes against).

    int8-quantized *weight* bundles need no knob: the manifest's
    ``quant`` record makes :meth:`_gather_weights` keep them int8 in
    HBM as ``(q, scale)`` operand pairs that every traced body
    dequantizes on load.
    """

    def __init__(self, model, *, max_slots: int = 4,
                 max_t: int = 64, max_prompt: int | None = None,
                 prompt_align: int = 8, device=None,
                 paged: bool | None = None,
                 page_tokens: int | None = None,
                 pool_tokens: int | None = None,
                 spec_k: int = 0,
                 kv_quant: bool | None = None,
                 kv_dtype=None) -> None:
        super().__init__()
        from znicz_tpu.export import ExportedModel
        from znicz_tpu.utils.config import root
        if isinstance(model, (str, bytes)) or hasattr(model,
                                                      "__fspath__"):
            model = ExportedModel.load(model, device=device)
        self.model = model
        decode_meta = dict(model.manifest.get("decode", {}))
        if paged is None:
            paged = bool(root.common.engine.get("paged_kv", True))
        self.paged = bool(paged)
        if page_tokens is None:
            page_tokens = int(decode_meta.get(
                "kv_page_tokens",
                root.common.engine.get("kv_page_tokens", 16)))
        if kv_quant is None:
            kv_quant = bool(decode_meta.get(
                "kv_quant", root.common.engine.get("kv_quant", False)))
        self.kv_quant = bool(kv_quant) and self.paged
        self.kv_dtype = np.dtype(kv_dtype if kv_dtype is not None
                                 else np.float32)
        self.spec_k = int(spec_k)
        if model.kind != "lm":
            raise ValueError(
                f"bundle '{model.manifest.get('workflow', '?')}' is a "
                f"'{model.kind}' — decode needs an LM (token-first "
                f"causal chain); re-export a generation model or use "
                f"ServingEngine for one-shot scoring")
        self.seq_meta = dict(model.sequence)
        self.vocab = int(self.seq_meta["vocab"])
        self.dim = int(self.seq_meta["dim"])
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.max_t = next_pow2(int(max_t))
        self.prompt_align = int(prompt_align)
        self.max_prompt = int(max_prompt if max_prompt is not None
                              else min(self.max_t // 2,
                                       bucket_for(
                                           self.seq_meta["train_t"],
                                           self.prompt_align)))
        if self.max_prompt >= self.max_t:
            raise ValueError(
                f"max_prompt ({self.max_prompt}) must leave room to "
                f"generate below max_t ({self.max_t})")
        if bucket_for(self.max_prompt, self.prompt_align) > self.max_t:
            raise ValueError(
                f"prompt ladder top "
                f"{bucket_for(self.max_prompt, self.prompt_align)} "
                f"(max_prompt {self.max_prompt} rounded up to the "
                f"prompt_align·2^k ladder) exceeds the max_t "
                f"{self.max_t} cache page — raise max_t or lower "
                f"max_prompt")
        self.device = model.device
        self._plan, cache_specs = self._build_plan()
        self.has_lstm = any(kind == "lstm" for _n, kind, _s
                            in cache_specs)
        if self.paged:
            self.page_tokens = next_pow2(
                min(int(page_tokens), self.max_t))
            self.max_blocks = self.max_t // self.page_tokens
            if pool_tokens is None:
                pool_tokens = int(decode_meta.get(
                    "pool_tokens", self.max_slots * self.max_t))
            pool_pages = max(1, int(pool_tokens) // self.page_tokens)
            self.pool_tokens = pool_pages * self.page_tokens
            specs = []
            for name, kind, shape in cache_specs:
                if kind == "attention":
                    specs.append((name, "page",
                                  (shape[-2], shape[-1]),
                                  np.int8 if self.kv_quant
                                  else self.kv_dtype))
                else:
                    specs.append((name, "slot", shape, np.float32))
            if self.kv_quant:
                # f32 per-(token, head) scale pools, appended AFTER
                # every data spec so the plan's aux indices stay
                # valid; kind "page" → same page ids, COW copies and
                # trash sink as the int8 rows they scale
                for op in self._plan:
                    if op.kind != "attention":
                        continue
                    for side in ("k", "v"):
                        name, _k, shape = cache_specs[op.aux[side]]
                        op.aux[f"{side}_scale"] = len(specs)
                        specs.append((f"{name}_scale", "page",
                                      (shape[-2],), np.float32))
            self.cache = PagedKVCache(
                specs, self.max_slots, self.page_tokens,
                self.max_blocks, pool_pages)
        else:
            self.page_tokens = self.max_t
            self.max_blocks = 1
            self.pool_tokens = self.max_slots * self.max_t
            self.cache = KVCache(
                [(name, shape) for name, _kind, shape in cache_specs],
                self.max_slots)
        if self.spec_k and (not self.paged or self.has_lstm):
            raise ValueError(
                "speculative decoding needs the paged cache and an "
                "attention-only sequence phase (LSTM carries cannot "
                "roll back a rejected draft)")
        self._prefill_programs: dict[int, "callable"] = {}
        self._decode_programs: dict[int, "callable"] = {}
        #: paged families, keyed (t_bucket, nb) / (b_bucket, nb)
        self._paged_prefill_programs: dict[tuple, "callable"] = {}
        self._paged_decode_programs: dict[tuple, "callable"] = {}
        self._verify_programs: dict[tuple, "callable"] = {}
        self._copy_program = None
        #: round 22 page-I/O family: scatter one staged page (spill
        #: restore / pool handoff) or one carry row set into a cache
        self._page_in_program = None
        self._carry_in_program = None
        self.compile_count = 0
        #: programs DESERIALIZED from the persisted AOT cache (round
        #: 23) — residency without a trace; never counted as compiles
        self.load_count = 0
        self.donating = model._donate_choice()
        # the published weight pytree: one immutable tuple-of-tuples
        # (one entry per plan op, None for absent leaves) every
        # prefill/decode dispatch reads exactly once — hot-swap
        # replaces the tuple between dispatches
        self._weights = self._gather_weights(self.model._params)
        self.weights_version = 0

    # ------------------------------------------------------------------
    # chain planning
    # ------------------------------------------------------------------
    def _gather_weights(self, params: dict) -> tuple:
        """Build the weight operand pytree from a bundle's param dict
        (absent leaves — e.g. a bias the export never carried — stay
        ``None``, a legal empty pytree node).

        Keys the bundle's ``quant`` record covers (round 21) become
        ``(q int8, scale f32)`` pairs — int8 stays resident in HBM
        (halved weight bytes per replica) and every traced body
        dequantizes on load via :func:`_dq_leaves`."""
        import jax.numpy as jnp
        from znicz_tpu.serving import quantize as _quantize
        qkeys = getattr(self.model, "_qkeys", frozenset())
        out = []
        for op in self._plan:
            leaves = []
            for key in op.wkeys:
                if key not in params:
                    leaves.append(None)
                elif key in qkeys:
                    leaves.append((
                        jnp.asarray(params[key], jnp.int8),
                        jnp.asarray(params[_quantize.scale_key(key)],
                                    jnp.float32)))
                else:
                    leaves.append(jnp.asarray(params[key],
                                              jnp.float32))
            out.append(tuple(leaves))
        return tuple(out)

    def _build_plan(self) -> tuple[list[_Op], list]:
        """Walk the manifest layers into decode ops + cache specs.

        Chain grammar: a *sequence* phase (embedding first, then
        pos_encoding / causal attention / LSTM), a bridge to
        position-independence (``last_token``, or a final
        ``return_sequence=False`` LSTM), then a *head* phase of
        per-sample FC layers ending in the vocabulary softmax."""
        units = self.model.forwards
        layers = self.model.manifest["layers"]
        from znicz_tpu.export import refuse_unserved
        refuse_unserved(units, "DecodeModel")
        plan: list[_Op] = []
        cache_specs: list[tuple[str, tuple]] = []
        phase = "seq"
        d = self.dim
        if not layers or layers[0]["type"] != "embedding":
            raise ValueError("decode chain must start with an "
                             "embedding layer (token-first)")
        for i, (spec, unit) in enumerate(zip(layers, units)):
            kind = spec["type"]
            if phase == "head" and kind not in _HEAD_KINDS:
                raise ValueError(
                    f"layer {i} ({kind}) after the sequence→sample "
                    f"bridge — only head layers {_HEAD_KINDS} may "
                    f"follow")
            if kind == "embedding":
                plan.append(_Op(kind, unit, (f"layer{i}_weights",)))
            elif kind == "pos_encoding":
                import jax.numpy as jnp
                # 2×max_t rows: paged tail-prefill windows slice at
                # an arbitrary start and must never hit the
                # dynamic_slice clamp (rows ≥ max_t feed only padded
                # positions, whose outputs are discarded)
                table = jnp.asarray(
                    unit.table_to(2 * self.max_t, d), jnp.float32)
                plan.append(_Op(kind, unit, table=table))
            elif kind == "attention":
                if not spec.get("config", {}).get("causal"):
                    raise ValueError(
                        f"layer {i}: attention must be causal=True to "
                        f"decode (a bidirectional layer has no valid "
                        f"incremental step)")
                heads = unit.n_heads
                dh = d // heads
                k_idx = len(cache_specs)
                cache_specs.append(
                    (f"l{i}.k", "attention", (self.max_t, heads, dh)))
                cache_specs.append(
                    (f"l{i}.v", "attention", (self.max_t, heads, dh)))
                plan.append(_Op(kind, unit, (
                    f"layer{i}_weights", f"layer{i}_bias",
                    f"layer{i}_weights_out", f"layer{i}_bias_out"),
                    aux={"k": k_idx, "v": k_idx + 1}))
            elif kind == "lstm":
                h_idx = len(cache_specs)
                cache_specs.append((f"l{i}.h", "lstm", (unit.units,)))
                cache_specs.append((f"l{i}.c", "lstm", (unit.units,)))
                plan.append(_Op(kind, unit, (
                    f"layer{i}_weights", f"layer{i}_bias"),
                    aux={"h": h_idx, "c": h_idx + 1}))
                d = unit.units
                if not unit.return_sequence:
                    phase = "head"  # the carry IS the sample bridge
            elif kind == "last_token":
                plan.append(_Op(kind, unit))
                phase = "head"
            elif kind in _HEAD_KINDS:
                if phase != "head":
                    raise ValueError(
                        f"layer {i} ({kind}) inside the sequence "
                        f"phase — FC layers flatten the time axis "
                        f"and cannot decode; bridge with last_token "
                        f"first")
                plan.append(_Op(kind, unit, (
                    f"layer{i}_weights", f"layer{i}_bias")))
            else:
                raise ValueError(
                    f"layer {i} ({kind}): no incremental decode step "
                    f"(supported: {_SEQ_KINDS + _HEAD_KINDS} + "
                    f"last_token)")
        if phase != "head":
            raise ValueError("chain never bridges to per-sample "
                             "features (last_token or a final "
                             "return_sequence=False LSTM)")
        if layers[-1]["type"] != "softmax":
            raise ValueError("decode chain must end in the softmax "
                             "vocabulary head")
        if not cache_specs:
            raise ValueError("stateless chain — nothing to cache, "
                             "nothing to decode")
        return plan, cache_specs

    # ------------------------------------------------------------------
    # traced bodies
    # ------------------------------------------------------------------
    def _head(self, op: _Op, w, x, final: bool):
        """One head layer on (B, D) features; the final softmax layer
        returns raw logits (softmax is monotone — greedy unchanged,
        and sampling normalizes on the host)."""
        import jax.numpy as jnp
        weights, b = w
        if final:
            return op.unit._logits(jnp, x, weights, b)
        return op.unit._forward(jnp, x, weights, b)

    def _prefill_fn(self, t_bucket: int):
        """The traced prefill body for one prompt-length bucket.
        ``weights`` is the per-op operand pytree — an argument, not a
        baked constant, so a hot-swap never invalidates the program."""
        import jax
        import jax.numpy as jnp
        plan = self._plan

        def fn(caches, weights, tokens, slot, length):
            # tokens (1, t_bucket) int32; slot, length () int32
            caches = list(caches)
            feat = None
            logits = None
            for j, op in enumerate(plan):
                w = _dq_leaves(weights[j])
                if op.kind == "embedding":
                    feat = op.unit.xla_embed(w[0], tokens)
                elif op.kind == "pos_encoding":
                    feat = (feat.astype(jnp.float32)
                            + op.table[:t_bucket][None])
                elif op.kind == "attention":
                    feat, k, v = op.unit.xla_prefill(feat, *w)
                    zero = jnp.int32(0)
                    caches[op.aux["k"]] = jax.lax.dynamic_update_slice(
                        caches[op.aux["k"]], k, (slot, zero, zero, zero))
                    caches[op.aux["v"]] = jax.lax.dynamic_update_slice(
                        caches[op.aux["v"]], v, (slot, zero, zero, zero))
                elif op.kind == "lstm":
                    feat, h, c = op.unit.xla_prefill(
                        feat, *w, length=jnp.reshape(length, (1,)))
                    caches[op.aux["h"]] = \
                        caches[op.aux["h"]].at[slot].set(h[0])
                    caches[op.aux["c"]] = \
                        caches[op.aux["c"]].at[slot].set(c[0])
                elif op.kind == "last_token":
                    # the last REAL position, not the padded tail
                    feat = jax.lax.dynamic_index_in_dim(
                        feat, length - 1, axis=1, keepdims=False)
                else:  # head layer
                    logits = self._head(op, w, feat, op is plan[-1])
                    feat = logits
            return tuple(caches), logits
        return fn

    def _decode_fn(self, b_bucket: int):
        """The traced single-token body for one live-batch bucket."""
        plan = self._plan

        def fn(caches, weights, tokens, slots, positions):
            # tokens/slots/positions: (b_bucket,) int32
            import jax.numpy as jnp
            caches = list(caches)
            rows = jnp.arange(b_bucket)
            feat = None
            logits = None
            for j, op in enumerate(plan):
                w = _dq_leaves(weights[j])
                if op.kind == "embedding":
                    feat = op.unit.xla_embed(w[0], tokens)[:, None, :]
                elif op.kind == "pos_encoding":
                    feat = op.unit.xla_decode_step(feat, positions,
                                                   op.table)
                elif op.kind == "attention":
                    k_rows = caches[op.aux["k"]][slots]
                    v_rows = caches[op.aux["v"]][slots]
                    feat, k_rows, v_rows = op.unit.xla_decode_step(
                        feat, k_rows, v_rows, positions, *w)
                    # only position `pos` changed per lane: scatter the
                    # new row back, padded lanes land in the scratch
                    # slot (duplicate-index writes there are garbage
                    # by design)
                    caches[op.aux["k"]] = caches[op.aux["k"]].at[
                        slots, positions].set(k_rows[rows, positions])
                    caches[op.aux["v"]] = caches[op.aux["v"]].at[
                        slots, positions].set(v_rows[rows, positions])
                elif op.kind == "lstm":
                    h = caches[op.aux["h"]][slots]
                    c = caches[op.aux["c"]][slots]
                    feat, h, c = op.unit.xla_decode_step(
                        feat, h, c, *w)
                    caches[op.aux["h"]] = \
                        caches[op.aux["h"]].at[slots].set(h)
                    caches[op.aux["c"]] = \
                        caches[op.aux["c"]].at[slots].set(c)
                    if op.unit.return_sequence:
                        feat = feat[:, None, :]
                elif op.kind == "last_token":
                    feat = feat[:, 0]
                else:
                    if feat.ndim == 3:  # head after a seq-phase bridge
                        feat = feat[:, 0]
                    logits = self._head(op, w, feat, op is plan[-1])
                    feat = logits
            return tuple(caches), logits
        return fn

    # ------------------------------------------------------------------
    # traced bodies — paged variants (round 15)
    # ------------------------------------------------------------------
    def _paged_prefill_fn(self, t_bucket: int, nb: int):
        """One prompt WINDOW (fresh prefill at ``start=0``, or the
        unshared tail after a prefix-cache hit at ``start>0``) written
        and attended through the page table.  ``table`` carries nb+1
        page ids (last = trash)."""
        import jax
        import jax.numpy as jnp
        plan = self._plan

        def fn(caches, weights, tokens, table, slot, start, length):
            # tokens (1, t_bucket); table (nb+1,); slot/start/length ()
            caches = list(caches)
            feat = None
            logits = None
            for j, op in enumerate(plan):
                w = _dq_leaves(weights[j])
                if op.kind == "embedding":
                    feat = op.unit.xla_embed(w[0], tokens)
                elif op.kind == "pos_encoding":
                    pe = jax.lax.dynamic_slice_in_dim(
                        op.table, start, t_bucket, axis=0)
                    feat = feat.astype(jnp.float32) + pe[None]
                elif op.kind == "attention":
                    ks = op.aux.get("k_scale")
                    if ks is None:
                        feat, kp, vp = op.unit.xla_prefill_paged(
                            feat, caches[op.aux["k"]],
                            caches[op.aux["v"]], table, start,
                            length, *w)
                    else:
                        vs = op.aux["v_scale"]
                        (feat, kp, vp, caches[ks],
                         caches[vs]) = op.unit.xla_prefill_paged(
                            feat, caches[op.aux["k"]],
                            caches[op.aux["v"]], table, start,
                            length, *w, k_scale=caches[ks],
                            v_scale=caches[vs])
                    caches[op.aux["k"]] = kp
                    caches[op.aux["v"]] = vp
                elif op.kind == "lstm":
                    # LSTM chains never share prefixes (start is
                    # always 0): the carry is the whole-prefix state
                    feat, h, c = op.unit.xla_prefill(
                        feat, *w, length=jnp.reshape(length, (1,)))
                    caches[op.aux["h"]] = \
                        caches[op.aux["h"]].at[slot].set(h[0])
                    caches[op.aux["c"]] = \
                        caches[op.aux["c"]].at[slot].set(c[0])
                elif op.kind == "last_token":
                    feat = jax.lax.dynamic_index_in_dim(
                        feat, length - 1, axis=1, keepdims=False)
                else:
                    logits = self._head(op, w, feat, op is plan[-1])
                    feat = logits
            return tuple(caches), logits
        return fn

    def _paged_decode_fn(self, b_bucket: int, nb: int):
        """Single-token step through the page table, bucketed on BOTH
        the live-batch size and the deepest lane's block count — a
        shallow batch reads exactly the pages it occupies, never the
        flat layout's full ``max_t`` reservation."""
        plan = self._plan

        def fn(caches, weights, tokens, tables, slots, positions):
            # tokens/slots/positions (b,); tables (b, nb+1)
            import jax.numpy as jnp
            caches = list(caches)
            feat = None
            logits = None
            for j, op in enumerate(plan):
                w = _dq_leaves(weights[j])
                if op.kind == "embedding":
                    feat = op.unit.xla_embed(w[0], tokens)[:, None, :]
                elif op.kind == "pos_encoding":
                    feat = op.unit.xla_decode_step(feat, positions,
                                                   op.table)
                elif op.kind == "attention":
                    ks = op.aux.get("k_scale")
                    if ks is None:
                        feat, kp, vp = op.unit.xla_decode_step_paged(
                            feat, caches[op.aux["k"]],
                            caches[op.aux["v"]], tables, positions,
                            *w)
                    else:
                        vs = op.aux["v_scale"]
                        (feat, kp, vp, caches[ks],
                         caches[vs]) = op.unit.xla_decode_step_paged(
                            feat, caches[op.aux["k"]],
                            caches[op.aux["v"]], tables, positions,
                            *w, k_scale=caches[ks],
                            v_scale=caches[vs])
                    caches[op.aux["k"]] = kp
                    caches[op.aux["v"]] = vp
                elif op.kind == "lstm":
                    h = caches[op.aux["h"]][slots]
                    c = caches[op.aux["c"]][slots]
                    feat, h, c = op.unit.xla_decode_step(
                        feat, h, c, *w)
                    caches[op.aux["h"]] = \
                        caches[op.aux["h"]].at[slots].set(h)
                    caches[op.aux["c"]] = \
                        caches[op.aux["c"]].at[slots].set(c)
                    if op.unit.return_sequence:
                        feat = feat[:, None, :]
                elif op.kind == "last_token":
                    feat = feat[:, 0]
                else:
                    if feat.ndim == 3:
                        feat = feat[:, 0]
                    logits = self._head(op, w, feat, op is plan[-1])
                    feat = logits
            return tuple(caches), logits
        return fn

    def _window_fn(self, b_bucket: int, w_len: int, nb: int):
        """Batched multi-token window per lane, written and attended
        through the page table in ONE forward, returning logits at
        EVERY window position (b, W, V).  Two callers: speculative
        verification (window = last accepted token + K drafts,
        lengths ≡ K+1) and batched tail prefill (window = each lane's
        unshared prompt tail, ragged ``lengths`` — admission
        coalescing, so a burst of prefix-hit prompts pays ONE
        dispatch instead of one each)."""
        import jax.numpy as jnp
        plan = self._plan

        def fn(caches, weights, tokens, tables, positions, lengths):
            # tokens (b, W); tables (b, nb+1); positions/lengths (b,)
            caches = list(caches)
            feat = None
            logits = None
            for j, op in enumerate(plan):
                w = _dq_leaves(weights[j])
                if op.kind == "embedding":
                    feat = op.unit.xla_embed(w[0], tokens)
                elif op.kind == "pos_encoding":
                    idx = jnp.minimum(
                        positions[:, None] + jnp.arange(w_len)[None],
                        op.table.shape[0] - 1)
                    feat = feat.astype(jnp.float32) + op.table[idx]
                elif op.kind == "attention":
                    ks = op.aux.get("k_scale")
                    if ks is None:
                        feat, kp, vp = op.unit.xla_window_paged(
                            feat, caches[op.aux["k"]],
                            caches[op.aux["v"]], tables, positions,
                            lengths, *w)
                    else:
                        vs = op.aux["v_scale"]
                        (feat, kp, vp, caches[ks],
                         caches[vs]) = op.unit.xla_window_paged(
                            feat, caches[op.aux["k"]],
                            caches[op.aux["v"]], tables, positions,
                            lengths, *w, k_scale=caches[ks],
                            v_scale=caches[vs])
                    caches[op.aux["k"]] = kp
                    caches[op.aux["v"]] = vp
                elif op.kind == "last_token":
                    # every window position flows to the head: fold
                    # the window into the batch for the head phase
                    feat = feat.reshape(b_bucket * w_len, -1)
                else:
                    logits = self._head(op, w, feat, op is plan[-1])
                    feat = logits
            return tuple(caches), logits.reshape(b_bucket, w_len, -1)
        return fn

    def _copy_fn(self):
        """Copy one page (every attention pool) — the copy-on-write
        a partial prefix-cache match performs before the divergent
        tail writes into the boundary block."""
        pool_indices = self.cache.pool_indices

        def fn(caches, src, dst):
            caches = list(caches)
            for i in pool_indices:
                caches[i] = caches[i].at[dst].set(caches[i][src])
            return tuple(caches)
        return fn

    def _page_in_fn(self):
        """Scatter ONE staged page (every pool) into row ``dst`` —
        the device half of a spill restore or a prefill→decode
        handoff (round 22).  The page operands arrive via the staging
        ring + uploader thread; only the cache tuple is donated, so
        the uploaded arrays stay valid for the caller."""
        pool_indices = self.cache.pool_indices

        def fn(caches, pages, dst):
            caches = list(caches)
            for j, i in enumerate(pool_indices):
                caches[i] = caches[i].at[dst].set(pages[j])
            return tuple(caches)
        return fn

    def _carry_in_fn(self):
        """Scatter one LSTM carry row set into ``slot`` — the
        slot-indexed half of the handoff contract (carries summarize
        the whole prefix in O(H), so they ride the transfer as rows,
        not pages)."""
        slot_indices = self.cache.slot_indices

        def fn(caches, rows, slot):
            caches = list(caches)
            for j, i in enumerate(slot_indices):
                caches[i] = caches[i].at[slot].set(rows[j])
            return tuple(caches)
        return fn

    # ------------------------------------------------------------------
    # AOT compilation
    # ------------------------------------------------------------------
    def _compile(self, fn, in_structs: tuple, site: str,
                 family: str | None = None, geom: tuple = ()):
        import jax
        donate = (0,) if self.donating else ()
        # round 23: the persisted executable store is consulted BEFORE
        # tracing.  The key covers the program family + bucket
        # geometry explicitly (two families can share a site), the
        # bundle's architecture digest, the operand structs, the
        # decode-plan knobs that shape a body without shaping its
        # operands, donation, platform and build — any mismatch is a
        # plain miss and this compiles exactly as before.
        from znicz_tpu.serving import aot_cache as _aot
        cache = _aot.active_cache()
        key = digest = None
        if cache is not None:
            family = family or site
            digest = _aot.program_digest(self.model.manifest)
            key = _aot.entry_key(
                family, digest=digest, geometry=geom,
                structs=in_structs, donate=self.donating,
                extra=("decode", self.paged, self.page_tokens,
                       self.kv_quant, str(self.kv_dtype), self.spec_k,
                       self.max_t, self.vocab))
            loaded = cache.get(key, site)
            if loaded is not None:
                # a deserialized load is NOT a compile — compile_count
                # and the per-site xla_compiles series stay flat
                self.load_count += 1
                return _aot.guard_donated(loaded, donate)
        with _tracing.TRACER.span(f"aot_compile:{site}",
                                  cat="compile"):
            compiled = jax.jit(fn, donate_argnums=donate).lower(
                *in_structs).compile()
        _metrics.xla_compiles(site).inc()
        self.compile_count += 1
        if cache is not None:
            cache.put(key, compiled, site,
                      meta={"family": family,
                            "program_digest": digest,
                            "geometry": [str(g) for g in geom]})
        return compiled

    def _cache_structs(self) -> tuple:
        import jax
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for a in self.cache.arrays)

    def _weight_structs(self) -> tuple:
        import jax

        def struct(a):
            if isinstance(a, tuple):  # (q int8, scale f32) pair
                return tuple(struct(x) for x in a)
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=getattr(a, "sharding",
                                                         None))
        return tuple(tuple(None if a is None else struct(a)
                           for a in ws) for ws in self._weights)

    def prefill_program(self, t_bucket: int):
        """The AOT prefill program for one prompt-length bucket
        (compiled on first use; :meth:`warmup` front-loads the whole
        ladder)."""
        prog = self._prefill_programs.get(t_bucket)
        if prog is None:
            import jax
            i32 = np.dtype(np.int32)
            prog = self._compile(
                self._prefill_fn(t_bucket),
                (self._cache_structs(), self._weight_structs(),
                 jax.ShapeDtypeStruct((1, t_bucket), i32),
                 jax.ShapeDtypeStruct((), i32),
                 jax.ShapeDtypeStruct((), i32)),
                "serving-prefill", family="prefill",
                geom=(t_bucket,))
            self._prefill_programs[t_bucket] = prog
        return prog

    def decode_program(self, b_bucket: int):
        """The AOT single-token program for one live-batch bucket."""
        prog = self._decode_programs.get(b_bucket)
        if prog is None:
            import jax
            vec = jax.ShapeDtypeStruct((b_bucket,), np.dtype(np.int32))
            prog = self._compile(
                self._decode_fn(b_bucket),
                (self._cache_structs(), self._weight_structs(),
                 vec, vec, vec),
                "serving-decode", family="decode",
                geom=(b_bucket,))
            self._decode_programs[b_bucket] = prog
        return prog

    def paged_prefill_program(self, t_bucket: int, nb: int):
        key = (t_bucket, nb)
        prog = self._paged_prefill_programs.get(key)
        if prog is None:
            import jax
            i32 = np.dtype(np.int32)
            scalar = jax.ShapeDtypeStruct((), i32)
            prog = self._compile(
                self._paged_prefill_fn(t_bucket, nb),
                (self._cache_structs(), self._weight_structs(),
                 jax.ShapeDtypeStruct((1, t_bucket), i32),
                 jax.ShapeDtypeStruct((nb + 1,), i32),
                 scalar, scalar, scalar),
                "serving-prefill", family="paged-prefill",
                geom=key)
            self._paged_prefill_programs[key] = prog
        return prog

    def paged_decode_program(self, b_bucket: int, nb: int):
        key = (b_bucket, nb)
        prog = self._paged_decode_programs.get(key)
        if prog is None:
            import jax
            i32 = np.dtype(np.int32)
            vec = jax.ShapeDtypeStruct((b_bucket,), i32)
            prog = self._compile(
                self._paged_decode_fn(b_bucket, nb),
                (self._cache_structs(), self._weight_structs(),
                 vec, jax.ShapeDtypeStruct((b_bucket, nb + 1), i32),
                 vec, vec),
                "serving-decode", family="paged-decode",
                geom=key)
            self._paged_decode_programs[key] = prog
        return prog

    def window_program(self, b_bucket: int, w_len: int, nb: int,
                       site: str = "serving-verify"):
        key = (b_bucket, w_len, nb)
        prog = self._verify_programs.get(key)
        if prog is None:
            import jax
            i32 = np.dtype(np.int32)
            vec = jax.ShapeDtypeStruct((b_bucket,), i32)
            prog = self._compile(
                self._window_fn(b_bucket, w_len, nb),
                (self._cache_structs(), self._weight_structs(),
                 jax.ShapeDtypeStruct((b_bucket, w_len), i32),
                 jax.ShapeDtypeStruct((b_bucket, nb + 1), i32),
                 vec, vec),
                site, family="window", geom=key)
            self._verify_programs[key] = prog
        return prog

    def verify_program(self, b_bucket: int, nb: int):
        if not self.spec_k:
            raise RuntimeError("spec_k=0 — no verify family planned")
        return self.window_program(b_bucket, self.spec_k + 1, nb)

    def copy_program(self):
        if self._copy_program is None:
            import jax
            i32 = np.dtype(np.int32)
            self._copy_program = self._compile(
                self._copy_fn(),
                (self._cache_structs(),
                 jax.ShapeDtypeStruct((), i32),
                 jax.ShapeDtypeStruct((), i32)),
                "serving-page", family="copy")
        return self._copy_program

    def page_in_program(self):
        if not self.paged:
            raise RuntimeError("page_in needs the paged cache")
        if self._page_in_program is None:
            import jax
            cache = self.cache
            page_structs = tuple(
                jax.ShapeDtypeStruct(
                    (cache.page_tokens,) + tuple(cache.specs[i][2]),
                    cache.arrays[i].dtype)
                for i in cache.pool_indices)
            self._page_in_program = self._compile(
                self._page_in_fn(),
                (self._cache_structs(), page_structs,
                 jax.ShapeDtypeStruct((), np.dtype(np.int32))),
                "serving-page", family="page-in")
        return self._page_in_program

    def carry_in_program(self):
        if not (self.paged and self.has_lstm):
            raise RuntimeError("carry_in needs a paged LSTM chain")
        if self._carry_in_program is None:
            import jax
            cache = self.cache
            row_structs = tuple(
                jax.ShapeDtypeStruct(tuple(cache.specs[i][2]),
                                     cache.arrays[i].dtype)
                for i in cache.slot_indices)
            self._carry_in_program = self._compile(
                self._carry_in_fn(),
                (self._cache_structs(), row_structs,
                 jax.ShapeDtypeStruct((), np.dtype(np.int32))),
                "serving-page", family="carry-in")
        return self._carry_in_program

    def prompt_ladder(self) -> list[int]:
        return ladder(self.max_prompt, self.prompt_align)

    def batch_ladder(self) -> list[int]:
        return ladder(self.max_slots)

    def block_ladder(self) -> list[int]:
        """Power-of-two block-count buckets: a decode dispatch reads
        only ``nb·page_tokens`` cache rows per lane."""
        return ladder(self.max_blocks) if self.paged else [1]

    def nb_for(self, top_position: int) -> int:
        """The block bucket covering positions ``0..top_position``."""
        blocks = -(-(int(top_position) + 1) // self.page_tokens)
        return min(next_pow2(max(1, blocks)), self.max_blocks)

    def fresh_nb(self, t_bucket: int) -> int:
        return self.nb_for(t_bucket - 1)

    def warmup(self, prefix_cache: bool = True,
               page_io: bool = False) -> int:
        """Compile EVERY program family up front — after this, a
        decode loop at any live-batch size, block depth and prompt mix
        performs zero compiles.  Returns programs compiled.

        ``prefix_cache=False`` skips the tail-prefill (start>0)
        variants and the COW copy program — engines without prefix
        sharing never dispatch them.  ``page_io=True`` (round 22)
        adds the page-in scatter (+ the carry scatter on LSTM
        chains): spill restores and pool handoffs then run
        compile-free too.

        "Compiled" means MADE RESIDENT: programs deserialized from the
        persisted AOT cache (round 23) count toward the return value
        (they satisfy the same zero-compiles-at-serve-time contract)
        but never toward ``compile_count``."""
        before = self.compile_count + self.load_count
        if not self.paged:
            for t_b in self.prompt_ladder():
                self.prefill_program(t_b)
            for b_b in self.batch_ladder():
                self.decode_program(b_b)
            return (self.compile_count + self.load_count) - before
        for t_b in self.prompt_ladder():
            for nb in self.block_ladder():
                if nb < self.fresh_nb(t_b):
                    continue  # a window never shrinks its own blocks
                if nb > self.fresh_nb(t_b) and not prefix_cache:
                    continue  # start>0 exists only with prefix hits
                self.paged_prefill_program(t_b, nb)
        for b_b in self.batch_ladder():
            for nb in self.block_ladder():
                self.paged_decode_program(b_b, nb)
                if self.spec_k:
                    self.verify_program(b_b, nb)
                if prefix_cache and not self.has_lstm:
                    # the admission-coalescing window family: a wave
                    # of prefix-hit tails admits in ONE dispatch
                    self.window_program(b_b, self.prompt_align, nb,
                                        site="serving-prefill")
        if prefix_cache:
            self.copy_program()
        if page_io:
            self.page_in_program()
            if self.has_lstm:
                self.carry_in_program()
        return (self.compile_count + self.load_count) - before

    @property
    def programs_live(self) -> int:
        return (len(self._prefill_programs)
                + len(self._decode_programs)
                + len(self._paged_prefill_programs)
                + len(self._paged_decode_programs)
                + len(self._verify_programs)
                + (1 if self._copy_program is not None else 0)
                + (1 if self._page_in_program is not None else 0)
                + (1 if self._carry_in_program is not None else 0))

    # ------------------------------------------------------------------
    # pool replication (round 22): programs are pure functions of the
    # cache operands, so ONE warmed DecodeModel serves any number of
    # same-geometry caches — disaggregated pool replicas scale
    # compile-free
    # ------------------------------------------------------------------
    def make_cache(self) -> PagedKVCache:
        """A fresh :class:`PagedKVCache` with IDENTICAL geometry to
        the model's own — the per-replica state of a disaggregated
        prefill/decode pool member.  Every compiled program accepts
        it via the ``cache=`` dispatch parameter."""
        if not self.paged:
            raise RuntimeError(
                "pool replication needs the paged cache (flat caches "
                "are slot-bound to one engine)")
        cache = self.cache
        return PagedKVCache(list(cache.specs), self.max_slots,
                            self.page_tokens, self.max_blocks,
                            cache.pool_pages)

    def page_shapes(self) -> list[tuple[tuple, object]]:
        """(shape, dtype) of ONE page per pool array — the frame
        geometry of the host tier and staging rings."""
        cache = self.cache
        return [((cache.page_tokens,) + tuple(cache.specs[i][2]),
                 np.dtype(cache.arrays[i].dtype))
                for i in cache.pool_indices]

    def carry_shapes(self) -> list[tuple[tuple, object]]:
        """(shape, dtype) of one slot's carry rows (LSTM chains)."""
        cache = self.cache
        return [(tuple(cache.specs[i][2]),
                 np.dtype(cache.arrays[i].dtype))
                for i in cache.slot_indices]

    # ------------------------------------------------------------------
    # dispatch (ONE thread per cache — no locking needed on a cache;
    # ``cache=None`` means the model's own.  Pool replicas pass their
    # private same-geometry cache and reuse every compiled program.)
    # ------------------------------------------------------------------
    def run_prefill(self, tokens: np.ndarray, slot: int,
                    start: int = 0, cache: PagedKVCache | None = None
                    ) -> np.ndarray:
        """Prefill one prompt window into ``slot``; returns the last
        real position's logits (V,).  ``tokens`` are the positions
        ``start..start+len-1`` — the whole prompt for a fresh
        admission (``start=0``), the unshared tail after a
        prefix-cache hit (paged only)."""
        cache = cache if cache is not None else self.cache
        n = int(tokens.shape[0])
        if start + n > self.max_prompt:
            raise ValueError(f"prompt of {start + n} tokens exceeds "
                             f"max_prompt {self.max_prompt}")
        t_b = bucket_for(n, self.prompt_align)
        padded = np.zeros((1, t_b), np.int32)
        padded[0, :n] = tokens
        if not self.paged:
            if start:
                raise ValueError("flat cache cannot tail-prefill")
            prog = self.prefill_program(t_b)
            caches, logits = prog(cache.arrays, self._weights,
                                  padded, np.asarray(slot, np.int32),
                                  np.asarray(n, np.int32))
            cache.arrays = caches
            return np.asarray(logits, np.float32)[0]
        nb = self.nb_for(start + t_b - 1)
        prog = self.paged_prefill_program(t_b, nb)
        caches, logits = prog(
            cache.arrays, self._weights, padded,
            cache.table_operand(slot, nb),
            np.asarray(slot, np.int32), np.asarray(start, np.int32),
            np.asarray(n, np.int32))
        cache.arrays = caches
        return np.asarray(logits, np.float32)[0]

    def run_decode(self, tokens: np.ndarray, slots: np.ndarray,
                   positions: np.ndarray,
                   cache: PagedKVCache | None = None) -> np.ndarray:
        """One token step for ``len(tokens)`` live lanes; pads to the
        covering live-batch bucket (padded lanes ride the scratch
        slot/trash table).  Returns logits (n_live, V)."""
        cache = cache if cache is not None else self.cache
        n = int(tokens.shape[0])
        b_b = bucket_for(n)

        def padded(arr, fill):
            out = np.full((b_b,), fill, np.int32)
            out[:n] = arr
            return out

        if not self.paged:
            prog = self.decode_program(b_b)
            caches, logits = prog(
                cache.arrays, self._weights, padded(tokens, 0),
                padded(slots, cache.trash_slot),
                padded(positions, 0))
            cache.arrays = caches
            return np.asarray(logits, np.float32)[:n]
        nb = self.nb_for(int(positions.max()))
        tables = np.full((b_b, nb + 1), cache.trash_page,
                         np.int32)
        tables[:n, :nb] = cache.tables[slots, :nb]
        prog = self.paged_decode_program(b_b, nb)
        caches, logits = prog(
            cache.arrays, self._weights, padded(tokens, 0),
            tables, padded(slots, cache.trash_slot),
            padded(positions, 0))
        cache.arrays = caches
        return np.asarray(logits, np.float32)[:n]

    def run_window(self, windows: np.ndarray, slots: np.ndarray,
                   positions: np.ndarray, lengths: np.ndarray,
                   site: str = "serving-verify",
                   cache: PagedKVCache | None = None) -> np.ndarray:
        """Batched window dispatch: ``windows`` (n, W) token windows
        starting at per-lane ``positions`` with ``lengths`` real
        tokens each; ONE forward writes all live K/V through the page
        tables and returns logits (n, W, V)."""
        cache = cache if cache is not None else self.cache
        n, w_len = windows.shape
        b_b = bucket_for(n)
        nb = self.nb_for(int(positions.max()) + w_len - 1)
        win = np.zeros((b_b, w_len), np.int32)
        win[:n] = windows
        tables = np.full((b_b, nb + 1), cache.trash_page,
                         np.int32)
        tables[:n, :nb] = cache.tables[slots, :nb]
        pos = np.zeros((b_b,), np.int32)
        pos[:n] = positions
        lens = np.zeros((b_b,), np.int32)
        lens[:n] = lengths
        prog = self.window_program(b_b, int(w_len), nb, site=site)
        caches, logits = prog(cache.arrays, self._weights, win,
                              tables, pos, lens)
        cache.arrays = caches
        return np.asarray(logits, np.float32)[:n]

    def run_verify(self, windows: np.ndarray, slots: np.ndarray,
                   positions: np.ndarray,
                   cache: PagedKVCache | None = None) -> np.ndarray:
        """Speculative verification: ``windows`` (n, spec_k+1) token
        windows starting at per-lane ``positions``; logits at every
        window position (n, spec_k+1, V)."""
        if not self.spec_k:
            raise RuntimeError("spec_k=0 — no verify family planned")
        lengths = np.full((windows.shape[0],), self.spec_k + 1,
                          np.int32)
        return self.run_window(windows, slots, positions, lengths,
                               cache=cache)

    def copy_page(self, src: int, dst: int,
                  cache: PagedKVCache | None = None) -> None:
        """Device-copy one page across every attention pool — the COW
        a partial prefix match pays before its divergent tail."""
        cache = cache if cache is not None else self.cache
        prog = self.copy_program()
        cache.arrays = prog(cache.arrays,
                            np.asarray(src, np.int32),
                            np.asarray(dst, np.int32))

    # ------------------------------------------------------------------
    # page / carry I-O (round 22): the data plane of spill restores
    # and prefill→decode handoffs
    # ------------------------------------------------------------------
    def export_page(self, pid: int,
                    cache: PagedKVCache | None = None
                    ) -> list[np.ndarray]:
        """D2H-copy page ``pid`` out of every attention pool — one
        (page_tokens, H, Dh) host array per pool, the unit the host
        tier stores and a handoff ships."""
        cache = cache if cache is not None else self.cache
        return [np.asarray(cache.arrays[i][pid])
                for i in cache.pool_indices]

    def page_in(self, pages, dst: int,
                cache: PagedKVCache | None = None) -> None:
        """Scatter one page (device or host arrays, one per pool)
        into pool row ``dst`` — a spill restore or handoff landing."""
        cache = cache if cache is not None else self.cache
        cache.arrays = self.page_in_program()(
            cache.arrays, tuple(pages), np.asarray(dst, np.int32))

    def export_carry(self, slot: int,
                     cache: PagedKVCache | None = None
                     ) -> list[np.ndarray]:
        """D2H-copy slot ``slot``'s recurrent carry rows (LSTM h/c) —
        the non-paged half of a handoff."""
        cache = cache if cache is not None else self.cache
        return [np.asarray(cache.arrays[i][slot])
                for i in cache.slot_indices]

    def carry_in(self, rows, slot: int,
                 cache: PagedKVCache | None = None) -> None:
        """Scatter carry rows into slot ``slot``."""
        cache = cache if cache is not None else self.cache
        cache.arrays = self.carry_in_program()(
            cache.arrays, tuple(rows), np.asarray(slot, np.int32))

    # ------------------------------------------------------------------
    # weight hot-swap (round 13)
    # ------------------------------------------------------------------
    def check_compatible(self, manifest: dict | None,
                         params: dict) -> None:
        """Validate a candidate against the planned chain; raises
        :class:`~znicz_tpu.export.SwapIncompatible` with the incumbent
        untouched on any mismatch."""
        from znicz_tpu.export import SwapIncompatible
        if manifest is not None:
            mine = [layer["type"] for layer
                    in self.model.manifest["layers"]]
            theirs = [layer["type"] for layer
                      in manifest.get("layers", [])]
            if mine != theirs:
                raise SwapIncompatible(
                    f"candidate layer table {theirs} != decode chain "
                    f"{mine}")
        for op, ws in zip(self._plan, self._weights):
            for key, cur in zip(op.wkeys, ws):
                new = params.get(key)
                if cur is None:
                    if new is not None:
                        raise SwapIncompatible(
                            f"{key}: candidate carries a parameter "
                            f"the compiled programs have no operand "
                            f"for")
                    continue
                if new is None:
                    raise SwapIncompatible(
                        f"candidate is missing parameter '{key}'")
                shape = tuple((cur[0] if isinstance(cur, tuple)
                               else cur).shape)
                if tuple(np.shape(new)) != shape:
                    raise SwapIncompatible(
                        f"{key}: candidate shape "
                        f"{tuple(np.shape(new))} != compiled "
                        f"{shape}")

    def swap_weights(self, params: dict,
                     manifest: dict | None = None) -> int:
        """Replace the weight operand pytree without recompiling:
        validate → stage (device_put onto each leaf's existing
        placement, fenced) → publish the new immutable tuple in one
        assignment.  The caller (:meth:`DecodeEngine.swap_weights`)
        guarantees no decode step is mid-flight when the flip lands —
        slots carrying old-model generations drain first."""
        import jax
        from znicz_tpu.export import SwapIncompatible
        from znicz_tpu.serving import quantize as _quantize
        qkeys = getattr(self.model, "_qkeys", frozenset())
        cand_rec = _quantize.is_quantized(manifest)
        if qkeys:
            if cand_rec is None:
                raise SwapIncompatible(
                    "candidate is f32 but the decode chain compiled "
                    "int8 dequantize-on-load programs — republish "
                    "the candidate with quantize='int8' (or restart "
                    "the replica f32)")
            if frozenset(cand_rec.get("weights", [])) != qkeys:
                raise SwapIncompatible(
                    "candidate quantizes a different key set than "
                    "the compiled programs "
                    f"({sorted(cand_rec.get('weights', []))} != "
                    f"{sorted(qkeys)})")
        elif cand_rec is not None:
            # quantized candidate into an f32-compiled chain:
            # dequantize host-side and stage f32 — recompile-free
            params = _quantize.dequantize_params(manifest, params)
        self.check_compatible(manifest, params)
        staged = []
        for op, ws in zip(self._plan, self._weights):
            new_ws = []
            for key, cur in zip(op.wkeys, ws):
                if cur is None:
                    new_ws.append(None)
                    continue
                if isinstance(cur, tuple):  # int8 (q, scale) operand
                    skey = _quantize.scale_key(key)
                    q = np.asarray(params[key], np.int8)
                    s = np.asarray(params[skey], np.float32)
                    arr = (jax.device_put(q), jax.device_put(s))
                    self.model._params[key] = q
                    self.model._params[skey] = s
                else:
                    new = np.asarray(params[key], np.float32)
                    sharding = getattr(cur, "sharding", None)
                    arr = (jax.device_put(new, sharding)
                           if sharding is not None
                           else jax.device_put(new))
                    self.model._params[key] = new
                new_ws.append(arr)
            staged.append(tuple(new_ws))
        for ws in staged:  # fence before publishing
            for leaf in ws:
                if leaf is None:
                    continue
                for a in (leaf if isinstance(leaf, tuple)
                          else (leaf,)):
                    a.block_until_ready()
        self._weights = tuple(staged)
        self.weights_version += 1
        return self.weights_version


class _PromptReq:
    """One queued generation request.

    ``pause_s`` accumulates the admission-pause time (swap drains)
    that overlapped this request's queue wait: TTFT observations and
    the TTFT deadline both stamp from **admission-eligible** time
    (``t_submit + pause_s``), so a drain neither pollutes the serving
    SLO histograms nor expires a request the engine was forbidden to
    admit (round-13 documented noise band, fixed in round 15)."""

    __slots__ = ("tokens", "n", "max_new", "future", "t_submit",
                 "deadline", "pause_s", "charged", "tenant", "priority",
                 "trace")

    def __init__(self, tokens: np.ndarray, max_new: int,
                 deadline_ms: float | None,
                 tenant: str | None = None, priority: int = 0) -> None:
        self.tokens = tokens
        self.n = int(tokens.shape[0])
        self.max_new = int(max_new)
        self.future: Future = Future()
        self.t_submit = time.monotonic()
        self.pause_s = 0.0
        self.charged = 0  # tokens held against the admission budget
        self.tenant = tenant
        self.priority = int(priority)
        self.deadline = (None if deadline_ms is None
                         else self.t_submit + float(deadline_ms) / 1e3)
        # request-scoped trace context (round 24): minted HERE at
        # submit (or adopted from the fleet router, which stamped its
        # routing decision on it first) and riding the request object
        # through queue → prefill → [handoff →] decode
        self.trace = (_tracing.adopt_pending_trace()
                      or _tracing.new_request_trace(
                          "request", tokens=self.n,
                          tenant=tenant or "-"))
        self.trace.phase_begin("queue")

    def expired(self, now: float) -> bool:
        return self.deadline is not None \
            and now >= self.deadline + self.pause_s


class _Live:
    """Host-side state of one sequence mid-generation."""

    __slots__ = ("req", "slot", "pos", "generated", "t_last")

    def __init__(self, req: _PromptReq, slot: int, first_token: int
                 ) -> None:
        self.req = req
        self.slot = slot
        #: position the NEXT input token will occupy (= prompt length
        #: right after prefill; the sampled token is fed back there)
        self.pos = req.n
        self.generated = [int(first_token)]
        self.t_last = time.monotonic()


class _PageSetupMixin:
    """Paged admission shared by :class:`DecodeEngine` and the
    disaggregated prefill workers (serving/disagg.py): prefix match →
    share/COW/alloc → spill-tier room-making.  The host expects
    ``self.model`` (a :class:`DecodeModel`), ``self.prefix``
    (:class:`PrefixCache` or None), ``self._spill``
    (``memory.HostPageTier`` or None), ``self._obs_id`` and the
    prefix/migration metric children; :meth:`_kv_cache` names the
    cache the host schedules (a pool worker's private replica cache,
    the engine's own otherwise)."""

    def _kv_cache(self) -> PagedKVCache:
        return self.model.cache

    def _setup_pages(self, slot: int, tokens: np.ndarray,
                     max_new: int) -> int:
        """Map the request's blocks into ``slot``'s table: shared full
        blocks by reference, a partially-matched boundary block via
        copy-on-write, fresh pages for the rest — RESERVING the whole
        worst-case span (prompt + token budget, capped at max_t) up
        front, so an admitted request can never be page-starved
        mid-generation and pool pressure degrades as deterministic
        admission shedding, never as a truncated neighbor.  Returns
        the matched token count (the tail prefill starts there).
        Raises :class:`PoolExhausted` with the slot's table cleaned."""
        model = self.model
        cache = self._kv_cache()
        n = int(tokens.shape[0])
        nodes: list = []
        matched = 0
        cow = None
        if self.prefix is not None:
            nodes, matched, cow = self.prefix.match_nodes(tokens)
        span = min(n + int(max_new), model.max_t)
        nblocks = -(-span // model.page_tokens)
        # Two-phase pinning (round 22, generalizing the round-15
        # pin-before-evict rule to the spill tier).  Phase 1 pins
        # every HBM-resident matched block into the slot's table
        # BEFORE any room-making: a restore below may spill or evict
        # other trie pages, and a matched-but-unpinned HBM page must
        # never be a victim.  Phase 2 restores host-resident matched
        # blocks one at a time, pinning each the moment it lands
        # (ref 2 = trie + slot, so spill_candidate's ref==1 test
        # can't re-spill it while we restore the next).  Host-
        # resident blocks are safe to defer: evict() only takes
        # page-resident leaves, and the host tier frees nothing on
        # its own.
        donor_pinned = False
        try:
            for b, node in enumerate(nodes):
                if node.page is not None:
                    cache.share_block(slot, b, node.page)
            if cow is not None and cow[0].page is not None:
                cache.ref[cow[0].page] += 1  # donor pin till copy
                donor_pinned = True
            for b, node in enumerate(nodes):
                if node.page is None:
                    self._restore_node(node)
                    cache.share_block(slot, b, node.page)
            if cow is not None and not donor_pinned:
                self._restore_node(cow[0])
                cache.ref[cow[0].page] += 1
                donor_pinned = True
            need_new = nblocks - len(nodes)
            if cache.free_pages < need_new:
                self._make_room(need_new)
            base = len(nodes)
            if cow is not None:
                pid = cache.new_block(slot, base)
                # the divergence copy: shared positions of the
                # boundary block come along, the divergent tail
                # overwrites its own private copy
                model.copy_page(cow[0].page, pid, cache=cache)
                base += 1
            for b in range(base, nblocks):
                cache.new_block(slot, b)
        except PoolExhausted:
            cache.release_slot_pages(slot)
            raise
        finally:
            if donor_pinned:
                cache.ref_dec(cow[0].page)
        if self.prefix is not None:
            if matched > 0:
                self._m_prefix_hit.inc()
                self._m_tok_shared.inc(matched)
            else:
                self._m_prefix_miss.inc()
            self._m_tok_computed.inc(n - matched)
        return matched

    def _restore_node(self, node) -> None:
        """Bring one host-resident trie block back to an HBM page
        through the staging ring; the node's trie pin moves tiers
        with it (frame freed, fresh page ref 1)."""
        cache = self._kv_cache()
        if cache.free_pages < 1:
            self._make_room(1)
        pid = cache.alloc_page()  # ref 1 = the trie pin, now on HBM
        dev = self._spill.upload(node.host)
        self.model.page_in(dev, pid, cache=cache)
        self._spill.free(node.host)
        node.page, node.host = pid, None
        self._m_mig_restore.inc()

    def _make_room(self, pages_needed: int) -> None:
        """Free HBM pages for an admission: spill cold shareable
        blocks to the host tier while it has frames, then fall back
        to plain trie eviction.  No-op without a prefix cache —
        new_block raises PoolExhausted and admission requeues."""
        if self.prefix is None:
            return
        cache = self._kv_cache()
        while cache.free_pages < pages_needed:
            if self._spill is not None and not self._spill.full:
                victim = self.prefix.spill_candidate(cache)
                if victim is not None:
                    hid = self._spill.store(
                        self.model.export_page(victim.page,
                                               cache=cache))
                    # sole holder was the trie pin → page frees now
                    cache.ref_dec(victim.page)
                    victim.page, victim.host = None, hid
                    self._m_mig_spill.inc()
                    continue
            evicted = self.prefix.evict(cache, pages_needed)
            if evicted:
                _metrics.prefix_cache_events(
                    self._obs_id, "evicted").inc(evicted)
            return


class DecodeEngine(_PageSetupMixin, Logger):
    """Continuous-batching token server over a :class:`DecodeModel`.

    Lifecycle mirrors :class:`~znicz_tpu.serving.ServingEngine`::

        with DecodeEngine("lm.npz", max_slots=4, max_t=64) as eng:
            tokens = eng.generate(prompt)            # sync
            future = eng.submit(prompt)              # async
            tokens = future.result()                 # np.int32 ids

    ``temperature=0`` (default) decodes greedily — byte-for-byte
    reproducible against the numpy oracle; ``temperature>0`` samples
    from the softmax on the host with a seeded generator (the logits
    cross anyway: sampling adds no device work).

    Scheduling: ``admission="continuous"`` (default) admits queued
    prompts into the in-flight batch between token steps; ``"static"``
    admits only when the previous batch fully drained —
    run-to-completion, the serve_bench A/B baseline.

    Degradation: ``deadline_ms`` bounds **TTFT** (a prompt still
    queued past it fails fast with :class:`DeadlineExceeded` and never
    occupies a slot); the circuit breaker watches dispatch outcomes
    and, while open, sheds NEW prompts with :class:`Overloaded` while
    in-flight sequences keep decoding to completion (the drain
    contract — generation in progress is the last thing to abandon).
    """

    def __init__(self, model, *, max_slots: int = 4, max_t: int = 64,
                 max_prompt: int | None = None, prompt_align: int = 8,
                 max_new_tokens: int = 32,
                 eos_token: int | None = None,
                 temperature: float = 0.0, seed: int = 0,
                 max_queue: int = 256,
                 admission: str = "continuous",
                 retry_budget: int = 1,
                 breaker_failure_rate: float = 0.5,
                 breaker_window: int = 8,
                 breaker_min_samples: int = 4,
                 breaker_cooldown_ms: float = 1000.0,
                 paged: bool | None = None,
                 page_tokens: int | None = None,
                 pool_tokens: int | None = None,
                 prefix_cache: bool | None = None,
                 spec_draft_k: int | None = None,
                 drafter=None,
                 max_queue_tokens: int | None = None,
                 max_queue_age_ms: float = 10_000.0,
                 kv_quant: bool | None = None,
                 kv_dtype=None,
                 spill_pages: int | None = None,
                 device=None) -> None:
        super().__init__()
        from znicz_tpu.serving.batcher import TokenBudget
        from znicz_tpu.utils.config import root
        if not isinstance(model, DecodeModel):
            from znicz_tpu.export import ExportedModel
            if isinstance(model, (str, bytes)) \
                    or hasattr(model, "__fspath__"):
                model = ExportedModel.load(model, device=device)
            decode_meta = dict(model.manifest.get("decode", {}))
            explicit_k = spec_draft_k is not None
            if spec_draft_k is None:
                spec_draft_k = int(decode_meta.get(
                    "spec_draft_k",
                    root.common.engine.get("spec_draft_k", 0)))
            if drafter is None:
                drafter = decode_meta.get("drafter")
            if drafter is None:
                if explicit_k and spec_draft_k:
                    raise ValueError(
                        "spec_draft_k > 0 needs a drafter bundle "
                        "(path, ExportedModel or DecodeModel)")
                spec_draft_k = 0  # default-config engines: spec off
            model = DecodeModel(model, max_slots=max_slots,
                                max_t=max_t, max_prompt=max_prompt,
                                prompt_align=prompt_align,
                                device=device, paged=paged,
                                page_tokens=page_tokens,
                                pool_tokens=pool_tokens,
                                spec_k=int(spec_draft_k or 0),
                                kv_quant=kv_quant, kv_dtype=kv_dtype)
        self.model = model
        self.spec_k = int(model.spec_k)
        # the drafter: a SMALL published bundle (population-trained)
        # decoding through its own flat cache at the same geometry —
        # slot ids are shared with the big model, so the two caches
        # track the same sequences
        self.drafter: DecodeModel | None = None
        if self.spec_k:
            if drafter is None:
                raise ValueError(
                    "spec_draft_k > 0 needs a drafter bundle "
                    "(path, ExportedModel or DecodeModel)")
            if not isinstance(drafter, DecodeModel):
                drafter = DecodeModel(
                    drafter, max_slots=model.max_slots,
                    max_t=model.max_t, max_prompt=model.max_prompt,
                    prompt_align=model.prompt_align,
                    device=device, paged=False, spec_k=0)
            if drafter.vocab != model.vocab:
                raise ValueError(
                    f"drafter vocab {drafter.vocab} != model vocab "
                    f"{model.vocab} — the draft/verify token spaces "
                    f"must agree")
            self.drafter = drafter
        if prefix_cache is None:
            prefix_cache = bool(root.common.engine.get(
                "prefix_cache", True))
        # prefix sharing needs the page table and position-indexed
        # state only (LSTM carries summarize the WHOLE prefix in one
        # vector — nothing block-shaped to share)
        self.prefix_cache_enabled = bool(
            prefix_cache and model.paged and not model.has_lstm)
        self.prefix = (PrefixCache(model.page_tokens)
                       if self.prefix_cache_enabled else None)
        # round 22: host-DRAM spill tier behind the prefix trie —
        # cold pages leave HBM for preallocated pinned-style host
        # frames and restore through the staging-ring uploader, so
        # the shareable working set is pool_pages + spill_pages
        if spill_pages is None:
            spill_pages = int(root.common.engine.get(
                "kv_spill_pages", 0))
        self._spill = None
        if self.prefix_cache_enabled and int(spill_pages) > 0:
            from znicz_tpu.memory import HostPageTier
            self._spill = HostPageTier(model.page_shapes(),
                                       int(spill_pages))
        self._token_budget = None
        if model.paged:
            budget = (int(max_queue_tokens) if max_queue_tokens
                      else 16 * model.pool_tokens)
            self._token_budget = TokenBudget(budget)
        #: pool-exhaustion shed threshold: a full pool with a YOUNG
        #: queue is normal continuous-batching backlog (requeue and
        #: wait for a lane to drain); only a STALLED queue sheds —
        #: the same age semantics as the batcher's stall trip
        self.max_queue_age = float(max_queue_age_ms) / 1e3
        if admission not in ("continuous", "static"):
            raise ValueError(f"admission must be 'continuous' or "
                             f"'static', got {admission!r}")
        self.admission = admission
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token = eos_token
        self.temperature = float(temperature)
        self.max_queue = int(max_queue)
        self.retry_budget = max(0, int(retry_budget))
        self.breaker_failure_rate = float(breaker_failure_rate)
        self.breaker_min_samples = int(breaker_min_samples)
        self.breaker_cooldown = float(breaker_cooldown_ms) / 1e3
        self._rng = np.random.default_rng(seed)
        # telemetry: per-engine children of the canonical series
        wf_name = model.model.manifest.get("workflow", "model")
        self._obs_id = f"{wf_name}#decode{next(_DECODE_SEQ)}"
        self._m_submitted = _metrics.serving_requests(
            self._obs_id, "submitted")
        self._m_served = _metrics.serving_requests(self._obs_id,
                                                   "served")
        self._m_rejected = _metrics.serving_requests(self._obs_id,
                                                     "rejected")
        self._m_ttft = _metrics.serving_ttft_seconds(self._obs_id)
        self._m_token = _metrics.serving_token_seconds(self._obs_id)
        self._m_tok_prompt = _metrics.serving_tokens(self._obs_id,
                                                     "prompt")
        self._m_tok_gen = _metrics.serving_tokens(self._obs_id,
                                                  "generated")
        self._m_slots = _metrics.serving_decode_slots(self._obs_id)
        self._m_state = _metrics.serving_breaker_state(self._obs_id)
        self._m_state.set(_STATE_CODE[_CLOSED])
        # round 15: paged/prefix/speculation canonical series
        self._m_swap_pause = _metrics.swap_pause_seconds(self._obs_id)
        if model.paged:
            _metrics.kv_pages_total(self._obs_id).set(
                model.cache.pool_pages)
            _metrics.kv_pages_used(self._obs_id).set_function(
                model.cache.pages_used)
        # round 21: KV bytes amortized per concurrent lane — the
        # number int8 pages halve at fixed geometry (cache geometry
        # is fixed at construction, so one set() suffices)
        _metrics.kv_bytes_per_lane(self._obs_id).set(
            model.cache.nbytes() / max(1, model.max_slots))
        # round 22: migration traffic + tier occupancy + queue age
        self._m_mig_spill = _metrics.kv_page_migrations(
            self._obs_id, "spill")
        self._m_mig_restore = _metrics.kv_page_migrations(
            self._obs_id, "restore")
        if self._spill is not None:
            tier = self._spill
            _metrics.kv_spill_pages(self._obs_id).set_function(
                lambda: tier.used)
        _metrics.serving_queue_age_seconds(
            self._obs_id, pool="all").set_function(self._queue_age)
        self._m_prefix_hit = _metrics.prefix_cache_events(
            self._obs_id, "hit")
        self._m_prefix_miss = _metrics.prefix_cache_events(
            self._obs_id, "miss")
        self._m_tok_shared = _metrics.prefix_tokens(self._obs_id,
                                                    "shared")
        self._m_tok_computed = _metrics.prefix_tokens(self._obs_id,
                                                      "computed")
        self._m_spec_acc = _metrics.spec_tokens(self._obs_id,
                                                "accepted")
        self._m_spec_rej = _metrics.spec_tokens(self._obs_id,
                                                "rejected")
        self.page_truncations = 0
        #: breaker opened by pool pressure (not failures): it closes
        #: again the moment a requeued prompt admits — capacity
        #: recovery needs no cooldown, unlike a failing backend
        self._pool_tripped = False
        # exact-value windows for dashboard percentiles
        self._ttft_win: deque = deque(maxlen=4096)
        self._token_win: deque = deque(maxlen=4096)
        # round 24: per-phase latency windows fed by the request
        # traces, exported as znicz_phase_p99_seconds callback gauges
        # so SERVE_BENCH rows and /metrics read the SAME exact
        # windowed p99 (handoff only moves on the disagg subclass)
        self._phase_win: dict[str, deque] = {
            p: deque(maxlen=4096)
            for p in ("queue", "prefill", "handoff", "decode")}
        for _p, _win in self._phase_win.items():
            _metrics.phase_p99_seconds(self._obs_id, _p).set_function(
                lambda w=_win: _metrics.window_p99(w))
        _metrics.phase_p99_seconds(self._obs_id, "ttft").set_function(
            lambda w=self._ttft_win: _metrics.window_p99(w))
        _metrics.phase_p99_seconds(self._obs_id, "token").set_function(
            lambda w=self._token_win: _metrics.window_p99(w))
        #: queued prompts in priority classes (round 16): the fleet's
        #: high-priority tenants reach a KV slot before any flooded
        #: low class, FIFO within a class
        self._pending = PriorityQueue()
        self._live: list[_Live] = []
        self._cond = threading.Condition()
        self._stop = False
        self._state = _CLOSED
        self._opened_at = 0.0
        self._outcomes: deque[bool] = deque(maxlen=int(breaker_window))
        self.expired_total = 0
        self.shed_total = 0
        self.retries_total = 0
        self.warmup_compiles = 0
        self.warmup_seconds = 0.0
        self._thread: threading.Thread | None = None
        self._started = False
        # hot-swap bookkeeping (round 13): a pending swap request the
        # scheduler applies between token steps once old-model lanes
        # drained (or the engine.swap_drain_ms bound expires)
        self._swap_req: dict | None = None
        self.model_version = 0
        self._m_version = _metrics.model_version(self._obs_id)
        self._m_version.set(0)
        self._m_swap_dur = _metrics.swap_duration_seconds(self._obs_id)
        self.swap_counts = {"promoted": 0, "rejected": 0,
                            "rolled_back": 0}
        self._swap_pauses: list[float] = []

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> "DecodeEngine":
        if self._started:
            return self
        t0 = time.monotonic()
        self.warmup_compiles = self.model.warmup(
            prefix_cache=self.prefix_cache_enabled,
            page_io=self._spill is not None)
        if self.drafter is not None:
            self.warmup_compiles += self.drafter.warmup()
        self.warmup_seconds = time.monotonic() - t0
        self._thread = threading.Thread(target=self._loop,
                                        name="decode-scheduler",
                                        daemon=True)
        self._started = True
        self._thread.start()
        self.info(
            "decode '%s': %d AOT programs warmed in %.2fs (prompt "
            "buckets %s, batch buckets %s, block buckets %s, "
            "slots=%d, max_t=%d, paged=%s, prefix_cache=%s, "
            "spec_k=%d, cache=%.1f MB, donate=%s)",
            self.model.model.manifest.get("workflow", "?"),
            self.warmup_compiles, self.warmup_seconds,
            self.model.prompt_ladder(), self.model.batch_ladder(),
            self.model.block_ladder(), self.model.max_slots,
            self.model.max_t, self.model.paged,
            self.prefix_cache_enabled, self.spec_k,
            self.model.cache.nbytes() / 1e6, self.model.donating)
        return self

    def shutdown(self, timeout: float = 60.0) -> None:
        """Drain: everything admitted keeps generating to completion,
        queued prompts are served, then the scheduler exits."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        self._started = False
        if self._spill is not None:
            self._spill.shutdown()
        # a stopped engine is not shedding: clear the breaker so the
        # process-level /readyz (which scans EVERY engine child of the
        # breaker gauge) doesn't stay not-ready on a dead engine's
        # last state
        with self._cond:
            self._state = _CLOSED
            self._outcomes.clear()
            self._m_state.set(_STATE_CODE[_CLOSED])

    def __enter__(self) -> "DecodeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------
    def submit(self, prompt, *, max_new_tokens: int | None = None,
               deadline_ms: float | None = None,
               tenant: str | None = None, priority: int = 0) -> Future:
        """Enqueue a prompt (1-D array of token ids); returns a future
        of the generated ids (np.int32, the first sampled token
        onward).  Raises :class:`QueueFull` under backpressure,
        :class:`Overloaded` while the breaker sheds, and the future
        fails with :class:`DeadlineExceeded` if ``deadline_ms`` passes
        before the first token (TTFT deadline).  ``tenant`` /
        ``priority`` (round 16): queued prompts admit to KV slots in
        strict priority order, and a token-budget-full queue sheds the
        NEWEST strictly lower-priority queued prompts to make room for
        a higher-priority arrival."""
        if not self._started:
            raise RuntimeError("engine not started — call start()")
        prompt = np.asarray(np.round(np.asarray(prompt, np.float64)),
                            np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size > self.model.max_prompt:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds max_prompt "
                f"{self.model.max_prompt} — truncate client-side")
        if deadline_ms is not None and deadline_ms <= 0:
            raise DeadlineExceeded(
                f"deadline_ms={deadline_ms} already expired at submit")
        req = _PromptReq(prompt,
                         max_new_tokens or self.max_new_tokens,
                         deadline_ms, tenant=tenant, priority=priority)
        preempted: list[_PromptReq] = []
        with self._cond:
            if self._stop:
                raise RuntimeError("engine is shut down")
            self._breaker_tick(time.monotonic())
            if self._state == _OPEN:
                self.shed_total += 1
                _metrics.serving_requests(self._obs_id, "shed").inc()
                self._m_rejected.inc()
                req.trace.event("breaker_shed", engine=self._obs_id)
                self._finish_trace(req, "shed")
                raise Overloaded(
                    "circuit breaker open — new prompts shed while "
                    "in-flight decodes drain (retry after "
                    f"{self.breaker_cooldown * 1e3:.0f}ms)")
            if len(self._pending) >= self.max_queue:
                self._m_rejected.inc()
                self._finish_trace(req, "shed")
                raise QueueFull(
                    f"decode queue full ({len(self._pending)} prompts "
                    f"pending, limit {self.max_queue})")
            if self._token_budget is not None:
                # token-denominated admission: the queue is bounded by
                # the WORK it holds (prompt + budget tokens), not the
                # request count — the bound that matches a pool whose
                # capacity is tokens
                want = req.n + req.max_new
                if not self._token_budget.try_acquire(want):
                    # preemptive admission (round 16): shed queued
                    # prompts of strictly LOWER priority, newest
                    # first, when that frees enough budget — the
                    # flooding class absorbs its own overload
                    preempted = self._make_budget_room(req, want)
                    if not self._token_budget.try_acquire(want):
                        self._m_rejected.inc()
                        self._finish_trace(req, "shed")
                        raise QueueFull(
                            f"decode token budget full "
                            f"({self._token_budget.used} of "
                            f"{self._token_budget.capacity} tokens "
                            f"held; request wants {want})")
                req.charged = want
            self._pending.append(req)
            self._cond.notify_all()
        for victim in preempted:  # fail outside the condition
            victim.trace.event("preempted", engine=self._obs_id)
            self._finish_trace(victim, "shed")
            if not victim.future.done():
                victim.future.set_exception(Overloaded(
                    "preempted by higher-priority traffic while the "
                    "decode token budget was full"))
        self._m_submitted.inc()
        return req.future

    def _make_budget_room(self, req: _PromptReq,
                          want: int) -> list[_PromptReq]:
        """Evict queued (never live) strictly lower-priority prompts,
        newest first, until ``want`` tokens could be acquired; returns
        the victims (their futures are failed by the caller outside
        the lock).  Call under ``_cond``."""
        victims: list[_PromptReq] = []
        if self._token_budget is None:
            return victims
        evictable = sorted(
            (r for r in self._pending
             if r.priority > req.priority and r.charged),
            key=lambda r: r.t_submit, reverse=True)
        if sum(r.charged for r in evictable) \
                + self._token_budget.available < want:
            return victims  # preemption cannot make room — shed req
        for victim in evictable:
            if self._token_budget.available >= want:
                break
            victims.append(victim)
            self._refund(victim)
            self.shed_total += 1
            _metrics.serving_requests(self._obs_id, "shed").inc()
        removed = set(map(id, victims))
        self._pending.sweep(lambda r: id(r) in removed)
        return victims

    def _refund(self, req: _PromptReq) -> None:
        if req.charged and self._token_budget is not None:
            self._token_budget.release(req.charged)
            req.charged = 0

    def generate(self, prompt, timeout: float | None = None,
                 **kwargs) -> np.ndarray:
        """Synchronous convenience: submit + wait."""
        return self.submit(prompt, **kwargs).result(timeout=timeout)

    # ------------------------------------------------------------------
    # weight hot-swap (round 13)
    # ------------------------------------------------------------------
    def current_bundle(self) -> tuple:
        """The live ``(manifest, params)`` — the rollback target a
        SwapController snapshots before promoting."""
        return (self.model.model.manifest,
                dict(self.model.model._params))

    def swap_weights(self, state, *, version: int | None = None,
                     drain_ms: float | None = None,
                     timeout: float | None = None,
                     outcome: str = "promoted") -> dict:
        """Hot-swap the decode weights without recompiling.

        In-flight generations belong to the OLD model: the scheduler
        stops admitting new prompts, lets live KV-cache slots decode
        to completion, and only then publishes the new weight pytree —
        so no sequence ever mixes two models' logits.  Lanes still
        live after ``drain_ms`` (default ``engine.swap_drain_ms``) are
        evicted with their tokens-so-far rather than holding the swap
        hostage.  Queued prompts are admitted AFTER the flip and
        prefill against the new model.

        Raises :class:`~znicz_tpu.export.SwapIncompatible` (validated
        before any drain starts — the incumbent keeps serving)."""
        from znicz_tpu.serving.engine import resolve_swap_state
        from znicz_tpu.utils.config import root
        manifest, params = resolve_swap_state(state)
        # fail BEFORE draining anything: an incompatible candidate
        # must not pause admission for even a millisecond
        self.model.check_compatible(manifest, params)
        if drain_ms is None:
            drain_ms = float(root.common.engine.get(
                "swap_drain_ms", 2000.0))
        t0 = time.monotonic()
        if not self._started:
            self.model.swap_weights(params, manifest=manifest)
            drain = {"drained": 0, "evicted": 0, "drain_ms": 0.0}
        else:
            fut: Future = Future()
            with self._cond:
                if self._swap_req is not None:
                    raise RuntimeError(
                        "a weight swap is already in progress")
                self._swap_req = {
                    "manifest": manifest, "params": params,
                    "deadline": t0 + float(drain_ms) / 1e3,
                    "future": fut, "t0": t0,
                    "live0": len(self._live)}
                self._cond.notify_all()
            drain = fut.result(
                timeout if timeout is not None
                else max(60.0, float(drain_ms) / 1e3 + 60.0))
        pause = time.monotonic() - t0
        if version is None:
            version = self.model_version + 1
        self.model_version = int(version)
        self._m_version.set(self.model_version)
        self._m_swap_dur.observe(pause)
        self._swap_pauses.append(pause)
        self.record_swap_outcome(outcome)
        self.info(
            "decode weights hot-swapped → version %d (%s, %.1f ms "
            "pause, %d lanes drained, %d evicted at the drain bound)",
            self.model_version, outcome, 1e3 * pause,
            drain.get("drained", 0), drain.get("evicted", 0))
        return {"version": self.model_version, "outcome": outcome,
                "pause_ms": round(1e3 * pause, 3),
                "weights_version": self.model.weights_version,
                **drain}

    def record_swap_outcome(self, outcome: str) -> None:
        self.swap_counts[outcome] = self.swap_counts.get(outcome, 0) + 1
        _metrics.swaps_total(self._obs_id, outcome).inc()
        _recorder.record("swap", engine=self._obs_id, outcome=outcome,
                         version=self.model_version)

    def set_model_version(self, version: int) -> None:
        """Label the CURRENTLY loaded bundle's published version."""
        self.model_version = int(version)
        self._m_version.set(self.model_version)

    def swap_pauses_ms(self) -> list[float]:
        return [1e3 * p for p in self._swap_pauses]

    def _maybe_apply_swap(self, force: bool = False) -> None:
        """Scheduler-thread half of the swap: once no old-model lane
        is live (or the drain deadline / shutdown forces it), evict
        stragglers with their tokens-so-far, flip the weight pytree,
        and resume admission."""
        req = self._swap_req
        if req is None:
            return
        now = time.monotonic()
        if self._live and not force and now < req["deadline"]:
            return  # still draining old-model generations
        evicted = 0
        for s in self._live:  # drain bound hit: return tokens-so-far
            self._finish(s)
            evicted += 1
        self._live = []
        self._m_slots.set(0)
        try:
            self.model.swap_weights(req["params"],
                                    manifest=req["manifest"])
        except Exception as exc:  # noqa: BLE001 — report to the caller
            req["future"].set_exception(exc)
        else:
            req["future"].set_result({
                "drained": req.get("live0", 0) - evicted,
                "evicted": evicted,
                "drain_ms": round(1e3 * (now - req["t0"]), 3)})
        if self.prefix is not None:
            # cached K/V are functions of the OLD weights: every
            # shared prefix page is stale the instant the flip lands
            dropped = self.prefix.clear(self.model.cache,
                                        tier=self._spill)
            if dropped:
                self.info("prefix cache invalidated by weight swap "
                          "(%d cached blocks dropped)", dropped)
        with self._cond:
            # admission-eligible TTFT (round 15): the drain pause is
            # a swap-policy cost, not serving latency — queued
            # requests' TTFT/deadline clocks shift past it, and the
            # pause itself lands on its own canonical counter
            pause_end = time.monotonic()
            self._m_swap_pause.inc(max(0.0, pause_end - req["t0"]))
            for r in self._pending:
                paused = max(0.0, pause_end
                             - max(r.t_submit, req["t0"]))
                r.pause_s += paused
                if paused > 0.0:
                    r.trace.event("swap_pause", engine=self._obs_id,
                                  pause_ms=round(1e3 * paused, 3))
            self._swap_req = None
            self._cond.notify_all()

    # ------------------------------------------------------------------
    # breaker (under _cond)
    # ------------------------------------------------------------------
    def _transition(self, state: str) -> None:
        if state == self._state:
            return
        self.warning("decode breaker %s → %s", self._state, state)
        _recorder.record("breaker", engine=self._obs_id,
                         src=self._state, to=state)
        self._state = state
        if state == _OPEN:
            self._opened_at = time.monotonic()
        self._m_state.set(_STATE_CODE[state])
        _metrics.serving_breaker_transitions(self._obs_id, state).inc()

    def _breaker_tick(self, now: float) -> None:
        if self._state == _OPEN \
                and now - self._opened_at >= self.breaker_cooldown:
            self._transition(_HALF_OPEN)

    def _record_outcome(self, ok: bool) -> None:
        with self._cond:
            if self._state == _HALF_OPEN:
                self._transition(_CLOSED if ok else _OPEN)
                self._outcomes.clear()
                return
            self._outcomes.append(ok)
            n = len(self._outcomes)
            if n >= self.breaker_min_samples:
                rate = self._outcomes.count(False) / n
                if rate >= self.breaker_failure_rate \
                        and self._state != _OPEN:
                    self.warning("decode breaker tripped: failure "
                                 "rate %.0f%% over %d dispatches",
                                 100 * rate, n)
                    self._transition(_OPEN)
                    self._outcomes.clear()

    # ------------------------------------------------------------------
    # scheduler
    # ------------------------------------------------------------------
    def _sample(self, logits: np.ndarray) -> int:
        if self.temperature <= 0:
            return int(np.argmax(logits))
        z = logits / self.temperature
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _sweep_expired(self, now: float) -> None:
        """TTFT deadline: fail-fast queued prompts whose deadline
        passed — they never reach prefill or occupy a slot.  Call
        under ``_cond``.  Deadlines stamp from admission-ELIGIBLE
        time: while a swap drain pauses admission the clock is
        stopped (the pause lands on each queued request's ``pause_s``
        when the flip completes)."""
        if self._swap_req is not None:
            return  # admission paused: nobody's clock is running
        if not any(r.deadline is not None for r in self._pending):
            return
        for req in self._pending.sweep(lambda r: r.expired(now)):
            self.expired_total += 1
            _metrics.serving_requests(self._obs_id,
                                      "expired").inc()
            self._refund(req)
            req.trace.event("deadline_evicted", engine=self._obs_id)
            self._finish_trace(req, "expired")
            req.future.set_exception(DeadlineExceeded(
                f"TTFT deadline passed after "
                f"{(now - req.t_submit - req.pause_s) * 1e3:.0f}ms "
                f"admission-eligible in queue"))

    def _chaos(self) -> None:
        spike = _faults.fire("serving.latency_spike")
        if spike is not None:
            time.sleep(float(spike.get("ms", 50.0)) / 1e3)
        if _faults.fire("serving.program_error") is not None:
            raise _faults.FaultInjected(
                "injected decode program failure")

    def _dispatch(self, fn, *args):
        """Run one program dispatch under the retry budget + breaker
        accounting.  Retries re-run against unchanged cache state —
        legal only when buffers are NOT donated (the host keeps valid
        references); under donation a failed dispatch is terminal."""
        attempts = 0
        budget = 0 if self.model.donating else self.retry_budget
        while True:
            try:
                self._chaos()
                out = fn(*args)
            except Exception:
                self._record_outcome(False)
                if attempts >= budget:
                    raise
                attempts += 1
                self.retries_total += 1
                _metrics.serving_requests(self._obs_id,
                                          "retried").inc()
                continue
            self._record_outcome(True)
            if attempts:
                _metrics.recoveries("serving_retry").inc()
            return out

    # -- request-trace plumbing (round 24) ------------------------------
    def _end_phase(self, req: _PromptReq, phase: str, **args) -> float:
        """Close one trace phase and feed the engine's windowed-p99
        gauge for it from the SAME measurement."""
        dur = req.trace.phase_end(phase, engine=self._obs_id, **args)
        if dur > 0.0:
            win = self._phase_win.get(phase)
            if win is not None:
                win.append(dur)
        return dur

    def _finish_trace(self, req: _PromptReq, outcome: str) -> None:
        _metrics.trace_requests(self._obs_id, outcome).inc()
        req.trace.finish(outcome)

    def _release_lane(self, live: _Live) -> None:
        if self.model.paged:
            self.model.cache.release_slot_pages(live.slot)
        self.model.cache.release(live.slot)
        self._refund(live.req)

    def _finish(self, live: _Live) -> None:
        self._release_lane(live)
        self._m_served.inc()
        self._end_phase(live.req, "decode",
                        tokens=len(live.generated))
        self._finish_trace(live.req, "ok")
        if not live.req.future.done():
            live.req.future.set_result(
                np.asarray(live.generated, np.int32))

    def _fail_lane(self, live: _Live, exc: Exception) -> None:
        self._release_lane(live)
        self._finish_trace(live.req, "failed")
        if not live.req.future.done():
            live.req.future.set_exception(exc)

    def _admit_cleanup(self, req: _PromptReq, slot: int,
                       exc: Exception) -> None:
        if self.model.paged:
            self.model.cache.release_slot_pages(slot)
        self.model.cache.release(slot)
        self._refund(req)
        self.warning("prefill failed: %s", exc)
        self._finish_trace(req, "failed")
        if not req.future.done():
            req.future.set_exception(exc)

    def _post_prefill(self, req: _PromptReq, slot: int,
                      logits: np.ndarray) -> None:
        """Shared admission bookkeeping once a prompt's first logits
        exist: trie registration, TTFT (admission-eligible clock),
        first sample, live-lane creation."""
        if self.prefix is not None:
            self.prefix.insert(req.tokens,
                               self.model.cache.tables[slot],
                               self.model.cache)
        token = self._sample(logits)
        self._end_phase(req, "prefill", tokens=req.n)
        req.trace.phase_begin("decode")
        ttft = time.monotonic() - req.t_submit - req.pause_s
        # stamp TTFT onto the future: the fleet's per-tenant latency
        # observes generation requests at TTFT (the admission-bound
        # SLO — completion time is work-proportional, round-12 split)
        req.future.ttft_s = ttft
        self._m_ttft.observe(ttft)
        self._ttft_win.append(ttft)
        self._m_tok_prompt.inc(req.n)
        self._m_tok_gen.inc()
        live = _Live(req, slot, token)
        if (self.eos_token is not None and token == self.eos_token) \
                or req.max_new <= 1:
            self._finish(live)
            return
        self._live.append(live)
        self._m_slots.set(len(self._live))

    def _admit_prefilled(self, req: _PromptReq, slot: int,
                         matched: int) -> None:
        """Single-prompt prefill dispatch for a slot whose pages are
        already set up (``matched`` tokens ride shared pages)."""
        self._end_phase(req, "queue")
        req.trace.phase_begin("prefill")
        try:
            with _tracing.TRACER.span("prefill", cat="serving",
                                      tokens=req.n, shared=matched):
                logits = self._dispatch(self.model.run_prefill,
                                        req.tokens[matched:], slot,
                                        matched)
                if self.drafter is not None:
                    # the drafter tracks the FULL prompt through its
                    # own flat cache (it is tiny — sharing buys
                    # nothing there)
                    self._dispatch(self.drafter.run_prefill,
                                   req.tokens, slot)
        except Exception as exc:  # noqa: BLE001 — isolate the prompt
            self._admit_cleanup(req, slot, exc)
            return
        self._post_prefill(req, slot, logits)

    def _admit_window(self, group: list[tuple]) -> None:
        """Admission coalescing (round 15): a burst of prompts whose
        unshared tails fit one ``prompt_align`` window — the
        steady-state shape of prefix-hit system-prompt traffic — pays
        ONE batched window dispatch instead of one prefill each."""
        w_len = self.model.prompt_align
        n = len(group)
        windows = np.zeros((n, w_len), np.int32)
        slots = np.empty((n,), np.int32)
        starts = np.empty((n,), np.int32)
        lengths = np.empty((n,), np.int32)
        for i, (req, slot, matched) in enumerate(group):
            tail = req.tokens[matched:]
            windows[i, :len(tail)] = tail
            slots[i] = slot
            starts[i] = matched
            lengths[i] = len(tail)
        for req, _slot, _m in group:
            self._end_phase(req, "queue")
            req.trace.phase_begin("prefill")
        try:
            with _tracing.TRACER.span("prefill_window", cat="serving",
                                      lanes=n, w=w_len):
                logits = self._dispatch(
                    self.model.run_window, windows, slots, starts,
                    lengths, "serving-prefill")
                if self.drafter is not None:
                    for req, slot, _m in group:
                        self._dispatch(self.drafter.run_prefill,
                                       req.tokens, slot)
        except Exception as exc:  # noqa: BLE001 — isolate the wave
            for req, slot, _m in group:
                self._admit_cleanup(req, slot, exc)
            return
        for i, (req, slot, _m) in enumerate(group):
            self._post_prefill(req, slot,
                               logits[i, int(lengths[i]) - 1])

    def _admit_many(self, reqs: list[_PromptReq]) -> list[_PromptReq]:
        """Admit a wave of prompts; returns the suffix to requeue
        when the page pool cannot hold one (order preserved — nothing
        is dropped or reordered past the blocked head).

        Prompts are matched against the trie IN ORDER, and a prefix
        MISS dispatches (and registers its blocks) immediately — so
        the second system-prompt request of a burst already shares
        the first one's pages, within one admission wave.  The
        prefix-hit tails then coalesce into one batched window
        dispatch."""
        model = self.model
        window: list[tuple] = []
        requeue: list[_PromptReq] = []
        for i, req in enumerate(reqs):
            slot = model.cache.acquire()
            matched = 0
            if model.paged:
                try:
                    matched = self._setup_pages(slot, req.tokens,
                                                req.max_new)
                except PoolExhausted:
                    model.cache.release(slot)
                    requeue = list(reqs[i:])
                    break
                if self._pool_tripped:
                    # capacity is back: resume taking traffic NOW
                    with self._cond:
                        self._pool_tripped = False
                        if self._state == _OPEN:
                            self._transition(_CLOSED)
            # the batched window path needs the paged window program
            # family (compiled when the prefix cache is on) and a
            # tail that fits the prompt_align window
            if (self.prefix is not None
                    and not model.has_lstm
                    and 0 < req.n - matched <= model.prompt_align):
                window.append((req, slot, matched))
            else:
                self._admit_prefilled(req, slot, matched)
        if len(window) == 1:
            self._admit_prefilled(*window[0])
        elif window:
            self._admit_window(window)
        return requeue

    def _emit_tokens(self, s: _Live, tokens: list[int],
                     now: float) -> bool:
        """Append emitted tokens to a lane (speculative steps emit
        several per dispatch); returns True when the lane finished
        (EOS / budget / max-T)."""
        dt = (now - s.t_last) / max(1, len(tokens))
        done = False
        for tok in tokens:
            s.pos += 1
            s.generated.append(int(tok))
            self._m_token.observe(dt)
            self._token_win.append(dt)
            self._m_tok_gen.inc()
            if ((self.eos_token is not None
                 and int(tok) == self.eos_token)
                    or len(s.generated) >= s.req.max_new
                    or s.pos >= self.model.max_t):
                done = True
                break
        s.t_last = now
        return done

    def _step(self) -> None:
        """One continuous-batching token step over every live lane.
        No page bookkeeping here: admission reserved every block a
        real token can land in, so the hot loop is pure dispatch."""
        live = self._live
        if not live:
            return
        tokens = np.asarray([s.generated[-1] for s in live], np.int32)
        slots = np.asarray([s.slot for s in live], np.int32)
        positions = np.asarray([s.pos for s in live], np.int32)
        try:
            with _tracing.TRACER.span("decode_step", cat="serving",
                                      lanes=len(live)):
                logits = self._dispatch(self.model.run_decode,
                                        tokens, slots, positions)
        except Exception as exc:  # noqa: BLE001 — the step is shared
            self.warning("decode step failed for %d lanes: %s",
                         len(live), exc)
            for s in live:
                self._fail_lane(s, exc)
            self._live = []
            self._m_slots.set(0)
            return
        now = time.monotonic()
        still: list[_Live] = []
        for i, s in enumerate(live):
            token = self._sample(logits[i])
            if self._emit_tokens(s, [token], now):
                self._finish(s)
            else:
                still.append(s)
        self._live = still
        self._m_slots.set(len(still))

    # ------------------------------------------------------------------
    # speculative decoding (round 15): draft k with the population
    # drafter, verify the window in ONE batched big-model forward
    # ------------------------------------------------------------------
    def _softmax(self, logits: np.ndarray) -> np.ndarray:
        z = logits / max(self.temperature, 1e-9)
        z = z - z.max(axis=-1, keepdims=True)
        p = np.exp(z)
        return p / p.sum(axis=-1, keepdims=True)

    def _accept_lane(self, vlogits: np.ndarray, drafts: np.ndarray,
                     qrow: np.ndarray | None) -> tuple[list[int], int]:
        """Leviathan accept/reject for one lane.  ``vlogits``
        (k+1, V) verifier logits, ``drafts`` (k,) drafted ids,
        ``qrow`` (k, V) drafter probabilities (sampled mode only).
        Returns ``(emitted_tokens, accepted_draft_count)``.  Greedy:
        accept while the verifier's argmax equals the draft, emit the
        verifier's token at the first mismatch — byte-identical to
        non-speculative greedy by construction.  No bonus token is
        emitted on a full accept: the drafter never consumed the last
        draft, so the next round feeds it instead (state stays exact
        with zero catch-up dispatches)."""
        emitted: list[int] = []
        accepted = 0
        for i in range(self.spec_k):
            d = int(drafts[i])
            if qrow is None:  # greedy
                g = int(np.argmax(vlogits[i]))
                emitted.append(g)
                if g != d:
                    break
                accepted += 1
            else:  # temperature: exact rejection sampling
                p = self._softmax(vlogits[i])
                q = qrow[i]
                if self._rng.random() < min(
                        1.0, float(p[d]) / max(float(q[d]), 1e-12)):
                    emitted.append(d)
                    accepted += 1
                    continue
                resid = np.maximum(p - q, 0.0)
                total = resid.sum()
                probs = resid / total if total > 0 else p
                emitted.append(int(self._rng.choice(len(p), p=probs)))
                break
        return emitted, accepted

    def _step_spec(self) -> None:
        """One speculative step: k drafter tokens per lane, one
        batched verification forward, 1..k tokens emitted per lane."""
        k = self.spec_k
        # no page bookkeeping: admission reserved every block a REAL
        # token can land in; the verify window's overhang past the
        # reservation holds only discardable draft overflow, and the
        # table routes those writes to the trash page by construction
        live = self._live
        if not live:
            return
        n = len(live)
        slots = np.asarray([s.slot for s in live], np.int32)
        base_pos = np.asarray([s.pos for s in live], np.int32)
        cur = np.asarray([s.generated[-1] for s in live], np.int32)
        drafts = np.empty((n, k), np.int32)
        qprobs = (np.empty((n, k, self.model.vocab), np.float64)
                  if self.temperature > 0 else None)
        try:
            with _tracing.TRACER.span("spec_draft", cat="serving",
                                      lanes=n, k=k):
                for j in range(k):
                    dlogits = self._dispatch(self.drafter.run_decode,
                                             cur, slots, base_pos + j)
                    if qprobs is None:
                        nxt = np.argmax(dlogits, axis=1)
                    else:
                        q = self._softmax(dlogits)
                        qprobs[:, j] = q
                        nxt = np.asarray(
                            [self._rng.choice(q.shape[1], p=q[i])
                             for i in range(n)])
                    drafts[:, j] = nxt
                    cur = nxt.astype(np.int32)
            windows = np.concatenate(
                [np.asarray([[s.generated[-1]] for s in live],
                            np.int32), drafts], axis=1)
            with _tracing.TRACER.span("spec_verify", cat="serving",
                                      lanes=n, k=k):
                vlogits = self._dispatch(self.model.run_verify,
                                         windows, slots, base_pos)
        except Exception as exc:  # noqa: BLE001 — the step is shared
            self.warning("speculative step failed for %d lanes: %s",
                         n, exc)
            for s in live:
                self._fail_lane(s, exc)
            self._live = []
            self._m_slots.set(0)
            return
        now = time.monotonic()
        still: list[_Live] = []
        for i, s in enumerate(live):
            emitted, accepted = self._accept_lane(
                vlogits[i], drafts[i],
                None if qprobs is None else qprobs[i])
            self._m_spec_acc.inc(accepted)
            self._m_spec_rej.inc(k - accepted)
            if self._emit_tokens(s, emitted, now):
                self._finish(s)
            else:
                still.append(s)
        self._live = still
        self._m_slots.set(len(still))

    def _loop(self) -> None:
        while True:
            admit: list[_PromptReq] = []
            with self._cond:
                while (not self._pending and not self._live
                       and not self._stop and self._swap_req is None):
                    self._cond.wait(timeout=0.25)
                    self._sweep_expired(time.monotonic())
                if self._stop and not self._pending and not self._live:
                    # a swap still pending at shutdown applies now —
                    # its caller is blocked on the future
                    self._maybe_apply_swap(force=True)
                    return
                now = time.monotonic()
                self._sweep_expired(now)
                self._breaker_tick(now)
                # during a swap drain NOTHING is admitted: queued
                # prompts wait for the flip and prefill against the
                # NEW model — a slot freed by an old-model eviction
                # never admits a new-model prompt early
                may_admit = (self._swap_req is None
                             and (self.admission == "continuous"
                                  or not self._live))
                # bound by the free-slot count HERE — slots are only
                # acquired inside _admit, so the live count cannot
                # gate this loop
                free = self.model.cache.free_slots
                while (may_admit and self._pending
                       and len(admit) < free):
                    admit.append(self._pending.popleft())
            # admissions coalesce: prefix-hit tails share one batched
            # window dispatch; pool exhaustion returns the blocked
            # suffix in order — nothing is dropped silently
            requeue = self._admit_many(admit)
            if requeue:
                with self._cond:
                    self._pending.requeue_front(requeue)
                    if self._live or self._swap_req is not None:
                        # token-capacity overload: a young backlog
                        # just waits for draining lanes to release
                        # pages; a STALLED one (head older than
                        # max_queue_age) sheds new prompts through
                        # the breaker until capacity returns
                        blocked = self._pending.peek()
                        head_age = (time.monotonic()
                                    - blocked.t_submit
                                    - blocked.pause_s)
                        if self._state == _CLOSED \
                                and head_age > self.max_queue_age:
                            self.warning(
                                "page pool exhausted (%d/%d pages "
                                "free, head queued %.1fs): shedding "
                                "new prompts while %d lanes drain",
                                self.model.cache.free_pages,
                                self.model.cache.pool_pages, head_age,
                                len(self._live))
                            self._transition(_OPEN)
                            self._pool_tripped = True
                        head = None
                    else:
                        # no lane will ever free a page — the prompt
                        # cannot fit this pool, period
                        head = self._pending.popleft()
                if head is not None:
                    self._refund(head)
                    self._m_rejected.inc()
                    if not head.future.done():
                        head.future.set_exception(PoolExhausted(
                            f"prompt of {head.n} tokens cannot fit "
                            f"the {self.model.cache.pool_pages}-page "
                            f"pool even with every lane drained and "
                            f"the prefix cache evicted"))
            if self._live:
                if self.spec_k and all(
                        s.pos + self.spec_k < self.model.max_t
                        for s in self._live):
                    self._step_spec()
                else:
                    self._step()
            self._maybe_apply_swap()

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def _queue_age(self) -> float:
        """Age of the oldest queued prompt (seconds) — the gauge's
        read callback.  Racy peek without the lock is fine: the
        scrape tolerates one-request staleness."""
        try:
            head = self._pending.peek()
        except RuntimeError:  # dict mutated mid-iteration
            return 0.0
        if head is None:
            return 0.0
        return max(0.0, time.monotonic() - head.t_submit
                   - head.pause_s)

    def stats(self) -> dict:
        from znicz_tpu.serving.engine import _percentile

        def window(win):
            vals = sorted(win)
            if not vals:
                return {}
            return {"p50": round(1e3 * _percentile(vals, 50), 3),
                    "p95": round(1e3 * _percentile(vals, 95), 3),
                    "p99": round(1e3 * _percentile(vals, 99), 3),
                    "mean": round(1e3 * sum(vals) / len(vals), 3),
                    "window": len(vals)}

        spec_acc = int(self._m_spec_acc.value)
        spec_rej = int(self._m_spec_rej.value)
        out = {
            "engine": ("decode-paged-aot" if self.model.paged
                       else "decode-bucketed-aot"),
            "admission": self.admission,
            "max_slots": self.model.max_slots,
            "max_t": self.model.max_t,
            "paged": self.model.paged,
            "page_tokens": (self.model.page_tokens
                            if self.model.paged else None),
            "pages": ({
                "total": self.model.cache.pool_pages,
                "used": self.model.cache.pages_used(),
                "pool_tokens": self.model.pool_tokens,
                "page_truncations": self.page_truncations,
            } if self.model.paged else None),
            "prefix_cache": ({
                "nodes": self.prefix.nodes,
                "hits": int(self._m_prefix_hit.value),
                "misses": int(self._m_prefix_miss.value),
                "shared_tokens": int(self._m_tok_shared.value),
                "computed_tokens": int(self._m_tok_computed.value),
                "spilled_nodes": self.prefix.spilled_nodes(),
                "spill_pages_used": (self._spill.used
                                     if self._spill else 0),
                "spill_capacity": (self._spill.capacity
                                   if self._spill else 0),
                "migrations": {
                    "spill": int(self._m_mig_spill.value),
                    "restore": int(self._m_mig_restore.value),
                },
            } if self.prefix is not None else None),
            "speculative": ({
                "draft_k": self.spec_k,
                "drafter": self.drafter.model.manifest.get(
                    "workflow", "?"),
                "accepted": spec_acc,
                "rejected": spec_rej,
                "accept_rate": round(
                    spec_acc / max(1, spec_acc + spec_rej), 3),
            } if self.spec_k else None),
            "prompt_buckets": self.model.prompt_ladder(),
            "batch_buckets": self.model.batch_ladder(),
            "block_buckets": self.model.block_ladder(),
            "programs_compiled": self.model.compile_count
            + (self.drafter.compile_count if self.drafter else 0),
            "programs_loaded": getattr(self.model, "load_count", 0)
            + (getattr(self.drafter, "load_count", 0)
               if self.drafter else 0),
            "programs_live": self.model.programs_live
            + (self.drafter.programs_live if self.drafter else 0),
            "warmup_seconds": round(self.warmup_seconds, 3),
            "cache_bytes": self.model.cache.nbytes(),
            "kv_bytes_per_lane": self.model.cache.nbytes()
            // max(1, self.model.max_slots),
            "quant": ({
                "weights": ("int8" if getattr(self.model.model,
                                              "_qkeys", None)
                            else "f32"),
                "kv_pages": ("int8" if self.model.kv_quant
                             else str(self.model.kv_dtype)),
            } if (self.model.kv_quant
                  or getattr(self.model.model, "_qkeys", None))
                else None),
            "submitted": int(self._m_submitted.value),
            "served": int(self._m_served.value),
            "rejected": int(self._m_rejected.value),
            "model_version": self.model_version,
            "weights_version": self.model.weights_version,
            "swaps": dict(self.swap_counts),
            "tokens_prompt": int(self._m_tok_prompt.value),
            "tokens_generated": int(self._m_tok_gen.value),
            "live_slots": len(self._live),
            "queued_prompts": len(self._pending),
            "ttft_ms": window(self._ttft_win),
            "token_ms": window(self._token_win),
            "resilience": {
                "breaker": self._state,
                "retry_budget": self.retry_budget,
                "retried": self.retries_total,
                "expired": self.expired_total,
                "shed": self.shed_total,
            },
            "token_budget": ({
                "capacity": self._token_budget.capacity,
                "used": self._token_budget.used,
                "over_released": self._token_budget.over_released,
            } if self._token_budget is not None else None),
        }
        from . import aot_cache as _aot
        out["aot_cache"] = _aot.status()
        return out

    @property
    def breaker_state(self) -> str:
        return self._state

    def ready(self) -> bool:
        return bool(self._started and self._state != _OPEN)

    def serving_status(self) -> dict:
        """``web_status.gather_status`` hook."""
        out = {"name": f"decode:{self.model.model.manifest.get('workflow', '?')}",
               "initialized": self._started,
               "stopped": not self._started}
        out.update(self.stats())
        return out
