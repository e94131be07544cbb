"""Continuous-batching serving engine over the Ralloc paged arena.

The engine owns the *mechanism*:
  * an ``AllocState`` whose blocks are KV pages (1 block = 1 page, so the
    position-independent offsets the allocator returns *are* page ids);
  * the decode step built by ``serving.decode`` (shard_map TP);
  * per-lane transient state (``serving.lane_state``) and the shared
    prefix cache (``serving.prefix_cache``).

Policy lives in ``serving.scheduler``: admission with a bounded wait
queue, arrivals/finishes interleaved with batched decode, and the
group-commit cadence for the publish queue below.

Page allocation happens lazily: a lane that crosses a page boundary gets
a fresh page from the allocator (vectorized ``alloc`` over all lanes —
the rank-indexed cache makes the common step allocation-free).  Evicted
sessions free their pages in one vectorized ``free``.

Group-commit publish: span-path publications split into a transient half
(``queue_publish`` — cache entry + prefix lease, effective immediately)
and a durable half parked in ``_publish_queue``; ``flush_publishes``
lands N queued records with ONE vectorized block allocation, one chained
``PrefixStore.append_batch`` and ONE root swing — the device mirror of
``core.prefix_index.publish_batch``'s single-fence-pair group commit.

Recoverability (paper §4.5 transplanted to inference): the persistent
fields of the allocator plus each session's block-table row (the "page
table", reachable from the session root) survive a crash; ``recover()``
rebuilds every transient allocator structure with the vectorized
mark–sweep and the engine resumes mid-generation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import jax_alloc as ja
from ..core import jax_recovery as jr
from ..core.prefix_index import hash_tokens
from ..core.prefix_trie import fingerprint, page_hashes
from ..models.config import ModelConfig
from . import decode as dec
from .lane_state import LaneStates, Session, reset_lane
from .prefix_store import PrefixStore
from .prefix_trie_cache import CacheNode, PrefixTrieCache
from .scheduler import EngineBusy, PendingPublish

__all__ = ["ServingEngine", "Session", "EngineBusy", "PAGE_CLS",
           "arena_config"]

PAGE_CLS = 0

# Engine metrics (cached at import; see repro.obs conventions).
# ``device.*`` counts invocations of the jit-compiled allocator wrappers
# (the device-side fast path is inside the trace and unobservable from
# the host — the host FreeRunIndex carries the per-bucket placement
# metrics); ``engine.publish_*`` tracks the group-commit queue.
_OBS_DEV_ALLOC = obs.counter("device.alloc_calls")
_OBS_DEV_ALLOC_LARGE = obs.counter("device.alloc_large_calls")
_OBS_DEV_TRIM = obs.counter("device.trim_calls")
_OBS_SPAN_RESERVE_FAIL = obs.counter("device.span_reserve_failed")
_OBS_PUB_QUEUED = obs.counter("engine.publish_queued")
_OBS_PUB_FLUSHES = obs.counter("engine.publish_flushes")
_OBS_PUB_DEPTH = obs.gauge("engine.publish_queue_depth")
_OBS_PUB_BATCH = obs.histogram("engine.publish_batch_size")


def arena_config(cfg: ModelConfig, lanes: int, max_seq: int,
                 pages_per_sb: int = 16) -> ja.ArenaConfig:
    """The KV arena an engine of this shape allocates from (1 block =
    1 page; the decode state holds ``num_sbs * sb_words + 1`` pages, the
    last one the dump page).

    A whole number of superblocks per lane, so that a decode-ahead span
    (max_seq pages rounded UP to superblocks by alloc_large) always fits
    for every lane at once — per-page slack alone would under-provision
    the superblock rounding."""
    per_lane_sbs = -(-(max_seq // cfg.page_size + 2) // pages_per_sb)
    return ja.ArenaConfig(num_sbs=lanes * per_lane_sbs + 1,
                          sb_words=pages_per_sb, class_words=(1,),
                          cache_cap=max(64, 2 * lanes))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, mesh, params, *, lanes: int = 8,
                 max_seq: int = 512, pages_per_sb: int = 16,
                 prefix_buckets: int = 4):
        self.cfg = cfg
        self.mesh = mesh
        self.params = params
        self.lanes = lanes
        self.max_seq = max_seq
        self.acfg = arena_config(cfg, lanes, max_seq, pages_per_sb)
        # root slots: one per lane (page tables) + one per hash bucket of
        # the durable prefix index's record chains (serving.prefix_store) —
        # bucket b's chain head mirrors into roots[lanes + b]
        self._index_root = lanes
        self.prefix_buckets = prefix_buckets
        self.astate = ja.init_state(self.acfg,
                                    max_roots=lanes + prefix_buckets)
        self._alloc = jax.jit(functools.partial(ja.alloc, cfg=self.acfg,
                                                cls=PAGE_CLS))
        self._free = jax.jit(functools.partial(ja.free, cfg=self.acfg,
                                               cls=PAGE_CLS))
        self._alloc_large = jax.jit(functools.partial(ja.alloc_large,
                                                      cfg=self.acfg))
        self._free_large = jax.jit(functools.partial(ja.free_large,
                                                     cfg=self.acfg))
        self._acquire_span = jax.jit(functools.partial(ja.acquire_span,
                                                       cfg=self.acfg))
        self._trim_large = jax.jit(functools.partial(ja.trim_large,
                                                     cfg=self.acfg))
        self.lane_states = LaneStates(lanes)
        pshape = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        self.step_fn, _, _ = dec.make_decode_step(cfg, mesh, pshape)
        self.dstate = dec.make_dstate(cfg, batch=lanes, max_seq=max_seq,
                                      pages_per_shard=self.acfg.total_words
                                      + 1)
        # prefix sharing (RadixAttention-style) — the trie cache keeps
        # the flat exact-match dict API (entries / tokens / page_refs /
        # lookup) and adds longest-prefix-match over published prompts:
        # a request matching k pages of a longer prompt leases only
        # those k pages' superblocks (serving.prefix_trie_cache)
        self.prefix_cache = PrefixTrieCache(page=cfg.page_size)
        # durable prefix index: span-path entries additionally own one
        # record block reachable from roots[_index_root], which is what
        # lets crash_and_recover re-publish them instead of re-prefilling
        self.prefix_store = PrefixStore(jr.num_slots(self.acfg),
                                        n_buckets=prefix_buckets)
        # group-commit queue: transiently-published span entries whose
        # durable record append waits for the next flush_publishes
        self._publish_queue: list[PendingPublish] = []
        self.publish_capacity = max(4, lanes)    # records per group commit

    def _mirror_index_roots(self) -> None:
        """Mirror every prefix-chain bucket head into its root slot
        (bucket b -> roots[lanes + b]); pure state update, no fence."""
        for b, head in enumerate(self.prefix_store.heads):
            self.astate = ja.set_root(self.astate, self._index_root + b,
                                      jnp.int32(head))

    # ------------------------------------------- component-state delegation
    @property
    def sessions(self) -> dict[int, Session]:
        return self.lane_states.sessions

    @property
    def free_lanes(self) -> list[int]:
        return self.lane_states.free_lanes

    @property
    def large_spans(self) -> dict[int, tuple[int, int]]:
        return self.lane_states.large_spans

    @property
    def shared_spans(self) -> dict[int, tuple[int, int, int]]:
        return self.lane_states.shared_spans

    @property
    def cur_tokens(self) -> np.ndarray:
        return self.lane_states.cur_tokens

    @property
    def _prefix_cache(self) -> dict[int, tuple]:
        return self.prefix_cache.entries

    @property
    def _prefix_tokens(self) -> dict[int, tuple]:
        return self.prefix_cache.tokens

    @property
    def page_refs(self) -> dict[int, int]:
        return self.prefix_cache.page_refs

    @page_refs.setter
    def page_refs(self, refs: dict[int, int]) -> None:
        self.prefix_cache.page_refs = refs

    # ------------------------------------------------------------- requests
    def add_request(self, prompt: list[int],
                    share_prefix: bool = False) -> int:
        lane = self.lane_states.acquire()
        if lane is None:
            raise EngineBusy(
                f"all {self.lanes} lanes are busy — queue admission through "
                f"serving.scheduler.Scheduler.submit")
        self.sessions[lane] = Session(lane=lane, tokens=list(prompt))
        # reset lane state (pos=0) and feed the prompt token by token
        self.dstate = reset_lane(self.dstate, lane)
        self.cur_tokens[lane] = prompt[0]
        # oversized prompt: its page table will not fit the per-step lazy
        # path gracefully — reserve one contiguous multi-superblock span up
        # front (device large-object path) sized *decode-ahead*: the span
        # covers every page the sequence can ever touch (max_seq, not just
        # the prompt), so generation never needs a mid-decode lazy page or
        # a span migration.  Clamped to the page-table width: generation
        # stops at max_seq, so pages past it would never be touched.
        # A shared-prefix *hit* on a published span skips the reservation
        # entirely: the lane acquires the published span instead.
        table_width = int(self.dstate["block_table"].shape[1])
        n_prompt_pages = min(-(-len(prompt) // self.cfg.page_size),
                             table_width)
        hit = self.prefix_cache.lookup(prompt) if share_prefix else None
        # longest-prefix match when the exact entry misses: a request
        # matching k whole pages of a published prompt leases only those
        # k pages' superblocks and decodes its suffix on its own lazily-
        # allocated pages.  A mid-edge match first materializes the
        # boundary as a trie split (durable when the node has a record).
        pnode, pk = None, 0
        if share_prefix and hit is None and self.cfg.attn_layers > 0:
            pnode, pk = self.prefix_cache.match_partial(prompt)
            if pnode is not None and pk * self.cfg.page_size >= len(prompt):
                pnode, pk = None, 0    # no suffix left: only the exact
                #                        entry may serve the whole prompt
            elif pnode is not None and pk < pnode.end_page:
                m = self._split_node(pnode, pk)
                if m is None:          # no record blocks: fall back to
                    #                    the deepest existing boundary
                    pnode, pk = self.prefix_cache.deepest_boundary(pnode, pk)
                else:
                    pnode = m
            if pnode is None:
                pk = 0
        if (self.cfg.attn_layers > 0 and hit is None and pnode is None
                and n_prompt_pages > self.acfg.sb_words):
            n_ahead = min(-(-self.max_seq // self.cfg.page_size), table_width)
            try:
                self._reserve_span(lane, max(n_prompt_pages, n_ahead))
            except MemoryError:
                # back out the admission completely: session gone, lane
                # decode state neutral, lane in the pool exactly once —
                # the lane must be indistinguishable from never-admitted
                # (the old path handed the lane back with this request's
                # pos/block-table/cur-token still written into it)
                del self.sessions[lane]
                self.dstate = reset_lane(self.dstate, lane)
                self.cur_tokens[lane] = 0
                self.lane_states.release(lane)
                raise
        if hit is not None:
            if hit[0] == "span":
                # lease the published span's *prefix*: the prompt's KV
                # pages are exactly the prefix superblocks this lane will
                # read — no copy, no fresh reservation, and no claim on
                # the publisher's decode-ahead tail (which frees for
                # reuse the moment its own leases drop)
                _, off, n_span, full, plen, kvp, next_tok, lease_sbs = hit
                self.astate, _ = self._acquire_span(
                    state=self.astate, off=jnp.int32(off),
                    n_sbs=jnp.int32(lease_sbs))
                self.shared_spans[lane] = (off, full, lease_sbs)
                pages = off + np.arange(full, dtype=np.int32)
            else:
                _, pages, plen, kvp, next_tok = hit
                pages = np.asarray(pages, np.int32)
                for p in pages.tolist():
                    self.prefix_cache.add_page_ref(p)
            bt = np.asarray(self.dstate["block_table"]).copy()
            bt[lane, :len(pages)] = pages
            self.dstate["block_table"] = jnp.asarray(bt)
            kv = np.asarray(self.dstate["kv_pos"]).copy()
            kv[lane, :len(pages)] = kvp
            self.dstate["kv_pos"] = jnp.asarray(kv)
            self.dstate["pos"] = self.dstate["pos"].at[lane].set(plen)
            # the model's continuation at the prompt boundary was
            # sampled by the publisher — it is part of the prefix
            self.sessions[lane].tokens = list(prompt) + [next_tok]
            self.cur_tokens[lane] = next_tok
        elif pnode is not None and pk > 0:
            # partial hit at a trie-node boundary: the node's span backs
            # the whole prefix [0, pk) at identity offsets, so ONE
            # acquire_span of the node's lease (= exactly the matched
            # pages' superblocks) makes this an ordinary shared-span
            # lane; the un-matched prompt suffix replays teacher-forced
            # on the lane's own lazily-allocated pages
            off, lease_sbs = pnode.span, pnode.lease_sbs
            self.astate, _ = self._acquire_span(
                state=self.astate, off=jnp.int32(off),
                n_sbs=jnp.int32(lease_sbs))
            self.shared_spans[lane] = (off, pk, lease_sbs)
            self.lane_states.partial_hits[lane] = pk
            pages = off + np.arange(pk, dtype=np.int32)
            bt = np.asarray(self.dstate["block_table"]).copy()
            bt[lane, :pk] = pages
            self.dstate["block_table"] = jnp.asarray(bt)
            kv = np.asarray(self.dstate["kv_pos"]).copy()
            page = self.cfg.page_size
            kv[lane, :pk] = np.arange(pk * page,
                                      dtype=np.int32).reshape(pk, page)
            self.dstate["kv_pos"] = jnp.asarray(kv)
            self.dstate["pos"] = self.dstate["pos"].at[lane].set(pk * page)
            self.cur_tokens[lane] = prompt[pk * page]
        # the allocator root for this lane points at its page table
        self.astate = ja.set_root(self.astate, lane, jnp.int32(lane))
        return lane

    def _reserve_span(self, lane: int, n_pages: int) -> None:
        """Back ``n_pages`` page-table slots of ``lane`` with one
        contiguous large-object span (page ids = span offsets).  Raises
        ``MemoryError`` with the lane untouched; ``add_request`` owns
        backing the admission out."""
        _OBS_DEV_ALLOC_LARGE.inc()
        self.astate, off = self._alloc_large(state=self.astate,
                                             nwords=jnp.int32(n_pages))
        off = int(off)
        if off < 0:
            _OBS_SPAN_RESERVE_FAIL.inc()
            raise MemoryError(
                f"KV arena cannot reserve a contiguous {n_pages}-page span")
        self.large_spans[lane] = (off, n_pages)
        bt = np.asarray(self.dstate["block_table"]).copy()
        bt[lane, :n_pages] = off + np.arange(n_pages, dtype=np.int32)
        self.dstate["block_table"] = jnp.asarray(bt)

    def _alloc_blocks(self, n: int) -> list[int]:
        """``n`` arena blocks (prefix-index record slots) in ONE
        vectorized alloc; -1 entries when the arena is full.

        Record slots occupy dedicated ranks *past* the lane range — the
        old single-record path requested rank 0, lane 0's slot in the
        rank-indexed cache, so fusing a record grab into a step's lane
        allocation could pop one cache entry for both a KV page and a
        record.  The tail ranks can never collide with any lane's, and
        the fixed ``lanes + publish_capacity`` width keeps this a single
        jit trace across batch sizes."""
        assert 0 < n <= self.publish_capacity
        need = np.zeros((self.lanes + self.publish_capacity,), bool)
        need[self.lanes:self.lanes + n] = True
        _OBS_DEV_ALLOC.inc()
        self.astate, offs = self._alloc(state=self.astate,
                                        need=jnp.asarray(need))
        return [int(o) for o in
                np.asarray(offs)[self.lanes:self.lanes + n]]

    def _split_node(self, node: CacheNode, k: int) -> CacheNode | None:
        """Materialize page boundary ``k`` inside in-process trie node
        ``node`` (X ``[s, e)`` → M ``[s, k)`` + X' ``[k, e)``, same
        span).  Returns M, or None when the arena cannot place the two
        record blocks a durable split needs (nothing changes then — the
        caller serves the deepest existing boundary instead).

        Device mirror of ``core.prefix_trie.PrefixTrie.split``, ordering
        included: both new records land (``PrefixStore.split`` splices
        them into X's chain position), children re-parent, and only then
        does the old record's lease drop and its block free.  Leases
        stay record ⇔ lease 1:1: M's new lease and X''s replacement are
        acquired up front, X's old lease releases at the end.  A node
        still parked in the publish queue has no record yet: its queue
        entry is replaced by two pending publishes and the split stays
        transient until the next flush."""
        if node.page_keys is None or node.tokens is None:
            return None                # recovered node: no page keys
        m_rec = x_rec = -1
        if node.rec_off >= 0:
            m_rec, x_rec = self._alloc_blocks(2)
            if m_rec < 0 or x_rec < 0:
                live = np.full((self.acfg.cache_cap,), -1, np.int32)
                live[:2] = (m_rec, x_rec)
                if (live >= 0).any():
                    self.astate = self._free(state=self.astate,
                                             offs=jnp.asarray(live),
                                             mask=jnp.asarray(live >= 0))
                return None
        m_lease = -(-k // self.acfg.sb_words)
        old_key, old_lease = node.key, node.lease_sbs
        old_rec = node.rec_off
        self.astate, _ = self._acquire_span(
            state=self.astate, off=jnp.int32(node.span),
            n_sbs=jnp.int32(m_lease))
        self.astate, _ = self._acquire_span(
            state=self.astate, off=jnp.int32(node.span),
            n_sbs=jnp.int32(node.lease_sbs))
        old_entry = self._prefix_cache.get(old_key)
        span_pages = old_entry[2] if old_entry is not None else node.end_page
        m = self.prefix_cache.split_transient(node, k)
        m.lease_sbs = m_lease
        m.rec_off = m_rec
        page = self.cfg.page_size
        kvp = np.arange(k * page, dtype=np.int32).reshape(k, page)
        self.prefix_cache.insert(
            m.key,
            ("span", node.span, span_pages, k, k * page, kvp, m.next_tok,
             m_lease),
            tokens=m.tokens)
        if old_rec >= 0:
            par = (self.prefix_cache.nodes[m.parent].rec_off
                   if m.parent >= 0 and m.parent in self.prefix_cache.nodes
                   else -1)
            self.prefix_store.split(
                old_rec,
                dict(rec_off=m_rec, key=m.key, span=node.span, n_pages=k,
                     span_pages=span_pages, next_tok=m.next_tok,
                     lease_sbs=m_lease, parent=par, start_page=m.start_page,
                     fprint=m.fprint),
                dict(rec_off=x_rec, key=node.key, span=node.span,
                     n_pages=node.end_page, span_pages=span_pages,
                     next_tok=node.next_tok, lease_sbs=node.lease_sbs,
                     parent=m_rec, start_page=k, fprint=node.fprint))
            node.rec_off = x_rec
            for ck in node.children:
                child = self.prefix_cache.nodes.get(ck)
                if child is not None and child.rec_off >= 0:
                    self.prefix_store.reparent(child.rec_off, x_rec)
            self._mirror_index_roots()
        else:
            # queued-only node: swap its parked publish for the pair (M
            # first — flush resolves X''s parent_key through it)
            for i, p in enumerate(self._publish_queue):
                if p.key == old_key:
                    self._publish_queue[i:i + 1] = [
                        PendingPublish(
                            key=m.key, span=node.span, n_pages=k,
                            span_pages=span_pages, next_tok=m.next_tok,
                            lease_sbs=m_lease, start_page=m.start_page,
                            parent_key=m.parent, fprint=m.fprint),
                        PendingPublish(
                            key=node.key, span=node.span,
                            n_pages=node.end_page, span_pages=span_pages,
                            next_tok=node.next_tok,
                            lease_sbs=node.lease_sbs, start_page=k,
                            parent_key=m.key, fprint=node.fprint)]
                    break
        # old record's lease drops last (a linked record always implied
        # a live span); its block frees after the relink, never before
        self.astate = self._free_large(state=self.astate,
                                       off=jnp.int32(node.span),
                                       n_sbs=jnp.int32(old_lease))
        if old_rec >= 0:
            offs = np.full((self.acfg.cache_cap,), -1, np.int32)
            offs[0] = old_rec
            self.astate = self._free(state=self.astate,
                                     offs=jnp.asarray(offs),
                                     mask=jnp.asarray(offs >= 0))
        return m

    # -------------------------------------------------------------- publish
    def queue_publish(self, lane: int) -> bool:
        """Register this lane's fully-processed prompt as a shared prefix.

        Only whole pages are shared (a partially-filled page would be
        written by the owner — violating block disjointness).  A lane
        holding a reserved span publishes the *span itself*: later
        matching requests acquire the span (one refcount each, see
        ``core.spans``) instead of copying pages into a fresh
        reservation; the span frees when the last holder exits.

        The transient half is immediate — cache entry + prefix lease, so
        sharers can hit before any flush — but the durable record append
        parks in the group-commit queue until ``flush_publishes``.
        Page-path entries are transient-only and complete here.  Returns
        True when a new entry was created."""
        s = self.sessions[lane]
        pos = int(np.asarray(self.dstate["pos"][lane]))
        page = self.cfg.page_size
        full = pos // page
        if full == 0:
            return False
        kv = np.asarray(self.dstate["kv_pos"][lane])
        span = self.large_spans.get(lane)
        if span is None:
            shared = self.shared_spans.get(lane)  # sharers may re-publish
            if shared is not None:
                span = shared[:2]                 # (off, backed prefix pages)
        if span is not None:
            off, n_span = span
            # only span-backed pages can be published under the span
            # entry: clamp to the leading block-table slots the span
            # actually backs (a sharer's post-prefix pages are its own
            # lazy allocations and hold *its* KV, not the span's)
            bt_lane = np.asarray(self.dstate["block_table"][lane])
            cover = 0
            while (cover < min(full, n_span, bt_lane.size)
                   and int(bt_lane[cover]) == off + cover):
                cover += 1
            full = min(full, cover)
            if full == 0:
                return False
            key = hash_tokens(s.tokens[:full * page])
            if self._prefix_cache.get(key) is not None:
                # already published (the cache holds exactly one reference
                # per entry): acquiring again would leak a span reference
                # when this entry is overwritten
                return False
            # the prefix cache itself holds one *prefix* lease — just the
            # superblocks the shared prompt pages occupy — so the prefix
            # survives the publishing session's eviction while the
            # decode-ahead tail stays free to be reclaimed
            lease_sbs = -(-full // self.acfg.sb_words)
            self.astate, _ = self._acquire_span(
                state=self.astate, off=jnp.int32(off),
                n_sbs=jnp.int32(lease_sbs))
            # the prefix boundary token, NOT the lane's current token:
            # mid-page publishes clamp the entry to full*page positions,
            # and a sharer's first decode input must be the token that
            # followed the *published* prefix, not whatever this lane is
            # decoding several positions later
            next_tok = int(s.tokens[full * page])
            self.prefix_cache.insert(
                key,
                ("span", off, n_span, full, full * page, kv[:full].copy(),
                 next_tok, lease_sbs),
                tokens=s.tokens[:full * page])
            # attach the prefix into the trie: the deepest existing
            # boundary becomes the parent (a mid-edge match materializes
            # it as a split first); the new node's edge covers [k, full)
            # but its span still backs the whole [0, full) prefix.
            # k < full always: a boundary AT full would mean this exact
            # prefix is already published, caught by the dedupe above.
            toks = tuple(int(t) for t in s.tokens[:full * page])
            parent, k = self.prefix_cache.match_partial(toks)
            if parent is not None and k < parent.end_page:
                m = self._split_node(parent, k)
                if m is None:
                    parent, k = self.prefix_cache.deepest_boundary(parent, k)
                else:
                    parent = m
            if parent is None:
                k = 0
            node = CacheNode(
                key=key, span=off, start_page=k, end_page=full,
                lease_sbs=lease_sbs, next_tok=next_tok,
                fprint=fingerprint(toks[k * page], toks[full * page - 1]),
                parent=(parent.key if parent is not None else -1),
                tokens=toks, page_keys=page_hashes(toks, page)[k:])
            self.prefix_cache.insert_node(node)
            # the durable index record (one ordinary arena block) parks in
            # the group-commit queue: flush_publishes appends the whole
            # batch behind a single root swing, mirroring the host
            # PrefixIndex.publish_batch fence amortization.  After a crash
            # the record re-publishes this entry and re-trims the lease,
            # so the prefix is hittable without re-prefill.
            self._publish_queue.append(PendingPublish(
                key=key, span=off, n_pages=full, span_pages=n_span,
                next_tok=next_tok, lease_sbs=lease_sbs,
                start_page=k, parent_key=node.parent, fprint=node.fprint))
            _OBS_PUB_QUEUED.inc()
            _OBS_PUB_DEPTH.set(len(self._publish_queue))
            return True
        bt = np.asarray(self.dstate["block_table"][lane])
        if pos != full * page:
            # share only a fully-processed, page-aligned prompt: a
            # mid-page publish would hand sharers a boundary token whose
            # preceding positions are NOT all inside the shared pages
            return False
        pages = tuple(int(p) for p in bt[:full])
        for p in pages:
            # +1: the prefix cache itself holds a reference, so the pages
            # survive the publishing session's eviction
            self.prefix_cache.add_page_ref(p)
        # page-path entries stay transient-only: their sharing is per-page
        # refcounts, not a span lease, and the durable index records only
        # span-backed prefixes (a crash forgets these — they re-prefill)
        pkey = hash_tokens(s.tokens[:full * page])
        self.prefix_cache.insert(
            pkey,
            ("pages", pages, full * page, kv[:full].copy(),
             int(self.cur_tokens[lane])),
            tokens=s.tokens[:full * page])
        return True

    def flush_publishes(self) -> int:
        """Land every parked publication durably: per batch of up to
        ``publish_capacity``, ONE vectorized record-block allocation, one
        chained ``append_batch`` and ONE root swing — the group commit.
        A full arena degrades safely: those publishes stay
        transient-only.  Returns the number of records appended."""
        appended = 0
        while self._publish_queue:
            batch = self._publish_queue[:self.publish_capacity]
            del self._publish_queue[:len(batch)]
            _OBS_PUB_FLUSHES.inc()
            _OBS_PUB_BATCH.observe(len(batch))
            recs = self._alloc_blocks(len(batch))
            rec_of: dict[int, int] = {}     # key -> record landed this batch
            payloads = []
            for rec, p in zip(recs, batch):
                if rec < 0:
                    continue
                # parent record offset resolves NOW: the parent either
                # landed earlier in this very batch (queued splits put M
                # before X') or already owns a record from a prior flush;
                # a parent that missed its block degrades to -1 and the
                # recovery coverage pass re-links by page boundary
                par = -1
                if p.parent_key >= 0:
                    par = rec_of.get(p.parent_key, -1)
                    if par < 0:
                        pn = self.prefix_cache.nodes.get(p.parent_key)
                        par = pn.rec_off if pn is not None else -1
                payloads.append(dict(
                    rec_off=rec, key=p.key, span=p.span,
                    n_pages=p.n_pages, span_pages=p.span_pages,
                    next_tok=p.next_tok, lease_sbs=p.lease_sbs,
                    parent=par, start_page=p.start_page, fprint=p.fprint))
                rec_of[p.key] = rec
            if payloads:
                self.prefix_store.append_batch(payloads)
                self._mirror_index_roots()
                for q in payloads:
                    self.prefix_cache.set_rec(q["key"], q["rec_off"])
                appended += len(payloads)
        _OBS_PUB_DEPTH.set(0)
        return appended

    @property
    def pending_publishes(self) -> int:
        return len(self._publish_queue)

    def publish_prefix(self, lane: int) -> None:
        """Immediate (ungrouped) publish: queue + flush in one call.
        Batched serving amortizes instead via ``queue_publish`` +
        ``flush_publishes`` on the scheduler's cadence."""
        self.queue_publish(lane)
        self.flush_publishes()

    def drop_prefix_cache(self) -> None:
        """Release the cache's references; fully-unreferenced pages (and
        spans whose last holder was the cache) free."""
        for key, entry in list(self._prefix_cache.items()):
            if entry[0] == "span":
                # durable unlink FIRST (a linked record must always imply
                # a live span — core.prefix_index ordering), then the
                # lease release, then the record block frees.  An entry
                # still parked in the publish queue has no record yet
                # (remove returns None) — dropping its queue slot below
                # is its whole un-publication.
                rec = self.prefix_store.remove(key)
                if rec is not None:
                    self._mirror_index_roots()
                # free_large releases the cache's prefix lease: a
                # transient decrement while holders remain, the actual
                # free of whatever range the cache was last to lease
                self.astate = self._free_large(state=self.astate,
                                               off=jnp.int32(entry[1]),
                                               n_sbs=jnp.int32(entry[7]))
                if rec is not None:
                    offs = np.full((self.acfg.cache_cap,), -1, np.int32)
                    offs[0] = rec.off
                    self.astate = self._free(state=self.astate,
                                             offs=jnp.asarray(offs),
                                             mask=jnp.asarray(offs >= 0))
                continue
            pages = entry[1]
            stale = []
            for p in pages:
                if p in self.page_refs:
                    self.page_refs[p] -= 1
                    if self.page_refs[p] <= 0:
                        stale.append(p)
                        del self.page_refs[p]
            if stale:
                offs = np.full((self.acfg.cache_cap,), -1, np.int32)
                offs[:len(stale)] = stale
                self.astate = self._free(state=self.astate,
                                         offs=jnp.asarray(offs),
                                         mask=jnp.asarray(offs >= 0))
        self.prefix_cache.clear()
        # parked appends for the just-dropped entries must never land
        self._publish_queue.clear()

    # ------------------------------------------------------------------ step
    def step(self) -> dict[int, int]:
        """One decode step for every active lane; returns emitted tokens."""
        active = self.lane_states.active()
        if not active.any():
            return {}
        # page-boundary lanes need a fresh page before the step — unless
        # the slot is already backed (prefix hit or a reserved large span)
        pos = np.asarray(self.dstate["pos"])
        page = self.cfg.page_size
        need = active & (pos % page == 0) & (self.cfg.attn_layers > 0)
        if need.any():
            # only boundary steps pay the block-table device→host sync
            bt_now = np.asarray(self.dstate["block_table"])
            slot = np.clip(pos // page, 0, bt_now.shape[1] - 1)
            need &= bt_now[np.arange(self.lanes), slot] < 0
        if need.any():
            _OBS_DEV_ALLOC.inc()
            self.astate, offs = self._alloc(state=self.astate,
                                            need=jnp.asarray(need))
            offs = np.asarray(offs)
            bt = np.asarray(self.dstate["block_table"]).copy()
            for lane in np.nonzero(need)[0]:
                if offs[lane] < 0:
                    raise MemoryError("KV arena exhausted")
                bt[lane, pos[lane] // page] = offs[lane]
            self.dstate["block_table"] = jnp.asarray(bt)

        self.dstate, toks = self.step_fn(self.params, self.dstate,
                                         jnp.asarray(self.cur_tokens))
        toks = np.asarray(toks)
        out = {}
        for lane, s in list(self.sessions.items()):
            if s.done:
                continue
            t = int(pos[lane]) + 1
            if t < len(s.tokens):
                self.cur_tokens[lane] = s.tokens[t]       # teacher-forced
            else:
                s.tokens.append(int(toks[lane]))
                self.cur_tokens[lane] = int(toks[lane])
                out[lane] = int(toks[lane])
            if len(s.tokens) >= self.max_seq - 1:
                self.finish(lane)
        return out

    def finish(self, lane: int) -> None:
        """Evict a session: free its pages (shared pages only at ref 0,
        leased span ranges only when their last lease releases).

        The lane's span records are *poisoned* here — popped before any
        release — so a dead lane can never free a span reallocated at
        the same offset: a second ``finish`` of the lane raises
        (``KeyError``), it cannot silently release someone else's span.
        """
        s = self.sessions.pop(lane)
        s.done = True
        bt = np.asarray(self.dstate["block_table"][lane])
        pages = bt[bt >= 0].astype(np.int32)
        span = self.large_spans.pop(lane, None)
        shared = self.shared_spans.pop(lane, None)
        self.lane_states.partial_hits.pop(lane, None)
        if span is not None:
            # the prompt's page table is one large span: free_large drops
            # the owner's full-extent lease — superblocks nobody else
            # leases free *now* (in particular the decode-ahead tail past
            # the published prefix, which only prefix leases cover);
            # pages decoded past the span were lazily allocated and go
            # through the per-page free below
            off, n_span = span
            self.astate = self._free_large(state=self.astate,
                                           off=jnp.int32(off),
                                           n_sbs=jnp.int32(-1))
            pages = pages[(pages < off) | (pages >= off + n_span)]
        elif shared is not None:
            # a sharer releases exactly the prefix range it leased; its
            # own decode pages (which may legitimately reuse freed tail
            # superblocks of this very span) free per-page below
            off, n_backed, lease_sbs = shared
            self.astate = self._free_large(state=self.astate,
                                           off=jnp.int32(off),
                                           n_sbs=jnp.int32(lease_sbs))
            pages = pages[(pages < off) | (pages >= off + n_backed)]
        keep = []
        for p in pages.tolist():
            if p in self.page_refs:
                self.page_refs[p] -= 1
                if self.page_refs[p] > 0:
                    keep.append(p)          # still referenced elsewhere
                else:
                    del self.page_refs[p]
        if keep:
            pages = np.asarray([p for p in pages.tolist() if p not in keep],
                               np.int32)
        if pages.size:
            offs = np.full((self.acfg.cache_cap,), -1, np.int32)
            offs[:pages.size] = pages
            self.astate = self._free(state=self.astate,
                                     offs=jnp.asarray(offs),
                                     mask=jnp.asarray(offs >= 0))
        self.dstate["block_table"] = \
            self.dstate["block_table"].at[lane].set(-1)
        self.astate = ja.set_root(self.astate, lane, jnp.int32(-1))
        self.lane_states.release(lane)

    def check_occupancy(self) -> dict:
        """The allocator's live blocks must be exactly the pages the live
        lanes hold: every page-class block is one lane's lazily-allocated
        page (held by no other lane), and every large span is one lane's
        reservation with its superblocks placed.  Valid only while no
        prefix is shared.  Raises on a mismatch; returns the counts."""
        if self.prefix_cache.entries or self.shared_spans:
            raise ValueError("check_occupancy assumes no prefix sharing")
        bt = np.asarray(self.dstate["block_table"])
        lazy: list[int] = []
        span_sbs = 0
        for lane in self.sessions:
            pages = bt[lane][bt[lane] >= 0]
            span = self.large_spans.get(lane)
            if span is not None:
                off, n = span
                if pages[:n].tolist() != list(range(off, off + n)):
                    raise AssertionError(f"lane {lane}: span pages "
                                         f"{pages[:n]} != [{off}, {off + n})")
                pages = pages[n:]
                span_sbs += -(-n // self.acfg.sb_words)
            lazy.extend(pages.tolist())
        if len(set(lazy)) != len(lazy):
            raise AssertionError(f"a lazy page is held by two lanes: {lazy}")
        live = ja.live_blocks(self.astate, self.acfg)
        sb_class = np.asarray(self.astate.sb_class)
        placed = int(np.isin(sb_class, (ja.LARGE_CLS, ja.LARGE_CONT)).sum())
        occ = {"live_pages": live[PAGE_CLS], "lane_pages": len(lazy),
               "live_spans": live["large"],
               "lane_spans": len(self.large_spans),
               "span_superblocks": placed, "lane_span_superblocks": span_sbs}
        if (occ["live_pages"] != occ["lane_pages"]
                or occ["live_spans"] != occ["lane_spans"]
                or placed != span_sbs):
            raise AssertionError(f"allocator occupancy != lane holdings: "
                                 f"{occ}")
        return occ

    # ------------------------------------------------------------- recovery
    def ref_table(self) -> np.ndarray:
        """Filter function output: each live session's root block (its
        first page) references the session's remaining pages.

        Lanes sharing a span root at the same head page, so their
        reference lists *accumulate* into that slot's row (the row is
        widened as needed) — losing one lane's refs would sweep its
        lazily-allocated decode pages out from under it.

        Prefix-index records contribute their own rows (the record
        type's filter function): ``[next record, span head]`` — the mark
        pass traces the chain precisely and counts the record→span
        reference like a lane root, which is what keeps a published span
        alive across a crash with no lane rooted on it."""
        S = jr.num_slots(self.acfg)
        R = int(self.dstate["block_table"].shape[1])
        bt = np.asarray(self.dstate["block_table"])
        rows: dict[int, list[int]] = {}
        for lane, s in self.sessions.items():
            if s.done:
                continue
            pages = bt[lane][bt[lane] >= 0]
            if pages.size == 0:
                continue
            rows.setdefault(int(pages[0]), []).extend(pages[1:].tolist())
        for rec_off, tgts in self.prefix_store.ref_rows().items():
            rows.setdefault(rec_off, []).extend(tgts)
        width = max([R] + [len(v) for v in rows.values()])
        refs = np.full((S, width), -1, np.int32)
        for root, tgts in rows.items():
            refs[root, :len(tgts)] = tgts
        return refs

    def crash_and_recover(self) -> dict:
        """Simulate losing all transient allocator state, then rebuild it
        from (persistent fields + session page tables + the durable
        prefix index) via vectorized GC.

        Engine-side sharing metadata is transient and comes back from
        what the roots can see: per-page refcounts are recounted from
        live block tables and span leases are reconstructed inside
        ``jr.recover`` as the number of root-reachable references to each
        span head — conservatively *full-extent*, because lease lengths
        are transient.  The durable prefix index is the exception the
        tentpole adds: surviving records re-publish their entries into
        the rebuilt cache (hittable without re-prefill) and every lease
        whose true length IS recorded — the cache's record lease and each
        live sharer's prefix lease — is re-trimmed to its page-derived
        superblock count, so the decode-ahead tail frees immediately
        after recovery instead of waiting for the reserver to
        re-finish."""
        # Named engine-recovery phases (repro.obs spans): timings + item
        # counts surface in the returned stats and the metrics snapshot,
        # mirroring core.recovery's host-side phase profile.
        phases: dict[str, dict] = {}

        def _phase(span):
            phases[span.name.split(".", 1)[1]] = {
                "seconds": span.seconds, "items": span.items}

        # torn / unrecoverable-orphan pre-prune, BEFORE the mark pass
        # (host ordering: prune_torn_nodes runs before recover's trace).
        # A torn record's span reference would otherwise phantom-lease
        # the span, and its marked block would leak as owned-by-nobody.
        prune_span = obs.span("engine_recovery.prune_records")
        prune_span.__enter__()
        recs0 = self.prefix_store.walk()
        trie_pruned = 0
        if recs0:
            by_off = {r.off: r for r in recs0}
            keep = {r.off for r in recs0
                    if self.prefix_store.seal_matches(r.off)}
            # recoverability: a node is servable iff kept records cover
            # [0, start_page) contiguously — fixpoint from boundary 0
            bounds, grew = {0}, True
            while grew:
                grew = False
                for off in keep:
                    r = by_off[off]
                    if r.start_page in bounds and r.n_pages not in bounds:
                        bounds.add(r.n_pages)
                        grew = True
            keep = {off for off in keep
                    if by_off[off].start_page in bounds}
            if len(keep) < len(recs0):
                self.prefix_store.prune(
                    np.asarray([r.off in keep for r in recs0], bool))
                trie_pruned = len(recs0) - len(keep)
            # survivors with dangling parents re-parent to ANY kept
            # record ending at their start page (navigation is by
            # cumulative hash — the parent field is only trie shape)
            for r in self.prefix_store.walk():
                if r.start_page == 0:
                    if r.parent != -1:
                        self.prefix_store.reparent(r.off, -1)
                    continue
                if (r.parent in keep and r.parent != r.off
                        and by_off[r.parent].n_pages == r.start_page):
                    continue
                cover = next((o for o in keep if o != r.off
                              and by_off[o].n_pages == r.start_page), None)
                self.prefix_store.reparent(
                    r.off, cover if cover is not None else -1)
        prune_span.add(trie_pruned)
        prune_span.__exit__(None, None, None)
        _phase(prune_span)
        with obs.span("engine_recovery.snapshot") as sp:
            persistent = ja.persistent_snapshot(self.astate)
            roots = np.full((self.lanes + self.prefix_buckets,), -1,
                            np.int32)
            bt = np.asarray(self.dstate["block_table"])
            for lane, s in self.sessions.items():
                pages = bt[lane][bt[lane] >= 0]
                if pages.size:
                    roots[lane] = int(pages[0])
            for b, head in enumerate(self.prefix_store.heads):
                roots[self._index_root + b] = head
            persistent["roots"] = jnp.asarray(roots)
            sp.add(int((roots >= 0).sum()))
        _phase(sp)
        with obs.span("engine_recovery.mark_sweep") as sp:
            new_state, marked = jr.recover(self.acfg, persistent,
                                           jnp.asarray(self.ref_table()))
            live_before = ja.live_blocks(self.astate, self.acfg)[PAGE_CLS]
            self.astate = new_state
            live_after = ja.live_blocks(new_state, self.acfg)[PAGE_CLS]
            sp.add(int(np.asarray(marked).sum()))
        _phase(sp)
        # drop + recount the engine's transient sharing records (recovery
        # step 2: caches start empty in a fresh process).  Span-backed
        # pages are excluded: their sharing is the *span's* refcount
        # (reconstructed inside jr.recover) and finish() never routes them
        # through the per-page free, so a per-page count would go stale
        # and poison the offset after the span frees and is reallocated.
        # (Exact token sequences die with the cache: re-published entries
        # are named by the record's hash alone.)
        with obs.span("engine_recovery.recount_refs") as sp:
            self.prefix_cache.clear()
            # queued-but-unflushed appends die with the process too: they
            # never became durable, no lease reconstruction references
            # them, and their cache entries were just cleared — dropping
            # the queue IS the crash semantics for an un-flushed group
            # commit
            self._publish_queue.clear()
            spans = list(self.large_spans.values()) + \
                [(off, n_backed) for off, n_backed, _ in
                 self.shared_spans.values()]
            counts: dict[int, int] = {}
            for lane, s in self.sessions.items():
                if s.done:
                    continue
                for p in bt[lane][bt[lane] >= 0].tolist():
                    if any(off <= p < off + n for off, n in spans):
                        continue
                    counts[p] = counts.get(p, 0) + 1
            self.page_refs = {p: c for p, c in counts.items() if c > 1}
            sp.add(len(self.page_refs))
        _phase(sp)
        # re-publish surviving index records into the rebuilt cache and
        # re-trim each record's reconstructed full-extent lease to its
        # recorded superblock count (a record whose root swing never
        # became durable is unmarked — pruned, exactly like the host GC
        # frees an unreachable core.prefix_index record)
        with obs.span("engine_recovery.republish") as sp:
            recs = self.prefix_store.walk()
            seal_ok = np.asarray([self.prefix_store.seal_matches(r.off)
                                  for r in recs] + [True], bool)
            live = jr.live_record_mask(self.acfg, marked,
                                       np.asarray([r.off for r in recs]
                                                  + [-1], np.int32),
                                       seal_ok=jnp.asarray(seal_ok))
            survivors = self.prefix_store.prune(
                np.asarray(live)[:len(recs)])
            page = self.cfg.page_size
            for rec in survivors:
                # a fully-processed prompt page p holds positions
                # p*page .. p*page+page-1 — kv_pos rebuilds
                # deterministically
                kvp = np.arange(rec.n_pages * page,
                                dtype=np.int32).reshape(rec.n_pages, page)
                self._prefix_cache[rec.key] = (
                    "span", rec.span, rec.span_pages, rec.n_pages,
                    rec.n_pages * page, kvp, rec.next_tok, rec.lease_sbs)
                _OBS_DEV_TRIM.inc()
                self.astate, _ = self._trim_large(
                    state=self.astate, off=jnp.int32(rec.span),
                    n_keep=jnp.int32(rec.lease_sbs), n_held=jnp.int32(-1))
            self._mirror_index_roots()
            # rebuild the trie shape from the surviving records
            # (token-less nodes: they match all-or-nothing, key +
            # fingerprint) so longest-prefix partial hits work
            # immediately after recovery
            self.prefix_cache.rebuild_from_records(survivors)
            sp.add(len(survivors))
        _phase(sp)
        # live sharers' prefix leases were also rebuilt full-extent;
        # their true lengths survive in shared_spans — re-trim them too,
        # so the post-recovery lease vector equals the pre-crash one
        with obs.span("engine_recovery.retrim_shared") as sp:
            for lane, (off, _n_backed,
                       lease_sbs) in self.shared_spans.items():
                if lane in self.sessions and not self.sessions[lane].done:
                    _OBS_DEV_TRIM.inc()
                    self.astate, _ = self._trim_large(
                        state=self.astate, off=jnp.int32(off),
                        n_keep=jnp.int32(lease_sbs), n_held=jnp.int32(-1))
                    sp.add(1)
        _phase(sp)
        return {"marked": int(np.asarray(marked).sum()),
                "live_before": live_before, "live_after": live_after,
                "index_records": len(survivors),
                "trie_pruned": trie_pruned,
                "phases": phases}
