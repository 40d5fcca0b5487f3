"""Continuous-batching scheduler for the licensed serving gateway.

The seed ``ServingEngine`` serves one request stream at a time: a static
batch is prefilled together and decoded in lock-step until the *longest*
request finishes.  The gateway instead schedules at *iteration* level
(Orca-style continuous batching): every scheduler step emits one
micro-batch — either a PREFILL of newly admitted requests or a DECODE
step over running ones — so a finished request's lane is refilled
immediately while the rest of the batch keeps decoding.

Licensing adds one constraint on top of stock continuous batching: all
requests in a micro-batch must share a **(license tier, weight version)**
key, because the batch is served through a single masked weight view
(§3.5 — one stored weight set, per-tier interval-masked views).  The
pieces here are pure host-side bookkeeping; the jitted compute lives in
``serving/gateway.py``:

* ``GatewayRequest``   — one in-flight generation with its pinned
  ``(tier, version)`` key, lane assignment, and latency timestamps;
* ``TierViewCache``    — LRU cache of licensed weight views keyed by
  (tier, version), so ``apply_license``/interval packing is paid once per
  key instead of once per request (shared with ``ServingEngine``);
* ``CachePool``        — lane-stacked KV/SSM cache pool shared by every
  tier, with gather/scatter by lane id and a scratch lane that absorbs
  padded writes (the contiguous fallback; the default block-paged pool
  is ``serving/paging.py``);
* ``Scheduler``        — admission queue + the prefill-priority,
  queue-age-fair, block-budgeted policy that picks the next micro-batch
  (and the preemption hook the paged pool's exhaustion path uses).
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Deque, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_lib
from repro.serving.paging import pad_lane_ids


class RequestState(str, Enum):
    QUEUED = "queued"        # admitted, waiting for a free lane
    PREFILLING = "prefilling"  # holds a lane, prompt chunking through
    RUNNING = "running"      # prefilled, holds a lane, decoding
    DONE = "done"            # produced max_new_tokens
    REJECTED = "rejected"    # failed admission (unknown tier / bad prompt)


@dataclass(eq=False)   # identity equality: requests live in queues
class GatewayRequest:
    """One generation request flowing through the gateway.

    ``license``/``version`` form the micro-batch key: the scheduler only
    groups requests whose (tier, version) match, so one masked weight
    view serves the whole batch.  ``version`` is pinned at admission —
    a weight update mid-flight never changes the view a request sees.
    """

    prompt: np.ndarray                       # (S,) int32
    max_new_tokens: int = 16
    license: str = "full"
    temperature: float = 0.0
    top_k: int = 0                           # 0 = no top-k truncation
    seed: int = 0

    # fleet serving (serving/fleet.py): the model slot the request was
    # submitted to and the tenant it is billed against — together with
    # (license, version) they complete the fleet's micro-batch key
    model: Optional[str] = None
    tenant: Optional[str] = None

    # assigned by the gateway
    rid: int = -1
    version: Optional[int] = None            # weight version pinned at admission
    state: RequestState = RequestState.QUEUED
    out_tokens: List[int] = field(default_factory=list)
    lane: Optional[int] = None               # cache-pool lane while RUNNING
    blocks: List[int] = field(default_factory=list)  # paged-pool block table
    prefix_tokens: int = 0                   # prompt tokens served from the
                                             # prefix cache at prefill
    cursor: int = 0                          # prompt tokens already prefilled
                                             # (chunked prefill progress)
    pos: int = 0                             # next decode position
    start_seq: int = -1                      # admission order (preemption age)
    preemptions: int = 0
    logits_rows: Optional[List[np.ndarray]] = None   # record_logits only
    error: Optional[str] = None
    submit_t: float = 0.0
    admit_t: Optional[float] = None          # first lane assignment; a
                                             # restart keeps it
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None

    # telemetry bookkeeping (serving/telemetry.py).  ``_ttft_done``
    # survives preemption — a restarted request re-emits its first token
    # but its TTFT was already counted once; ``_last_tok_t`` does not
    # (the inter-token gap across a preemption gap is not a decode gap).
    _ttft_done: bool = False
    _last_tok_t: Optional[float] = None
    _open_span: Optional[str] = None         # current lifecycle B span

    @property
    def group_key(self) -> Tuple[str, Optional[int]]:
        return (self.license, self.version)

    @property
    def latency(self) -> Optional[float]:
        """Submit -> last token wall time (None until DONE)."""
        if self.finish_t is None:
            return None
        return self.finish_t - self.submit_t

    @property
    def ttft(self) -> Optional[float]:
        """Submit -> first token wall time."""
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t


@dataclass
class ScheduledAction:
    """One micro-batch decision: prefill or decode a tier-homogeneous group.

    ``suffix_bucket`` records the prefix-aware admission decision for
    prefills: the uncached-suffix width every member of the batch shares
    (None when grouping is off or for decode actions).  ``model`` is the
    serving slot's model name — under a ``FleetGateway`` every action is
    keyed (model, tier, version); a standalone gateway stamps its own
    (single) model name."""

    kind: str                                # "prefill" | "decode"
    tier: str
    version: Optional[int]
    requests: List[GatewayRequest]
    suffix_bucket: Optional[int] = None
    model: Optional[str] = None


class TierViewCache:
    """LRU cache of licensed weight views keyed by (tier, version).

    ``build(tier_name, version)`` materializes a view on miss — for the
    float path that is ``apply_license`` over the full tree, for the int8
    path just the packed license intervals.  Either way the cost is paid
    once per (tier, version), not once per request: the amortization the
    gateway's throughput claim rests on.  Hit/miss/invalidation counters
    are exported via :meth:`stats` and asserted by the benchmarks.
    """

    def __init__(self, build: Callable[[str, Optional[int]], Any],
                 capacity: int = 8):
        self._build = build
        self.capacity = int(capacity)
        self._entries: "OrderedDict[Tuple[str, Optional[int]], Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0

    def get(self, tier: str, version: Optional[int] = None) -> Any:
        key = (tier, version)
        if key in self._entries:
            self.hits += 1
            self._entries.move_to_end(key)
            return self._entries[key]
        self.misses += 1
        view = self._build(tier, version)
        self._entries[key] = view
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1
        return view

    def __contains__(self, key: Tuple[str, Optional[int]]) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def invalidate(self, *, tier: Optional[str] = None,
                   version: Optional[int] = None) -> int:
        """Drop entries matching the given tier and/or version (None = any)."""
        doomed = [k for k in self._entries
                  if (tier is None or k[0] == tier)
                  and (version is None or k[1] == version)]
        for k in doomed:
            del self._entries[k]
        self.invalidations += len(doomed)
        return len(doomed)

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "entries": len(self._entries)}


class CachePool:
    """Shared KV/SSM cache pool: ``num_lanes`` per-request cache slots.

    Leaves are lane-stacked: leading axis indexes the lane, each lane
    holding a batch-1 cache from ``init_cache(cfg, 1, capacity)``.  The
    gateway's decode is ``vmap``-ed over this axis, which is what lets
    every lane carry its own absolute position (requests at different
    depths share one micro-batch).  One extra *scratch* lane (index
    ``num_lanes``) absorbs the writes of padding lanes, so scatters with
    duplicate pad indices can never corrupt a live request.
    """

    def __init__(self, cfg: ModelConfig, num_lanes: int, capacity: int):
        self.num_lanes = int(num_lanes)
        self.capacity = int(capacity)
        lane = model_lib.init_cache(cfg, 1, capacity)
        self.cache = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (self.num_lanes + 1, *x.shape)),
            lane,
        )

    @property
    def scratch(self) -> int:
        return self.num_lanes

    @property
    def cache_tokens(self) -> int:
        """Token capacity reserved across lanes (excludes the scratch lane);
        the equal-memory axis the paged-pool benchmark compares on."""
        return self.num_lanes * self.capacity

    @property
    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(self.cache))

    def stats(self) -> Dict[str, int]:
        """Occupancy facts.  Shares only the ``cache_tokens``/``num_lanes``
        core with ``PagedCachePool.stats`` — block-geometry keys
        (``num_blocks``, ``free``, ...) exist only on the paged pool, so
        pool-agnostic callers must key off ``metrics()['cache_pool']['paged']``
        before reading them."""
        return {"cache_tokens": self.cache_tokens,
                "num_lanes": self.num_lanes, "capacity": self.capacity}

    def pad_lanes(self, lanes: Sequence[int], width: int) -> List[int]:
        """Pad a lane-id list to ``width`` with the scratch lane."""
        return pad_lane_ids(lanes, width, self.scratch)

    def gather(self, lanes: Sequence[int]):
        idx = jnp.asarray(lanes, jnp.int32)
        return jax.tree_util.tree_map(lambda x: x[idx], self.cache)

    def scatter(self, lanes: Sequence[int], lane_caches) -> None:
        idx = jnp.asarray(lanes, jnp.int32)
        self.cache = jax.tree_util.tree_map(
            lambda pool, new: pool.at[idx].set(new.astype(pool.dtype)),
            self.cache, lane_caches,
        )


class Scheduler:
    """Prefill-priority continuous-batching policy with block-aware admission.

    * admission serves the waiting (tier, version) group whose **oldest
      member has waited longest** (queue-wait aging, not deque position —
      the two differ once a preempted request is requeued at the front),
      then every same-key request in queue order, up to the free lane
      count, ``max_batch``, and — when a block allocator is attached —
      the free-block budget above the watermark.  Aging means a hot
      tier's prefill stream cannot starve another tier's queued requests:
      whichever group is oldest is served next, regardless of how many
      hot-tier requests sit in front of it.
    * with nothing to prefill, decode round-robins over the running
      (tier, version) groups so no tier starves, rotating *within* a
      group when it exceeds ``max_batch``;
    * :meth:`preempt` returns a running request to the *front* of the
      queue (it keeps its original ``submit_t``, so aging re-admits it
      first) — the gateway invokes it on the youngest running request
      when the block pool is exhausted mid-decode.
    * ``chunked=True`` switches to the left-aligned chunked-prefill
      policy: admitted requests enter PREFILLING and advance one chunk
      per prefill action, strictly alternating with decode steps over
      the RUNNING set — the bounded-stall guarantee that no decode step
      waits longer than one chunk.  Admission budgets blocks per request
      via ``blocks_needed`` (true prompt length) instead of the flat
      worst-case ``prefill_blocks``.
    """

    def __init__(self, num_lanes: int, max_batch: int, *,
                 allocator: Any = None, prefill_blocks: int = 0,
                 watermark_blocks: int = 0,
                 reclaimable: Optional[Callable[[], int]] = None,
                 suffix_bucket: Optional[
                     Callable[[GatewayRequest], int]] = None,
                 suffix_revalidate: Optional[
                     Callable[[GatewayRequest], int]] = None,
                 chunked: bool = False,
                 blocks_needed: Optional[
                     Callable[[GatewayRequest], int]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.num_lanes = int(num_lanes)
        self.max_batch = int(max_batch)
        # injectable clock: every wait/latency timestamp in the gateway
        # stack flows through this, so tests can drive virtual time
        self.clock = clock
        self.allocator = allocator
        self.prefill_blocks = int(prefill_blocks)
        self.watermark_blocks = int(watermark_blocks)
        # blocks the gateway can reclaim on demand (prefix-cache retained
        # chains with no live request references) — they count toward the
        # admission budget because eviction frees them before allocation
        self.reclaimable = reclaimable
        # prefix-aware admission grouping: probe of a request's uncached
        # suffix width (the gateway wires PrefixCache.peek through this).
        # Prefill lanes share one static suffix width, so batching a
        # full-match lane (1-token suffix) with a cold lane pads the hit
        # up to the cold lane's full width — grouping by bucket keeps
        # each micro-batch at its own (narrow) width instead.
        self.suffix_bucket = suffix_bucket
        # fresh (cache-bypassing) probe used to re-validate members at
        # batch formation: a cached probe taken before an eviction can
        # report a bucket the radix tree no longer backs, and admitting
        # on it would mis-group the batch
        self.suffix_revalidate = suffix_revalidate
        # chunked mode: admitted requests enter PREFILLING and their
        # prompts advance chunk-by-chunk, strictly alternating with
        # decode steps (no decode waits longer than one chunk)
        self.chunked = bool(chunked)
        # per-request block need (chunked admission budgets per prompt
        # length instead of the flat worst-case ``prefill_blocks``)
        self.blocks_needed = blocks_needed
        # fleet hooks, wired post-construction by FleetGateway
        # (serving/fleet.py).  ``global_budget`` returns how many MORE of
        # this slot's blocks the fleet-wide cache budget can cover
        # (counting every slot's reclaimable chains); admission takes the
        # min of the local and global budgets, so one hot model cannot
        # admit past the fleet's shared memory even with a free local
        # pool.  ``admission_filter`` re-validates a QUEUED request at
        # batch formation (tenant entitlement revoked since submit);
        # returning False drops it from the queue — the callback itself
        # marks the request rejected.
        self.global_budget: Optional[Callable[[], int]] = None
        self.admission_filter: Optional[
            Callable[[GatewayRequest], bool]] = None
        self.waiting: Deque[GatewayRequest] = deque()
        self.running: List[GatewayRequest] = []
        self._free_lanes: List[int] = list(range(num_lanes))
        self._rr = 0
        self._chunk_rr = 0
        self._group_cursor: Dict[Hashable, int] = {}
        self._start_seq = 0
        self._last_prefill = False

    # ----------------------------------------------------------- bookkeeping
    def submit(self, req: GatewayRequest) -> None:
        req.state = RequestState.QUEUED
        self.waiting.append(req)

    def free_lanes(self) -> int:
        return len(self._free_lanes)

    def start(self, req: GatewayRequest, *, prefilling: bool = False) -> int:
        """Move a request to RUNNING (or PREFILLING, when its prompt will
        chunk through over several steps), assigning it a lane."""
        lane = self._free_lanes.pop()
        req.lane = lane
        req.state = (RequestState.PREFILLING if prefilling
                     else RequestState.RUNNING)
        req.start_seq = self._start_seq
        self._start_seq += 1
        self.running.append(req)
        return lane

    def finish(self, req: GatewayRequest) -> None:
        """Release the lane of a completed request."""
        self.running.remove(req)
        if req.lane is not None:
            self._free_lanes.append(req.lane)
        req.lane = None
        req.state = RequestState.DONE
        req.finish_t = self.clock()

    def preempt(self, req: GatewayRequest) -> None:
        """Evict a running request back to the head of the queue.

        The request restarts from scratch on re-admission (recompute
        preemption): generation is deterministic given (seed, prompt,
        view), so a restarted request reproduces its evicted tokens.
        Caller is responsible for releasing any cache blocks it held.
        """
        self.running.remove(req)
        if req.lane is not None:
            self._free_lanes.append(req.lane)
        req.lane = None
        req.pos = 0
        req.cursor = 0
        req.prefix_tokens = 0
        req.out_tokens.clear()
        if req.logits_rows is not None:
            req.logits_rows.clear()
        req.first_token_t = None
        req._last_tok_t = None
        req.preemptions += 1
        req.state = RequestState.QUEUED
        self.waiting.appendleft(req)

    def youngest_running(self) -> Optional[GatewayRequest]:
        """Most recently started request — the preemption victim."""
        if not self.running:
            return None
        return max(self.running, key=lambda r: r.start_seq)

    def pinned_versions(self) -> set:
        """Weight versions still referenced by queued or running requests."""
        return {r.version for r in self.waiting} | {r.version for r in self.running}

    def pinned_tier_versions(self) -> set:
        """(tier, version) pairs referenced by queued or running requests —
        the views DEGRADED lease serving is contractually pinned to."""
        return {(r.license, r.version)
                for r in list(self.waiting) + list(self.running)}

    def hot_tiers(self) -> List[str]:
        """License tiers with queued or running requests, busiest first.

        This is the occupancy signal the staged-update prewarm uses: tiers
        serving traffic *now* are the ones whose first admission at a new
        weight version would otherwise pay a cold view materialization."""
        counts: Dict[str, int] = {}
        for r in list(self.running) + list(self.waiting):
            counts[r.license] = counts.get(r.license, 0) + 1
        return sorted(counts, key=lambda t: (-counts[t], t))

    # --------------------------------------------------------- wait metrics
    def oldest_wait_s(self, now: Optional[float] = None) -> float:
        """Age of the oldest queued request (0.0 with an empty queue)."""
        if not self.waiting:
            return 0.0
        now = self.clock() if now is None else now
        return now - min(r.submit_t for r in self.waiting)

    def queue_wait_by_tier(self, now: Optional[float] = None) -> Dict[str, float]:
        """Per-tier age of the oldest queued request."""
        now = self.clock() if now is None else now
        out: Dict[str, float] = {}
        for r in self.waiting:
            age = now - r.submit_t
            out[r.license] = max(out.get(r.license, 0.0), age)
        return out

    # ---------------------------------------------------------------- policy
    def _prefill_room(self) -> int:
        room = min(len(self._free_lanes), self.max_batch)
        if self.allocator is not None and self.prefill_blocks > 0:
            budget = self.allocator.num_free - self.watermark_blocks
            if self.reclaimable is not None:
                budget += self.reclaimable()
            if self.global_budget is not None:
                budget = min(budget, self.global_budget())
            room = min(room, max(0, budget // self.prefill_blocks))
        return room

    def next_action(self) -> Optional[ScheduledAction]:
        if self.chunked:
            return self._next_action_chunked()
        act = self._admission_batch()
        if act is not None:
            return act
        return self._decode_action()

    def _next_action_chunked(self) -> Optional[ScheduledAction]:
        """Chunked-prefill policy: strict alternation between prefill
        chunks (continuing PREFILLING requests first, admitting new ones
        otherwise) and decode steps, so no decode step ever waits longer
        than one chunk and no prefill starves behind a decode stream."""
        chunking = [r for r in self.running
                    if r.state is RequestState.PREFILLING]
        decoding = [r for r in self.running
                    if r.state is RequestState.RUNNING]
        if self._last_prefill and decoding:
            self._last_prefill = False
            return self._decode_action()
        act = (self._chunk_action(chunking) if chunking
               else self._admission_batch())
        if act is not None:
            self._last_prefill = True
            return act
        if decoding:
            self._last_prefill = False
            return self._decode_action()
        return None

    def _chunk_action(self, chunking: List[GatewayRequest]) -> ScheduledAction:
        """Continue mid-prefill requests: round-robin over their (tier,
        version) groups, rotating within a group past ``max_batch``."""
        groups: Dict[Hashable, List[GatewayRequest]] = {}
        for r in chunking:
            groups.setdefault(r.group_key, []).append(r)
        keys = sorted(groups, key=str)
        key = keys[self._chunk_rr % len(keys)]
        self._chunk_rr += 1
        members = groups[key]
        if len(members) > self.max_batch:
            cur = self._group_cursor.get(("chunk", key), 0) % len(members)
            members = (members + members)[cur:cur + self.max_batch]
            self._group_cursor[("chunk", key)] = cur + self.max_batch
        return ScheduledAction("prefill", key[0], key[1], list(members))

    def _admission_batch(self) -> Optional[ScheduledAction]:
        if self.admission_filter is not None and self.waiting:
            # entitlement re-check at batch formation: a tenant revoked
            # since submit must not reach a lane.  The filter marks the
            # request rejected; only survivors stay queued.  In-flight
            # (PREFILLING/RUNNING) requests are never revisited — like
            # tier redefinitions, a revocation drains, it never cancels.
            self.waiting = deque(
                r for r in self.waiting if self.admission_filter(r))
        room = self._prefill_room()
        if not (room and self.waiting):
            return None
        # aging: serve the group whose oldest member arrived first;
        # deque position breaks ties (plain FIFO when ages are equal)
        oldest: Dict[Tuple, Tuple[float, int]] = {}
        for i, r in enumerate(self.waiting):
            cand = (r.submit_t, i)
            if r.group_key not in oldest or cand < oldest[r.group_key]:
                oldest[r.group_key] = cand
        key = min(oldest, key=lambda k: oldest[k])
        bucket: Optional[int] = None
        anchor: Optional[GatewayRequest] = None
        probed: Dict[int, int] = {}          # id(req) -> bucket, one
                                             # probe per request per pass
        if self.suffix_bucket is not None:

            def _bucket(r: GatewayRequest) -> int:
                got = probed.get(id(r))
                if got is None:
                    got = probed[id(r)] = self.suffix_bucket(r)
                return got

            # the oldest member defines the batch's suffix width;
            # same-key requests with a different cached-suffix bucket
            # wait for their own batch rather than padding this one.
            # The anchor's probe is taken fresh when a revalidator is
            # wired: a stale cached bucket must not define the batch.
            anchor = self.waiting[oldest[key][1]]
            if self.suffix_revalidate is not None:
                bucket = probed[id(anchor)] = self.suffix_revalidate(anchor)
            else:
                bucket = _bucket(anchor)
        budget: Optional[int] = None
        if self.allocator is not None and self.blocks_needed is not None:
            budget = self.allocator.num_free - self.watermark_blocks
            if self.reclaimable is not None:
                budget += self.reclaimable()
            if self.global_budget is not None:
                budget = min(budget, self.global_budget())
        batch: List[GatewayRequest] = []
        remaining: Deque[GatewayRequest] = deque()
        for r in self.waiting:               # one pass: select + requeue
            take = (len(batch) < room and r.group_key == key and
                    (bucket is None or _bucket(r) == bucket))
            if (take and bucket is not None and r is not anchor
                    and self.suffix_revalidate is not None):
                # re-validate at formation: the cached probe may predate
                # an eviction that shrank this request's cached prefix
                fresh = probed[id(r)] = self.suffix_revalidate(r)
                take = fresh == bucket
            if take and budget is not None:
                need = self.blocks_needed(r)
                take = need <= budget
                if take:
                    budget -= need
            if take:
                batch.append(r)
            else:
                remaining.append(r)
        if not batch:
            self.waiting = remaining
            return None
        self.waiting = remaining
        return ScheduledAction("prefill", key[0], key[1], batch,
                               suffix_bucket=bucket)

    def _decode_action(self) -> Optional[ScheduledAction]:
        pool = [r for r in self.running if r.state is RequestState.RUNNING]
        if not pool:
            return None
        groups: Dict[Hashable, List[GatewayRequest]] = {}
        for r in pool:
            groups.setdefault(r.group_key, []).append(r)
        keys = sorted(groups, key=str)
        key = keys[self._rr % len(keys)]
        self._rr += 1
        members = groups[key]
        if len(members) > self.max_batch:
            cur = self._group_cursor.get(key, 0) % len(members)
            members = (members + members)[cur:cur + self.max_batch]
            self._group_cursor[key] = cur + self.max_batch
        return ScheduledAction("decode", key[0], key[1], list(members))
