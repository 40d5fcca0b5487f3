"""Licensed serving gateway: continuous batching over tier-keyed weight views.

This is the serving front end the ROADMAP's "heavy traffic" north star
needs on a single device: requests tagged with a ``LicenseTier`` stream
in, the :class:`~repro.serving.scheduler.Scheduler` groups them into
tier-homogeneous micro-batches, and every batch is served through a
**(tier, version)-keyed cache of masked weight views** — the paper's
one-stored-model-many-tiers claim (§3.5) amortized across requests
instead of paid per request.

Execution model
---------------
Two jitted functions, each compiled once per gateway:

* ``prefill``: by default (paged + reconstructible lane state)
  *left-aligned chunked* — every prompt keeps its true positions from 0
  and advances up to ``chunk_size`` tokens per prefill action, with
  chunk actions strictly interleaved against decode steps so no decode
  ever waits longer than one chunk; the per-lane variable-offset suffix
  step (``prefill_suffix_step``) is the chunk engine.  The legacy
  bucket path (``chunk_size=0``, and the fallback for ring/SSM lane
  state): ``vmap`` over ``max_batch`` lanes of a batch-1
  ``prefill_step`` with a fixed prompt bucket (``max_prompt``); short
  prompts are right-aligned with repeated-first-token padding (same
  trick as ``ServingEngine``).
* ``decode``: ``vmap`` over lanes of a batch-1 ``serve_step`` where the
  absolute position is *per lane* — this is what makes the batching
  continuous: lanes at different depths (different requests' positions)
  decode together, and a finished lane is refilled by the next prefill
  without draining the batch.

Both take the weight view as an argument, so one compilation serves
every tier and weight version.  By default each lane's logits feed a
**fused on-device sampling step** (``engine.sample_lane``) so a decode
step ships one token id per lane device->host instead of a full logits
row; ``fuse_sampling=False`` (or ``record_logits=True``) is the
return-logits escape hatch tests and the equivalence benchmark use.

Cache memory
------------
KV/SSM state lives in a shared pool.  Prefill gathers/scatters per-lane
views around the vmapped step; decode — the hot path — runs
**kernel-resident** by default (``kernel_decode``): one batched step
whose cache operands are the paged pool's physical block arrays, so
attention reads each cache byte once through the micro-batch's trimmed
block tables and the one new K/V token per lane is a block-indexed
scatter — no contiguous view of any sequence exists during decode.  Two
pool modes, selected by the ``paged`` config flag:

* ``paged=True`` (default): a :class:`~repro.serving.paging.PagedCachePool`
  — per-token KV leaves live as fixed-size physical blocks addressed
  through per-request block tables, so short and long requests share the
  pool without over-reserving, and ``max_lanes`` (concurrency) decouples
  from ``max_batch`` (vmap width).  Admission is gated on free *blocks*
  (plus a watermark); if decode exhausts the pool, the **youngest**
  running request is preempted back to the queue head (recompute-style —
  generation is deterministic per (seed, prompt, view), so the restart
  reproduces its tokens).  Models with no per-token cache (pure SSM)
  fall back to the contiguous pool automatically.
* ``paged=False``: the seed fixed-slab :class:`CachePool`, one
  ``capacity``-token lane per ``max_batch`` slot.

With paging, a **shared-prefix radix cache** (``serving/prefix.py``,
``prefix_cache=True`` default) retains finished prompts' block chains
per (tier, version) scope: a later request whose prompt shares a cached
prefix adopts those blocks by reference and prefills only the uncached
remainder; shared blocks are read-only — decode copy-on-writes a shared
tail block before its first write into it — and retained chains with no
live request are evicted LRU-first under allocation pressure.  Under
chunked prefill the radix keys are the TRUE token ids (left alignment
puts every prompt's positions at 0..len), so prompts of *different
lengths* sharing a system prefix share its KV blocks — the padded
bucket rows of the legacy path could only ever match same-bucket rows.

Licensing integration
---------------------
* float path: the view is ``apply_license(base, tier)`` — masking cost
  paid once per (tier, version), cached in :class:`TierViewCache`;
* int8 path (``quantized=True``): ONE int8 store serves every tier and
  the view is just the tier's packed license intervals, fused into the
  in-scan masked dequant (``kernels/masked_dequant`` semantics); with
  ``materialize_int8_views=True`` the gateway instead runs the fused
  masked-dequant kernel once per (tier, version) and caches the
  full-precision licensed view — trading memory for per-step speed on
  long decode streams.
* protocol: :meth:`LicensedGateway.from_server` boots the gateway from a
  ``LicenseServer`` via the §3.1.2 delta protocol (an internal
  ``EdgeClient`` holds the raw weights); :meth:`begin_sync` starts a
  *staged* pull (``serving/updates.py``) whose bounded
  fetch/apply/requantize/prewarm steps ride along with scheduler
  iterations and whose weights+tiers flip is one atomic step —
  :meth:`sync` is the blocking form of the same machinery.  Admission
  validates the tier (locally or against the server) and pins the
  request to the current version, so in-flight requests are never
  re-masked mid-generation; stale versions and their views are dropped
  once the last pinned request drains.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.transport import Transport, TransportError
from repro.serving.engine import (prefill_step, prefill_suffix_step,
                                  right_align, sample, sample_lane,
                                  serve_step, serve_step_paged)
from repro.serving.fleet import ModelSlot
from repro.serving.paging import cdiv
from repro.serving.scheduler import (GatewayRequest, RequestState,
                                     ScheduledAction)
from repro.serving.tracing import NULL_SPAN


def _pow2(n: int) -> int:
    """Smallest power of two >= n (bucketing for jit specialization)."""
    return 1 << max(0, int(n) - 1).bit_length()


def _phased(name: str):
    """Run the whole method inside the gateway's program span ``name``."""
    def wrap(method):
        @functools.wraps(method)
        def run(self, *args, **kwargs):
            with self._phase(name):
                return method(self, *args, **kwargs)
        return run
    return wrap


def _finish_lane(logits, seed, n_out, temp, top_k, *, fused, with_rng,
                 with_topk):
    """One lane's epilogue: raw logits row, or the fused on-device sample."""
    if not fused:
        return logits
    key = jax.random.fold_in(jax.random.PRNGKey(seed), n_out)
    return sample_lane(logits, key, temp, top_k,
                       with_rng=with_rng, with_topk=with_topk)


@functools.lru_cache(maxsize=None)
def _compiled_steps(cfg: ModelConfig, fused: bool = False,
                    with_rng: bool = True, with_topk: bool = True):
    """Jitted lane-vmapped prefill/decode, shared by every gateway on the
    same (hashable, frozen) config — one compile per (config, shape,
    fused, rng, topk) key.  ``fused=True`` samples per lane on device and
    returns token ids; ``fused=False`` returns the raw logits rows.
    ``with_rng``/``with_topk`` specialize the fused sampler to the
    micro-batch (all-greedy batches skip the categorical, no-top-k
    batches skip the vocab sort) — at most 4 fused variants ever compile."""

    def _finish(logits, seed, n_out, temp, top_k):
        return _finish_lane(logits, seed, n_out, temp, top_k, fused=fused,
                            with_rng=with_rng, with_topk=with_topk)

    def _prefill_one(view_params, tokens, cache, seed, n_out, temp, top_k, li):
        logits, cache = prefill_step(view_params, cfg, tokens[None], cache,
                                     license_intervals=li)
        return _finish(logits[0], seed, n_out, temp, top_k), cache

    def _decode_one(view_params, tok, cache, pos, seed, n_out, temp, top_k, li):
        logits, cache = serve_step(view_params, cfg, tok[None, None], cache,
                                   pos, license_intervals=li)
        return _finish(logits[0], seed, n_out, temp, top_k), cache

    return (jax.jit(jax.vmap(_prefill_one,
                             in_axes=(None, 0, 0, 0, 0, 0, 0, None))),
            jax.jit(jax.vmap(_decode_one,
                             in_axes=(None, 0, 0, 0, 0, 0, 0, 0, None))))


@functools.lru_cache(maxsize=None)
def _compiled_paged_decode(cfg: ModelConfig, fused: bool = False,
                           with_rng: bool = True, with_topk: bool = True,
                           kernel: str = "off"):
    """Jitted *kernel-resident* decode step: one batched call over the
    micro-batch (not a per-lane vmap) whose cache operands are the paged
    pool's physical block arrays — attention reads each cache byte once
    through the (trimmed) block tables and writes the one new K/V token
    per lane through its block index.  No per-lane contiguous cache is
    ever materialized; only the constant-size lane state rides in and
    out.  One compilation per (config, used-table-width, sampling
    variant); widths are ``ceil(context / block_size)`` so at most
    ``blocks_per_lane`` widths ever compile per config."""

    def _finish(logits, seed, n_out, temp, top_k):
        return _finish_lane(logits, seed, n_out, temp, top_k, fused=fused,
                            with_rng=with_rng, with_topk=with_topk)

    def _step(view_params, toks, cache, tables, poss, seeds, nouts, temps,
              topks, li):
        rows, cache = serve_step_paged(view_params, cfg, toks[:, None],
                                       cache, tables, poss,
                                       license_intervals=li, kernel=kernel)
        return jax.vmap(_finish)(rows, seeds, nouts, temps, topks), cache

    # donate the cache operand: the pool's block arrays are updated IN
    # PLACE (absorb_decode adopts the outputs wholesale and the old
    # storage is dropped), so a step's one-token write never copies the
    # pool.  Without donation XLA would clone O(num_blocks) bytes per
    # step — more traffic than the gather/scatter path this replaces.
    # Backends without donation support (CPU) fall back to a copy.
    return jax.jit(_step, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _compiled_prefix_prefill(cfg: ModelConfig, fused: bool = False,
                             with_rng: bool = True, with_topk: bool = True):
    """Jitted lane-vmapped *suffix* prefill for prefix-cache hits.

    Per lane: ``tokens`` is the uncached tail of the prompt bucket padded
    on the right to the micro-batch's suffix width, ``pos`` the lane's
    cached-prefix length (the variable prefill offset), and ``last`` the
    row of the last real token (right padding means it is not row -1).
    One compilation per (config, suffix width, sampling variant); suffix
    widths are multiples of the block size minus nothing — at most
    ``prompt_blocks + 1`` distinct widths ever compile per config."""

    def _one(view_params, tokens, cache, pos, last, seed, n_out, temp,
             top_k, li):
        logits, cache = prefill_suffix_step(view_params, cfg, tokens[None],
                                            cache, pos,
                                            license_intervals=li)
        row = _finish_lane(logits[0, last], seed, n_out, temp, top_k,
                           fused=fused, with_rng=with_rng,
                           with_topk=with_topk)
        return row, cache

    return jax.jit(jax.vmap(_one,
                            in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, None)))


class LicensedGateway:
    """Continuous-batching serving gateway with per-tier licensed views.

    Parameters
    ----------
    cfg, params:
        Model config and raw (float) weights, as for ``ServingEngine``.
    tiers:
        Name -> :class:`LicenseTier`; ``"full"`` is always available.
        Unknown tiers are also resolved against ``server`` when attached.
    quantized:
        Serve from ONE int8 store with license masks fused into the
        in-scan dequant (see ``serving/quantized.py``).
    already_quantized:
        ``params`` is already an int8 store (used by
        ``ServingEngine.gateway()``); implies ``quantized``.
    materialize_int8_views:
        int8 mode only: run the fused masked-dequant once per
        (tier, version) and cache full-precision licensed views.
    max_batch:
        Lanes per micro-batch (the vmap width).
    max_prompt:
        Maximum prompt length; longer prompts are rejected at admission.
        Under chunked prefill (default) every prompt is *left-aligned* —
        its absolute positions run from 0, independent of other lanes —
        so logits match an unpadded run of the same prompt.  Under the
        legacy bucket path (``chunk_size=0``) shorter prompts are
        right-aligned into the bucket with repeated-first-token padding,
        so absolute positions (and therefore logits) match a
        ``ServingEngine`` group padded to the same width instead.
    max_new_cap:
        Decode budget per lane; ``max_new_tokens`` is clamped to it.
    paged:
        Use the block-paged cache pool (default).  ``False`` selects the
        seed contiguous ``CachePool`` — the fallback config every
        pre-paging behavior maps onto.
    block_size / num_blocks / max_lanes / watermark_blocks:
        Paged-pool geometry.  ``num_blocks`` defaults to full
        provisioning (``max_lanes * ceil(capacity/block_size)`` — equal
        memory to the contiguous pool at ``max_lanes == max_batch``, and
        preemption-free); size it smaller to oversubscribe.  Admission
        requires ``watermark_blocks`` free blocks above a prefill's
        need, reserving decode-growth headroom.
    prefix_cache:
        Retain finished prompts' block chains in a (tier, version)-scoped
        radix cache (``serving/prefix.py``) and serve later requests'
        shared prefixes from them: prefill runs only on the uncached
        suffix (per-lane variable offsets), shared blocks are adopted by
        reference, and decode copy-on-writes a shared tail block before
        its first write into it.  Retained chains with no live request
        are evicted LRU-first whenever admission or decode growth needs
        blocks, so retention never shrinks the usable pool.  Paged mode
        only; auto-disabled (with ``prefix_cache=True`` silently inert)
        when any per-lane cache state is not a reconstructible position
        counter — SSM/RG-LRU state and sliding-window ring caches cannot
        be seeded from blocks.  ``False`` restores PR 2 behavior exactly.
    chunk_size:
        Left-aligned chunked prefill: each prefill action advances every
        PREFILLING lane by up to ``chunk_size`` prompt tokens, and chunk
        actions strictly alternate with decode steps — a long prompt
        never stalls in-flight decodes for more than one chunk, and the
        radix cache keys on true token ids so prefix reuse crosses
        prompt-length boundaries.  Default (None): the pool's
        ``block_size`` when supported (paged pool with reconstructible
        lane state — the ``prefix_cache`` condition), else 0.  ``0``
        forces the legacy right-aligned bucket prefill; an explicit
        positive value on an unsupported model raises.  Values above
        ``max_prompt`` are clamped.  Smaller chunks bound decode stalls
        tighter at the cost of more prefill step launches — this is the
        latency-SLO knob.
    kernel_decode:
        Kernel-resident paged decode (default auto).  Decode runs as ONE
        batched step whose cache operands are the pool's physical block
        arrays: attention reads each cache byte exactly once through the
        micro-batch's trimmed block tables, and the new K/V token is a
        block-indexed scatter — the per-step gather/scatter round trip of
        each lane's full logical cache disappears (it survives only for
        prefill, CoW copies, and the constant-size SSM/LRU lane state).
        Auto-disabled (clean fallback to gather/scatter decode) for
        sliding-window models, whose ring caches are per-lane state, and
        moot for pure-recurrent models (contiguous pool).  ``False``
        restores the PR 3 decode path exactly.
    decode_pallas:
        How the kernel-resident step reads the cache: ``"pallas"`` routes
        attention through the scalar-prefetch Pallas kernel
        (``kernels/paged_attention.py``), ``"interpret"`` the same kernel
        in interpret mode (CPU testing), ``"off"`` the pure-JAX
        block-gather fallback with identical semantics.  Default (None)
        picks "pallas" on TPU backends, "off" elsewhere.  int8-KV and
        MLA caches always use the fallback path.
    fuse_sampling:
        Sample per lane on device and return token ids (default).
        ``False`` is the return-logits escape hatch: logits rows come
        back to the host and are sampled there (identical tokens).
    record_logits:
        Keep each emitted step's logits row on the request
        (``req.logits_rows``) for equivalence tests; implies
        ``fuse_sampling=False``.
    """

    def __init__(self, cfg: ModelConfig, params: Any, **kw):
        # all serving state lives on a ModelSlot (serving/fleet.py) so a
        # FleetGateway can compose many models behind one loop; the
        # __getattr__/__setattr__ pair below forwards every slot
        # attribute, keeping this class's execution methods (and its
        # whole public surface) unchanged for single-model callers
        self.slot = ModelSlot(cfg, params, **kw)
        self.slot.gateway = self

    def __getattr__(self, name: str):
        # reached only when normal lookup fails: slot state (pool,
        # scheduler, views, stats, cfg, version, ...) resolves here
        slot = object.__getattribute__(self, "__dict__").get("slot")
        if slot is None:
            raise AttributeError(name)
        return getattr(slot, name)

    def __setattr__(self, name: str, value: Any) -> None:
        slot = self.__dict__.get("slot")
        if slot is not None and hasattr(slot, name):
            setattr(slot, name, value)
        else:
            object.__setattr__(self, name, value)

    def _note_retrace(self, family: str, key: Any) -> None:
        """Feed one jit-specialization key to the retracing sentinel
        (no-op unless the slot was built with ``sanitize=True``)."""
        if self.sanitizer is not None:
            self.sanitizer.retrace.note(family, key)

    def _steps(self, reqs: List[GatewayRequest]):
        """(prefill, decode) jitted pair specialized to this micro-batch's
        sampling needs; batches with no stochastic lane skip the
        categorical draw, batches with no top-k lane skip the sort."""
        if not self.fuse_sampling:
            self._note_retrace("steps", (False, False, False))
            return _compiled_steps(self.cfg, False)
        with_rng = any(r.temperature > 0 for r in reqs)
        with_topk = with_rng and any(r.top_k for r in reqs)
        self._note_retrace("steps", (True, with_rng, with_topk))
        return _compiled_steps(self.cfg, True, with_rng, with_topk)

    def _prefix_steps(self, reqs: List[GatewayRequest]):
        """Suffix-prefill jit specialized like :meth:`_steps`."""
        if not self.fuse_sampling:
            self._note_retrace("prefix_prefill", (False, False, False))
            return _compiled_prefix_prefill(self.cfg, False)
        with_rng = any(r.temperature > 0 for r in reqs)
        with_topk = with_rng and any(r.top_k for r in reqs)
        self._note_retrace("prefix_prefill", (True, with_rng, with_topk))
        return _compiled_prefix_prefill(self.cfg, True, with_rng, with_topk)

    def _paged_decode_step(self, reqs: List[GatewayRequest]):
        """Kernel-resident decode jit specialized like :meth:`_steps`."""
        if not self.fuse_sampling:
            self._note_retrace("paged_decode", (False, False, False))
            return _compiled_paged_decode(self.cfg, False,
                                          kernel=self.decode_pallas)
        with_rng = any(r.temperature > 0 for r in reqs)
        with_topk = with_rng and any(r.top_k for r in reqs)
        self._note_retrace("paged_decode", (True, with_rng, with_topk))
        return _compiled_paged_decode(self.cfg, True, with_rng, with_topk,
                                      kernel=self.decode_pallas)

    # ------------------------------------------------------------ weight views
    # (_resolve_tier / _materialize and the scheduler callbacks
    # _suffix_bucket / _suffix_bucket_fresh / _blocks_needed live on
    # ModelSlot — they are pure slot-state functions the slot wires into
    # its own TierViewCache and Scheduler at construction)
    def _refresh_server_tiers(self) -> None:
        """Re-pull tiers learned from the server.

        A redefined tier (an operator tightening 'free' on a live
        gateway) or a revoked one must not keep serving its old masks —
        but in-flight requests are never re-masked mid-generation, so
        the change is *deferred* until the tier's current requests
        drain.  While a revocation OR redefinition is pending, new
        admissions to the tier are rejected: nothing new may be served
        under the superseded masks, and with no new joiners the tier
        drains (and the change lands) in bounded time.

        Under a wire fault the refresh *defers* rather than fails: the
        current tiers keep serving (the DEGRADED-lease contract) and the
        stale flag re-runs this on the next lease restore."""
        touched = False
        for name in list(self._server_tiers):
            try:
                fresh = self.retry_policy.run(
                    lambda n=name: self._transport.tier(self.model, n),
                    on_retry=self._count_wire_retry)
                touched = True
            except KeyError:
                fresh = None                       # revoked server-side
                touched = True
            except TransportError:
                self._tiers_stale = True
                if touched:
                    self._lease_renew()
                self._apply_pending_tiers()
                return
            cur = self.tiers.get(name)
            if fresh is not None and cur is not None and fresh.masks == cur.masks:
                self._pending_tiers.pop(name, None)
                continue
            self._pending_tiers[name] = fresh
        if touched:
            self._lease_renew()
        self._tiers_stale = False
        self._apply_pending_tiers()

    def _tier_in_flight(self, name: str) -> bool:
        return (any(r.license == name for r in self.scheduler.waiting)
                or any(r.license == name for r in self.scheduler.running))

    def _apply_pending_tiers(self) -> None:
        for name, fresh in list(self._pending_tiers.items()):
            if self._tier_in_flight(name):
                continue                           # defer until drained
            if fresh is None:
                self.tiers.pop(name, None)
                self._server_tiers.discard(name)
                if self.obs:
                    self.audit.record("tier_revoke", model=self.model,
                                      tier=name)
            else:
                self.tiers[name] = fresh
                if self.obs:
                    self.audit.record("tier_redefine", model=self.model,
                                      tier=name,
                                      fingerprint=fresh.fingerprint())
            self.views.invalidate(tier=name)
            if self.prefix is not None:
                # cached blocks encode the old mask's activations
                self.prefix.drop_scope(tier=name)
            del self._pending_tiers[name]

    def view_for(self, tier: str, version: Optional[int] = None):
        """Licensed weight view for (tier, version) — cached."""
        return self.views.get(tier, self.version if version is None else version)

    # ------------------------------------------------------------ telemetry
    def _phase(self, name: str, attrs: Optional[Dict[str, Any]] = None):
        """Program span ``name`` (the table in ``serving/tracing.py``), or
        the shared null context when telemetry is off."""
        return self.tracer.span(name, attrs) if self.obs else NULL_SPAN

    def _span(self, req: GatewayRequest, name: Optional[str],
              attrs: Optional[Dict[str, Any]] = None) -> None:
        """Close the request's open lifecycle span and begin ``name``
        (None = just close).  Per-request lifecycle phases (queue ->
        prefill -> decode) are sequential, never nested, so one slot per
        request suffices and every B gets its E."""
        if self.obs:
            if req._open_span is not None:
                self.tracer.end(req._open_span, req.rid)
            if name is not None:
                self.tracer.begin(name, req.rid, attrs)
        req._open_span = name

    def _note_admission(self, req: GatewayRequest) -> None:
        """Record a request leaving the queue for a lane: ``admit_t`` and
        the queue-wait histogram (first admission only — a restart's wait
        is preemption recovery, not admission wait), admit/restart
        instant, and the prefill lifecycle span."""
        if req.admit_t is None:
            req.admit_t = self.clock()
        if not self.obs:
            return
        if req.preemptions == 0:
            self.h_queue.observe(req.admit_t - req.submit_t)
            name = "admit"
        else:
            name = "restart"
        self.tracer.instant(name, req.rid,
                            {"tier": req.license, "version": req.version,
                             "lane": req.lane})
        self._span(req, "prefill", {"tier": req.license,
                                    "version": req.version})

    def _note_first_token(self, req: GatewayRequest, now: float) -> None:
        """First token of a (possibly restarted) prefill: TTFT is counted
        ONCE per request — a preemption clears ``first_token_t`` but not
        ``_ttft_done``, so the restart's re-emission never double-counts."""
        req.first_token_t = now
        if not self.obs:
            return
        if not req._ttft_done:
            req._ttft_done = True
            self.h_ttft.observe(now - req.submit_t)
        self._span(req, "decode")

    def render_prometheus(self) -> str:
        """Prometheus text exposition of every registered instrument."""
        return self.telemetry.render_prometheus()

    def chrome_trace(self) -> str:
        """This gateway's event tape as Chrome trace_event JSON."""
        return self.tracer.chrome_trace(
            process_name=self.model or "gateway")

    def audit_events(self, event: Optional[str] = None) -> List[Dict[str, Any]]:
        """The licensing audit stream (optionally filtered by event)."""
        return self.audit.events(event)

    # -------------------------------------------------------------- admission
    def _reject(self, req: GatewayRequest, error: str) -> GatewayRequest:
        req.state = RequestState.REJECTED
        req.error = error
        self.stats["rejected"] += 1
        if self.obs:
            self.tracer.instant("reject", req.rid,
                                {"tier": req.license, "reason": error})
        return req

    @_phased("gw:submit")
    def submit(self, prompt, *, license: str = "full", max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int = 0,
               seed: int = 0, tenant: Optional[str] = None) -> GatewayRequest:
        """Admit one request: validate the tier, pin the weight version.
        ``tenant`` is carried for fleet accounting — quota enforcement
        itself lives in ``FleetGateway.submit`` (a standalone gateway
        records but never polices it)."""
        req = GatewayRequest(
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=min(int(max_new_tokens), self.max_new_cap),
            license=license, model=self.model, tenant=tenant,
            # snap sub-epsilon temperatures to greedy: the fused sampler
            # clamps its divisor at 1e-6, so only the t <= 0 branch keeps
            # the fused and host paths token-identical down there
            temperature=0.0 if temperature <= 1e-6 else temperature,
            # top_k >= vocab truncates nothing; clamping keeps the host
            # sampler (lax.top_k needs k <= vocab) and the fused sampler
            # (clips its kth index) on identical behavior
            top_k=min(max(0, int(top_k)), self.cfg.padded_vocab), seed=seed,
        )
        if self.record_logits:
            req.logits_rows = []
        req.rid = self._next_rid
        self._next_rid += 1
        req.submit_t = self.clock()
        try:
            serve_as, lease_err = self._lease_admission(license)
            if lease_err is not None:
                raise KeyError(lease_err)
            if serve_as != license:
                # OFFLINE floor policy: serve the most restrictive
                # locally-known tier instead of an unverifiable grant
                if self.obs:
                    self.tracer.instant("lease_floor", req.rid,
                                        {"requested": license,
                                         "served_as": serve_as})
                license = serve_as
                req.license = serve_as
            if license in self._pending_tiers:
                # a pending revocation OR redefinition refuses admissions:
                # serving new requests under the superseded masks while
                # in-flight ones drain would let an observer see (old
                # tier, new version) — the mixed state the atomic flip
                # exists to rule out.  The tier drains in bounded time
                # precisely because nothing new joins it.
                verb = ("revoked" if self._pending_tiers[license] is None
                        else "redefined; retry once in-flight requests "
                             "drain")
                raise KeyError(f"license tier {license!r} is being {verb}")
            self._resolve_tier(license)
        except KeyError as e:
            return self._reject(req, str(e))
        if not 1 <= len(req.prompt) <= self.max_prompt:
            return self._reject(req, f"prompt length {len(req.prompt)} "
                                     f"outside [1, {self.max_prompt}]")
        if req.max_new_tokens < 1:
            return self._reject(req, "max_new_tokens < 1")
        if not -2**31 <= int(seed) < 2**31:
            # seeds ride the fused sampler as an int32 lane array; an
            # out-of-range one must bounce here, not crash the run() loop
            return self._reject(req, f"seed {seed} outside int32 range")
        req.version = self.version
        self.scheduler.submit(req)
        self.stats["admitted"] += 1
        if self.obs:
            self.tracer.instant(
                "submit", req.rid,
                {"tier": req.license, "version": req.version,
                 "model": self.model, "tenant": req.tenant,
                 "prompt_tokens": len(req.prompt),
                 "max_new_tokens": req.max_new_tokens})
            self._span(req, "queue")
        return req

    # ------------------------------------------------------------- scheduling
    @_phased("gw:step")
    def step(self, *, drive_stager: bool = True) -> Optional[ScheduledAction]:
        """Run ONE scheduler iteration (one prefill or decode micro-batch),
        plus — when a staged weight sync is active — ONE bounded stager
        step, so a version bump's work rides along with serving instead of
        ever stalling it.  A ``FleetGateway`` passes
        ``drive_stager=False`` and advances at most one slot's stager
        per fleet iteration itself."""
        with self._phase("sched:next"):
            act = self.scheduler.next_action()
        if act is not None:
            act.model = self.model
            attrs = ({"tier": act.tier, "version": act.version}
                     if self.obs else None)
            with self._phase("sched:" + act.kind, attrs) as sp:
                if act.kind == "prefill":
                    if self.chunked:
                        self._run_chunked_prefill(act)
                    else:
                        self._run_prefill(act)
                else:
                    self._run_decode(act)
                if self.obs:
                    attrs["batch"] = len(act.requests)
                    if act.suffix_bucket is not None:
                        attrs["suffix_bucket"] = act.suffix_bucket
            if self.obs:
                (self.h_prefill if act.kind == "prefill"
                 else self.h_decode).observe(sp.dur)
                self.tracer.counter("queue_depth",
                                    len(self.scheduler.waiting))
                self.tracer.counter("running",
                                    len(self.scheduler.running))
                if self.paged:
                    self.tracer.counter("blocks_held",
                                        self.pool.allocator.num_held)
        if drive_stager and self._stager is not None and self._stager.active:
            try:
                self._stager.step()
            except TransportError:
                # retries exhausted: the stager aborted inside step()
                # (staged weights dropped, failure counted toward
                # quarantine) — serving continues on the current version
                pass
        if self._server is not None:
            self._lease_tick()
        if self.sanitizer is not None and act is not None:
            self.sanitizer.after_step(self)
        if act is None:
            return None
        # a decode whose whole batch was preempted executed nothing —
        # keep the trace invariant that every entry covers >= 1 request
        if act.requests:
            self.trace.append((act.kind, act.tier, act.version,
                               len(act.requests)))
        return act

    def run(self, max_steps: int = 1_000_000) -> List[GatewayRequest]:
        """Drain the queue; returns requests completed during this call.
        An active staged sync keeps stepping after the queue empties, so
        returning from ``run`` implies any begun version flip landed."""
        drained: List[GatewayRequest] = []
        self._drain_sink = drained
        try:
            for _ in range(max_steps):
                if self.step() is None and not self.sync_active:
                    if self.sanitizer is not None:
                        # queue and lanes are empty: anything still held
                        # must be reachable via the prefix tree
                        self.sanitizer.check_drained(self)
                    break
        finally:
            self._drain_sink = None
        return drained

    def _sampling_lanes(self, reqs, width: Optional[int] = None):
        """Per-lane (seed, n_generated, temperature, top_k) arrays for the
        fused sampler; padding lanes sample junk that is discarded.
        ``width`` defaults to ``max_batch``; the chunked-prefill path
        passes its trimmed vmap width."""
        width = self.max_batch if width is None else width
        seeds = np.zeros(width, np.int32)
        nouts = np.zeros(width, np.int32)
        temps = np.zeros(width, np.float32)
        topks = np.zeros(width, np.int32)
        for i, r in enumerate(reqs):
            seeds[i] = r.seed
            nouts[i] = len(r.out_tokens)
            temps[i] = r.temperature
            topks[i] = r.top_k
        return (jnp.asarray(seeds), jnp.asarray(nouts), jnp.asarray(temps),
                jnp.asarray(topks))

    def _alloc_blocks(self, n: int) -> List[int]:
        """Allocate ``n`` blocks, reclaiming retained prefix chains (LRU)
        if the free list alone can't cover it.  The scheduler's admission
        budget counts reclaimable blocks, so this must succeed for any
        admitted prefill.  Under a fleet the global byte budget is
        settled first: admission counted fleet-wide reclaimable bytes,
        so cross-slot eviction must be able to make strict room."""
        if self.fleet is not None:
            assert self.fleet._ensure_headroom(self, n), \
                "scheduler admitted past the fleet cache budget"
        got = self.pool.allocator.alloc(n)
        if got is None and self.prefix is not None:
            self.prefix.evict(n - self.pool.allocator.num_free)
            got = self.pool.allocator.alloc(n)
        assert got is not None, "scheduler admitted past the block budget"
        return got

    def _decref_block(self, b: int) -> None:
        """Drop one request reference, keeping the prefix cache's O(1)
        reclaimable counter exact: when exactly one reference survives
        and it is the tree's, the block just became evictable."""
        if self.pool.allocator.decref(b) == 1 and self.prefix is not None:
            self.prefix.note_release(b)

    def _release_blocks(self, req: GatewayRequest) -> None:
        """Drop the request's reference on every block it holds.  Private
        blocks return to the free list; blocks shared with the prefix
        cache (or another request) stay alive under the remaining refs —
        release, not free, is what makes retention safe."""
        for b in req.blocks:
            self._decref_block(b)
        req.blocks = []

    def _scatter_tables(self, tables: np.ndarray,
                        reqs: List[GatewayRequest]) -> np.ndarray:
        """Write-back tables with every *shared* block redirected to the
        null block.  Shared blocks are immutable: a prefix-cached prefill
        re-writes identical gathered bytes and the one recomputed token of
        a fully-matched prompt, decode re-writes untouched rows — all
        redundant, and redirecting them keeps retained chains bit-stable
        under concurrent readers (decode CoWs before any real write)."""
        out = tables.copy()
        alloc = self.pool.allocator
        n_cols = out.shape[1]              # chunked prefill trims columns
        for i, r in enumerate(reqs):
            for j, b in enumerate(r.blocks[:n_cols]):
                if alloc.refcount(b) > 1:
                    out[i, j] = self.pool.null_block
        return out

    def _run_prefill(self, act: ScheduledAction) -> None:
        with self._phase("view:get"):
            view_params, li = self.views.get(act.tier, act.version)
        reqs = act.requests
        with self._phase("gw:inputs"):
            toks = right_align([r.prompt for r in reqs], self.max_prompt,
                               self.max_batch)
            seeds, nouts, temps, topks = self._sampling_lanes(reqs)
        # longest-cached-prefix lookup (before any allocation: matching
        # increfs the chains, so eviction under this batch's own pressure
        # can never free a block another lane is about to adopt).  The
        # prompt row is the *padded* bucket — identical rows mean identical
        # absolute positions, the condition for KV reuse under RoPE.
        scope = (act.tier, act.version)
        matches: List[Tuple[List[int], int]] = []
        if self.prefix is not None:       # paged-only by construction
            with self._phase("pool:grow"):
                for i in range(len(reqs)):
                    blocks, ntok = self.prefix.match(scope, toks[i])
                    # always recompute >= 1 token: the first sampled
                    # token needs the last prompt position's logits
                    capped = min(ntok, self.max_prompt - 1)
                    if capped == 0 and blocks:
                        # the cap zeroed a real match (max_prompt == 1):
                        # the chain is unusable — release its references
                        for b in blocks:
                            self._decref_block(b)
                        blocks = []
                    matches.append((blocks, capped))
        hit = any(n > 0 for _, n in matches)
        if hit:
            lanes = [self.scheduler.start(r) for r in reqs]
            for r in reqs:
                self._note_admission(r)
            outs = self._run_prefix_prefill(
                act, toks, matches, lanes, view_params, li,
                (seeds, nouts, temps, topks))
        else:
            with self._phase("step:dispatch"):
                prefill, _ = self._steps(reqs)
                outs, lane_caches = prefill(view_params, jnp.asarray(toks),
                                            self._zero_lanes, seeds, nouts,
                                            temps, topks, li)
            lanes = [self.scheduler.start(r) for r in reqs]
            for r in reqs:
                self._note_admission(r)
            if self.paged:
                with self._phase("pool:grow"):
                    for r in reqs:
                        r.blocks = self._alloc_blocks(self._prefill_blocks)
                    self._note_block_use()
            with self._phase("pool:absorb"):
                lane_ids = self.pool.pad_lanes(lanes, self.max_batch)
                if self.paged:
                    tables = self.pool.pad_tables([r.blocks for r in reqs],
                                                  self.max_batch)
                    self.pool.scatter(lane_ids, tables, lane_caches)
                else:
                    self.pool.scatter(lane_ids, lane_caches)
            self.stats["prefill_lane_tokens"] += self.max_prompt * len(reqs)
        self.stats["max_running"] = max(self.stats["max_running"],
                                        len(self.scheduler.running))
        if self.prefix is not None:
            # donate the prompt chains (full blocks + partial tail) so the
            # next same-prefix request prefills only its suffix
            for i, r in enumerate(reqs):
                self.prefix.insert(scope, toks[i],
                                   r.blocks[: self._prefill_blocks])
        with self._phase("step:fetch"):
            outs = np.asarray(outs)
        with self._phase("gw:emit"):
            now = self.clock()
            for i, r in enumerate(reqs):
                r.pos = self.max_prompt
                self._note_first_token(r, now)
                if self.fuse_sampling:
                    self._emit(r, tok=int(outs[i]))
                else:
                    self._emit(r, logits_row=outs[i])
        self.stats["prefill_batches"] += 1
        if act.suffix_bucket is not None:
            self.bucket_batches[act.suffix_bucket] = \
                self.bucket_batches.get(act.suffix_bucket, 0) + 1

    def _run_prefix_prefill(self, act: ScheduledAction, toks: np.ndarray,
                            matches: List[Tuple[List[int], int]],
                            lanes: List[int], view_params, li, sampling):
        """Prefill a micro-batch with >= 1 prefix-cache hit: every lane
        runs only its uncached suffix, at its own offset, in one vmapped
        step.

        Lanes share one (static) suffix width ``W = max(suffix lens)``;
        a lane whose suffix is shorter is padded on the *right* (its
        writes land beyond the prompt in its own decode region, masked
        by ``len`` until decode overwrites them) and its last real row is
        selected per lane.  Adopted blocks enter the table by reference;
        write-back redirects every shared block to the null block, so
        retained chains are never mutated."""
        reqs = act.requests
        seeds, nouts, temps, topks = sampling
        suffix = [self.max_prompt - n for _, n in matches]
        w = max(suffix)
        sub = np.zeros((self.max_batch, w), np.int32)
        poss = np.zeros(self.max_batch, np.int32)
        lasts = np.zeros(self.max_batch, np.int32)
        with self._phase("pool:grow"):
            for i, r in enumerate(reqs):
                blocks, ntok = matches[i]
                sub[i, : suffix[i]] = toks[i, ntok:]
                sub[i, suffix[i]:] = toks[i, -1]   # right pad: junk region
                poss[i] = ntok
                lasts[i] = suffix[i] - 1
                fresh = self._alloc_blocks(self._prefill_blocks - len(blocks))
                r.blocks = list(blocks) + fresh
                r.prefix_tokens = ntok
                self.stats["prefix_tokens_reused"] += ntok
                if self.obs and ntok:
                    self.tracer.instant("prefix_hit", r.rid,
                                        {"tokens": ntok})
            self._note_block_use()
        self.stats["prefill_lane_tokens"] += w * len(reqs)
        with self._phase("gw:inputs"):
            lane_ids = self.pool.pad_lanes(lanes, self.max_batch)
        with self._phase("pool:gather"):
            tables = self.pool.pad_tables([r.blocks for r in reqs],
                                          self.max_batch)
            caches = self.pool.gather(lane_ids, tables,
                                      fresh_lane_state=True)
        with self._phase("step:dispatch"):
            prefill = self._prefix_steps(reqs)
            outs, lane_caches = prefill(view_params, jnp.asarray(sub),
                                        caches, jnp.asarray(poss),
                                        jnp.asarray(lasts), seeds, nouts,
                                        temps, topks, li)
        with self._phase("pool:absorb"):
            # the step's len accounting saw only W suffix tokens; pin the
            # counters to the true logical fill before they reach the pool
            lane_caches = self.pool.override_counters(lane_caches,
                                                      self.max_prompt)
            self.pool.scatter(lane_ids, self._scatter_tables(tables, reqs),
                              lane_caches)
        return outs

    # ------------------------------------------------------ chunked prefill
    def _run_chunked_prefill(self, act: ScheduledAction) -> None:
        """One chunked-prefill action: admit newly scheduled requests
        (adopt cached prefix blocks, allocate the rest, park the cursor
        past the reused tokens), then advance every member one
        ``chunk_size`` chunk.  An admission runs its first chunk in the
        same action, so a prompt no longer than one chunk still reaches
        its first token in a single step — the legacy one-step-prefill
        latency."""
        if act.requests[0].state is not RequestState.PREFILLING:
            with self._phase("pool:grow"):
                self._admit_chunked(act)
        self._run_prefill_chunk(act)

    def _admit_chunked(self, act: ScheduledAction) -> None:
        """Admission half of a chunked prefill: prefix-match every
        prompt on its TRUE token ids (left alignment gives every prompt
        absolute positions from 0, so different-length prompts sharing
        a prefix share its blocks — the cross-length reuse padded
        bucket rows ruled out), then allocate the uncached remainder.
        Matching runs for the whole batch BEFORE any allocation:
        matching increfs the chains, so this batch's own allocation
        pressure can never evict a block another lane is about to
        adopt."""
        scope = (act.tier, act.version)
        reqs = act.requests
        matches: List[Tuple[List[int], int]] = []
        for r in reqs:
            if self.prefix is not None:
                blocks, ntok = self.prefix.match(scope, r.prompt)
            else:
                blocks, ntok = [], 0
            # always recompute >= 1 token: the first sampled token needs
            # the last prompt position's logits
            capped = min(ntok, len(r.prompt) - 1)
            if capped == 0 and blocks:
                # the cap zeroed a real match (1-token prompt): the
                # chain is unusable — release the match's references
                for b in blocks:
                    self._decref_block(b)
                blocks = []
            matches.append((blocks, capped))
        bs = self.pool.block_size
        for r, (blocks, capped) in zip(reqs, matches):
            self.scheduler.start(r, prefilling=True)
            self._note_admission(r)
            if self.obs and capped:
                self.tracer.instant("prefix_hit", r.rid, {"tokens": capped})
            # a partial match adopts only FULL blocks (the radix tree
            # matches a partial tail only when it covers the whole
            # prompt), so the uncached suffix starts on a block boundary
            # and chunk writes never touch a shared block: aligned tails
            # are CoW-free by construction
            fresh = self._alloc_blocks(
                max(0, cdiv(len(r.prompt), bs) - len(blocks)))
            r.blocks = list(blocks) + fresh
            r.cursor = capped
            r.prefix_tokens = capped
            self.stats["prefix_tokens_reused"] += capped
        self._note_block_use()
        self.stats["prefill_batches"] += 1
        self.stats["max_running"] = max(self.stats["max_running"],
                                        len(self.scheduler.running))

    def _run_prefill_chunk(self, act: ScheduledAction) -> None:
        """Advance every member by one left-aligned chunk.

        All lanes share the static ``chunk_size`` width; a lane with
        fewer tokens left is right-padded with junk whose writes land
        past its real rows — scattered to the null block beyond its
        table, or into private rows that the next chunk / first decode
        write overwrites and the ``len`` counter masks until then.  A
        lane whose cursor reaches the prompt end emits its first token
        (the last chunk's selected row is the last prompt position's
        logits) and enters decode."""
        with self._phase("view:get"):
            view_params, li = self.views.get(act.tier, act.version)
        reqs = act.requests
        w = self.chunk_size
        bs = self.pool.block_size
        # trim the vmap width and the gathered table to what THIS chunk
        # can touch: a chunk step must move O(context) bytes, not
        # O(max_batch * capacity), or one chunk stalls decode far longer
        # than one decode step and the interleaving SLO is fiction.
        # Pow2 buckets bound the number of jit specializations to
        # log2(max_batch) * log2(blocks_per_lane).
        b = min(self.max_batch, _pow2(len(reqs)))
        # cols must cover cursor + w INCLUDING junk pad rows: the linear
        # attend-cache write clamps out-of-range slots onto the last one,
        # and a junk row colliding with the chunk's final real token
        # would corrupt the K/V its own last query attends.  Covering
        # the junk keeps every pad write on a distinct slot strictly
        # past the real rows (causally unattended, scattered to null).
        need = max(cdiv(r.cursor + w, bs) for r in reqs)
        cols = min(self.pool.blocks_per_lane, _pow2(need))
        self._note_retrace("prefill_chunk", (b, cols))
        with self._phase("gw:inputs"):
            sub = np.zeros((b, w), np.int32)
            poss = np.zeros(b, np.int32)
            lasts = np.zeros(b, np.int32)
            fills = np.zeros(b, np.int32)
            valid = np.zeros(len(reqs), np.int32)
            for i, r in enumerate(reqs):
                v = min(w, len(r.prompt) - r.cursor)
                valid[i] = v
                sub[i, :v] = r.prompt[r.cursor: r.cursor + v]
                sub[i, v:] = int(r.prompt[-1])     # right pad: junk region
                poss[i] = r.cursor
                lasts[i] = v - 1
                fills[i] = r.cursor + v
            seeds, nouts, temps, topks = self._sampling_lanes(reqs, b)
            lane_ids = self.pool.pad_lanes([r.lane for r in reqs], b)
        with self._phase("pool:gather"):
            tables = self.pool.pad_tables([r.blocks[:cols] for r in reqs],
                                          b, n_cols=cols)
            # per-lane counters are pinned to the true fill below, and
            # the attend-cache step masks positionally — fresh lane state
            # is correct for EVERY chunk, not just the first
            caches = self.pool.gather(lane_ids, tables,
                                      fresh_lane_state=True)
        with self._phase("step:dispatch"):
            prefill = self._prefix_steps(reqs)
            outs, lane_caches = prefill(view_params, jnp.asarray(sub),
                                        caches, jnp.asarray(poss),
                                        jnp.asarray(lasts), seeds, nouts,
                                        temps, topks, li)
        with self._phase("pool:absorb"):
            lane_caches = self.pool.override_counters(lane_caches,
                                                      jnp.asarray(fills))
            self.pool.scatter(lane_ids, self._scatter_tables(tables, reqs),
                              lane_caches)
        self.stats["prefill_lane_tokens"] += w * len(reqs)
        self.stats["prefill_chunks"] += 1
        with self._phase("step:fetch"):
            outs = np.asarray(outs)
        with self._phase("gw:emit"):
            now = self.clock()
            scope = (act.tier, act.version)
            for i, r in enumerate(reqs):
                r.cursor += int(valid[i])
                if self.obs:
                    self.tracer.instant("prefill_chunk", r.rid,
                                        {"cursor": r.cursor,
                                         "tokens": int(valid[i])})
                if r.cursor < len(r.prompt):
                    continue
                r.state = RequestState.RUNNING
                r.pos = len(r.prompt)
                self._note_first_token(r, now)
                if self.prefix is not None:
                    # donate the TRUE-token chain (full blocks + partial
                    # tail) so any future prompt sharing the prefix — at
                    # any length — adopts it
                    self.prefix.insert(scope, r.prompt, r.blocks)
                if self.fuse_sampling:
                    self._emit(r, tok=int(outs[i]))
                else:
                    self._emit(r, logits_row=outs[i])

    def _try_alloc_one(self) -> Optional[int]:
        """One block from the free list, reclaiming retained prefix chains
        if needed — never preempts.  None when the pool is truly full.
        Under a fleet, the global byte budget gates first: when no
        retained chain anywhere can be reclaimed to cover one more of
        this slot's blocks, report exhaustion — the caller's
        within-slot youngest-preemption frees this slot's own bytes
        (never another model's)."""
        if (self.fleet is not None
                and not self.fleet._ensure_headroom(self, 1)):
            return None
        got = self.pool.allocator.alloc(1)
        if got is None and self.prefix is not None and self.prefix.evict(1):
            got = self.pool.allocator.alloc(1)
        return got[0] if got is not None else None

    def _grow_one(self, r: GatewayRequest,
                  keep: List[GatewayRequest]) -> Optional[int]:
        """One block for ``r``, trying free list, then prefix-cache
        eviction, then youngest-first preemption.  Returns the block id,
        or None if ``r`` itself was preempted to make room."""
        while True:
            got = self._try_alloc_one()
            if got is not None:
                return got
            victim = self.scheduler.youngest_running()
            if victim is r and len(self.scheduler.running) == 1:
                raise RuntimeError(
                    "block pool exhausted by a single request")
            self._preempt(victim)
            if victim in keep:
                keep.remove(victim)
            if victim is r:
                return None

    def _grow_block_tables(self, reqs: List[GatewayRequest]) \
            -> List[GatewayRequest]:
        """Give every request the block its next decode write needs, and a
        *private* copy of it when the block is shared.

        On pool exhaustion, first evict retained (request-free) prefix
        chains LRU-first, then preempt the youngest running request
        (release its block references, requeue it at the queue head) and
        retry; a victim inside this micro-batch is dropped from it.
        Terminates because the pool holds at least one full request
        (constructor guard), every eviction/preemption strictly drops
        references, and the oldest running request is never chosen while
        others run.

        Copy-on-write: this step writes position ``pos`` into block
        ``pos // bs``.  If that block is shared — the prompt tail donated
        to (or adopted from) the prefix cache — the request gets a fresh
        block holding a device copy and swaps its table entry; the shared
        original stays pristine for its other holders.
        """
        keep = list(reqs)
        if self.prefix is not None:
            # reclaim the batch's whole shortfall — growth blocks plus a
            # copy per shared write target (potential CoW) — in ONE
            # eviction pass instead of one tree walk per block; only
            # mid-pass churn falls back to _try_alloc_one's evict(1)
            need = 0
            for r in keep:
                if r.state != RequestState.RUNNING:
                    continue
                tail = r.pos // self.pool.block_size
                need += max(0, tail + 1 - len(r.blocks))
                if tail < len(r.blocks) and \
                        self.pool.allocator.refcount(r.blocks[tail]) > 1:
                    need += 1
            shortfall = need - self.pool.allocator.num_free
            if shortfall > 0:
                self.prefix.evict(shortfall)
        for r in list(keep):
            if r.state != RequestState.RUNNING:
                continue                   # preempted earlier in this pass
            needed = r.pos // self.pool.block_size + 1
            while len(r.blocks) < needed:
                b = self._grow_one(r, keep)
                if b is None:
                    break                  # r was preempted
                r.blocks.append(b)
            if r.state != RequestState.RUNNING:
                continue
            tail = needed - 1              # block receiving this step's write
            if self.pool.allocator.refcount(r.blocks[tail]) > 1:
                # shared write target: prefer a private copy, but with no
                # spare block (fully provisioned pool) steal the tree's
                # reference back instead — forfeiting one tail's future
                # hits beats preempting a running request for a copy
                b = self._try_alloc_one()
                if b is None:
                    if (self.prefix is not None
                            and self.pool.allocator.refcount(
                                r.blocks[tail]) == 2
                            and self.prefix.forget_block(r.blocks[tail])):
                        continue           # unshared now: write in place
                    b = self._grow_one(r, keep)
                    if b is None:
                        continue           # r itself was preempted
                self.pool.copy_block(r.blocks[tail], b)
                self._decref_block(r.blocks[tail])
                r.blocks[tail] = b
                self.stats["cow_copies"] += 1
        self._note_block_use()
        return keep

    def _preempt(self, req: GatewayRequest) -> None:
        self._release_blocks(req)
        # the restart will re-emit these tokens; keep the counter equal to
        # tokens actually delivered
        self.stats["tokens_generated"] -= len(req.out_tokens)
        if self.obs:
            self._span(req, None)
            self.tracer.instant("preempt", req.rid,
                                {"tokens_lost": len(req.out_tokens)})
        self.scheduler.preempt(req)
        if self.obs:
            # back at the queue head: the lifecycle re-enters its queue
            # phase until re-admission emits a "restart"
            self._span(req, "queue")
        self.stats["preempted"] += 1

    def _note_block_use(self) -> None:
        self.stats["max_blocks_in_use"] = max(
            self.stats["max_blocks_in_use"], self.pool.allocator.num_held)

    def _run_decode(self, act: ScheduledAction) -> None:
        if self.paged:
            with self._phase("pool:grow"):
                act.requests = self._grow_block_tables(act.requests)
            if not act.requests:
                return                     # whole batch preempted
            if self.sanitizer is not None:
                # post-CoW: every table entry live, write targets private
                self.sanitizer.check_decode_writes(act.requests, self.pool)
        with self._phase("view:get"):
            view_params, li = self.views.get(act.tier, act.version)
        reqs = act.requests
        with self._phase("gw:inputs"):
            lanes = self.pool.pad_lanes([r.lane for r in reqs],
                                        self.max_batch)
            toks = np.zeros(self.max_batch, np.int32)
            poss = np.zeros(self.max_batch, np.int32)
            for i, r in enumerate(reqs):
                toks[i] = r.out_tokens[-1]
                poss[i] = r.pos
            seeds, nouts, temps, topks = self._sampling_lanes(reqs)
        if self.paged and self.kernel_decode:
            # kernel-resident path: the pool's block arrays ARE the cache
            # operands.  Tables are trimmed to the batch's used width, so
            # attention reads O(context) bytes once through the table;
            # the one new K/V token per lane is written through its block
            # index (the target is private — _grow_block_tables CoW'd a
            # shared tail before this step), and shared prefix blocks are
            # never write targets, so no null-redirect is needed.
            with self._phase("pool:gather"):
                used = max(r.pos // self.pool.block_size + 1 for r in reqs)
                self._note_retrace("decode_width", used)
                tables = self.pool.pad_tables(
                    [r.blocks[:used] for r in reqs], self.max_batch, used)
                caches = self.pool.decode_cache(lanes)
            with self._phase("step:dispatch"):
                step = self._paged_decode_step(reqs)
                outs, caches = step(view_params, jnp.asarray(toks), caches,
                                    jnp.asarray(tables), jnp.asarray(poss),
                                    seeds, nouts, temps, topks, li)
            with self._phase("pool:absorb"):
                self.pool.absorb_decode(lanes, caches)
            self.stats["resident_decode_steps"] += 1
        else:
            with self._phase("pool:gather"):
                if self.paged:
                    tables = self.pool.pad_tables([r.blocks for r in reqs],
                                                  self.max_batch)
                    caches = self.pool.gather(lanes, tables)
                else:
                    caches = self.pool.gather(lanes)
            with self._phase("step:dispatch"):
                _, decode = self._steps(reqs)
                outs, caches = decode(view_params, jnp.asarray(toks), caches,
                                      jnp.asarray(poss), seeds, nouts, temps,
                                      topks, li)
            with self._phase("pool:absorb"):
                if self.paged:
                    # shared (prefix-cache) blocks are read-only: redirect
                    # their redundant write-back to the null block (the
                    # write target itself is always private — CoW'd above)
                    wb = (self._scatter_tables(tables, reqs)
                          if self.prefix is not None else tables)
                    self.pool.scatter(lanes, wb, caches)
                else:
                    self.pool.scatter(lanes, caches)
        with self._phase("step:fetch"):
            outs = np.asarray(outs)
        with self._phase("gw:emit"):
            for i, r in enumerate(reqs):
                r.pos += 1
                if self.fuse_sampling:
                    self._emit(r, tok=int(outs[i]))
                else:
                    self._emit(r, logits_row=outs[i])
        self.stats["decode_steps"] += 1

    def _emit(self, req: GatewayRequest, tok: Optional[int] = None,
              logits_row: Optional[np.ndarray] = None) -> None:
        """Append one token (sampled on host from ``logits_row`` when the
        fused path is off) and retire the request if it is finished."""
        if tok is None:
            if req.logits_rows is not None:
                req.logits_rows.append(np.asarray(logits_row, np.float32))
            if req.temperature <= 0:
                tok = int(np.argmax(logits_row))
            else:
                # host side top_k is concrete -> the static sample() path
                # (skips sample_lane's traced-k sort); same tokens either way
                key = jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                         len(req.out_tokens))
                tok = int(sample(jnp.asarray(logits_row)[None], key,
                                 temperature=req.temperature,
                                 top_k=req.top_k)[0])
        req.out_tokens.append(tok)
        self.stats["tokens_generated"] += 1
        if self.obs:
            # inter-token gap: decode cadence only.  The first token has
            # no predecessor, and a preemption clears ``_last_tok_t`` —
            # the restart's recovery pause is not a decode gap.
            now = self.clock()
            if req._last_tok_t is not None:
                self.h_gap.observe(now - req._last_tok_t)
            req._last_tok_t = now
        if len(req.out_tokens) >= req.max_new_tokens:
            self.scheduler.finish(req)
            if self.obs:
                self._span(req, None)
                self.tracer.instant("finish", req.rid,
                                    {"tokens": len(req.out_tokens),
                                     "preemptions": req.preemptions,
                                     "blocks": len(req.blocks)})
            if self.paged:
                # release references, don't free: blocks the prefix cache
                # retains (the prompt chain) survive for future hits
                self._release_blocks(req)
            self.completed.append(req)
            if self._drain_sink is not None:
                self._drain_sink.append(req)
            self.stats["completed"] += 1
            if self.on_finish is not None:
                # fleet tenant accounting (inflight release + usage)
                self.on_finish(req)
            self._gc_versions()

    # ---------------------------------------------------------- weight updates
    def update_weights(self, params: Any, *, version: Optional[int] = None,
                       already_quantized: bool = False) -> int:
        """Install new base weights under a new version.

        In-flight requests stay pinned to their admitted version; new
        admissions pin the new one.  Views for versions no longer pinned
        are invalidated once their last request drains.
        """
        if self.quantized and not already_quantized:
            from repro.serving.quantized import quantize_serving_params

            params = quantize_serving_params(params)
        version = self.version + 1 if version is None else int(version)
        if version < self.version:
            raise ValueError(f"version {version} is older than the current "
                             f"version {self.version}")
        if version in self._weights:
            # overwriting a live version: views built from the old weights
            # must not survive the swap — nor cached prefix activations
            self.views.invalidate(version=version)
            if self.prefix is not None:
                self.prefix.drop_scope(version=version)
        prev = self.version
        self._weights[version] = params
        self.version = version
        if self.obs:
            self.audit.record("version_install", model=self.model,
                              from_version=prev, to_version=version)
        self._gc_versions()
        return version

    def _gc_versions(self) -> None:
        live = self.scheduler.pinned_versions() | {self.version}
        if self._staging_version is not None:
            # a staged sync pre-registers the incoming version (and may
            # have prewarmed its views) before any request pins it
            live.add(self._staging_version)
        for v in [v for v in self._weights if v not in live]:
            del self._weights[v]
            self.views.invalidate(version=v)
            if self.prefix is not None:
                self.prefix.drop_scope(version=v)
        if self._pending_tiers:
            self._apply_pending_tiers()

    # ------------------------------------------------------- protocol plumbing
    @classmethod
    def from_server(cls, cfg: ModelConfig, server, model: str, template: Any,
                    transport: Optional[Transport] = None,
                    retry: Any = None, **kw) -> "LicensedGateway":
        """Boot a gateway as an edge serving pod of ``server`` (Fig. 2).

        ``template`` is a zeroed params pytree; the full production
        snapshot is pulled through the §3.1.2 delta protocol, and
        :meth:`sync` keeps pulling increments from then on.  An explicit
        ``transport`` routes every wire call (boot pull included) through
        it — a ChaosTransport here exercises the whole path; ``retry``
        overrides the gateway's RetryPolicy."""
        from repro.core.protocol import EdgeClient

        client = EdgeClient(model, template, license_name="full")
        client.request_update(transport if transport is not None else server,
                              retry=retry)
        gw = cls(cfg, client.params, server=server, model=model,
                 version=client.version, transport=transport,
                 **({} if retry is None else {"retry_policy": retry}), **kw)
        gw._client = client
        return gw

    def _register_staging(self, version: int, params: Any) -> None:
        """Pre-register a staged version's serving params so its views can
        be prewarmed before the flip.  ``_gc_versions`` keeps the staging
        version alive even though nothing pins it yet."""
        if version in self._weights:
            # overwriting a live version's weights: views (and cached
            # prefix activations) built from the old bytes must not
            # survive into the prewarm
            self.views.invalidate(version=version)
            if self.prefix is not None:
                self.prefix.drop_scope(version=version)
        self._staging_version = version
        self._weights[version] = params

    def _install_staged(self, version: int) -> None:
        """The stager's atomic flip: bump the served version AND apply tier
        redefinitions published alongside it, in one step with no
        scheduler iteration in between.  Prewarmed views survive (no
        invalidation here); in-flight requests stay pinned to the version
        they were admitted under."""
        assert version == self._staging_version, (version,
                                                  self._staging_version)
        if version < self.version:
            raise ValueError(f"version {version} is older than the current "
                             f"version {self.version}")
        prev = self.version
        self.version = version
        self._staging_version = None
        if self.obs:
            # the ONE choke point every flip funnels through — staged
            # step()-driven syncs and blocking sync() alike — so the
            # audit stream shows exactly one version_flip per bump
            self.audit.record("version_flip", model=self.model,
                              from_version=prev, to_version=version)
        if self._server is not None:
            # tier redefinitions land with the bump — an admission never
            # sees (new tiers, old version) or (old tiers, new version)
            self._refresh_server_tiers()
        self._gc_versions()

    def begin_sync(self, server: Any = None, **stager_kw) -> bool:
        """Start a *staged* (non-blocking) sync against the license server.

        Returns True when a newer production version exists and a staging
        session began — subsequent :meth:`step` calls each carry one
        bounded unit of fetch/apply/requantize/prewarm work and the new
        version flips in atomically at a step boundary.  Returns False
        when the client is already current (tier-only redefinitions are
        applied immediately — there is no flip to couple them to).  A
        sync already in progress is left to finish (returns True).  A
        wire fault that outlives the retry budget during the probe
        returns False — the gateway keeps serving and the caller may
        try again later."""
        server = server or self._server
        if server is None or self._client is None:
            raise RuntimeError("gateway was not booted with from_server()")
        if self._stager is not None and self._stager.active:
            return True
        from repro.serving.updates import UpdateStager

        stager = UpdateStager(self, server, **stager_kw)
        try:
            if stager.begin():
                self._stager = stager
                return True
        except TransportError:
            pass
        return False

    def sync_step(self) -> Optional[str]:
        """Advance an active staged sync by one bounded unit (for callers
        driving the stager without scheduler traffic); returns the phase
        that executed, or None when no sync is active."""
        if self._stager is None or not self._stager.active:
            return None
        return self._stager.step()

    @property
    def sync_active(self) -> bool:
        return self._stager is not None and self._stager.active

    def sync(self, server: Any = None, **stager_kw) -> bool:
        """Pull newer production weights (and tier redefinitions) from the
        license server — blocking, but through the same staged machinery
        as :meth:`begin_sync`, so the weights + tier flip is atomic either
        way.

        Returns True if a new weight version was installed (and pinned for
        all subsequent admissions)."""
        flipped = False
        while self.sync_active:           # finish a staged sync first
            self._stager.step()
            flipped = True
        if not self.begin_sync(server, **stager_kw):
            return flipped
        while self.sync_active:
            self._stager.step()
        return True

    # ---------------------------------------------------------------- metrics
    def _tenant_breakdown(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant usage on THIS slot: live requests (queued/running),
        tokens generated, cache blocks held, completions in the history
        window.  Tenant-less requests are not listed."""
        out: Dict[str, Dict[str, int]] = {}

        def _d(t: str) -> Dict[str, int]:
            return out.setdefault(t, {
                "inflight": 0, "queued": 0, "completed": 0,
                "tokens_generated": 0, "blocks_held": 0})

        for r in self.scheduler.running:
            if r.tenant is None:
                continue
            d = _d(r.tenant)
            d["inflight"] += 1
            d["blocks_held"] += len(r.blocks)
            d["tokens_generated"] += len(r.out_tokens)
        for r in self.scheduler.waiting:
            if r.tenant is None:
                continue
            d = _d(r.tenant)
            d["inflight"] += 1
            d["queued"] += 1
        for r in self.completed:
            if r.tenant is None:
                continue
            d = _d(r.tenant)
            d["completed"] += 1
            d["tokens_generated"] += len(r.out_tokens)
        return out

    def metrics(self) -> Dict[str, Any]:
        """Counters, queue-wait ages, pool occupancy, latency percentiles.
        ``oldest_wait_s``/``queue_wait_by_tier`` come from this slot's
        OWN scheduler queue — under a fleet each slot reports its own
        fairness ages, never another model's backlog."""
        out: Dict[str, Any] = dict(self.stats)
        out["model"] = self.model
        out["view_cache"] = self.views.stats()
        out["oldest_wait_s"] = self.scheduler.oldest_wait_s()
        out["queue_wait_by_tier"] = self.scheduler.queue_wait_by_tier()
        out["tenants"] = self._tenant_breakdown()
        out["cache_pool"] = {"paged": self.paged, **self.pool.stats()}
        out["decode_path"] = {"kernel_resident": self.kernel_decode,
                              "pallas": self.decode_pallas}
        out["staged_update"] = ({"active": False} if self._stager is None
                                else {"active": self._stager.active,
                                      **self._stager.stats()})
        out["chunked_prefill"] = {
            "enabled": self.chunked, "chunk_size": self.chunk_size,
            # prefill actions executed (one chunk each); decode steps
            # never wait longer than one of these
            "chunks": self.stats["prefill_chunks"]}
        out["admission_grouping"] = {
            # suffix-width bucketing is the LEGACY bucket-prefill
            # grouping; chunked mode admits per true prompt length
            "enabled": self.prefix is not None and not self.chunked,
            # prefill batches served per shared uncached-suffix width: a
            # full-match batch shows up under width 1, never padded to a
            # cold batch's max_prompt
            "batches_by_suffix_width": dict(self.bucket_batches)}
        out["lease"] = {
            "state": self._lease_state,
            "server_attached": self._server is not None,
            "ttl_s": self.lease_ttl_s,
            "grace_s": self.lease_grace_s,
            "policy": self.lease_policy,
            "renew_age_s": self.clock() - self._lease_renewed_t,
            "degraded_seconds_total": self.degraded_seconds_total(),
            "quarantined_versions": sorted(self.quarantined_versions),
            "pinned_views": len(self.scheduler.pinned_tier_versions()),
        }
        out["prefix_cache"] = {"enabled": self.prefix is not None}
        if self.prefix is not None:
            out["prefix_cache"].update(self.prefix.stats())
            out["prefix_cache"]["prefix_tokens_reused"] = \
                self.stats["prefix_tokens_reused"]
            out["prefix_cache"]["cow_copies"] = self.stats["cow_copies"]
        out["latency"] = {
            "ttft_s": self.h_ttft.summary(),
            "inter_token_s": self.h_gap.summary(),
            "queue_wait_s": self.h_queue.summary(),
            "step_prefill_s": self.h_prefill.summary(),
            "step_decode_s": self.h_decode.summary(),
            "stager_step_s": self.h_stager.summary(),
        }
        lats = [r.latency for r in self.completed if r.latency is not None]
        if lats:
            out["latency_p50_ms"] = float(np.percentile(lats, 50) * 1e3)
            out["latency_p99_ms"] = float(np.percentile(lats, 99) * 1e3)
        return out
