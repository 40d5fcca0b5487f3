"""Staged weight sync: version bumps that never stall a decode step.

``LicensedGateway.sync()`` used to pull the whole §3.1.2 update packet
and run ``update_weights()`` synchronously on the serving thread — the
full delta-apply, an optional whole-model requantize, and the view
invalidation all landed between two scheduler steps, and the first
admission at the new version then paid a cold view materialization on
top.  :class:`UpdateStager` splits that work into small, *bounded* steps
the gateway interleaves with its scheduler iterations:

```
poll ──▶ STAGE ──▶ REQUANT ──▶ PREWARM ──▶ FLIP
         (fetch one ≤max_step_bytes part      (int8 path: re-quantize
          from the server's UpdateCursor       ≤requant_layers_per_step
          and delta-apply it into the          TOUCHED layers per step,
          staging copy — kernels/delta_apply   reusing every untouched
          scatters in place)                   leaf of the live store)
                               (materialize the TierViewCache entry of
                                one currently-hot tier per step at the
                                NEW version, before anything serves it)
                                              (one atomic step: bump the
                                               gateway/client version AND
                                               apply tier redefinitions
                                               published alongside it)
```

Invariants the stager preserves:

* **Serving state is untouched until the flip.**  The staging params are
  a private copy (``apply_packet`` is copy-on-apply; the in-place kernel
  consumes only staging buffers); in-flight requests stay pinned to
  their admitted version throughout and produce bit-identical tokens to
  an update-free run.
* **Bounded work per step.**  A STAGE step transfers + applies at most
  ``max_step_bytes`` of delta (one indivisible chunk page may
  overshoot).  The layer being patched is held RESIDENT on device
  across its parts — uploaded once when its first part arrives,
  scattered into in place (``delta_apply`` donation), downloaded once
  when the cursor moves past it — so a step's total traffic is the
  delta bytes plus at most the layer-boundary transfers, never
  2×layer-bytes per part.  A REQUANT step re-quantizes at most
  ``requant_layers_per_step`` layers; a PREWARM step builds one tier
  view.  No step ever performs the full delta-apply or a whole-model
  requantize (the quantized fallback to a full requantize exists only
  for a gateway whose version diverged from its edge client's —
  impossible through the ``sync`` API).  Server-side, the masking of
  shipped values is equally per-part (``fetch_update``); only the §4.2
  delta query itself runs at ``begin`` — and the begin step is timed
  like any other scheduler step in the update benchmark.
* **Atomic flip.**  Tier redefinitions published together with the
  version bump go live in the same stager step that installs the new
  weights — an admission between any two scheduler steps sees either
  (old tiers, old version) or (new tiers, new version), never a mix.
  A redefined tier still serving in-flight requests at the flip defers
  (they are never re-masked mid-generation) and refuses NEW admissions
  until it drains — like a pending revocation — so the deferred window
  admits nothing under (old masks, new version).  (Tier-only changes,
  with no version bump, still apply immediately at ``begin`` — there is
  no flip to couple them to.)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from repro.analysis import lockstep
from repro.core.pytree_io import flatten_params, unflatten_like
from repro.core.transport import (PayloadCorruption, RetryPolicy, Transport,
                                  TransportError, TransportTimeout,
                                  as_transport)
from repro.serving.tracing import NULL_SPAN, STAGER_TID

# the cursor-protocol fields whose ownership moves with the fetch
# worker (see the guarded-by annotations in __init__ and
# repro.analysis.lockstep for the dynamic check)
_WORKER_FIELDS = ("_cursor", "_pos", "_cursor_dead")


class _ReopenRequired(Exception):
    """Internal worker→serving-thread signal: the cursor is dead (a
    disconnect or corrupted delivery) and reopening it needs the §4.2
    delta query — sqlite, which is bound to the serving thread.  Never
    escapes the stager."""


@functools.cache
def _page_update():
    """Jitted, buffer-donating contiguous page write: the staging buffer
    is consumed and the page lands in place on backends with donation
    support (elsewhere it degrades to one device-side copy per page —
    still never a host round trip).  ``start`` is a traced scalar, so one
    compilation serves every page offset of a (layer, page) shape pair."""
    import jax

    return jax.jit(
        lambda buf, page, start: jax.lax.dynamic_update_slice(
            buf, page, (start,)),
        donate_argnums=(0,))


class UpdateStager:
    """Incremental ``sync()``: fetch → stage → requantize → prewarm → flip.

    One stager serves one update session; the gateway constructs it in
    :meth:`LicensedGateway.begin_sync` and advances it one :meth:`step`
    per scheduler iteration (or in a tight loop for the blocking
    ``sync()``).  ``stats()`` exports the per-step accounting the update
    benchmark asserts its bounds on.
    """

    def __init__(self, gateway: Any, server: Any, *,
                 max_step_bytes: int = 256 << 10,
                 requant_layers_per_step: int = 2,
                 background_fetch: bool = True,
                 fetch_depth: int = 2,
                 transport: Optional[Transport] = None,
                 retry: Optional[RetryPolicy] = None,
                 join_timeout_s: float = 5.0):
        self.gw = gateway
        # every wire call goes through a Transport; ``server`` may be a
        # raw LicenseServer or a Transport over one.  When the gateway
        # was booted against the same server, its transport (and any
        # chaos schedule on it) is reused so one seam governs the sync.
        if transport is not None:
            self.transport = transport
        elif isinstance(server, Transport):
            self.transport = server
        else:
            gwt = getattr(gateway, "_transport", None)
            self.transport = (gwt if gwt is not None and gwt.server is server
                              else as_transport(server))
        self.server = self.transport.server
        self.retry = (retry if retry is not None
                      else getattr(gateway, "retry_policy", None)
                      or RetryPolicy())
        self.join_timeout_s = float(join_timeout_s)
        self.max_step_bytes = int(max_step_bytes)
        self.requant_layers_per_step = int(requant_layers_per_step)
        # true background fetch: the wire transfer (server.fetch_update
        # — pure in-memory cursor slicing + masking, no sqlite) runs on
        # a worker thread so wire time overlaps compute; the APPLY stays
        # on the serving thread, the flip stays at a step boundary.  The
        # worker stays at most ``fetch_depth`` parts-batches ahead
        # (bounded queue), so staging memory stays bounded too.
        self.background_fetch = bool(background_fetch)
        self.fetch_depth = max(1, int(fetch_depth))
        self._fetch_thread = None
        self._fetch_queue = None
        self._fetch_stop = None
        self.phase = "idle"
        self.to_version: Optional[int] = None
        self._cursor = None  # guarded-by: owner(__init__, begin, _reopen, abort, _flip)
        self._staged: Any = None          # staging copy of the raw params
        self._staged_q: Any = None        # staging int8 store (quantized path)
        self._touched: Set[str] = set()   # layer names the delta touched
        self._requant_queue: List[str] = []
        self._prewarm_queue: List[str] = []
        # fault-tolerance state: the last durably-applied cursor
        # position (the resume token), wire bytes accumulated across
        # reopened sessions, and whether the current cursor may have
        # advanced past parts the client never received
        self._pos: Tuple[int, int] = (0, 0)  # guarded-by: owner(__init__, begin, _fetch_parts)
        self._wire_bytes = 0  # guarded-by: owner(__init__, begin, _reopen)
        self._cursor_dead = False  # guarded-by: owner(__init__, begin, _reconnect, _fetch_parts)
        self.stats_: Dict[str, Any] = {
            "steps": 0, "parts_applied": 0, "bytes_applied": 0,
            "max_step_bytes_applied": 0, "layers_requantized": 0,
            "views_prewarmed": 0, "flips": 0,
            "retries": 0, "resumes": 0, "corrupt_parts": 0,
            "fetch_workers_leaked": 0,
        }

    # ------------------------------------------------------------------ state
    @property
    def active(self) -> bool:
        return self.phase not in ("idle", "done", "failed")

    def stats(self) -> Dict[str, Any]:
        out = dict(self.stats_)
        out["phase"] = self.phase
        out["to_version"] = self.to_version
        out["layers_touched"] = len(self._touched)
        out["max_step_bytes_bound"] = self.max_step_bytes
        out["background_fetch"] = self.background_fetch
        out["wire"] = dict(self.transport.stats)
        return out

    # ------------------------------------------------------------------ begin
    def begin(self) -> bool:
        """Poll the server.  Returns True when a staged update session
        started (a newer production version exists); False when the
        client is current — in which case tier-only redefinitions are
        applied immediately, since there is no version flip to join —
        or when the newer version is quarantined (repeated failed syncs
        toward it; serving continues on the current version).  Wire
        faults retry under the policy; exhaustion raises
        ``TransportError`` (``begin_sync`` turns that into "no sync
        started, keep serving")."""
        gw, client = self.gw, self.gw._client
        # cheap poll first: a no-op sync must not pay the §4.2 delta
        # query or leave an empty session in the server's audit log
        prod = self._wire(lambda: self.transport.production_version(gw.model))
        if prod == client.version:
            gw._refresh_server_tiers()
            self.phase = "done"
            return False
        if prod in gw.quarantined_versions:
            self.phase = "done"
            return False
        cursor = self._wire(lambda: self.transport.open_update(
            gw.model, client.version, client.license_name))
        if cursor.to_version == client.version:   # raced: moved back to us
            gw._refresh_server_tiers()
            self.phase = "done"
            return False
        if cursor.to_version in gw.quarantined_versions:
            self.phase = "done"
            return False
        if cursor.to_version < gw.version:
            raise ValueError(
                f"server production version {cursor.to_version} is older "
                f"than the gateway's current version {gw.version}")
        self._cursor = cursor
        self.to_version = cursor.to_version
        self._pos = cursor.tell()
        self._wire_bytes = 0
        self._cursor_dead = False
        # flat staging view: untouched layers stay the client's own (np)
        # arrays by reference; a touched layer is uploaded once, patched
        # in place on device part-by-part, and downloaded once when the
        # cursor moves past it (_finalize_layer)
        self._flat = dict(flatten_params(client.params))
        self._pending_layer: Optional[str] = None
        self._pending_buf = None
        self._staged = None               # assembled when the cursor drains
        self._touched = set()
        # incremental requant reuses the live int8 store's untouched
        # leaves; that store must correspond to the client's version
        # (always true through the sync API — update_weights() bypassing
        # the client is the only way to diverge, and then we requantize
        # everything in one fallback step)
        self._requant_base = (gw._weights.get(gw.version)
                              if gw.quantized and gw.version == client.version
                              else None)
        self.phase = "stage"
        if gw.obs:
            gw.audit.record("sync_begin", model=gw.model,
                            from_version=client.version,
                            to_version=cursor.to_version)
        if self.background_fetch:
            self._start_fetch_worker()
        return True

    # ------------------------------------------------------------ wire faults
    def _note_retry(self, attempt: int, exc: BaseException,
                    delay: float) -> None:
        """Per-retry accounting hook (runs on whichever thread made the
        wire call): stager counters, slot counters, and the
        ``sync_retry`` audit event."""
        self.stats_["retries"] += 1
        if isinstance(exc, PayloadCorruption):
            self.stats_["corrupt_parts"] += 1
        gw = self.gw
        gw._count_wire_retry(attempt, exc, delay,
                             to_version=self.to_version)

    def _wire(self, fn):
        """One wire call under the retry policy; success renews the
        license lease."""
        result = self.retry.run(fn, on_retry=self._note_retry)
        self.gw._lease_renew()
        return result

    def _reopen(self) -> None:
        """Reconnect after a lost or corrupted delivery: the dead
        cursor may have advanced past parts this client never received,
        so it is abandoned (its session log entry stays — an abandoned
        stream is still audit-visible) and a fresh session is opened,
        seeked to the last durably-applied position.  The delta query
        is deterministic, so the resumed row ranges line up exactly."""
        gw, client = self.gw, self.gw._client
        lockstep.checkpoint("stager.reopen",
                            touches=("_cursor", "_wire_bytes"))
        old, self._cursor = self._cursor, None
        if old is not None:
            self._wire_bytes += old.fetched_bytes
        cursor = self.transport.open_update(gw.model, client.version,
                                            client.license_name,
                                            resume=self._pos)
        if cursor.to_version != self.to_version:
            # the server moved on mid-sync: resuming would splice two
            # different deltas — not transient, abort the session
            raise RuntimeError(
                f"server production version moved {self.to_version} -> "
                f"{cursor.to_version} mid-sync; aborting this session")
        self._cursor = cursor
        self.stats_["resumes"] += 1

    def _reconnect(self) -> None:
        """Serving-thread reopen: clears the dead-cursor flag once the
        fresh session is seeked into place."""
        self._reopen()
        self._cursor_dead = False

    def _fetch_parts(self, allow_reopen: bool = True,
                     ) -> Tuple[List[Any], bool]:
        """One bounded parts batch off the wire, surviving faults: a
        failed delivery retries under the policy, resuming from the
        last durable cursor position instead of tearing the sync down.
        Returns ``(parts, done)``.  Runs on the fetch worker when
        background fetch is on, on the serving thread otherwise — it is
        the only mutator of cursor/position state while fetching.

        ``allow_reopen=False`` (the worker): a dead cursor raises
        :class:`_ReopenRequired` instead of reopening, because the
        reopen runs the sqlite-backed delta query and sqlite connections
        are bound to the serving thread.  Timeouts (the cursor never
        moved) still retry in place — pure in-memory work."""

        lockstep.checkpoint("stager.fetch_parts", touches=_WORKER_FIELDS)

        def attempt():
            if self._cursor_dead:
                if not allow_reopen:
                    raise _ReopenRequired()
                self._reconnect()
            try:
                return self.transport.fetch_update(self._cursor,
                                                   self.max_step_bytes)
            except TransportTimeout:
                # the request never reached the server: the cursor is
                # intact, a plain retry re-issues the same fetch
                raise
            except TransportError:
                # a disconnect may have advanced the cursor past lost
                # parts; a corrupt delivery did advance it — both resume
                # via a reopen seeked to _pos
                self._cursor_dead = True
                raise

        parts = self.retry.run(attempt, on_retry=self._note_retry)
        # durable position: everything up to here is about to be applied
        # locally (apply cannot fault — it is host/device work)
        lockstep.checkpoint("stager.advance_pos", touches=("_pos",))
        self._pos = self._cursor.tell()
        self.gw._lease_renew()
        return parts, self._cursor.done

    # ------------------------------------------------------- background fetch
    def _start_fetch_worker(self) -> None:
        """Spawn the wire-transfer worker: it loops ``fetch_update``
        against the (private, in-memory) cursor and hands each bounded
        parts batch through a depth-limited queue.  Only the *transfer*
        is off-thread — the delta APPLY consumes the queue on the
        serving thread inside :meth:`_step_stage`, so device state is
        still touched by exactly one thread."""
        import queue
        import threading

        q: "queue.Queue" = queue.Queue(maxsize=self.fetch_depth)
        stop = threading.Event()

        def _loop() -> None:
            try:
                while not stop.is_set():
                    # timeouts retry in place here; a dead cursor
                    # (disconnect/corruption) hands off to the serving
                    # thread, which owns the sqlite-backed reopen
                    try:
                        parts, done = self._fetch_parts(allow_reopen=False)
                    except _ReopenRequired:
                        while not stop.is_set():
                            try:
                                q.put(("reconnect", None, False),
                                      timeout=0.05)
                                return
                            except queue.Full:
                                continue
                        return
                    while not stop.is_set():
                        try:
                            q.put(("parts", parts, done), timeout=0.05)
                            break
                        except queue.Full:
                            continue
                    if done:
                        return
            except BaseException as exc:  # noqa: BLE001 — relayed to step()
                # surface the failure on the serving thread: _step_stage
                # re-raises it, step() aborts the session (the standard
                # teardown), and the exception propagates to the caller
                while not stop.is_set():
                    try:
                        q.put(("error", exc, True), timeout=0.05)
                        return
                    except queue.Full:
                        continue

        self._fetch_queue = q
        self._fetch_stop = stop
        self._fetch_thread = threading.Thread(
            target=_loop, name="update-stager-fetch", daemon=True)
        # the handoff point: from here until the join in
        # _stop_fetch_worker, cursor state belongs to the worker
        lockstep.transfer_ownership(_WORKER_FIELDS, "worker")
        self._fetch_thread.start()

    def _stop_fetch_worker(self) -> bool:
        """Tear the worker down (idempotent): signal stop, unblock any
        pending put by draining, join.  Returns False — and records the
        leak in ``stats()`` — when the worker is still alive after
        ``join_timeout_s``: a live worker may still be writing cursor
        and staging state, so callers on the flip path must FAIL the
        sync rather than proceed (the old code silently ignored the
        join timeout and flipped anyway)."""
        if self._fetch_thread is None:
            return True
        import queue

        self._fetch_stop.set()
        try:
            while True:
                self._fetch_queue.get_nowait()
        except queue.Empty:
            pass
        self._fetch_thread.join(timeout=self.join_timeout_s)
        leaked = self._fetch_thread.is_alive()
        if leaked:
            self.stats_["fetch_workers_leaked"] += 1
        else:
            # join is the visibility barrier: cursor state is the
            # serving thread's again.  A LEAKED worker keeps ownership —
            # any serve-side touch after a failed join is the exact
            # hazard the lockstep checker exists to flag.
            lockstep.transfer_ownership(_WORKER_FIELDS, "serve")
        self._fetch_thread = None
        self._fetch_queue = None
        self._fetch_stop = None
        return not leaked

    # ------------------------------------------------------------------- step
    def step(self) -> Optional[str]:
        """Run ONE bounded unit of staging work; returns the phase that
        executed (None when the stager is idle/done).

        A step that raises ABORTS the session first (staging state torn
        down, the pre-registered version and any prewarmed views dropped,
        ``active`` becomes False) and then re-raises: the gateway keeps
        serving on its current version and a later ``begin_sync`` opens a
        fresh cursor from scratch, so a failed stage can neither wedge
        the serving loop nor flip a partially-applied update in."""
        if not self.active:
            return None
        phase = self.phase
        self.stats_["steps"] += 1
        gw = self.gw
        span = (gw.tracer.span("stager:" + phase,
                               {"to_version": self.to_version},
                               tid=STAGER_TID)
                if gw.obs else NULL_SPAN)
        try:
            with span:
                if phase == "stage":
                    self._step_stage()
                elif phase == "requant":
                    self._step_requant()
                elif phase == "prewarm":
                    self._step_prewarm()
                elif phase == "flip":
                    self._flip()
        except BaseException:
            self.abort()
            raise
        if gw.obs:
            gw.h_stager.observe(span.dur)
        return phase

    def abort(self) -> None:
        """Tear down an in-progress session (no-op once done/failed).
        Everything staged is private until the flip, so aborting is just
        dropping it — plus unregistering the pre-registered version if
        prewarm had begun (only when the flip has not already happened:
        a failure *inside* the flip after the version bump must not
        yank the now-live weights)."""
        if not self.active:
            return
        self._stop_fetch_worker()
        gw = self.gw
        if self.to_version is not None \
                and gw._staging_version == self.to_version:
            gw._weights.pop(self.to_version, None)
            gw.views.invalidate(version=self.to_version)
            if gw.prefix is not None:
                gw.prefix.drop_scope(version=self.to_version)
            gw._staging_version = None
        self._cursor = None
        self._staged = self._staged_q = None
        self._pending_layer = None
        self._pending_buf = None
        if gw.obs:
            gw.audit.record("sync_abort", model=gw.model,
                            phase=self.phase, to_version=self.to_version)
        if self.to_version is not None:
            gw._note_sync_failure(self.to_version)
        self.phase = "failed"

    def _apply_part(self, part) -> None:
        """Apply one fetched part to the resident staging buffer of its
        layer: sparse (index, value) rows go through the in-place
        ``delta_apply`` scatter kernel; a chunk page is a *contiguous*
        run, so it is a donated ``dynamic_update_slice`` — no scatter
        needed (the scatter-as-compare kernel is built for sparse
        deltas; page-dense updates would pay O(tiles × page) compares)."""
        import jax.numpy as jnp

        from repro.kernels import ops

        if part.layer not in self._flat:
            raise KeyError(f"delta for unknown layer {part.layer!r}")
        if self._pending_layer is not None and self._pending_layer != part.layer:
            self._finalize_layer()
        if self._pending_layer is None:
            self._pending_layer = part.layer
            self._pending_buf = jnp.asarray(self._flat[part.layer]).reshape(-1)
        buf = self._pending_buf
        if part.chunks is not None:
            ce = part.chunk_elems
            for ci, page in part.iter_pages():
                buf = _page_update()(buf,
                                     jnp.asarray(page).astype(buf.dtype),
                                     np.int32(ci * ce))
        elif len(part.indices):
            buf = ops.delta_apply(buf, jnp.asarray(part.indices),
                                  jnp.asarray(part.values).astype(buf.dtype),
                                  donate=True)
        self._pending_buf = buf

    def _finalize_layer(self) -> None:
        name = self._pending_layer
        self._flat[name] = np.asarray(self._pending_buf).reshape(
            self._flat[name].shape)
        self._pending_layer = None
        self._pending_buf = None

    def _step_stage(self) -> None:
        lockstep.checkpoint("stager.stage")
        if self._fetch_thread is not None:
            # the wire transfer already happened (or is happening) on the
            # worker; a blocking get here is never slower than the
            # synchronous fetch it replaces, and is usually a no-wait hit
            kind, payload, done = self._fetch_queue.get()
            if kind == "error":
                raise payload
            if kind == "reconnect":
                # the worker exited on a dead cursor: reopen it here
                # (the sqlite-bound delta query) and restart the worker
                # — this stager step's bounded unit IS the reconnect
                if not self._stop_fetch_worker():
                    raise RuntimeError(
                        "background fetch worker failed to stop during "
                        "reconnect")
                self.retry.run(self._reconnect, on_retry=self._note_retry)
                self.gw._lease_renew()
                self._start_fetch_worker()
                return
            parts = payload
        else:
            parts, done = self._fetch_parts()
        if parts:
            for part in parts:
                self._apply_part(part)
            got = int(sum(p.nbytes for p in parts))
            self.stats_["parts_applied"] += len(parts)
            self.stats_["bytes_applied"] += got
            self.stats_["max_step_bytes_applied"] = max(
                self.stats_["max_step_bytes_applied"], got)
            self._touched.update(p.layer for p in parts)
        if done:
            # worker (if any) has exited on its own: ``done`` rode the
            # queue with the final batch, so cursor fields read from the
            # serving thread from here on (fetched_bytes at the flip)
            # are past the last worker write
            if not self._stop_fetch_worker():
                raise RuntimeError(
                    "background fetch worker failed to stop; refusing to "
                    "flip with a live worker still writing")
            if self._pending_layer is not None:
                self._finalize_layer()
            # assemble the staged tree: touched layers are the patched
            # buffers, untouched leaves the client's arrays by reference
            self._staged = unflatten_like(self.gw._client.params, self._flat)
            if self.gw.quantized:
                self._requant_queue = sorted(self._touched)
                self._staged_q = self._requant_base
                self.phase = "requant"
            else:
                self._enter_prewarm()

    def _step_requant(self) -> None:
        from repro.serving.quantized import (quantize_serving_params,
                                             requantize_layers)

        if self._requant_base is None:
            # diverged gateway (see begin): full requantize, one step
            self._staged_q = quantize_serving_params(self._staged)
            self._requant_queue = []
        else:
            batch = self._requant_queue[:self.requant_layers_per_step]
            del self._requant_queue[:len(batch)]
            self._staged_q = requantize_layers(self._staged_q, self._flat,
                                               batch)
            self.stats_["layers_requantized"] += len(batch)
        if not self._requant_queue:
            self._enter_prewarm()

    def _enter_prewarm(self) -> None:
        gw = self.gw
        serving = self._staged_q if gw.quantized else self._staged
        gw._register_staging(self.to_version, serving)
        # hot tiers from scheduler occupancy: the tiers serving traffic
        # now are the ones whose first new-version admission would pay a
        # cold view build.  Tiers pending revocation are skipped, and the
        # queue is capped at the view cache's SPARE slots: prewarming
        # must never LRU-evict a view (in-flight pinned requests decode
        # through the old-version entries; evicting one buys a cold
        # rebuild mid-generation — the very stall staging removes).
        # hot_tiers() is busiest-first, so any cap keeps the tiers whose
        # warm view matters most; with no spare slots prewarm is skipped
        # and the first admission builds its view as before.
        spare = gw.views.capacity - len(gw.views)
        self._prewarm_queue = [
            t for t in gw.scheduler.hot_tiers()
            if not (t in gw._pending_tiers and gw._pending_tiers[t] is None)
        ][: max(0, spare)]
        self.phase = "prewarm"
        if not self._prewarm_queue:
            self.phase = "flip"

    def _step_prewarm(self) -> None:
        gw = self.gw
        if len(gw.views) >= gw.views.capacity:
            # an admission since _enter_prewarm filled the spare slots:
            # stop rather than LRU-evict a live view (the remaining
            # tiers build their views cold on first admission, as before)
            self._prewarm_queue = []
        else:
            tier = self._prewarm_queue.pop(0)
            try:
                gw.views.get(tier, self.to_version)
                self.stats_["views_prewarmed"] += 1
            except KeyError:
                pass                      # tier vanished mid-staging
        if not self._prewarm_queue:
            self.phase = "flip"

    def _flip(self) -> None:
        """Atomic install: new weights + tier redefinitions in one step."""
        gw, client = self.gw, self.gw._client
        lockstep.checkpoint("stager.flip",
                            touches=("_cursor", "_wire_bytes"))
        gw._install_staged(self.to_version)
        client.params = self._staged
        client.version = self.to_version
        client.bytes_downloaded += self._wire_bytes + self._cursor.fetched_bytes
        client.updates += 1
        self.stats_["flips"] += 1
        gw._note_sync_success(self.to_version)
        self._cursor = None
        self._staged = self._staged_q = None
        self.phase = "done"
