#!/usr/bin/env python3
"""Bring-up smoke: the licensed gateway serving qwen2.5-3b on one TPU chip.

Drives the serving path through its public entry points at the model's
published width and depth (36 layers, d_model 2048, 16 heads / 2 KV
heads, d_ff 11008, vocab 151936, bf16; random weights from a seed):

  a. device: the first JAX device must be a TPU, or the script exits 1
     before doing anything;
  b. float: bf16 weights, tiers ``full`` + ``free`` (a masked view, a
     second bf16 copy), kernel-resident decode through the compiled Pallas
     ``paged_attention`` / ``paged_decode_write``; every logits row is
     compared with the same gateway on the pure-JAX block gather
     (``decode_pallas="off"``);
  c. int8: one int8 store, tier masks fused into the in-scan dequant; the
     same requests and the same comparison;
  d. update: v1 committed to a ``WeightStore``; v2 changes a chunk-stored
     matrix and 4096 rows of a QKV bias; ``begin_sync()`` rides along
     ``run()`` until exactly one version flip lands; the synced layers are
     checked against a numpy apply of the same change.

Each phase prints one JSON line (compile and wall seconds, tokens,
device memory, logit difference); any failure ends the script non-zero.
The last line is ``{"ok": true, "device": {...}}``.

Run:  python chip_smoke.py
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ARCH = "qwen2.5-3b"
SEED = 0
MAX_PROMPT = 256
MAX_NEW = 16
# (tier, prompt tokens, new tokens).  Every tier's batch decodes at
# positions 240..254, so one table width (16 blocks of 16) serves every
# decode step and each gateway compiles one decode program.
REQUESTS = (("full", 240, 16), ("full", 96, 16),
            ("free", 240, 16), ("free", 160, 16))
# Gate on the kernel vs gather logits: per row, |difference|_2 over
# |gather row|_2.  The kernel attends in f32, the gather rounds
# probabilities to bf16 before the value matmul; in bf16 at 36 layers
# (d_model 256, CPU) that noise measured up to 1.8e-2, while changing one
# of 240 context tokens moved a row by 5e-2 or more.
LOGIT_RTOL = 4e-2
BIAS_ROWS = 4096        # changed rows of the sparse-row layer in v2


def device_or_exit():
    """The first JAX device, which must be a TPU; otherwise exit 1."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        sys.exit(1)
    return dev


def _tiers():
    from repro.core.licensing import LicenseTier

    return {"free": LicenseTier(name="free", masks={"*": ((0.0, 0.01),)})}


def _prompts(cfg, salt: int):
    rng = np.random.default_rng(SEED + salt)
    return [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
            for _, n, _ in REQUESTS]


def _memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use")}


def _serve(cfg, weights, *, quantized: bool, decode_pallas, expect: str,
           device):
    """One gateway over ``weights``: a cold pass (compiles every program
    the requests need) and a warm pass of fresh prompts of the same
    lengths.  Returns the gateway, the warm requests and the timings."""
    from repro.serving import LicensedGateway

    gw = LicensedGateway(cfg, weights, tiers=_tiers(),
                         already_quantized=quantized, max_batch=4,
                         max_prompt=MAX_PROMPT, max_new_cap=MAX_NEW,
                         chunk_size=MAX_PROMPT, record_logits=True,
                         decode_pallas=decode_pallas, model=cfg.name)
    if gw.decode_pallas != expect or gw.kernel_decode is not True:
        raise RuntimeError(f"decode path resolved to decode_pallas="
                           f"{gw.decode_pallas!r}, kernel_decode="
                           f"{gw.kernel_decode!r}; expected {expect!r}, True")
    passes = []
    for salt in (1, 2):
        reqs = [gw.submit(p, license=tier, max_new_tokens=n)
                for p, (tier, _, n) in zip(_prompts(cfg, salt), REQUESTS)]
        t0 = time.perf_counter()
        gw.run()
        passes.append(time.perf_counter() - t0)
        for r, (_, _, n) in zip(reqs, REQUESTS):
            if r.error is not None or len(r.out_tokens) != n:
                raise RuntimeError(f"request {r.rid} ({r.license}) ended with "
                                   f"{len(r.out_tokens)}/{n} tokens: {r.error}")
    if gw.metrics()["resident_decode_steps"] == 0:
        raise RuntimeError("no decode step took the kernel-resident path")
    timing = {"compile_s": passes[0] - passes[1], "wall_s": passes[1],
              "tokens": sum(len(r.out_tokens) for r in reqs)}
    return gw, reqs, {**timing, **_memory(device)}


def _compare(cfg, kernel_reqs, gather_reqs) -> dict:
    """Largest per-row relative logit difference over every row both runs
    computed from the same tokens (after a differing greedy token the rows
    are not comparable, so a request stops there)."""
    worst, worst_max, rows, same = 0.0, 0.0, 0, True
    for a, b in zip(kernel_reqs, gather_reqs):
        for i, (ra, rb) in enumerate(zip(a.logits_rows, b.logits_rows)):
            if i and a.out_tokens[i - 1] != b.out_tokens[i - 1]:
                break
            ra, rb = ra[:cfg.vocab_size], rb[:cfg.vocab_size]
            if not (np.isfinite(ra).all() and np.isfinite(rb).all()):
                raise RuntimeError(f"non-finite logits in request {a.rid}")
            d = ra - rb
            worst = max(worst, float(np.linalg.norm(d) / np.linalg.norm(rb)))
            worst_max = max(worst_max,
                            float(np.abs(d).max() / np.abs(rb).max()))
            rows += 1
        same &= a.out_tokens == b.out_tokens
    if worst > LOGIT_RTOL:
        raise RuntimeError(f"kernel vs gather logits differ by {worst:.3g} "
                           f"(relative L2), tolerance {LOGIT_RTOL}")
    return {"logit_rel_l2": worst, "logit_rel_max": worst_max,
            "logit_rtol": LOGIT_RTOL, "rows_compared": rows,
            "tokens_identical": bool(same)}


def _path_phase(name, cfg, weights, *, quantized, decode_pallas, expect,
                device, keep=False):
    """Serve the requests on the pure-JAX gather, then on the kernel, and
    compare.  Returns the phase record (and the kernel gateway if kept)."""
    gather_gw, gather_reqs, gather = _serve(cfg, weights,
                                            quantized=quantized,
                                            decode_pallas="off",
                                            expect="off", device=device)
    del gather_gw
    gc.collect()           # the masked views go before the next gateway's
    gw, reqs, rec = _serve(cfg, weights, quantized=quantized,
                           decode_pallas=decode_pallas, expect=expect,
                           device=device)
    rec = {"phase": name, **rec, "gather_compile_s": gather["compile_s"],
           "gather_wall_s": gather["wall_s"],
           **_compare(cfg, reqs, gather_reqs)}
    if not keep:
        del gw
        gc.collect()
        gw = None
    return rec, gw


def _update_phase(cfg, gw, host_params, device) -> dict:
    """Commit v1, publish v2, stage it into ``gw`` while it serves."""
    import jax

    from repro.core.protocol import EdgeClient, LicenseServer
    from repro.core.weightstore import WeightStore

    # uncompressed pages: zlib on random bf16 commits at ~46 s/GB on a
    # host core (5+ minutes for v1 at this size), raw pages at ~2.3 s/GB
    store = WeightStore(":memory:", compress_chunks=False)
    server = LicenseServer(store)
    t0 = time.perf_counter()
    v1 = server.publish(cfg.name, host_params, tag="v1")
    commit_v1_s = time.perf_counter() - t0
    if v1 != gw.version:
        raise RuntimeError(f"store v1 is {v1}, gateway serves {gw.version}")
    # The pod already holds v1 (the weights it serves), as an edge device
    # provisioned from an image would.  from_server() would pull v1 as a
    # full snapshot: every weight as an (int64, f32) row, ~41 GB at this
    # size, scattered through delta_apply.
    client = EdgeClient(cfg.name, host_params)
    client.version = v1
    gw._client = client

    mixer = host_params["units"]["b0"]["mixer"]
    rng = np.random.default_rng(SEED + 3)
    wk = mixer["wk"].copy()                       # chunk-stored matrix
    wk[0, :8] = (wk[0, :8].astype(np.float32) * 2).astype(wk.dtype)
    wk[-1, -8:] = (wk[-1, -8:].astype(np.float32) * -1).astype(wk.dtype)
    bq = mixer["bq"].copy()                       # sparse-row layer
    n_rows = min(BIAS_ROWS, bq.size // 2)         # < 4096 only at toy sizes
    rows = rng.choice(bq.size, n_rows, replace=False)
    vals = (rng.standard_normal(n_rows) * 0.02).astype(bq.dtype)
    bq.reshape(-1)[rows] = vals
    v2_params = jax.tree_util.tree_map(lambda x: x, host_params)
    v2_params["units"]["b0"]["mixer"] = {**mixer, "wk": wk, "bq": bq}
    t0 = time.perf_counter()
    v2 = server.publish(cfg.name, v2_params, tag="v2")
    commit_v2_s = time.perf_counter() - t0

    inflight = [gw.submit(p, license=tier, max_new_tokens=n)
                for p, (tier, _, n) in zip(_prompts(cfg, 4), REQUESTS)]
    gw.step()                                     # v1 requests in flight
    if not gw.begin_sync(server):
        raise RuntimeError("begin_sync found no newer version")
    t0 = time.perf_counter()
    gw.run()                                      # decode + stager steps
    sync_wall_s = time.perf_counter() - t0
    flips = gw.audit_events("version_flip")
    staged = gw.metrics()["staged_update"]
    if len(flips) != 1 or gw.version != v2 or staged["flips"] != 1:
        raise RuntimeError(f"expected one flip to v{v2}, got {flips} "
                           f"(serving v{gw.version})")
    for r in inflight:
        if r.version != v1 or len(r.out_tokens) != r.max_new_tokens:
            raise RuntimeError(f"in-flight request {r.rid} not served at v1")

    # the layers the stager synced against a numpy apply of the delta
    want_bq = mixer["bq"].copy()
    want_bq.reshape(-1)[rows] = vals
    synced = client.params["units"]["b0"]["mixer"]
    for name, want in (("bq", want_bq), ("wk", wk)):
        if not np.array_equal(np.asarray(synced[name]), want):
            raise RuntimeError(f"synced {name} differs from the numpy apply")
    if client.params["lm_head"] is not host_params["lm_head"]:
        raise RuntimeError("an untouched layer was copied by the sync")

    after = gw.submit(_prompts(cfg, 5)[0], max_new_tokens=MAX_NEW)
    gw.run()
    if after.version != v2 or len(after.out_tokens) != MAX_NEW:
        raise RuntimeError("a request admitted after the flip was not "
                           "served at v2")
    store.close()
    return {"phase": "update", "commit_v1_s": commit_v1_s,
            "commit_v2_s": commit_v2_s, "sync_wall_s": sync_wall_s,
            "stager_steps": staged["steps"],
            "parts_applied": staged["parts_applied"],
            "bytes_applied": staged["bytes_applied"],
            "bias_rows": n_rows, "flips": len(flips),
            "tokens": sum(len(r.out_tokens) for r in inflight)
            + len(after.out_tokens), **_memory(device)}


def run_smoke(cfg, device, *, decode_pallas=None):
    """Phases b-d on ``cfg``; yields one record per phase.
    ``decode_pallas=None`` takes the gateway's default, which must resolve
    to the compiled kernel ("pallas")."""
    import jax

    from repro.models import init_params
    from repro.serving.quantized import quantize_serving_params

    expect = decode_pallas or "pallas"
    t0 = time.perf_counter()
    # op by op: jitted, the 36 unrolled unit inits compile for ~90 s; eager
    # peaks at ~2x the stacked units (~12.5 GB) before they are stacked
    params = jax.block_until_ready(init_params(jax.random.PRNGKey(SEED), cfg))
    yield {"phase": "init", "wall_s": time.perf_counter() - t0,
           "params": int(sum(x.size for x in jax.tree.leaves(params))),
           **_memory(device)}

    rec, _ = _path_phase("float", cfg, params, quantized=False,
                         decode_pallas=decode_pallas, expect=expect,
                         device=device)
    yield rec

    host_params = jax.device_get(params)          # v1 for the update phase
    t0 = time.perf_counter()
    qparams = jax.block_until_ready(quantize_serving_params(params))
    quantize_s = time.perf_counter() - t0
    del params
    gc.collect()
    rec, gw = _path_phase("int8", cfg, qparams, quantized=True,
                          decode_pallas=decode_pallas, expect=expect,
                          device=device, keep=True)
    yield {**rec, "quantize_s": quantize_s}
    del qparams

    yield _update_phase(cfg, gw, host_params, device)


def main() -> None:
    dev = device_or_exit()
    sys.path.insert(0, str(HERE / "src"))
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    print(json.dumps({"phase": "device", "platform": dev.platform,
                      "kind": dev.device_kind, "count": len(jax.devices()),
                      "compile_cache": cache}), flush=True)
    for rec in run_smoke(get_config(ARCH), dev):
        print(json.dumps(rec), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
