"""One run of one cell: set-up, the measured window, the metrics, the check.

``run_cell`` is the whole run; ``bench/run.py`` wraps it for the command
line.  The system under test is ``repro.serving.LicensedGateway``; the
benchmark gives it weights made from the seed, drives it with the cell's
traffic through ``submit``/``step``, and reads its counters, its kernel
names in the profiler trace, and the tokens it served.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ specs
def load_benchmark() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_spec(name: str) -> Dict:
    bm = load_benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bm["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell, "config": cfg,
            "end_to_end": [m for m in bm["end_to_end"] if applies(m)],
            "per_layer": [m for m in bm["per_layer"] if applies(m)]}


def reader(metric: str) -> Callable:
    path = BENCH / "metrics" / f"{metric}.py"
    mod_name = "bench_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ device
def device_or_fail(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator (platform cpu, "
                     f"{devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[0]


def use_compile_cache() -> str:
    """The repo's persistent compile cache (a fixed path inside the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every program
    cached however small or quick to compile."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache as repo_cache

    path = repo_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs built (compiled or loaded from the cache), with the
    name of each."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def n(self) -> int:
        return len(self.names)

    def _on(self, event: str, *_a, fun_name: str = "?", **_k) -> None:
        if event == self.EVENT:
            self.names.append(fun_name)


# ------------------------------------------------------------ the program
def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    act = cfg["hidden_act"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        mlp_type={"silu": "swiglu", "relu2": "squared_relu"}[act],
        attn_bias=bool(cfg["attention_bias"]),
        norm_layernorm=bool(cfg.get("layer_norm_eps")),
        rope_theta=float(cfg["rope_theta"]), dtype_name="bfloat16",
        remat=False, source=cfg["source"])


def served_layout(mcfg):
    """Shapes of the served int8 store (no array is made)."""
    import jax

    from repro.models import init_params
    from repro.serving.quantized import quantize_serving_params

    return jax.eval_shape(lambda: quantize_serving_params(
        init_params(jax.random.PRNGKey(0), mcfg)))


def tiers_of(cfg: Dict):
    from repro.core.licensing import LicenseTier

    return {name: LicenseTier.from_json(name, masks)
            for name, masks in cfg["serving"]["tiers"].items() if masks}


def make_gateway(mcfg, params, cfg: Dict):
    from repro.serving import LicensedGateway

    g = cfg["serving"]["gateway"]
    return LicensedGateway(
        mcfg, params, tiers=tiers_of(cfg), already_quantized=True,
        max_batch=g["max_batch"], max_lanes=g["max_lanes"],
        max_prompt=g["max_prompt"], max_new_cap=g["max_new_cap"],
        block_size=g["block_size"], num_blocks=g["num_blocks"],
        chunk_size=g["chunk_size"], model=cfg["name"])


def warm_up(gw, cfg: Dict, mix: Dict) -> Dict[str, int]:
    """Run every program shape the traffic can use, through the gateway's
    own entry points: each decode table width (one request per width,
    alone, so the batch's width is its own), and each prefill batch size
    through every chunk table width, for the unmasked and the masked
    tier kinds (a tier with masks passes interval arrays, ``full`` none).
    Returns the request counts."""
    import numpy as np

    g = cfg["serving"]["gateway"]
    bs, chunk = g["block_size"], g["chunk_size"]
    cap = int(mix["max_context"])
    widths = -(-cap // bs)
    kinds = []
    if "full" in mix["tiers"]:
        kinds.append("full")
    masked = sorted(t for t in mix["tiers"] if t in cfg["serving"]["tiers"]
                    and cfg["serving"]["tiers"][t])
    if masked:
        kinds.append(masked[0])
    rng = np.random.default_rng(0)
    vocab = cfg["vocab_size"]
    n = 0

    def serve(batch):
        nonlocal n
        reqs = [gw.submit(rng.integers(0, vocab, p, dtype=np.int32),
                          license=t, max_new_tokens=k) for t, p, k in batch]
        gw.run()
        for r in reqs:
            if r.error is not None or len(r.out_tokens) != r.max_new_tokens:
                raise RuntimeError(f"warm-up request failed: {r.error}")
        n += len(reqs)

    # decode widths 1..widths: a request whose decode reaches position
    # (w - 1) * bs decodes at table width w (b = 1 prefill through every
    # chunk width); where max_prompt stops short of it, decode goes on
    for w in range(1, widths + 1):
        p = min((w - 1) * bs + 1, g["max_prompt"])
        k = max(2, (w - 1) * bs + 2 - p)
        serve([(t, p, k) for t in kinds])
    # prefill batch sizes 2, 4, .. max_batch through every chunk width:
    # prompts whose last chunk starts at the deepest chunk boundary
    longest = min(g["max_prompt"], cap - 1)
    last = (longest - 1) // chunk * chunk
    b = 2
    while b <= g["max_batch"]:
        serve([(t, last + 1, 1) for t in kinds for _ in range(b)])
        b *= 2
    return {"warm_requests": n}


# ------------------------------------------------------------------ the run
@dataclass
class Run:
    """What a metric reader sees."""
    cfg: Dict
    cell: Dict
    seconds: float
    setup_s: float = math.nan
    client: Any = None
    counters: Dict[str, Dict] = field(default_factory=dict)
    trace: Any = None
    trace_steps: List[Any] = field(default_factory=list)
    peaks: Dict[str, float] = field(default_factory=dict)

    @property
    def gateway_settings(self) -> Dict:
        return self.cfg["serving"]["gateway"]


@dataclass
class Setup:
    """What set-up leaves for the served traffic: the seeded weights on
    the device, with every program shape the traffic uses warmed."""
    spec: Dict
    cfg: Dict
    mix: Dict
    dev: Any
    mcfg: Any
    layout: Any
    params: Any
    counter: CompileCounter
    info: Dict


def set_up(workload: str, seed: int, *, require_chip: bool = True,
           spec: Optional[Dict] = None, mix: Optional[Dict] = None,
           compile_cache: bool = True, log=print) -> Setup:
    """Device check, compile cache, weights from the seed, warm-up."""
    import jax

    from bench import traffic, weights

    spec = spec or cell_spec(workload)
    cell, cfg = spec["cell"], spec["config"]
    dev = (device_or_fail(cell["chips"]) if require_chip
           else jax.devices()[0])
    cache_dir = use_compile_cache() if compile_cache else None
    counter = CompileCounter()
    mix = mix or traffic.load_mix(cell["traffic"])
    sys.path.insert(0, str(ROOT / "src"))
    mcfg = model_config(cfg)
    layout = served_layout(mcfg)
    t0 = time.perf_counter()
    params = weights.build(layout, seed,
                           tied=bool(cfg["tie_word_embeddings"]))
    jax.block_until_ready(params)
    mstats = dev.memory_stats() or {}
    log(json.dumps({"weights": {
        "bytes_in_use": mstats.get("bytes_in_use"),
        "bytes_limit": mstats.get("bytes_limit")}}))
    info = {"weights_s": time.perf_counter() - t0, "compile_cache": cache_dir,
            "memory_limit_bytes": mstats.get("bytes_limit")}
    gw = make_gateway(mcfg, params, cfg)
    info["warm"] = warm_up(gw, cfg, mix)
    del gw
    gc.collect()
    return Setup(spec, cfg, mix, dev, mcfg, layout, params, counter, info)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, rate: Optional[float] = None,
             control: bool = False, require_chip: bool = True,
             on_gateway: Optional[Callable] = None,
             spec: Optional[Dict] = None, mix: Optional[Dict] = None,
             keep_trace: Optional[str] = None, compile_cache: bool = True,
             log=lambda s: print(s, file=sys.stderr, flush=True)) -> Dict:
    """The whole run; returns the result line's object."""
    import jax

    from bench import check, costs, traffic, weights
    from bench import trace as tracemod
    from bench.client import Client, nearest_rank

    su = set_up(workload, seed, require_chip=require_chip, spec=spec,
                mix=mix, compile_cache=compile_cache, log=log)
    spec, cfg, mix, dev, counter = su.spec, su.cfg, su.mix, su.dev, su.counter
    cell = spec["cell"]
    gw = make_gateway(su.mcfg, su.params, cfg)
    if on_gateway is not None:
        on_gateway(gw)
    reqs = traffic.generate(mix, seed, seconds, cfg["vocab_size"], rate)
    run = Run(cfg=cfg, cell=cell, seconds=seconds)
    try:
        run.peaks = costs.peaks(dev.device_kind)
    except KeyError:
        if require_chip:
            raise
    client = Client(gw, reqs, annotate=trace)
    client.record_steps = trace
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    marks: Dict[str, Any] = {}
    events = {}
    if trace:
        # the last 8 s of the window (or its second half): stopping the
        # profiler writes the trace and stalls the host, so it stops
        # when the window closes
        a = seconds - min(8.0, seconds * 0.5)

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            marks["ann"] = jax.profiler.TraceAnnotation("bench.window")
            marks["ann"].__enter__()
            marks["t0"] = time.perf_counter()

        events = {a: start}
    client.events = events
    compiles_setup = counter.n

    def window_open():
        marks["before"] = dict(gw.stats)
        marks["compiles_open"] = counter.n

    def window_end():
        marks["after"] = dict(gw.stats)
        marks["compiles"] = counter.names[marks["compiles_open"]:]
        if "ann" in marks:
            marks["t1"] = time.perf_counter()
            marks["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    # set-up ends where the traffic starts: the lead-in, then the window
    t_lead = time.perf_counter()
    run.setup_s = t_lead - t_start
    client.run(t_lead + traffic.lead_in_s(mix), seconds,
               on_window_open=window_open, on_window_end=window_end)
    run.client = client
    run.counters = {"before": marks["before"], "after": marks["after"]}
    mstats = dev.memory_stats() or {}
    mem = mstats.get("peak_bytes_in_use")
    info = dict(su.info, setup_compiles=compiles_setup,
                lead_in_compiles=marks["compiles_open"] - compiles_setup,
                window_compiles=len(marks["compiles"]),
                window_compiled=sorted(set(marks["compiles"])),
                attempted=len(client.tracked), failed=client.failed(),
                due_in_window=len(client.ttft_ms()),
                ttft_p95_ms=nearest_rank(client.ttft_ms(), 0.95),
                client_late_s=client.lateness_s(),
                decode_path=gw.metrics()["decode_path"])
    log(json.dumps({"info": info}))

    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if trace:
        tr = tracemod.load(tracemod.find_xplane(trace_dir))
        run.trace = tr
        run.trace_steps = [s for s in client.steps
                           if s.t0 >= marks["t0"] and s.t1 <= marks["t1"]]
        device["busy_s"] = tracemod.busy_seconds(tr)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": [[k, v] for k, v in tracemod.top_ops(tr)],
                     "idle_gaps": [[k, v]
                                   for k, v in tracemod.idle_by_host(tr)]}
        import shutil

        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the comparison: each tier's floor of finished requests (a minute
    # past the close at most), served state freed, then the reference
    chk = cfg["check"]
    need = check.floors(client.tracked, sorted(mix["tiers"]),
                        chk["min_served_tokens_per_tier"])
    client.finish(need, 60.0)
    finished = [t for t in client.tracked if t.finished]
    params, layout = su.params, su.layout
    del gw, su, params, client.gw
    gc.collect()
    picked = check.sample(finished, seed, need)
    readings = {"logit_gap": -math.inf, "served_tokens": 0, "requests": 0}
    if picked:
        ref = check.reference_for(cfg, weights.layer_names(layout), seed)
        t_ref = time.perf_counter()
        readings = check.judge(ref, picked, cfg["serving"]["tiers"],
                               control=control)
        readings["reference_s"] = time.perf_counter() - t_ref
    ok, rows = check.verdict(readings["logit_gap"],
                             check.served_by_tier(picked),
                             chk["logit_gap_limit"], need)
    log(json.dumps({"readings": readings}))
    for name, v, lim, kind in rows:
        log(f"check {name} {v} {'<=' if kind == 'max' else '>='} {lim}")
    out = {"correct": ok, "attempted": len(client.tracked),
           "failed": client.failed(), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["info"] = info
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim, _ in rows}
    return out


def sweep(workload: str, seed: int, seconds: float, rates, *,
          log=lambda s: print(s, file=sys.stderr, flush=True)) -> List[Dict]:
    """Offered load against served load, after ONE set-up: for each rate,
    the traffic's lead-in and then a window (for finding the knee once;
    not a cell's measurement).  A rate is sustained when the tokens
    served in the window keep pace (90% or more) with the tokens offered
    (the whole schedule's output tokens over its span), and the requests
    waiting for a first token do not grow through the window."""
    import jax

    from bench import traffic
    from bench.client import Client, nearest_rank

    su = set_up(workload, seed, log=log)
    rows = []
    for r in rates:
        gw = make_gateway(su.mcfg, su.params, su.cfg)
        c = Client(gw, traffic.generate(su.mix, seed, seconds,
                                        su.cfg["vocab_size"], r))
        queued = {}

        def waiting(key, c=c):
            queued[key] = sum(1 for t in c.tracked if not t.tok_t)

        c.run(time.perf_counter() + traffic.lead_in_s(su.mix), seconds,
              on_window_open=lambda: waiting("open"),
              on_window_end=lambda: waiting("close"), drain_s=0.0)
        span = traffic.lead_in_s(su.mix) + seconds
        offered = sum(t.spec.max_new_tokens for t in c.tracked) / span \
            * seconds
        served = c.tokens_in_window()
        row = {"rate": r, "attempted": len(c.tracked),
               "offered_tokens": offered, "served_tokens": served,
               "queued_at_open": queued["open"],
               "queued_at_close": queued["close"],
               "ttft_p50_ms": nearest_rank(c.ttft_ms(), 0.5),
               "ttft_p95_ms": nearest_rank(c.ttft_ms(), 0.95),
               "itl_p95_ms": nearest_rank(c.itl_ms(), 0.95),
               "output_tok_s": served / seconds,
               "sustained": (served >= 0.9 * offered
                             and queued["close"] <= queued["open"] + 1)}
        log(json.dumps(row))
        rows.append(row)
        del gw, c
        gc.collect()
    jax.clear_caches()
    return rows
