"""The gateway's program spans on the profiler clock.

A toy gateway serves chunked-prefill and decode steps (and, in the
second case, the legacy bucket prefill with gather/scatter decode) under
``jax.profiler.trace`` on the CPU.  Every name in ``serving/tracing.py``'s
span table must appear in the profiler trace under exactly that name,
nested inside ``gw:step`` (``gw:submit`` beside it), and on the tape;
``telemetry=False`` must leave neither.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, smoke_variant
from repro.core.licensing import LicenseTier
from repro.models import init_params
from repro.serving import LicensedGateway, RequestState

STEP_SPANS = ("sched:next", "sched:prefill", "sched:decode", "view:get",
              "pool:grow", "gw:inputs", "pool:gather", "step:dispatch",
              "pool:absorb", "step:fetch", "gw:emit")
SPANS = ("gw:step", "gw:submit") + STEP_SPANS

CASES = {
    # two 4-token chunks per prompt, kernel-resident paged decode
    "chunked": dict(chunk_size=4),
    # right-aligned bucket prefill, gather/scatter decode
    "bucket": dict(chunk_size=0, kernel_decode=False),
}


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tiers = {"free": LicenseTier(name="free", masks={"*": ((0.0, 0.004),)})}
    return cfg, params, tiers


def _serve(setup, **kw):
    cfg, params, tiers = setup
    gw = LicensedGateway(cfg, params, tiers=tiers, max_batch=2,
                         max_prompt=8, max_new_cap=4, block_size=4, **kw)
    rng = np.random.default_rng(0)
    reqs = [gw.submit(rng.integers(0, 500, 8, dtype=np.int32),
                      license=t, max_new_tokens=3)
            for t in ("free", "full")]
    gw.run()
    assert all(r.state is RequestState.DONE for r in reqs)
    return gw


def _host_events(log_dir):
    """Every host event of the newest trace under ``log_dir``, as
    (name, start_ns, end_ns)."""
    path = max(glob.glob(str(log_dir / "**" / "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _profiled(setup, tmp_path, **kw):
    """Serve once to compile, then again under the profiler; returns the
    gateway and every host event as (name, start_ns, end_ns)."""
    _serve(setup, **kw)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        gw = _serve(setup, **kw)
    return gw, _host_events(tmp_path)


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_span_on_the_profiler_clock_and_the_tape(setup, tmp_path,
                                                       case):
    gw, events = _profiled(setup, tmp_path, **CASES[case])
    names = {n for n, _, _ in events}
    assert set(SPANS) <= names, sorted(set(SPANS) - names)
    steps = [(s, t) for n, s, t in events if n == "gw:step"]
    submits = [(s, t) for n, s, t in events if n == "gw:submit"]
    for n, s, t in events:
        if n in STEP_SPANS:
            assert any(a <= s and t <= b for a, b in steps), (n, s, t)
    # submit() runs outside any step
    assert not any(a <= s and t <= b for s, t in submits for a, b in steps)
    tape = {ev[3] for ev in gw.tracer.events if ev[1] == "X"}
    assert set(SPANS) <= tape
    # the histograms take their durations from the spans
    assert gw.h_prefill.count == sum(
        ev[3] == "sched:prefill" for ev in gw.tracer.events)
    assert gw.h_decode.count == sum(
        ev[3] == "sched:decode" for ev in gw.tracer.events)


def test_telemetry_off_leaves_no_program_span(setup, tmp_path):
    gw, events = _profiled(setup, tmp_path, chunk_size=4, telemetry=False)
    assert not {n for n, _, _ in events} & set(SPANS)
    assert not any(n.startswith("stager:") for n, _, _ in events)
    assert len(gw.tracer.events) == 0


def test_stager_spans_nest_in_the_step_that_drives_them(setup, tmp_path):
    from repro.core.protocol import LicenseServer
    from repro.core.weightstore import WeightStore

    cfg, params, _ = setup
    params = jax.device_get(params)
    server = LicenseServer(WeightStore(":memory:", row_limit=2048))
    server.publish("lm", params, tag="v1")
    template = jax.tree_util.tree_map(np.zeros_like, params)
    gw = LicensedGateway.from_server(cfg, server, "lm", template,
                                     max_batch=2, max_prompt=8,
                                     max_new_cap=4)
    server.publish("lm", jax.tree_util.tree_map(lambda x: x * 1.01, params),
                   tag="v2")
    assert gw.begin_sync(max_step_bytes=4 << 20) is True
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        while gw.sync_active:
            gw.step()
    assert gw.version == 2
    events = _host_events(tmp_path)
    steps = [(s, t) for n, s, t in events if n == "gw:step"]
    stager = [(n, s, t) for n, s, t in events if n.startswith("stager:")]
    assert "stager:flip" in {n for n, _, _ in stager}
    for n, s, t in stager:
        assert any(a <= s and t <= b for a, b in steps), (n, s, t)
    assert gw.h_stager.count == len(stager)


# ---------------------------------------------- names the benchmark keys on
def _pallas_names(closed):
    out = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                out.append(e.params["name"])
            for v in e.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    walk(sub)

    walk(closed.jaxpr)
    return out


def test_pallas_kernels_carry_their_public_names():
    """The profiler names a Pallas op after its kernel; the benchmark's
    ``paged_attention_roofline`` finds the kernel by that name."""
    from repro.kernels import paged_attention as pa

    q = np.zeros((2, 4, 8), np.float32)
    kv = np.zeros((5, 4, 2, 8), np.float32)
    tab = np.zeros((2, 3), np.int32)
    lane = np.ones(2, np.int32)
    attn = jax.make_jaxpr(
        lambda *a: pa.paged_attention(*a, interpret=True))(q, kv, kv, tab,
                                                           lane)
    write = jax.make_jaxpr(
        lambda *a: pa.paged_decode_write(*a, interpret=True))(
            kv, kv, kv[:2, 0], kv[:2, 0], lane, lane)
    assert _pallas_names(attn) == ["paged_attention"]
    assert _pallas_names(write) == ["paged_decode_write"]


def test_decode_and_chunk_programs_lower_as_pinned_modules(setup,
                                                          monkeypatch):
    """``step.decode_ms`` reads module ``jit__step`` (the kernel-resident
    decode) and ``step.prefill_chunk_ms`` module ``jit__one`` (the
    chunked prefill) in the device trace."""
    from repro.serving import gateway as gwmod

    modules = {}

    def spy(factory):
        def make(*a, **k):
            fn = factory(*a, **k)

            def call(*args):
                text = fn.lower(*args).as_text()
                modules[factory.__name__] = text.split()[1]
                return fn(*args)
            return call
        return make

    for name in ("_compiled_paged_decode", "_compiled_prefix_prefill"):
        monkeypatch.setattr(gwmod, name, spy(getattr(gwmod, name)))
    _serve(setup, chunk_size=4)
    assert modules == {"_compiled_paged_decode": "@jit__step",
                       "_compiled_prefix_prefill": "@jit__one"}
