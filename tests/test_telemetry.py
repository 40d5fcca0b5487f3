"""Observability layer: histogram/percentile math, Prometheus text
exposition, Chrome trace_event export + validation, request-lifecycle
spans through the gateway (preempt/restart included), the licensing
audit stream, injectable-clock plumbing, and the metrics()-schema lint
shared with the fleet (see tests/test_fleet.py for the fleet side)."""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core.licensing import LicenseTier
from repro.core.protocol import LicenseServer
from repro.core.weightstore import WeightStore
from repro.models import init_params
from repro.serving import (Histogram, LicensedGateway, RequestState,
                           Telemetry, TraceRecorder, validate_chrome_trace,
                           validate_gateway_metrics)
from repro.serving.tracing import AuditLog
from repro.analysis.metrics import declared_match, unregistered_metric_keys

MAX_PROMPT = 8
MAX_NEW = 8


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tiers = {"free": LicenseTier(name="free", masks={"*": ((0.0, 0.004),)})}
    return cfg, params, tiers


def _gateway(setup, **kw):
    cfg, params, tiers = setup
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_prompt", MAX_PROMPT)
    kw.setdefault("max_new_cap", MAX_NEW)
    return LicensedGateway(cfg, params, tiers=tiers, **kw)


def _prompt(seed, n=MAX_PROMPT):
    return np.random.default_rng(seed).integers(0, 500, n, dtype=np.int32)


# ------------------------------------------------------------- instruments
def test_histogram_buckets_and_percentiles():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 6.0, 20.0):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(31.0)
    assert h.counts == [1, 1, 1, 1, 1]        # one per bucket + one +Inf
    # rank 2.5 lands mid-way through the (2, 4] bucket
    assert h.p50 == pytest.approx(3.0)
    # the +Inf bucket reports the last finite edge, never infinity
    assert h.percentile(100) == pytest.approx(8.0)
    assert h.summary() == {"count": 5, "sum": pytest.approx(31.0),
                           "p50": pytest.approx(3.0), "p90": h.p90,
                           "p99": h.p99}
    # exact edge counts as <= edge (Prometheus ``le`` semantics)
    h2 = Histogram("edge", buckets=(1.0, 2.0))
    h2.observe(2.0)
    assert h2.counts == [0, 1, 0]
    assert Histogram("empty").percentile(99) == 0.0
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))


def test_counter_gauge_pull_model():
    """fn-backed instruments read live state at export time — the
    hot path never touches them."""
    stats = {"n": 0}
    t = Telemetry()
    c = t.counter("reqs_total", fn=lambda: stats["n"])
    g = t.gauge("depth", fn=lambda: stats["n"] * 2)
    stats["n"] = 7
    assert c.value == 7 and g.value == 14
    assert t.counter("reqs_total") is c       # get-or-create, same key
    with pytest.raises(ValueError):
        t.gauge("reqs_total")                 # kind collision
    push = t.counter("errs_total")
    push.inc()
    push.inc(2)
    assert push.value == 3


def test_disabled_registry_histograms_are_noops():
    t = Telemetry(enabled=False)
    h = t.histogram("lat_s")
    h.observe(1.0)
    assert h.count == 0 and h.sum == 0.0


def test_prometheus_exposition():
    t = Telemetry()
    t.counter("served_total", labels={"model": "m1"}, help="reqs").inc(3)
    h = t.histogram("wait_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = t.render_prometheus()
    assert "# HELP served_total reqs" in text
    assert "# TYPE served_total counter" in text
    assert 'served_total{model="m1"} 3' in text
    assert "# TYPE wait_s histogram" in text
    # buckets are CUMULATIVE, +Inf equals the observation count
    assert 'wait_s_bucket{le="0.1"} 1' in text
    assert 'wait_s_bucket{le="1.0"} 2' in text
    assert 'wait_s_bucket{le="+Inf"} 3' in text
    assert "wait_s_count 3" in text
    assert "wait_s_sum 5.55" in text


def test_adopt_merges_and_rejects_collisions():
    a, b = Telemetry(), Telemetry()
    b.counter("x_total", labels={"model": "m2"})
    a.adopt(b)
    assert a.counter("x_total", labels={"model": "m2"}).value == 0
    a.adopt(a)                                 # self-adopt is a no-op
    c = Telemetry()
    c.counter("x_total", labels={"model": "m2"})
    with pytest.raises(ValueError):
        a.adopt(c)


# ------------------------------------------------------------ trace / audit
def test_trace_recorder_chrome_export():
    t = {"now": 0.0}

    def clk():
        t["now"] += 1.0
        return t["now"]

    rec = TraceRecorder(clock=clk)
    rec.begin("queue", rid=0)
    rec.instant("admit", rid=0, attrs={"tier": "free"})
    rec.end("queue", rid=0)
    rec.begin("decode", rid=0)                 # left open: auto-closed
    rec.counter("depth", 3)
    events = validate_chrome_trace(rec.chrome_trace())
    phases = [e["ph"] for e in events if e["ph"] != "M"]
    assert phases.count("B") == phases.count("E") == 2
    names = {e["name"] for e in events}
    assert {"queue", "admit", "decode", "depth"} <= names
    admit = next(e for e in events if e["name"] == "admit")
    assert admit["args"]["tier"] == "free"
    # a hand-built tape with an unclosed B must fail validation
    bad = json.dumps([{"ph": "B", "ts": 0, "pid": 1, "tid": 2, "name": "x"}])
    with pytest.raises(ValueError):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError):
        validate_chrome_trace("not json")


def test_audit_log_order_and_merge():
    t = {"now": 10.0}
    log = AuditLog(clock=lambda: t["now"])
    log.record("tier_grant", tier="free", model="m")
    log.record("version_flip", from_version=1, to_version=2)
    ev = log.events()
    assert [e["event"] for e in ev] == ["tier_grant", "version_flip"]
    assert ev[0]["seq"] == 0 and ev[1]["seq"] == 1
    assert log.events("version_flip")[0]["to_version"] == 2
    lines = log.render_jsonl().strip().splitlines()
    assert len(lines) == 2 and json.loads(lines[0])["event"] == "tier_grant"
    other = AuditLog(clock=lambda: 5.0)
    other.record("sync_begin", model="m")
    merged = AuditLog.merge([log, other])
    assert [e["event"] for e in merged] == \
        ["sync_begin", "tier_grant", "version_flip"]


# ------------------------------------------------------- gateway lifecycle
def test_gateway_trace_metrics_audit_roundtrip(setup, tmp_path):
    gw = _gateway(setup)
    reqs = [gw.submit(_prompt(i), license="free" if i % 2 else "full",
                      max_new_tokens=3) for i in range(3)]
    gw.submit(_prompt(9, n=50), license="full")          # rejected
    gw.run()
    assert all(r.state == RequestState.DONE for r in reqs)

    m = gw.metrics()
    validate_gateway_metrics(m)
    # lint: every metrics() key is registered in the telemetry registry
    assert unregistered_metric_keys(m, gw.telemetry.declared) == []

    # lifecycle spans, in order, on a completed request
    names = gw.tracer.span_names(reqs[0].rid)
    for span in ("submit", "queue", "admit", "prefill", "decode", "finish"):
        assert span in names, f"missing {span} in {names}"
    assert names.index("queue") < names.index("prefill") < \
        names.index("decode")

    # the whole-gateway tape is a valid Chrome trace: parseable JSON
    # array, monotonic per-track timestamps, matched B/E pairs
    path = tmp_path / "trace.json"
    path.write_text(gw.chrome_trace())
    events = validate_chrome_trace(path.read_text())
    assert isinstance(json.loads(path.read_text()), list)
    assert any(e["name"].startswith("sched:") for e in events)
    assert any(e["ph"] == "C" for e in events)           # counter tracks

    # latency histograms: TTFT once per request, gaps between the rest
    assert gw.h_ttft.count == 3
    assert gw.h_gap.count == m["tokens_generated"] - 3
    assert gw.h_queue.count == 3
    assert m["latency"]["ttft_s"]["count"] == 3

    text = gw.render_prometheus()
    assert "serving_ttft_seconds_bucket" in text
    assert "serving_requests_admitted_total" in text

    # audit stream: tier grants at boot, view materializations on use,
    # and the rejection left a trace instant, not an audit entry
    audit = {e["event"] for e in gw.audit_events()}
    assert {"tier_grant", "view_materialize"} <= audit
    assert "reject" in {e["name"] for e in events}


def test_telemetry_off_leaves_no_wake(setup):
    """telemetry=False: no spans, no histogram observes, no audit —
    the benchmark baseline the <3% overhead gate compares against."""
    gw = _gateway(setup, telemetry=False)
    r = gw.submit(_prompt(0), license="free", max_new_tokens=3)
    gw.run()
    assert r.state == RequestState.DONE
    assert not gw.obs
    assert len(gw.tracer.events) == 0
    assert r.admit_t is not None              # stamped with telemetry off
    assert gw.h_ttft.count == 0 and gw.h_gap.count == 0
    assert gw.audit_events() == []
    validate_gateway_metrics(gw.metrics())    # schema holds either way


def test_injectable_clock_everywhere(setup):
    """Satellite fix: queue waits come from the injected clock — a
    frozen clock advanced by hand yields EXACT wait numbers, which
    direct time.monotonic()/perf_counter() calls could never produce."""
    t = {"now": 100.0}
    gw = _gateway(setup, clock=lambda: t["now"])
    gw.submit(_prompt(0), license="free", max_new_tokens=2)
    t["now"] = 103.5
    m = gw.metrics()
    assert m["oldest_wait_s"] == pytest.approx(3.5)
    assert m["queue_wait_by_tier"]["free"] == pytest.approx(3.5)
    gw.run()
    assert gw.h_queue.count == 1
    assert gw.h_queue.sum == pytest.approx(3.5)   # observed at admission
    # every trace timestamp came from the frozen clock
    assert all(ev[0] in (100.0, 103.5) for ev in gw.tracer.events)


def test_preempt_restart_spans_and_ttft_counted_once(setup):
    """A preempted-and-restarted request's trace shows the preempt and
    restart events, its spans still pair up, and TTFT/queue-wait land
    in the histograms exactly once despite the second admission."""
    gw = _gateway(setup, max_batch=2, paged=True, block_size=4,
                  prefix_cache=False, max_lanes=4, num_blocks=7)
    reqs = [gw.submit(_prompt(i), license="free",
                      max_new_tokens=3 + 2 * (i % 2)) for i in range(5)]
    gw.run()
    assert gw.stats["preempted"] > 0
    assert all(r.state == RequestState.DONE for r in reqs)

    victims = [r for r in reqs if r.preemptions]
    assert victims
    for r in victims:
        names = gw.tracer.span_names(r.rid)
        assert "preempt" in names and "restart" in names
        assert names.index("preempt") < names.index("restart")
        evs = gw.tracer.request_events(r.rid)
        assert sum(e["name"] == "preempt" for e in evs) == r.preemptions
    # B/E pairs survive mid-flight preemption on every track
    validate_chrome_trace(gw.chrome_trace())
    assert gw.h_ttft.count == len(reqs)       # once per request, ever
    assert gw.h_queue.count == len(reqs)      # first admission only
    # admit_t is stamped at the first admission and kept by the restart;
    # the queue-wait histogram observes exactly admit_t - submit_t
    for r in victims:
        evs = gw.tracer.request_events(r.rid)
        admit = next(e["ts"] for e in evs if e["name"] == "admit")
        preempt = min(e["ts"] for e in evs if e["name"] == "preempt")
        assert r.submit_t <= r.admit_t <= admit < preempt
    assert gw.h_queue.sum == pytest.approx(
        sum(r.admit_t - r.submit_t for r in reqs))


# --------------------------------------------------------- staged-sync audit
def test_staged_flip_emits_exactly_one_version_flip(setup):
    cfg, params, _ = setup
    params = jax.device_get(params)
    store = WeightStore(":memory:", row_limit=2048)
    server = LicenseServer(store)
    server.publish("lm", params, tag="v1")
    server.publish_tier("lm", LicenseTier(name="free",
                                          masks={"*": ((0.0, 0.004),)}))
    template = jax.tree_util.tree_map(lambda x: np.zeros_like(x), params)
    gw = LicensedGateway.from_server(cfg, server, "lm", template,
                                     max_batch=2, max_prompt=MAX_PROMPT,
                                     max_new_cap=16)
    a = gw.submit(_prompt(1), license="free", max_new_tokens=10)
    gw.step()                                 # a is mid-stream
    newp = jax.tree_util.tree_map(lambda x: np.asarray(x) * 1.01, params)
    server.publish("lm", newp, tag="v2")
    assert gw.begin_sync(max_step_bytes=4 << 20) is True
    for _ in range(10_000):
        if not (gw.sync_active or gw.scheduler.waiting
                or gw.scheduler.running):
            break
        gw.step()
    assert a.state == RequestState.DONE and gw.version == 2

    flips = gw.audit_events("version_flip")
    assert len(flips) == 1                    # exactly one, at the flip
    assert flips[0]["from_version"] == 1 and flips[0]["to_version"] == 2
    assert len(gw.audit_events("sync_begin")) == 1
    assert gw.h_stager.count > 0              # phases were timed
    events = validate_chrome_trace(gw.chrome_trace())
    stager = {e["name"] for e in events if e["name"].startswith("stager:")}
    assert "stager:flip" in stager

    # the blocking path funnels through the same choke point: still one
    # flip event per version bump
    server.publish("lm", params, tag="v3")
    assert gw.sync() is True
    assert gw.version == 3
    assert len(gw.audit_events("version_flip")) == 2


# ------------------------------------------------------------- schema lint
def test_unregistered_keys_lint_flags_strays():
    # the schema primitives live in repro.analysis.metrics now; this
    # exercises them through a live Telemetry declaration set
    t = Telemetry()
    t.declare("known", "nested.*")
    assert unregistered_metric_keys(
        {"known": 1, "nested": {"a": 2, "b": 3}}, t.declared) == []
    assert unregistered_metric_keys({"stray": 1}, t.declared) == ["stray"]
    assert declared_match("nested.deep.leaf", t.declared)
    assert not declared_match("nested2", t.declared)
