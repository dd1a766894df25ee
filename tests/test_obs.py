"""Serving telemetry: metrics registry primitives, Prometheus/JSON
exposition round-trips, request-span traces, and the engine's
instrumentation contract (span lifecycle ordering, preemptions recorded
exactly once, stream delivery unaffected, counter back-compat)."""
import json

import numpy as np
import pytest

from repro.configs import get_reduced
from repro.obs import (
    NULL,
    MetricsRegistry,
    Telemetry,
    Tracer,
    to_prometheus,
    write_metrics,
)
from repro.obs.metrics import Histogram
from repro.serving import BASE_TENANT, EngineConfig, MultiTenantEngine
from repro.serving.paging import BlockAllocator


# ---------------------------------------------------------------------------
# registry + instruments
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    g = reg.gauge("g", "a gauge")
    h = reg.histogram("h_ms", "a histogram", buckets=(1.0, 10.0, 100.0))
    c.inc()
    c.inc(2.5)
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotonic
    g.set(7)
    g.inc(3)
    g.dec()
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert c.value == 3.5 and g.value == 9.0
    assert h.count == 4 and h.sum == 555.5 and h.mean == pytest.approx(138.875)
    # quantile returns the holding bucket's upper edge; overflow → inf
    assert h.quantile(0.25) == 1.0 and h.quantile(0.5) == 10.0
    assert h.quantile(1.0) == float("inf")
    assert Histogram(buckets=(1.0,)).quantile(0.5) == 0.0  # empty


def test_registry_labels_memoize_and_validate():
    reg = MetricsRegistry()
    fam = reg.counter("ops_total", "ops", labels=("cause",))
    a1 = fam.labels(cause="x")
    a2 = fam.labels(cause="x")
    b = fam.labels(cause="y")
    assert a1 is a2 and a1 is not b
    a1.inc()
    a1.inc()
    b.inc()
    snap = reg.snapshot()["ops_total"]
    assert {(s["labels"]["cause"], s["value"]) for s in snap["series"]} == {
        ("x", 2.0), ("y", 1.0)
    }
    with pytest.raises(ValueError):
        fam.labels(reason="x")  # wrong label name
    # same name must re-register with the same kind and label schema
    with pytest.raises(ValueError):
        reg.gauge("ops_total")
    with pytest.raises(ValueError):
        reg.counter("ops_total", labels=())


def test_registry_callbacks_sampled_at_snapshot_only():
    reg = MetricsRegistry()
    calls = []
    reg.callback("depth", lambda: calls.append(1) or len(calls), help="probe")
    reg.callback("done_total", lambda: 5, kind="counter")
    assert calls == []  # registration does not sample
    snap = reg.snapshot()
    assert calls == [1]
    assert snap["depth"]["series"][0]["value"] == 1.0
    assert snap["done_total"]["type"] == "counter"
    with pytest.raises(ValueError):
        reg.callback("depth", lambda: 0)  # name collision with callback
    with pytest.raises(ValueError):
        reg.counter("depth")  # instrument colliding with callback
    with pytest.raises(ValueError):
        reg.callback("x", lambda: 0, kind="histogram")


def test_disabled_registry_is_null_and_empty():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    h = reg.histogram("h_ms", labels=("phase",))
    assert c is NULL and h.labels(phase="x") is NULL
    c.inc()
    h.observe(3.0)  # all no-ops
    assert NULL.value == 0.0 and NULL.quantile(0.9) == 0.0
    assert reg.snapshot() == {}
    reg.callback("cb", lambda: 1 / 0)  # never sampled, never raises
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------


def test_prometheus_text_round_trip():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", labels=("cause",)).labels(
        cause='a"b\\c\n'
    ).inc(3)
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = to_prometheus(reg.snapshot())
    assert "# TYPE req_total counter" in text
    assert "# HELP lat_ms latency" in text
    # label escaping: backslash, quote, newline
    assert 'req_total{cause="a\\"b\\\\c\\n"} 3' in text
    # cumulative buckets + +Inf tail + sum/count
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_sum 105.5" in text and "lat_ms_count 3" in text


def test_write_metrics_json_vs_prom_by_extension(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total", "c").inc(2)
    snap = reg.snapshot()
    jpath = tmp_path / "sub" / "m.json"
    ppath = tmp_path / "m.prom"
    write_metrics(str(jpath), snap)  # creates parent dirs
    write_metrics(str(ppath), snap)
    assert json.loads(jpath.read_text()) == snap
    assert "# TYPE c_total counter" in ppath.read_text()


def test_tracer_emits_valid_chrome_trace(tmp_path):
    tr = Tracer()
    tr.thread_name(0, 1, "lane 1")
    tr.complete("work", 0, 1, ts=0.001, dur=0.002, args={"k": "v"})
    tr.instant("mark", 0, 1)
    path = tmp_path / "t" / "trace.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "work" and x["ts"] == 1000 and x["dur"] == 2000
    assert {"pid", "tid", "ts"} <= set(x)
    assert any(e["ph"] == "M" for e in evs)  # process/thread metadata
    assert any(e["ph"] == "i" for e in evs)


# ---------------------------------------------------------------------------
# allocator gauges (peak tracked on every alloc/free)
# ---------------------------------------------------------------------------


def test_allocator_tracks_in_use_and_peak_gauges():
    reg = MetricsRegistry()
    alloc = BlockAllocator(8, 4, metrics=reg)
    a = alloc.alloc(3)
    assert alloc.peak_in_use == 3
    for b in a:
        alloc.decref(b)
    b2 = alloc.alloc(2)
    snap = reg.snapshot()
    assert snap["kv_pool_blocks_in_use"]["series"][0]["value"] == 2.0
    assert snap["kv_pool_blocks_peak"]["series"][0]["value"] == 3.0
    assert snap["kv_pool_blocks_capacity"]["series"][0]["value"] == 7.0
    for b in b2:
        alloc.decref(b)
    assert reg.snapshot()["kv_pool_blocks_in_use"]["series"][0]["value"] == 0.0
    assert alloc.peak_in_use == 3  # peak is a high-water mark


# ---------------------------------------------------------------------------
# engine instrumentation contract
# ---------------------------------------------------------------------------


def _tiny_engine(**kw):
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    econf = EngineConfig.oracle_dense(n_lanes=2, n_slots=3, max_len=32, **kw)
    return cfg, MultiTenantEngine(cfg, econf)


def test_engine_span_lifecycle_and_latency_histograms():
    cfg, eng = _tiny_engine()
    r = eng.submit(BASE_TENANT, np.arange(2, 8, dtype=np.int32), 4)
    eng.run()
    names = r.trace.names()
    # milestone ordering: submit → admit → prefill → first_token → retire,
    # each exactly once
    assert [n for n in names if n != "defer"] == [
        "submit", "admit", "prefill", "first_token", "retire"
    ]
    assert r.trace.ttft_ms is not None and r.trace.ttft_ms >= 0
    assert r.trace.e2e_ms is not None and r.trace.e2e_ms >= r.trace.ttft_ms
    snap = eng.metrics()
    assert snap["serve_ttft_ms"]["series"][0]["count"] == 1
    assert snap["serve_e2e_ms"]["series"][0]["count"] == 1
    assert snap["serve_tokens_total"]["series"][0]["value"] == 4.0
    assert snap["serve_requests_total"]["series"][0]["value"] == 1.0
    assert snap["serve_retired_total"]["series"][0]["value"] == 1.0
    # step-phase histograms cover the decode loop
    phases = {s["labels"]["phase"] for s in snap["serve_step_phase_ms"]["series"]}
    assert {"admit", "dispatch", "sync", "emit"} <= phases
    # jit compile-event callbacks hook the _cache_size machinery
    assert snap["serve_jit_compiles_prefill"]["series"][0]["value"] >= 1.0
    assert snap["serve_jit_compiles_decode"]["series"][0]["value"] >= 1.0
    # chrome trace carries the lane timeline
    doc = eng.telemetry.tracer.to_chrome()
    spans = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"queued", "prefill", "decode"} <= spans
    assert any(s.startswith("req ") for s in spans)


def test_engine_block_pressure_preemption_counted_once_and_stream_unaffected():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")

    def run(telemetry):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=2, n_slots=2, max_len=32, block_size=8,
                n_blocks=1 + 5, telemetry=telemetry,
            ),
        )
        a = eng.submit(BASE_TENANT, np.arange(2, 10, dtype=np.int32), 16)
        b = eng.submit(BASE_TENANT, np.arange(12, 20, dtype=np.int32), 16)
        events = list(eng.stream())
        return eng, a, b, events

    eng, a, b, events = run(telemetry=True)
    assert eng.preemptions >= 1
    snap = eng.metrics()
    by_cause = {
        s["labels"]["cause"]: s["value"]
        for s in snap["serve_preemptions_total"]["series"]
    }
    assert by_cause["block_pressure"] == float(eng.preemptions)
    # the victim's trace records each preemption exactly once
    assert b.trace.names().count("preempt") == b.preemptions
    # delivered (exactly-once) tokens < decoded (incl. re-derivation)
    assert snap["serve_tokens_total"]["series"][0]["value"] == len(events)
    assert eng.decoded_tokens > len(events)
    # telemetry must not perturb scheduling: disabled engine decodes the
    # same tokens through the same preemption schedule
    eng_off, a_off, b_off, events_off = run(telemetry=False)
    assert a_off.trace is None and eng_off.metrics() == {}
    assert a_off.tokens == a.tokens and b_off.tokens == b.tokens
    assert [(e.uid, e.token) for e in events_off] == [
        (e.uid, e.token) for e in events
    ]


def test_engine_quantum_preemption_recorded_per_requeue():
    cfg = get_reduced("xlstm_125m").replace(dtype="float32")
    eng = MultiTenantEngine(
        cfg, EngineConfig(n_lanes=1, n_slots=2, max_len=48, quantum=3)
    )
    rng = np.random.default_rng(0)
    r1 = eng.submit(BASE_TENANT, rng.integers(2, cfg.vocab_size, size=7).astype(np.int32), 9)
    r2 = eng.submit(BASE_TENANT, rng.integers(2, cfg.vocab_size, size=5).astype(np.int32), 9)
    eng.run()
    assert eng.slice_preemptions >= 2
    snap = eng.metrics()
    by_cause = {
        s["labels"]["cause"]: s["value"]
        for s in snap["serve_preemptions_total"]["series"]
    }
    assert by_cause["quantum"] == float(eng.slice_preemptions)
    marks = r1.trace.names().count("preempt") + r2.trace.names().count("preempt")
    assert marks == eng.slice_preemptions
    # a restored request re-admits without re-prefilling: admits exceed
    # prefill marks for the preempted traces
    for r in (r1, r2):
        if r.preemptions:
            assert r.trace.names().count("admit") == r.preemptions + 1
            assert r.trace.names().count("prefill") == 1


def test_engine_prefix_and_cow_counters_match_attrs():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=2, n_slots=3, max_len=32, block_size=8,
            share_prefix=True,
        ),
    )
    prompt = np.arange(2, 18, dtype=np.int32)  # two full blocks
    eng.submit(BASE_TENANT, prompt, 4)
    eng.submit(BASE_TENANT, prompt, 4)  # same family+prompt → shared prefix
    eng.run()
    snap = eng.metrics()
    assert eng.prefix_cache.hits > 0
    assert snap["serve_prefix_hits_total"]["series"][0]["value"] == float(
        eng.prefix_cache.hits
    )
    assert snap["serve_prefix_misses_total"]["series"][0]["value"] == float(
        eng.prefix_cache.misses
    )
    assert snap["serve_cow_forks_total"]["series"][0]["value"] == float(
        eng.cow_forks
    )
    assert snap["kv_prefix_hit_rate"]["series"][0]["value"] == pytest.approx(
        eng.prefix_cache.hits / (eng.prefix_cache.hits + eng.prefix_cache.misses)
    )


def test_engine_deferred_promotions_back_compat_property():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    # hot tier of 1 usable slot + cold tier: t2 spills cold at
    # registration, and its request can't promote while t1's active
    # request pins the only hot slot → cold_promote deferral episode
    eng = MultiTenantEngine(
        cfg, EngineConfig(n_lanes=2, n_slots=2, max_len=32, cold_slots=4)
    )
    from repro.serving import random_lambda
    import jax

    eng.add_tenant("t1", random_lambda(jax.random.PRNGKey(1), eng.params, 0.2))
    eng.add_tenant("t2", random_lambda(jax.random.PRNGKey(2), eng.params, 0.2))
    eng.submit("t1", np.arange(2, 8, dtype=np.int32), 8)
    r = eng.submit("t2", np.arange(2, 8, dtype=np.int32), 4)
    eng.run()
    assert eng.deferred_promotions >= 1  # property reads the counter
    snap = eng.metrics()
    by_cause = {
        s["labels"]["cause"]: s["value"]
        for s in snap["serve_deferrals_total"]["series"]
    }
    assert by_cause["cold_promote"] == float(eng.deferred_promotions)
    assert "defer" in r.trace.names()
    # λ-store occupancy callbacks ride the same snapshot
    assert snap["lam_hot_slots_capacity"]["series"][0]["value"] == 1.0
    assert snap["lam_promotes_total"]["series"][0]["value"] == float(
        eng.lam_store.promotes
    )


def test_engine_disabled_telemetry_is_inert():
    cfg, eng = _tiny_engine(telemetry=False)
    r = eng.submit(BASE_TENANT, np.arange(2, 8, dtype=np.int32), 4)
    eng.run()
    assert r.trace is None
    assert eng.metrics() == {}
    assert eng.telemetry.tracer is None
    assert eng.deferred_promotions == 0
    with pytest.raises(RuntimeError):
        eng.telemetry.write_trace("/tmp/never.json")
    assert len(r.tokens) == 4  # serving itself is unaffected


# ---------------------------------------------------------------------------
# profiler spans and device-program names
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def profiled_engine(tmp_path_factory):
    """A paged engine with chunked prefill, run once to compile,
    then again under a ``jax.profiler`` capture; returns the engine, its
    requests and the ``serve.*`` events of the capture as
    (name, stats) pairs."""
    import glob

    import jax

    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(cfg, EngineConfig(
        layout="paged", n_lanes=2, n_slots=3, max_len=64, block_size=8,
        prefill_chunk=8))

    def serve():
        reqs = [eng.submit(BASE_TENANT, np.arange(2, 2 + n, dtype=np.int32), g)
                for n, g in ((6, 4), (20, 3), (9, 5))]
        eng.run()
        return reqs

    serve()
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        reqs = serve()
    path = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, {k: v for k, v in e.stats})
             for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    return eng, reqs, spans


def test_engine_spans_on_the_profilers_clock(profiled_engine):
    _, reqs, spans = profiled_engine
    names = {n for n, _ in spans}
    assert {"serve.admit", "serve.prefill", "serve.grow", "serve.dispatch",
            "serve.sync", "serve.emit"} <= names
    # every monolithic prefill and every chunk names its request
    uids = {r.uid for r in reqs}
    prefills = [a for n, a in spans if n == "serve.prefill"]
    assert prefills and all(a["uid"] in uids and a["prompt"] > 0 for a in prefills)
    chunks = [a for n, a in spans if n == "serve.prefill_chunk" and "uid" in a]
    assert {a["uid"] for a in chunks} == {reqs[1].uid}  # the 20-token prompt
    assert sorted(a["start"] for a in chunks) == [0, 8, 16]
    # the decode dispatch says how many lanes decoded and how far they attend
    dispatch = [a for n, a in spans if n == "serve.dispatch"]
    assert dispatch
    assert all(1 <= a["lanes"] <= 2 and a["attend_blocks"] >= 1 for a in dispatch)
    assert all(a["rows"] == 2 for a in dispatch)  # two lanes: the full batch
    assert sum(a["admitted"] for n, a in spans if n == "serve.admit") >= len(reqs)


def test_every_step_phase_has_a_span_of_its_name(profiled_engine):
    eng, _, spans = profiled_engine
    snap = eng.metrics()
    phases = {s["labels"]["phase"] for s in snap["serve_step_phase_ms"]["series"]}
    assert {"admit", "prefill_chunk", "grow", "dispatch", "sync", "emit"} <= phases
    assert {f"serve.{p}" for p in phases} <= {n for n, _ in spans}


def test_decode_lowers_as_a_stably_named_program():
    _, eng = _tiny_engine()
    assert eng.lower_decode().as_text().startswith("module @jit_serve_decode")


def test_compact_decode_compiles_once_per_attend_bucket(monkeypatch):
    """16 lanes with at most 8 live: every paged decode step runs the 8-row
    lane bucket, so the decode compiles one variant per attend bucket, as
    it does over all lanes; each dispatch span's ``rows`` and the
    ``serve_decode_rows_total`` counter read 8 rows a step."""
    from repro.obs import telemetry as telemetry_mod

    dispatched = []
    real_span = telemetry_mod.span

    def span(name, **args):
        if name == "dispatch":
            dispatched.append(args)
        return real_span(name, **args)

    monkeypatch.setattr(telemetry_mod, "span", span)
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(cfg, EngineConfig(
        layout="paged", n_lanes=16, n_slots=2, max_len=64, block_size=8))

    def rows_total():
        return eng.metrics()["serve_decode_rows_total"]["series"][0]["value"]

    eng.submit(BASE_TENANT, np.arange(2, 7, dtype=np.int32), 3)
    eng.step()  # admits and prefills the request, then decodes it
    assert dispatched == [{"lanes": 1, "rows": 8, "attend_blocks": 1}]
    assert rows_total() == 8
    # requests of 2, 4 and 8 blocks alone (attend buckets 2, 4, 8), then
    # eight at once
    for n, g in ((9, 6), (20, 10), (40, 20)):
        eng.submit(BASE_TENANT, np.arange(2, 2 + n, dtype=np.int32), g)
        eng.run()
    for n, g in ((3, 4), (12, 9), (30, 3), (6, 2), (9, 6), (5, 5), (7, 3), (4, 8)):
        eng.submit(BASE_TENANT, np.arange(2, 2 + n, dtype=np.int32), g)
    eng.run()
    assert all(1 <= a["lanes"] <= 8 and a["rows"] == 8 for a in dispatched)
    assert rows_total() == 8 * len(dispatched) == 8 * eng.steps
    buckets = {a["attend_blocks"] for a in dispatched}
    assert buckets == {1, 2, 4, 8}
    compiles = eng.metrics()["serve_jit_compiles_decode"]["series"][0]["value"]
    assert compiles == len(buckets)
    assert eng.lower_decode(8).as_text().startswith("module @jit_serve_decode")
