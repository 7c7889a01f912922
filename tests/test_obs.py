"""The fabric flight recorder: metrics registry, tracer, latency hists.

Four claims under test:

  * the Prometheus text export is spec-compliant — HELP/TYPE per family,
    label escaping, ``+Inf``/``NaN`` rendering, cumulative histogram
    buckets — and round-trips through the strict scrape-side parser;
  * the two telemetry planes export ``telemetry_updates_total`` as two
    *distinct* labeled series (the name-collision regression), and the
    registry refuses genuine duplicates naming both sources;
  * histogram quantile estimates bracket the true sample quantile within
    one bucket (property-tested via the tests/_hyp shim);
  * the tracer records the full stack-module lifecycle as Chrome
    trace-event JSON — stable names/phases for the migration scenario,
    valid JSON, monotonic timestamps per track (the golden-trace test,
    validated by tools/check_trace.py itself).
"""
import importlib.util
import json
import math
import pathlib

import pytest

from _hyp import given, settings, st
from test_placement import make_fake_cluster

from repro.obs import (
    Histogram, MetricsRegistry, NullTracer, TenantHistograms, Tracer,
    escape_label_value, format_value, parse_prometheus_text,
    parse_series_key, render_prometheus, trace_to,
)
from repro.obs import tracing
from repro.serve.scheduler import Request

_CHECK_TRACE = pathlib.Path(__file__).resolve().parents[1] \
    / "tools" / "check_trace.py"
_spec = importlib.util.spec_from_file_location("check_trace", _CHECK_TRACE)
check_trace_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_trace_mod)


# ---------------------------------------------------------------------------
# Prometheus text format
# ---------------------------------------------------------------------------


def test_render_emits_help_and_type_once_per_family():
    text = render_prometheus({
        "nk_cluster_engines": 3.0,
        'nk_engine_load{engine="0"}': 0.5,
        'nk_engine_load{engine="1"}': 0.25,
    })
    assert text.count("# HELP nk_engine_load") == 1
    assert text.count("# TYPE nk_engine_load gauge") == 1
    assert text.count("# TYPE nk_cluster_engines gauge") == 1
    # every non-comment line is a sample
    samples = [ln for ln in text.splitlines() if not ln.startswith("#")]
    assert len(samples) == 3


def test_metric_types_inferred_from_name():
    text = render_prometheus({
        "nk_cluster_steps_total": 7.0,
        'nk_admit_wait_seconds_bucket{le="+Inf",tenant="0"}': 2.0,
        'nk_admit_wait_seconds_sum{tenant="0"}': 0.5,
        'nk_admit_wait_seconds_count{tenant="0"}': 2.0,
    })
    assert "# TYPE nk_cluster_steps_total counter" in text
    assert "# TYPE nk_admit_wait_seconds histogram" in text
    # the histogram family gets ONE header covering bucket/sum/count
    assert text.count("# TYPE nk_admit_wait_seconds") == 1


def test_label_escaping_round_trips():
    nasty = 'quote " backslash \\ newline \n done'
    esc = escape_label_value(nasty)
    assert "\n" not in esc
    key = f'nk_migration_info{{tenant="{esc}"}}'
    name, labels = parse_series_key(key)
    assert name == "nk_migration_info"
    assert dict(labels)["tenant"] == nasty
    text = render_prometheus({key: 1.0})
    parsed = parse_prometheus_text(text)
    assert parsed[(name, labels)] == 1.0


def test_special_values_render_and_parse():
    assert format_value(float("inf")) == "+Inf"
    assert format_value(float("-inf")) == "-Inf"
    assert format_value(float("nan")) == "NaN"
    text = render_prometheus({"nk_engine_load": float("inf"),
                              "nk_cluster_parked": float("nan")})
    parsed = parse_prometheus_text(text)
    assert parsed[("nk_engine_load", ())] == float("inf")
    assert math.isnan(parsed[("nk_cluster_parked", ())])


def test_parser_rejects_duplicate_series_and_garbage():
    with pytest.raises(ValueError):
        parse_prometheus_text("nk_x 1\nnk_x 2\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("not a metric line at all!\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("# TYPE nk_x flub\nnk_x 1\n")


def test_render_parse_round_trip_preserves_every_series():
    counters = {
        "nk_cluster_engines": 3.0,
        'telemetry_updates_total{plane="serve"}': 12.0,
        'telemetry_updates_total{plane="bytes"}': 9.0,
        'nk_engine_load{engine="2"}': 0.125,
        'nk_migration_info{dst="1",seq="8",src="0",tenant="0"}': 8.0,
    }
    parsed = parse_prometheus_text(render_prometheus(counters))
    assert len(parsed) == len(counters)
    for key, value in counters.items():
        assert parsed[parse_series_key(key)] == value


# ---------------------------------------------------------------------------
# the telemetry name-collision regression + registry
# ---------------------------------------------------------------------------


def _both_planes():
    import numpy as np

    from repro.control.telemetry import EngineTelemetry, SchedulerTelemetry
    from repro.core.engine import CoreEngine
    from repro.serve.scheduler import TenantScheduler

    class _Payload:
        dtype = np.uint8

        def __init__(self, n):
            self.shape = (int(n),)

    sched = TenantScheduler()
    sched.add_tenant(0, rate_tokens_per_s=8.0)
    stel = SchedulerTelemetry(sched)
    stel.update(0.0)
    stel.update(1.0)
    core = CoreEngine(enforcement="account")
    core.set_tenant_rate(0, 1e6)
    core.dispatch("shm_move", _Payload(256), ("pod",), tenant_id=0, now=0.5)
    etel = EngineTelemetry(core)
    etel.update(0.0)
    etel.update(1.0)
    return stel, etel


def test_telemetry_updates_are_distinct_labeled_series():
    """Regression: both planes used to export bare
    ``telemetry_updates_total``; one silently shadowed the other in any
    combined scrape. Now each carries its plane label."""
    stel, etel = _both_planes()
    reg = MetricsRegistry()
    reg.register_provider(stel, name="serve-telemetry")
    reg.register_provider(etel, name="bytes-telemetry")
    parsed = parse_prometheus_text(reg.export_prometheus())
    planes = {dict(lbl)["plane"]: v for (n, lbl), v in parsed.items()
              if n == "telemetry_updates_total"}
    assert set(planes) == {"serve", "bytes"}
    assert planes["serve"] == stel.updates
    assert planes["bytes"] == etel.updates


def test_registry_rejects_duplicate_series_naming_both_sources():
    _, etel = _both_planes()
    _, etel2 = _both_planes()
    reg = MetricsRegistry()
    reg.register_provider(etel, name="first")
    reg.register_provider(etel2, name="second")
    with pytest.raises(ValueError) as ei:
        reg.collect()
    assert "first" in str(ei.value) and "second" in str(ei.value)


def test_registry_instruments_and_providers_export_together():
    reg = MetricsRegistry()
    c = reg.counter("nk_test_events_total", "Test events")
    g = reg.gauge("nk_test_depth", "Test depth")
    h = reg.histogram("nk_test_wait_seconds", "Test waits")
    c.inc()
    c.inc(2.0, tenant="0")
    g.set(4.0)
    h.observe(0.01, tenant="0")
    reg.register_provider(lambda: {"nk_provider_value": 1.0},
                          name="fn-provider")
    parsed = parse_prometheus_text(reg.export_prometheus())
    assert parsed[("nk_test_events_total", ())] == 1.0
    assert parsed[("nk_test_events_total", (("tenant", "0"),))] == 2.0
    assert parsed[("nk_test_depth", ())] == 4.0
    assert parsed[("nk_provider_value", ())] == 1.0
    assert parsed[("nk_test_wait_seconds_count", (("tenant", "0"),))] == 1.0


@pytest.mark.parametrize("model_axis,in_place", [(1, 1.0), (2, 0.0)])
def test_engine_gauges_its_in_place_decode_segments(model_axis, in_place,
                                                    rcfg_small):
    """internlm2's one cache segment takes the in-place decode write on one
    device, and the masked select where the model axis shards the cache."""
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_schedule
    from repro.serve.engine import ServeEngine
    cfg = get_smoke_config("internlm2-1.8b")
    assert len(build_schedule(cfg)) == 1
    eng = ServeEngine(cfg, rcfg_small, make_host_mesh(1, model_axis),
                      batch_slots=2, max_seq=32)
    reg = MetricsRegistry()
    reg.register_provider(eng, name="engine")
    text = reg.export_prometheus()
    assert "# TYPE nk_decode_cache_inplace_segments gauge" in text
    assert parse_prometheus_text(text) == {
        ("nk_decode_cache_inplace_segments", ()): in_place}


@pytest.mark.parametrize("name,layers", [("internlm2-1.8b", 2),
                                         ("deepseek-v2-236b", 0)])
def test_engine_exports_the_decode_kernels_counters_on_a_tpu(
        name, layers, rcfg_small, monkeypatch):
    """Where the programs lower for a TPU the engine gauges the layers
    whose decode runs the flash-decode kernel (none for latent attention),
    and counts the cache blocks it reads only where some layer does."""
    from repro.configs import get_smoke_config
    from repro.serve import engine as engine_mod
    monkeypatch.setattr(engine_mod, "lowering_platform", lambda mesh: "tpu")
    cfg = get_smoke_config(name)
    eng = engine_mod.ServeEngine(cfg, rcfg_small, None, batch_slots=2,
                                 max_seq=32)
    assert eng.decode_kernel_layers == layers
    reg = MetricsRegistry()
    reg.register_provider(eng, name="engine")
    text = reg.export_prometheus()
    assert "# TYPE nk_decode_attention_kernel_layers gauge" in text
    got = parse_prometheus_text(text)
    assert got[("nk_decode_attention_kernel_layers", ())] == layers
    blocks = {("nk_decode_kv_blocks_read_total", ()),
              ("nk_decode_kv_blocks_total", ())}
    if layers:
        assert "# TYPE nk_decode_kv_blocks_read_total counter" in text
        assert {k: got[k] for k in blocks} == dict.fromkeys(blocks, 0.0)
    else:
        assert not blocks & set(got)


# ---------------------------------------------------------------------------
# latency histograms
# ---------------------------------------------------------------------------


def test_histogram_basic_stats_and_quantiles():
    h = Histogram()
    for v in (0.001, 0.01, 0.01, 0.1, 1.0):
        h.observe(v)
    assert h.total == 5
    assert h.min == pytest.approx(0.001)
    assert h.max == pytest.approx(1.0)
    assert h.mean == pytest.approx(sum((0.001, 0.01, 0.01, 0.1, 1.0)) / 5)
    # the p50 estimate is the upper edge of the bucket holding the median
    lo, hi = h.quantile_bounds(0.50)
    assert lo <= 0.01 <= hi
    assert h.quantile(0.50) == hi


def test_histogram_merge_since_and_payload_round_trip():
    a, b = Histogram(), Histogram()
    for v in (0.002, 0.02):
        a.observe(v)
    b.observe(0.2)
    snap = a.copy()
    a.observe(0.5)
    win = a.since(snap)
    assert win.total == 1
    assert win.quantile(0.99) >= 0.5       # the new sample's bucket edge
    a.merge(b)
    assert a.total == 4
    back = Histogram.from_payload(a.to_payload())
    assert back.total == a.total
    assert back.counts == a.counts
    assert back.sum == pytest.approx(a.sum)


def test_histogram_counters_are_cumulative_and_parse():
    h = Histogram()
    for v in (0.001, 0.05, 5.0, 1e9):       # 1e9 lands in overflow
        h.observe(v)
    c = h.counters("nk_admit_wait_seconds", tenant="7")
    text = render_prometheus(c)
    parsed = parse_prometheus_text(text)
    inf_key = parse_series_key(
        'nk_admit_wait_seconds_bucket{tenant="7",le="+Inf"}')
    assert parsed[inf_key] == 4.0
    assert parsed[("nk_admit_wait_seconds_count", (("tenant", "7"),))] == 4.0
    # cumulative: counts never decrease as le rises
    buckets = sorted(
        ((float("inf") if dict(lbl)["le"] == "+Inf"
          else float(dict(lbl)["le"])), v)
        for (n, lbl), v in parsed.items() if n.endswith("_bucket"))
    values = [v for _, v in buckets]
    assert values == sorted(values)


@settings(max_examples=60, deadline=None)
@given(samples=st.lists(st.floats(min_value=1e-4, max_value=500.0),
                        min_size=1, max_size=200),
       q=st.sampled_from([0.5, 0.9, 0.95, 0.99]))
def test_quantile_bounds_bracket_true_sample_quantile(samples, q):
    """The histogram estimate stays within one bucket of the truth."""
    h = Histogram()
    for v in samples:
        h.observe(v)
    rank = max(1, math.ceil(q * len(samples)))
    truth = sorted(samples)[rank - 1]
    lo, hi = h.quantile_bounds(q)
    assert lo <= truth <= hi or truth == pytest.approx(lo) \
        or truth == pytest.approx(hi)
    assert h.quantile(q) == hi


@settings(max_examples=40, deadline=None)
@given(samples=st.lists(st.floats(min_value=1e-3, max_value=50.0),
                        min_size=2, max_size=80),
       split=st.integers(min_value=1, max_value=79))
def test_histogram_merge_equals_observing_everything(samples, split):
    split = min(split, len(samples) - 1)
    a, b, whole = Histogram(), Histogram(), Histogram()
    for v in samples[:split]:
        a.observe(v)
    for v in samples[split:]:
        b.observe(v)
    for v in samples:
        whole.observe(v)
    a.merge(b)
    assert a.counts == whole.counts
    assert a.total == whole.total
    assert a.sum == pytest.approx(whole.sum)


def test_tenant_histograms_track_pop_and_merge():
    th = TenantHistograms("nk_ttft_seconds")
    th.observe(0, 0.01)
    th.observe(1, 0.1)
    th.observe(0, 0.02)
    assert th.get(0).total == 2
    c = th.counters()
    assert any("tenant=\"1\"" in k for k in c)
    popped = th.pop(0)
    assert popped.total == 2
    assert th.get(0).total == 0            # gone; get() hands back empty
    th.absorb(0, popped)
    assert th.get(0).total == 2


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_null_tracer_is_default_and_inert():
    assert isinstance(tracing.TRACER, NullTracer)
    assert not tracing.TRACER.enabled
    # every recording call is a no-op returning None
    assert tracing.TRACER.instant("t", "x", 0.0) is None
    assert tracing.TRACER.span("t", "x", 0.0, 1.0) is None
    assert tracing.TRACER.async_begin("t", "x", 1, 0.0) is None
    assert tracing.TRACER.async_end("t", "x", 1, 1.0) is None


def test_null_region_is_one_shared_no_op():
    null = NullTracer()
    r = null.region("engine", "step")
    assert r is null.region("scheduler", "pick") is tracing.TRACER.region(
        "engine", "readback")
    with r as entered:
        with r:                            # reusable and re-entrant
            assert entered is None


def test_regions_stay_out_of_the_recorded_json():
    with trace_to() as tr:
        with tracing.TRACER.region("engine", "step"):
            tr.instant("engine", "request.admit", 1.0)
    assert [e["name"] for e in tr.events if e["ph"] != "M"] == \
        ["request.admit"]


def test_profiler_tracer_regions_are_named_annotations():
    import jax
    pt = tracing.ProfilerTracer()
    assert not pt.enabled                  # span/instant stay no-ops
    assert pt.instant("t", "x", 0.0) is None
    assert pt.span("t", "x", 0.0, 1.0) is None
    r = pt.region("engine", "readback")
    assert isinstance(r, jax.profiler.TraceAnnotation)
    with r:
        pass


def test_admit_wait_recorded_on_the_wall_clock_path():
    """Served with no ``now`` (the wall-clock path), the pick still
    measures each request's wait, on time.monotonic()."""
    import time
    from repro.serve.scheduler import TenantScheduler
    s = TenantScheduler()
    s.submit(Request(0, [1, 2], 4, req_id=0,
                     arrival=time.monotonic() - 0.25))
    s.submit(Request(0, [1, 2], 4, req_id=1))        # arrival unknown
    assert s.next_request().req_id == 0
    assert s.next_request().req_id == 1
    assert s.admit_wait_hist.get(0).total == 1
    assert 0.25 <= s.admit_wait_sum[0] < 60.0


def test_trace_to_swaps_and_restores_the_global():
    before = tracing.TRACER
    with trace_to() as tr:
        assert tracing.TRACER is tr and tr.enabled
        tr.instant("track", "evt", 1.5, tenant=3)
    assert tracing.TRACER is before


def test_tracer_event_encoding():
    tr = Tracer()
    tr.span("cluster", "migrate.transfer", 1.0, 1.0, tenant=0)
    tr.instant("cluster", "park", 2.0, engine=1)
    tr.async_begin("cluster", "migrate.drain", 0, 1.0)
    tr.async_end("cluster", "migrate.drain", 0, 1.25)
    doc = tr.chrome_trace()
    by_ph = {}
    for ev in doc["traceEvents"]:
        by_ph.setdefault(ev["ph"], []).append(ev)
    assert set(by_ph) == {"M", "X", "i", "b", "e"}
    x = by_ph["X"][0]
    assert x["ts"] == 1_000_000 and x["dur"] == 0
    assert isinstance(x["ts"], int)
    assert x["args"]["tenant"] == 0
    assert by_ph["i"][0]["s"] == "t"
    assert by_ph["b"][0]["id"] == by_ph["e"][0]["id"]
    assert json.loads(tr.to_json())["traceEvents"]
    assert tr.counters()["nk_trace_events_total"] == 4.0


# ---------------------------------------------------------------------------
# the golden migration trace (jit-free fake cluster)
# ---------------------------------------------------------------------------

# the stable lifecycle signature: every (name, ph) the scenario below
# must emit on the cluster track, in order
GOLDEN_LIFECYCLE = [
    ("migrate.transfer", "X"), ("migrate.drain", "b"),
    ("migrate.drain", "e"), ("migrate.finalize", "X"),
    ("migrate.transfer", "X"), ("migrate.drain", "b"),
    ("migrate.drain", "e"), ("migrate.finalize", "X"),
    ("park", "i"), ("unpark", "i"),
]

LIFECYCLE_NAMES = {"migrate.transfer", "migrate.drain", "migrate.finalize",
                   "park", "unpark"}


def _traced_fake_migration():
    with trace_to() as tr:
        cl = make_fake_cluster(3)
        for t in range(3):
            cl.add_tenant(t, engine=t)
            cl.submit(Request(t, [1, 2], 4, req_id=t, arrival=0.0))
        for i in range(8):
            cl.step(now=0.1 * (i + 1))
        cl.migrate(0, 1, now=1.0)            # operator rebalance
        for i in range(4):
            cl.step(now=1.0 + 0.1 * (i + 1))
        cl.migrate(2, 0, now=2.0)            # drain engine 2...
        for i in range(4):
            cl.step(now=2.0 + 0.1 * (i + 1))
        cl.park(2, now=3.0)                  # ...maintenance window
        cl.unpark(2, now=3.5)
    return tr


def test_golden_migration_trace_names_and_phases_are_stable():
    tr = _traced_fake_migration()
    doc = json.loads(tr.to_json())             # valid JSON by construction
    lifecycle = [(e["name"], e["ph"]) for e in doc["traceEvents"]
                 if e.get("name") in LIFECYCLE_NAMES]
    assert lifecycle == GOLDEN_LIFECYCLE
    # the scheduler's request lifecycle shows up too (FakeEngine admits
    # through the real TenantScheduler; dispatch/finish are ServeEngine's)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"request.arrival", "request.admit"} <= names


def test_golden_migration_trace_passes_the_validator():
    tr = _traced_fake_migration()
    doc = json.loads(tr.to_json())
    assert check_trace_mod.check_trace(doc, scenario="migration") == []


def test_trace_timestamps_monotonic_per_track():
    tr = _traced_fake_migration()
    last = {}
    for ev in tr.chrome_trace()["traceEvents"]:
        if ev["ph"] in ("M", "b", "e"):
            continue
        track = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last.get(track, -1)
        last[track] = max(last.get(track, -1),
                          ev["ts"] + ev.get("dur", 0))


def test_disabled_tracer_records_nothing_during_cluster_run():
    set_before = tracing.TRACER
    cl = make_fake_cluster(2)
    cl.add_tenant(0, engine=0)
    cl.submit(Request(0, [1, 2], 4, req_id=0, arrival=0.0))
    for i in range(4):
        cl.step(now=0.1 * (i + 1))
    cl.migrate(0, 1, now=1.0)
    assert tracing.TRACER is set_before      # nothing swapped it
    assert not tracing.TRACER.enabled


def test_cluster_counters_include_latency_histograms_and_moves():
    cl = make_fake_cluster(3)
    for t in range(3):
        cl.add_tenant(t)
        cl.submit(Request(t, [1, 2], 4, req_id=10 + t, arrival=0.0))
    for i in range(6):
        cl.step(now=0.1 * (i + 1))
    cl.migrate(0, (cl.placement[0] + 1) % 3, now=1.0)
    for i in range(4):
        cl.step(now=1.0 + 0.1 * (i + 1))
    parsed = parse_prometheus_text(
        render_prometheus(cl.counters()))
    names = {n for n, _ in parsed}
    assert "nk_admit_wait_seconds_bucket" in names
    assert "nk_migration_info" in names
    info = [(dict(lbl), v) for (n, lbl), v in parsed.items()
            if n == "nk_migration_info"]
    assert len(info) == 1
    lbl, v = info[0]
    assert lbl["tenant"] == "0" and lbl["src"] != lbl["dst"]
    assert float(lbl["seq"]) == v
