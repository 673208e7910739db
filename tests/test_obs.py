"""Observability (repro/obs): span tracer, metrics registry, attribution.

Covers the PR-8 acceptance checks: span nesting + thread-safety, the
disabled-mode overhead contract (<1% of the fast e2e reconstruction),
Perfetto trace_event schema of exported traces, histogram bucket edge
semantics, and the predicted-vs-measured attribution join on a 1x1x1-mesh
traced reconstruction (every nonzero PerfBreakdown stage must get a
measured counterpart).
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.cache import CountingLRU
from repro.core.geometry import default_geometry
from repro.core.phantom import forward_project
from repro.core.plan import clear_engine_cache, plan_from_spec
from repro.io import ProjectionSource, SourcePrefetcher, VolumeSink
from repro.obs import attribution, metrics, trace
from repro.obs.metrics import (
    DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry,
)
from repro.obs.trace import Span, Tracer
from repro.parallel.mesh import make_mesh

PERFETTO_KEYS = {"ph", "ts", "dur", "name", "pid", "tid"}
# The production I/O path's sub-spans (io/streams.py), nested in their stage.
IO_SPANS = {"stage.read.copy": "stage.read", "stage.read.h2d": "stage.read",
            "stage.write.d2h": "stage.write",
            "stage.write.file": "stage.write"}


def _annotations(monkeypatch) -> list:
    """Record every `jax.profiler.TraceAnnotation` entered and exited."""
    log = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return log


@pytest.fixture
def tracer():
    """Fresh enabled tracer installed as the process default (so library
    instrumentation points record into it), restored afterward."""
    tr = Tracer(enabled=True)
    prev = trace.set_tracer(tr)
    yield tr
    trace.set_tracer(prev)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestSpans:
    def test_span_records_complete_event(self, tracer):
        with tracer.span("unit.outer", k=1) as sp:
            sp.set(extra="v")
        (ev,) = tracer.events()
        assert ev["ph"] == "X" and ev["name"] == "unit.outer"
        assert ev["dur"] >= 0 and ev["ts"] >= 0
        assert ev["args"] == {"k": 1, "extra": "v"}
        assert ev["tid"] == threading.get_ident()

    def test_nesting_by_interval_containment(self, tracer):
        with tracer.span("unit.outer"):
            with tracer.span("unit.inner"):
                time.sleep(0.001)
        by_name = {e["name"]: e for e in tracer.events()}
        inner, outer = by_name["unit.inner"], by_name["unit.outer"]
        assert inner["tid"] == outer["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["dur"] >= inner["dur"]

    def test_disabled_returns_shared_null_span(self):
        tr = Tracer(enabled=False)
        s1, s2 = tr.span("a"), tr.span("b", k=1)
        assert s1 is s2                       # preallocated no-op singleton
        with s1 as sp:
            assert sp.fence(123) == 123
            sp.set(x=1)
        assert tr.events() == [] and sp.duration_s == 0.0

    def test_timed_span_measures_without_recording(self):
        tr = Tracer(enabled=False)
        with tr.span("unit.measured", timed=True) as sp:
            time.sleep(0.002)
        assert sp.duration_s >= 0.002
        assert tr.events() == []              # measured, never recorded

    def test_fence_records_dispatch_time(self, tracer):
        with tracer.span("unit.fenced") as sp:
            out = jnp.arange(8) * 2
            sp.fence(out)
        (ev,) = tracer.events()
        assert "dispatch_us" in ev["args"]
        assert 0 <= ev["args"]["dispatch_us"] <= ev["dur"]

    def test_exception_annotates_and_still_records(self, tracer):
        with pytest.raises(ValueError):
            with tracer.span("unit.bad"):
                raise ValueError("boom")
        (ev,) = tracer.events()
        assert ev["args"]["error"] == "ValueError"

    def test_thread_safety(self, tracer):
        n_threads, per = 8, 200
        barrier = threading.Barrier(n_threads)   # all truly concurrent

        def work():
            barrier.wait()
            for i in range(per):
                with tracer.span("unit.t", i=i):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = tracer.events()
        assert len(evs) == n_threads * per
        assert len({e["tid"] for e in evs}) == n_threads

    def test_max_events_bound_drops_new_spans(self):
        tr = Tracer(enabled=True, max_events=10)
        for i in range(15):
            with tr.span(f"unit.{i}"):
                pass
        assert len(tr.events()) == 10 and tr.dropped == 5
        assert tr.export()["otherData"]["dropped"] == 5
        tr.clear()
        assert tr.events() == [] and tr.dropped == 0

    def test_recording_span_is_a_trace_annotation(self, tracer,
                                                   monkeypatch):
        log = _annotations(monkeypatch)
        with tracer.span("unit.outer"):
            with tracer.span("unit.inner"):
                pass
        assert log == [("enter", "unit.outer"), ("enter", "unit.inner"),
                       ("exit", "unit.inner"), ("exit", "unit.outer")]

    def test_disabled_path_has_no_event_annotation_or_fence(
            self, monkeypatch, tmp_path):
        """A disabled tracer, on the spans and on the instrumented I/O path:
        no event, no annotation, no fence — `timed=True` included."""
        log = _annotations(monkeypatch)
        fences = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: fences.append(x) or x)
        tr = Tracer(enabled=False)
        prev = trace.set_tracer(tr)
        try:
            with tr.span("unit.a") as sp:
                sp.fence(jnp.ones(2))
            with tr.span("unit.b", timed=True):
                pass
            src = ProjectionSource.write(str(tmp_path / "p"),
                                         np.ones((4, 2, 3), np.float32))
            VolumeSink(str(tmp_path / "v")).write(src.load())
        finally:
            trace.set_tracer(prev)
        assert tr.events() == [] and log == [] and fences == []

    def test_stage_totals_sums_per_name(self, tracer):
        for _ in range(3):
            with tracer.span("stage.fake"):
                time.sleep(0.001)
        totals = tracer.stage_totals()
        assert totals["stage.fake"] >= 0.003
        assert tracer.stage_totals("nomatch.") == {}


class TestPerfettoExport:
    def test_schema_required_keys(self, tracer):
        with tracer.span("unit.a", k=1):
            with tracer.span("unit.b"):
                pass
        out = tracer.export()
        json.loads(json.dumps(out))           # wire-format serializable
        assert len(out["traceEvents"]) == 2
        for ev in out["traceEvents"]:
            assert ev["ph"] == "X" and PERFETTO_KEYS <= set(ev)
            assert isinstance(ev["ts"], float) and ev["ts"] >= 0
            assert isinstance(ev["dur"], float) and ev["dur"] >= 0

    def test_save_round_trips(self, tracer, tmp_path):
        with tracer.span("unit.saved"):
            pass
        path = tracer.save(str(tmp_path / "trace.json"))
        loaded = json.load(open(path))
        assert loaded["traceEvents"][0]["name"] == "unit.saved"
        assert PERFETTO_KEYS <= set(loaded["traceEvents"][0])


class TestDisabledOverhead:
    def test_disabled_span_under_one_percent_of_fast_e2e(self):
        """The acceptance contract: with tracing disabled, the per-span
        hot-path cost (one attr load + branch, shared null span) must be
        <1% of the fast e2e reconstruction at well above the real span
        density (a source->engine->sink call crosses 3 instrumentation
        points; assert at 8)."""
        g = default_geometry(16, n_proj=8)
        proj = jnp.asarray(forward_project(g))
        clear_engine_cache()
        fdk = plan_from_spec(g, "auto").build()
        jax.block_until_ready(fdk(proj))      # compile + warm
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(fdk(proj))
        e2e_s = (time.perf_counter() - t0) / 5

        tr = Tracer(enabled=False)
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot"):
                pass
        per_span_s = (time.perf_counter() - t0) / n
        assert per_span_s * 8 < 0.01 * e2e_s, (
            f"disabled span costs {per_span_s * 1e9:.0f} ns; 8 of them "
            f"exceed 1% of the {e2e_s * 1e3:.1f} ms e2e reconstruction")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter(self):
        c = Counter("n")
        c.inc()
        c.inc(4)
        c.inc(0)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_tracks_high_water(self):
        gg = Gauge("depth")
        gg.set(3)
        gg.inc()
        gg.dec(2)
        assert gg.value == 2.0 and gg.max_value == 4.0

    def test_histogram_edge_validation(self):
        with pytest.raises(ValueError):
            Histogram("h", buckets=())
        with pytest.raises(ValueError):
            Histogram("h", buckets=(1.0, 1.0, 2.0))       # not strict
        with pytest.raises(ValueError):
            Histogram("h", buckets=(2.0, 1.0))            # not increasing
        with pytest.raises(ValueError):
            Histogram("h", buckets=(1.0, float("inf")))   # inf is implicit

    def test_histogram_bucket_placement(self):
        h = Histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 3.0, 100.0):
            h.observe(v)
        s = h.snapshot()
        assert s["buckets"] == {"le_1": 2, "le_2": 0, "le_4": 1,
                                "le_inf": 1}
        assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 100.0
        assert s["sum"] == pytest.approx(104.5)
        assert s["mean"] == pytest.approx(104.5 / 4)

    def test_empty_histogram_snapshot(self):
        s = Histogram("h", buckets=(1.0,)).snapshot()
        assert s["count"] == 0 and s["mean"] is None and s["min"] is None

    def test_default_time_buckets_are_valid_edges(self):
        h = Histogram("h")                    # default edges must construct
        assert h.edges == DEFAULT_TIME_BUCKETS
        assert list(h.edges) == sorted(h.edges)

    def test_registry_get_or_create_and_collisions(self):
        reg = MetricsRegistry()
        assert reg.counter("a.b") is reg.counter("a.b")
        with pytest.raises(TypeError):
            reg.gauge("a.b")                  # name taken by a Counter
        reg.histogram("h", (1.0, 2.0))
        with pytest.raises(ValueError):
            reg.histogram("h", (1.0, 3.0))    # edge mismatch
        assert reg.names() == ["a.b", "h"]

    def test_registry_value_and_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(2)
        reg.gauge("g").set(7)
        reg.histogram("h", (1.0,)).observe(0.5)
        assert reg.value("c") == 2
        assert reg.value("missing", default=None) is None
        snap = reg.snapshot()
        assert snap["c"] == 2 and snap["g"] == {"value": 7.0, "max": 7.0}
        assert snap["h"]["count"] == 1
        assert "c: 2" in reg.render()
        reg.reset()
        assert reg.names() == []

    def test_counting_lru_mirrors_to_default_registry(self):
        reg = metrics.default_registry()
        base = reg.value("cache.obs_test_lru.hits", 0)
        lru = CountingLRU(2, name="obs_test_lru")
        lru.put("a", 1)
        lru.put("b", 2)
        lru.put("c", 3)                       # evicts "a"
        assert lru.get("b") == 2
        assert lru.get("zz") is None
        lru.get([1, 2])                       # unhashable
        assert reg.value("cache.obs_test_lru.hits") - base == lru.hits == 1
        assert reg.value("cache.obs_test_lru.misses") >= lru.misses == 1
        assert reg.value("cache.obs_test_lru.evictions") >= 1
        assert reg.value("cache.obs_test_lru.unhashable") >= 1

    def test_prefetcher_counts_into_default_registry(self):
        reg = metrics.default_registry()
        before = reg.value("io.prefetch.loads", 0)
        pf = SourcePrefetcher([lambda: 1, lambda: 2], depth=2)
        assert list(pf) == [1, 2]
        pf.close()
        assert reg.value("io.prefetch.loads") - before == 2


# ---------------------------------------------------------------------------
# attribution: predicted (PerfBreakdown) vs measured (traced engine)
# ---------------------------------------------------------------------------

class TestAttribution:
    @pytest.fixture(scope="class")
    def traced_run(self, tmp_path_factory):
        """One traced source -> engine -> sink reconstruction on the 1x1x1
        mesh, auto-planned, with the resulting trace."""
        tmp = tmp_path_factory.mktemp("attr")
        g = default_geometry(16, n_proj=8)
        proj = np.asarray(forward_project(g))
        src = ProjectionSource.write(str(tmp / "proj"), proj,
                                     chunks=(1, 1, 1))
        sink = VolumeSink(str(tmp / "vol"))
        mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
        clear_engine_cache()
        plan = plan_from_spec(g, "auto", mesh=mesh)
        tr = Tracer(enabled=True)
        prev = trace.set_tracer(tr)
        try:
            fdk = plan.build_traced(source=src, sink=sink)
            volume = np.asarray(fdk())
        finally:
            trace.set_tracer(prev)
        return g, plan, mesh, src, sink, tr, volume

    def test_every_engine_stage_measured(self, traced_run):
        _, _, _, _, _, tr, _ = traced_run
        measured = {e["name"] for e in tr.spans("stage.")}
        assert measured == set(attribution.STAGE_FIELDS) | set(IO_SPANS), (
            "traced run must emit one span per engine stage, and the read "
            "and write split into their two parts")
        for name in attribution.STAGE_FIELDS:
            assert len([e for e in tr.spans(name)]) >= 1

    def test_every_nonzero_predicted_stage_has_measured_counterpart(
            self, traced_run):
        _, plan, _, _, _, tr, _ = traced_run
        rows = attribution.compare(plan, tr)
        assert {r.field for r in rows} == set(
            attribution.STAGE_FIELDS.values())
        for r in rows:
            if r.predicted_s > 0:
                assert r.n_spans > 0 and r.measured_s > 0, (
                    f"stage {r.stage} predicted {r.predicted_s}s but "
                    "never measured")
            if r.predicted_s <= 0:
                assert r.error is None
            else:
                assert r.error == pytest.approx(
                    r.measured_s / r.predicted_s - 1.0)

    def test_traced_engine_matches_untraced(self, traced_run):
        g, plan, mesh, src, _, _, volume = traced_run
        ref = np.asarray(plan.build()(src.load(mesh)))
        np.testing.assert_allclose(volume, ref, rtol=2e-5, atol=2e-5)

    def test_sink_holds_the_volume(self, traced_run):
        _, _, _, _, sink, _, volume = traced_run
        np.testing.assert_allclose(np.asarray(sink.read()), volume,
                                   rtol=1e-6, atol=1e-6)

    def test_compare_accepts_exported_dict_and_event_list(self, traced_run):
        _, plan, _, _, _, tr, _ = traced_run
        from_tracer = attribution.compare(plan, tr)
        from_dict = attribution.compare(plan, tr.export())
        from_list = attribution.compare(plan, tr.events())
        for a, b, c in zip(from_tracer, from_dict, from_list):
            assert a == b == c

    def test_render_report(self, traced_run):
        _, plan, _, _, _, tr, _ = traced_run
        report = attribution.render_report(attribution.compare(plan, tr))
        for stage in attribution.STAGE_FIELDS:
            assert stage in report
        assert "predicted" in report and "measured" in report

    def test_perfetto_schema_of_real_engine_trace(self, traced_run):
        _, _, _, _, _, tr, _ = traced_run
        for ev in tr.export()["traceEvents"]:
            assert PERFETTO_KEYS <= set(ev)


# ---------------------------------------------------------------------------
# instrumented subsystems
# ---------------------------------------------------------------------------

class TestIOSpans:
    """The production path's read and write, each split into its two parts
    (io/streams.py), nested in the stage span of core/plan.py."""

    @pytest.mark.parametrize("codec", [None, "fp8_e4m3"])
    @pytest.mark.parametrize("on_mesh", [False, True])
    def test_read_and_write_split_inside_their_stage(self, tracer, tmp_path,
                                                     codec, on_mesh):
        g = default_geometry(16, n_proj=8)
        proj = np.asarray(forward_project(g))
        src = ProjectionSource.write(str(tmp_path / "proj"), proj,
                                     codec=codec)
        mesh = (make_mesh((1, 1, 1), ("pod", "data", "model"))
                if on_mesh else None)
        plan = plan_from_spec(g, "auto", mesh=mesh)
        jax.block_until_ready(
            plan.build(source=src, sink=VolumeSink(str(tmp_path / "v")))())
        spans = {e["name"]: e for e in tracer.spans("stage.")}
        assert set(spans) == {"stage.read", "stage.write"} | set(IO_SPANS)
        for name, stage in IO_SPANS.items():
            inner, outer = spans[name], spans[stage]
            assert inner["tid"] == outer["tid"]
            assert outer["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        for first, second in (("stage.read.copy", "stage.read.h2d"),
                              ("stage.write.d2h", "stage.write.file")):
            a, b = spans[first], spans[second]
            assert a["ts"] + a["dur"] <= b["ts"]
        assert "dispatch_us" in spans["stage.read.h2d"]["args"]

    def test_split_write_stores_the_same_bytes(self, tmp_path):
        """`VolumeSink.write` (snapshot, then save) lays down the files and
        manifest that `save_array` of the device array does."""
        from repro.io import shard_store

        vol = jax.device_put(np.arange(4 * 6 * 8, dtype=np.float32)
                             .reshape(4, 6, 8))
        VolumeSink(str(tmp_path / "sink")).write(vol, layout={"kind": "x"})
        shard_store.save_array(str(tmp_path / "direct"), vol,
                               extra_manifest={"layout": {"kind": "x"}})
        for rel in ("MANIFEST.json", "shards/shard_00000.bin"):
            with open(tmp_path / "sink" / rel, "rb") as a, \
                    open(tmp_path / "direct" / rel, "rb") as b:
                assert a.read() == b.read(), rel


class TestInstrumentation:
    def test_built_engine_emits_fenced_span(self, tracer):
        g = default_geometry(16, n_proj=8)
        proj = jnp.asarray(forward_project(g))
        clear_engine_cache()
        fdk = plan_from_spec(g, "auto").build()
        jax.block_until_ready(fdk(proj))
        spans = tracer.spans("engine.reconstruct")
        assert len(spans) == 1
        ev = spans[0]
        assert "dispatch_us" in ev["args"]
        assert ev["args"]["schedule"] in ("fused", "pipelined", "chunked")
        assert ev["args"]["grid"] == "1x1"

    def test_service_drain_emits_spans_and_latency(self, tracer):
        from repro.service import ReconstructionService
        g = default_geometry(16, n_proj=8)
        proj = jnp.asarray(forward_project(g))
        svc = ReconstructionService(max_batch=2)
        try:
            for _ in range(2):
                svc.submit(projections=proj, geometry=g)
            svc.drain()
            st = svc.stats()
        finally:
            svc.close()
        assert st["served"] == 2 and st["buckets"] == 1
        assert st["latency"]["queue_wait"]["count"] == 2
        assert st["latency"]["time_to_volume"]["count"] == 2
        assert st["latency"]["bucket_assembly"]["count"] == 1
        assert st["latency"]["time_to_volume"]["min"] > 0
        names = {e["name"] for e in tracer.spans("service.")}
        assert {"service.drain", "service.bucket",
                "service.bucket.assemble"} <= names
        assert svc.metrics.value("service.scans.served") == 2

    def test_measure_proposal_traces_through_timed_span(self, tracer):
        from repro.planner import auto_plan
        g = default_geometry(16, n_proj=8)
        clear_engine_cache()
        auto_plan(g, measure=True, top_k=1)
        # measured refinement runs inside planner.measure spans (timed=True
        # records them when the tracer is enabled); cache hits skip them,
        # so only assert when any measurement actually ran.
        spans = tracer.spans("planner.measure")
        for ev in spans:
            assert ev["dur"] > 0 and ev["args"]["iters"] >= 1
