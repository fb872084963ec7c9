"""Observability tests (ISSUE 8): span nesting and thread-safety,
trace-id propagation through the serve layer (cold / warm / coalesced /
degraded requests), snapshot adapter parity with the legacy per-module
accessors, ``stats["timings"]`` schema compatibility, and the JSONL /
Chrome-trace / Prometheus export round-trips.

Tests that assert exact degradation behaviour run inside
``faults.isolated()`` so the CI chaos job's ambient ``REPRO_FAULTS``
schedule cannot perturb them.
"""

import dataclasses
import functools
import gc
import glob
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import faults, obs
from repro.core import make_machine, stencil_graph
from repro.hier import HierarchySpec
from repro.mapping import MappingPipeline, PipelineConfig
from repro.core.machine import block_allocation
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.serve import MappingService, get_scenario

SCALE = 256

BASE = "minighost-xk7_sparse-flat-wh"


def _req(name=BASE, seed=0, scale=SCALE, **overrides):
    sc = get_scenario(name, scale=scale, seed=seed)
    req = sc.request()
    if overrides:
        cfg = dataclasses.replace(sc.config(), **overrides)
        req = dataclasses.replace(req, config=cfg, _signature=None)
    return req


def _has_jax():
    from repro.core.orderings import resolve_partition_backend
    return resolve_partition_backend("jax") == "jax"


# ---------------------------------------------------------------------------
# Spans: nesting, identity, errors
# ---------------------------------------------------------------------------

def test_span_nesting_and_trace_identity():
    t = Tracer()
    with t.span("outer", k=1) as outer:
        assert outer.parent_id is None
        assert len(outer.trace_id) == 16
        assert t.current() is outer
        with t.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
            assert t.current() is inner
        assert t.current() is outer
    assert t.current() is None
    done = t.finished()
    assert [s.name for s in done] == ["inner", "outer"]  # finish order
    assert all(s.t1 is not None and s.duration_s >= 0 for s in done)


def test_sibling_roots_mint_distinct_traces():
    t = Tracer()
    with t.span("a"):
        pass
    with t.span("b"):
        pass
    a, b = t.finished()
    assert a.trace_id != b.trace_id


def test_span_records_escaping_exception():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("no")
    (sp,) = t.finished()
    assert sp.attrs["error"] == "ValueError"
    assert sp.t1 is not None


def test_annotate_and_duration_while_open():
    t = Tracer()
    with t.span("x") as sp:
        sp.annotate(points=4).annotate(backend="numpy")
        assert sp.duration_s >= 0  # measurable while still open
    assert sp.attrs == {"points": 4, "backend": "numpy"}


def test_span_tree_and_format():
    t = Tracer()
    with t.span("root"):
        with t.span("kid1"):
            pass
        with t.span("kid2"):
            pass
    tree = obs.span_tree(t.finished())
    assert len(tree) == 1
    root, kids = tree[0]
    assert root.name == "root"
    assert [k[0].name for k in kids] == ["kid1", "kid2"]
    text = obs.format_tree(t.finished())
    lines = text.splitlines()
    assert lines[0].startswith("root") and "  kid1" in lines[1]


def test_span_tree_orphans_surface_as_roots():
    from repro.obs.trace import Span
    parent = Span("p", "t1")
    child = Span("k", "t1", parent.span_id)
    # parent fell off the ring: the child surfaces instead of vanishing
    roots = [n[0].name for n in obs.span_tree([child])]
    assert roots == ["k"]


# ---------------------------------------------------------------------------
# Spans: threads
# ---------------------------------------------------------------------------

def test_threads_do_not_share_span_context():
    t = Tracer()
    seen = []

    def work(i):
        with t.span(f"thread{i}") as sp:
            assert sp.parent_id is None  # no inherited parent
            seen.append(sp.trace_id)

    with t.span("main"):
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert len(set(seen)) == 4  # each thread rooted its own trace


def test_attach_joins_a_cross_thread_trace():
    t = Tracer()
    with t.span("request") as root:
        parent = t.current()

        def worker():
            with t.attach(parent):
                with t.span("rung"):
                    pass

        th = threading.Thread(target=worker)
        th.start()
        th.join()
    rung = next(s for s in t.finished() if s.name == "rung")
    assert rung.trace_id == root.trace_id
    assert rung.parent_id == root.span_id


def test_attach_none_is_a_passthrough():
    t = Tracer()
    with t.attach(None) as got:
        assert got is None
        assert t.current() is None


def test_tracer_is_thread_safe_under_contention():
    t = Tracer(max_finished=10_000)
    n_threads, per_thread = 8, 50

    def work():
        for _ in range(per_thread):
            with t.span("a"):
                with t.span("b"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    done = t.finished()
    assert len(done) == n_threads * per_thread * 2
    ids = [s.span_id for s in done]
    assert len(set(ids)) == len(ids)


def test_finished_ring_is_bounded():
    t = Tracer(max_finished=8)
    for i in range(20):
        with t.span(f"s{i}"):
            pass
    done = t.finished()
    assert len(done) == 8
    assert done[-1].name == "s19"  # newest kept, oldest dropped


def test_sinks_receive_spans_and_bad_sinks_are_dropped():
    t = Tracer()
    got = []

    def bad(span):
        raise RuntimeError("sink died")

    t.add_sink(got.append)
    t.add_sink(bad)
    with t.span("one"):
        pass
    with t.span("two"):
        pass
    assert [s.name for s in got] == ["one", "two"]
    assert bad not in t._sinks  # dropped after the first raise


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_primitive_series_and_snapshot():
    r = MetricsRegistry()
    r.counter("reqs")
    r.counter("reqs", 2)
    r.gauge("depth", 7)
    r.observe("lat", 0.25)
    r.observe("lat", 0.75)
    snap = r.snapshot()
    assert snap["counters"]["reqs"] == 3
    assert snap["gauges"]["depth"] == 7.0
    h = snap["histograms"]["lat"]
    assert h["count"] == 2 and h["sum"] == 1.0
    assert h["min"] == 0.25 and h["max"] == 0.75 and h["mean"] == 0.5


def test_series_cap_drops_not_grows():
    r = MetricsRegistry(max_series=2)
    r.counter("a")
    r.gauge("b", 1)
    r.counter("c")       # over the cap: dropped
    r.observe("d", 1.0)  # dropped too
    r.counter("a")       # existing series still bump
    snap = r.snapshot()
    assert set(snap["counters"]) == {"a"}
    assert snap["counters"]["a"] == 2
    assert snap["meta"]["dropped_series"] == 2


def test_registered_objects_are_weak_and_bounded():
    class Obj:
        def __init__(self, i):
            self.i = i

        def stats(self):
            return {"i": self.i}

    r = MetricsRegistry(max_objects=2)
    keep = [Obj(0), Obj(1)]
    for o in keep:
        r.register_object("objs", o)
    gone = Obj(99)
    r.register_object("objs", gone)  # pushes Obj(0) past the cap
    assert len(r.snapshot()["objs"]) == 2
    del gone
    gc.collect()
    vals = [v["i"] for v in r.snapshot()["objs"].values()]
    assert vals == [keep[1].i]  # dead ref pruned, live one kept


def test_provider_sections_and_errors_are_contained():
    r = MetricsRegistry()
    r.register_provider("good", lambda: {"x": 1})
    r.register_provider("bad", lambda: 1 / 0)
    snap = r.snapshot()
    assert snap["good"] == {"x": 1}
    assert snap["bad"] == {"error": "ZeroDivisionError"}


def test_instrument_compile_cache_contract_and_autoregistration():
    @functools.lru_cache(maxsize=None)
    def compiled(key):
        return object()

    stats_fn, reset_fn = obs.instrument_compile_cache(
        "obs_test_cache", compiled)
    assert stats_fn() == {"hits": 0, "misses": 0, "entries": 0}
    compiled("a")
    compiled("a")
    compiled("b")
    assert stats_fn() == {"hits": 1, "misses": 2, "entries": 2}
    # the same counters appear in the process snapshot, unasked
    assert obs.snapshot()["caches"]["obs_test_cache"] == stats_fn()
    reset_fn()
    assert stats_fn() == {"hits": 0, "misses": 0, "entries": 0}


def test_snapshot_parity_with_legacy_cache_accessors():
    if not _has_jax():
        pytest.skip("compile caches need jax")
    from repro.core import metrics_jax, partition_jax
    from repro.kernels.mapscore import ops as mapscore_ops
    from repro.mapping import fused
    caches = obs.snapshot()["caches"]
    legacy = {"scorer_jax": metrics_jax.scorer_cache_stats,
              "scorer_pallas": mapscore_ops.scorer_cache_stats,
              "partition_jax": partition_jax.partition_cache_stats,
              "fused": fused.fused_cache_stats}
    for name, accessor in legacy.items():
        assert caches[name] == accessor(), name


def test_snapshot_covers_live_services_and_lrus():
    with faults.isolated():
        svc = MappingService(capacity=4)
        svc.map(_req())
        svc.map(_req())
    snap = obs.snapshot()
    assert any(sec == svc.stats()
               for sec in snap["services"].values())
    assert any(sec == svc.results.stats()
               for sec in snap["lrus"].values())
    d = snap["derived"]
    assert d["availability"] is None or 0.0 <= d["availability"] <= 1.0
    assert "result_cache_hit_rate" in d and "compiles" in d


def test_span_rollup():
    t = Tracer()
    for _ in range(3):
        with t.span("stage"):
            pass
    with t.span("other"):
        pass
    roll = obs.span_rollup(t.finished())
    assert roll["stage"]["count"] == 3 and roll["other"]["count"] == 1
    assert roll["stage"]["total_s"] >= roll["stage"]["max_s"] >= 0


# ---------------------------------------------------------------------------
# Pipeline integration: timings schema + trace ids
# ---------------------------------------------------------------------------

def _flat_case():
    m = make_machine((8, 8), wrap=True)
    return m, block_allocation(m), stencil_graph((8, 8))


def test_flat_timings_schema_is_span_derived():
    m, alloc, g = _flat_case()
    res = MappingPipeline(PipelineConfig(sfc="FZ", rotations=4)).map(
        g, alloc)
    t = res.stats["timings"]
    assert {"partition_s", "score_s", "total_s"} <= set(t)
    assert "fused_s" not in t
    assert t["total_s"] >= t["partition_s"] + t["score_s"] - 1e-9
    root = next(s for s in obs.finished(res.stats["trace_id"])
                if s.name == "pipeline.map")
    assert root.parent_id is None
    assert root.attrs["hierarchy"] == "flat"
    assert t["total_s"] == root.duration_s


def test_hier_timings_schema_is_span_derived():
    m, alloc, g = _flat_case()
    res = MappingPipeline(PipelineConfig(
        sfc="FZ", rotations=4,
        hierarchy=HierarchySpec.node())).map(g, alloc)
    t = res.stats["timings"]
    assert {"coarsen_s", "partition_s", "score_s", "refine_s",
            "total_s"} <= set(t)
    spans = obs.finished(res.stats["trace_id"])
    names = [s.name for s in spans]
    for stage in ("pipeline.coarsen", "pipeline.partition",
                  "pipeline.score", "pipeline.refine", "pipeline.map"):
        assert stage in names
    root = next(s for s in spans if s.name == "pipeline.map")
    assert all(s.trace_id == root.trace_id for s in spans)


# ---------------------------------------------------------------------------
# Serve integration: one trace per request
# ---------------------------------------------------------------------------

def test_cold_and_warm_requests_trace_distinctly():
    with faults.isolated():
        svc = MappingService()
        cold = svc.map(_req())
        warm = svc.map(_req())
    assert cold.status == "cold" and warm.status == "warm"
    assert cold.trace_id and warm.trace_id
    assert cold.trace_id != warm.trace_id
    # the shared result names the trace that COMPUTED it
    assert cold.result.stats["trace_id"] == cold.trace_id
    assert warm.result.stats["trace_id"] == cold.trace_id
    names = {s.name for s in obs.finished(cold.trace_id)}
    assert {"serve.request", "serve.rung", "pipeline.map",
            "pipeline.partition", "pipeline.score"} <= names
    warm_names = {s.name for s in obs.finished(warm.trace_id)}
    assert "serve.request" in warm_names
    assert "pipeline.map" not in warm_names  # warm = lookup only
    root = next(s for s in obs.finished(cold.trace_id)
                if s.name == "serve.request")
    assert root.attrs["status"] == "cold"


def test_coalesced_batch_shares_the_primary_trace():
    with faults.isolated():
        svc = MappingService()
        resps = svc.map_many([_req(), _req(), _req(seed=1)])
    assert [r.status for r in resps] == ["cold", "coalesced", "cold"]
    assert resps[1].trace_id == resps[0].trace_id
    assert resps[2].trace_id != resps[0].trace_id


def test_degraded_request_yields_one_trace_covering_all_rungs():
    if not _has_jax():
        pytest.skip("degradation off the jax rung needs jax")
    with faults.isolated():
        svc = MappingService()
        req = _req(score_backend="jax", rotations=4)
        with faults.injected("score.jax", "error", count=1):
            resp = svc.map(req)
        assert resp.result.stats["degraded"] == "score_numpy"
        spans = obs.finished(resp.trace_id)
        rungs = [s for s in spans if s.name == "serve.rung"]
        assert [s.attrs["rung"] for s in rungs] == ["full", "score_numpy"]
        assert rungs[0].attrs["error"] == "InjectedFault"
        assert rungs[1].attrs["degraded"] == "score_numpy"
        # the failed backend call site is in the SAME trace
        failed = next(s for s in spans if s.name == "score.jax")
        assert failed.attrs["error"] == "InjectedFault"
        assert {s.trace_id for s in spans} == {resp.trace_id}


def test_deadline_worker_spans_join_the_request_trace():
    with faults.isolated():
        # a finite deadline routes non-terminal rungs through a daemon
        # worker thread; score_backend="jax" gives the ladder a second
        # rung so "full" is non-terminal
        svc = MappingService(deadline_s=30.0)
        req = _req(score_backend="jax", rotations=4)
        resp = svc.map(req)
    spans = obs.finished(resp.trace_id)
    assert any(s.name == "pipeline.map" for s in spans)
    threads = {s.thread for s in spans}
    assert len(threads) > 1  # rung ran off-thread yet stayed in-trace


# ---------------------------------------------------------------------------
# The annotation hook: spans in the jax profiler's trace
# ---------------------------------------------------------------------------

@pytest.fixture
def annotation_restored():
    was = obs.trace._ANNOTATION
    yield
    obs.set_annotation(was)


def test_annotation_factory_wraps_every_span(annotation_restored):
    log = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    t = Tracer()
    obs.set_annotation(Note)
    with t.span("outer"):
        with pytest.raises(ValueError):
            with t.span("inner"):
                raise ValueError
    assert log == [("enter", "outer"), ("enter", "inner"),
                   ("exit", "inner"), ("exit", "outer")]
    # a factory may decline (no profiler session), and uninstalls
    obs.set_annotation(lambda name: None)
    with t.span("declined"):
        pass
    obs.set_annotation(None)
    with t.span("plain"):
        pass
    assert len(log) == 4
    assert [s.name for s in t.finished()] == ["inner", "outer",
                                              "declined", "plain"]


def test_fresh_import_of_obs_imports_no_jax():
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(obs.__file__))))
    code = ("import sys, repro.obs; "
            "print(sorted(m for m in sys.modules "
            "if m == 'jax' or m.startswith('jax.')))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": src})
    assert out.stdout.strip() == "[]"


def test_spans_enter_the_jax_profiler_trace(tmp_path):
    """While a profiler session runs, every span of a mapping is also a
    host event of the same name in the profiler's trace, opened in the
    same order, each inside its parent's event."""
    if not _has_jax():
        pytest.skip("the annotation factory is installed with jax")
    import jax
    from jax.profiler import ProfileData
    from repro.core import gemini_xk7, sfc_allocation
    from repro.mapping import MappingPipeline, PipelineConfig

    m = gemini_xk7(dims=(8, 4, 4), cores_per_node=4)
    alloc = sfc_allocation(m, 256, nfragments=2, seed=3)
    g = stencil_graph((4, 8, 8))
    pipe = MappingPipeline(PipelineConfig(
        sfc="FZ", rotations=4, hierarchy=HierarchySpec.node(),
        partition_backend="jax", score_backend="jax"))
    pipe.map(g, alloc)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = pipe.map(g, alloc)
    finally:
        jax.profiler.stop_trace()
    spans = sorted(obs.finished(res.stats["trace_id"]), key=lambda s: s.t0)
    names = {s.name for s in spans}
    assert {"pipeline.contract", "pipeline.expand",
            "fused.execute"} <= names
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    events = sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns)
         for p in ProfileData.from_file(path).planes for ln in p.lines
         for e in ln.events if e.name in names), key=lambda e: e[1])
    assert [e[0] for e in events] == [s.name for s in spans]
    event_of = {s.span_id: e for s, e in zip(spans, events)}
    for s in spans:
        if s.parent_id in event_of:
            parent, child = event_of[s.parent_id], event_of[s.span_id]
            assert parent[1] <= child[1] and child[2] <= parent[2], s.name


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = obs.JsonlSink(path)
    obs.add_sink(sink)
    try:
        with obs.span("outer", k=1):
            with obs.span("inner"):
                pass
    finally:
        obs.remove_sink(sink)
        sink.close()
    rows = obs.read_jsonl(path)
    assert [r["name"] for r in rows] == ["inner", "outer"]
    inner, outer = rows
    assert inner["trace_id"] == outer["trace_id"]
    assert inner["parent_id"] == outer["span_id"]
    assert outer["parent_id"] is None
    assert outer["attrs"] == {"k": 1}
    assert all(r["duration_s"] >= 0 for r in rows)


def test_env_sink_installation(tmp_path, monkeypatch):
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv(obs.export.TRACE_ENV, path)
    sink = obs.install_env_sink()
    try:
        with obs.span("env-armed"):
            pass
    finally:
        obs.remove_sink(sink)
        sink.close()
    assert any(r["name"] == "env-armed" for r in obs.read_jsonl(path))
    monkeypatch.delenv(obs.export.TRACE_ENV)
    assert obs.install_env_sink() is None


def test_chrome_trace_round_trip(tmp_path):
    t = Tracer()
    with t.span("req", backend="numpy"):
        with t.span("stage"):
            pass
    with t.span("req2"):
        pass
    doc = obs.chrome_trace(t.finished())
    events = doc["traceEvents"]
    assert [e["name"] for e in events] == ["stage", "req", "req2"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
    by_name = {e["name"]: e for e in events}
    assert by_name["stage"]["pid"] == by_name["req"]["pid"]
    assert by_name["req2"]["pid"] != by_name["req"]["pid"]
    assert by_name["req"]["args"]["backend"] == "numpy"
    path = str(tmp_path / "chrome.json")
    obs.write_chrome_trace(path, t.finished())
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(doc))


def test_prometheus_text_is_valid_exposition():
    obs.counter("obs_test_requests", 2)
    obs.observe("obs_test_latency_s", 0.5)
    text = obs.prometheus_text()
    assert text.endswith("\n")
    assert "# TYPE repro_obs_test_requests_total counter" in text
    assert "repro_obs_test_requests_total 2.0" in text
    assert "repro_obs_test_latency_s_count 2" in text or \
        "repro_obs_test_latency_s_count 1" in text
    typed = [ln.split()[2] for ln in text.splitlines()
             if ln.startswith("# TYPE")]
    assert len(typed) == len(set(typed))  # one TYPE line per family
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name_labels, value = ln.rsplit(" ", 1)
        float(value)  # every sample parses (NaN included)
        assert name_labels.startswith("repro_")


def test_prometheus_includes_labelled_cache_families():
    if not _has_jax():
        pytest.skip("compile-cache families need jax")
    text = obs.prometheus_text()
    assert 'repro_compile_cache_entries{cache="scorer_jax"}' in text
    assert 'repro_compile_cache_entries{cache="fused"}' in text


def test_jax_profile_is_a_noop_without_env(monkeypatch):
    monkeypatch.delenv(obs.export.JAX_PROFILE_ENV, raising=False)
    before = len(obs.finished())
    with obs.jax_profile("bench") as got:
        assert got is None
    assert len(obs.finished()) == before  # no span, no trace


def test_service_map_and_direct_pipeline_agree():
    # the obs instrumentation must not perturb results: a traced
    # service request equals the bare pipeline output bit for bit
    with faults.isolated():
        sc = get_scenario(BASE, scale=SCALE, seed=3)
        req = sc.request()
        svc = MappingService()
        resp = svc.map(req)
        from repro.mapping import MappingPipeline
        direct = MappingPipeline(req.config).map(req.graph, req.alloc)
    assert np.array_equal(resp.result.task_to_proc, direct.task_to_proc)
