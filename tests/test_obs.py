"""Observability subsystem: spans/export/validation, the metrics registry,
the shared stats-dataclass plumbing, the jit-retrace watchdog (including the
stale-jit-cache repro it exists to catch), and the async queue_wait_fraction
zero-dispatch guard."""
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import nsga2
from repro.launch import mesh as meshlib
from repro.launch.serve import Request, Server
from repro.models import registry as R
from repro.obs import config as obs_config, metrics, trace, watchdog
from repro.obs.metrics import stats_dataclass


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test starts with empty trace/metrics state and obs OFF."""
    prior = obs_config.enabled()
    obs_config.set_enabled(False)
    trace.reset()
    metrics.reset()
    yield
    obs_config.set_enabled(prior)
    trace.reset()
    metrics.reset()


# ---------------------------------------------------------------------------
# trace: no-op mode, nested spans, Chrome schema
# ---------------------------------------------------------------------------


def test_span_disabled_is_shared_noop_and_records_nothing():
    s1 = trace.span("x", a=1)
    s2 = trace.span("y")
    assert s1 is s2  # the shared singleton: no allocation when off
    with s1:
        pass
    trace.instant("z")
    trace.async_begin("req", 1)
    trace.async_end("req", 1)
    metrics.counter_inc("c")
    assert trace.events() == []
    assert metrics.snapshot()["counters"] == {}


class _AnnotationRecorder:
    """Stands in for jax.profiler.TraceAnnotation; logs enter/exit."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name = name
        self.kwargs = kwargs

    def __enter__(self):
        self.log.append(("enter", self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kwargs))
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    _AnnotationRecorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _AnnotationRecorder)
    return _AnnotationRecorder.log


def test_span_enters_profiler_annotation_with_bare_name(annotations):
    with obs.enabled_scope(True):
        a = time.perf_counter()
        with trace.span("serve.round", rows=3, chunk=8):
            with trace.span("serve.sync"):
                pass
        b = time.perf_counter()
    assert annotations == [("enter", "serve.round", {}),
                           ("enter", "serve.sync", {}),
                           ("exit", "serve.sync", {}),
                           ("exit", "serve.round", {})]
    evs = {e["name"]: e for e in trace.events() if e["ph"] == "X"}
    assert evs["serve.round"]["args"] == {"rows": 3, "chunk": 8}
    start = trace.origin() + evs["serve.round"]["ts"] * 1e-6
    assert a <= start <= b


def test_span_disabled_enters_no_annotation(annotations):
    assert trace.span("serve.round", rows=3) is trace.span("serve.sync")
    with trace.span("serve.round"):
        pass
    assert annotations == []
    assert trace.events() == []


def test_span_imports_jax_only_when_on():
    """Off, a span imports nothing; the first span recorded on imports
    jax.profiler for its annotation (a fresh interpreter)."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from repro.obs import config, trace\n"
            "with trace.span('x'):\n"
            "    pass\n"
            "off = 'jax' in sys.modules\n"
            "config.set_enabled(True)\n"
            "with trace.span('x'):\n"
            "    pass\n"
            "print(off, 'jax.profiler' in sys.modules)\n")
    src = str(pathlib.Path(trace.__file__).resolve().parents[2])
    env = dict(os.environ, PYTHONPATH=src, REPRO_OBS="0")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout.split()
    assert out == ["False", "True"]


def test_nested_spans_export_and_validate(tmp_path):
    with obs.enabled_scope(True):
        with trace.span("outer", depth=0):
            with trace.span("inner", depth=1):
                trace.instant("mark", slot=np.int64(3))
        trace.async_begin("req", 7, tier="exact")
        trace.async_instant("req", 7, "admit", slot=0)
        trace.async_end("req", 7, tokens=4)
        path = trace.export_trace(tmp_path / "trace_test.json")
    doc = json.loads(path.read_text())
    assert trace.validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    # inner closes before outer; both carry durations and args.
    assert [e["name"] for e in spans] == ["inner", "outer"]
    assert all(e["dur"] >= 0 for e in spans)
    assert spans[1]["dur"] >= spans[0]["dur"]
    assert {e["ph"] for e in evs} >= {"X", "M", "i", "b", "n", "e"}
    asyncs = [e for e in evs if e["ph"] in "bne"]
    assert all(e["id"] == "7" and e["cat"] == "req" for e in asyncs)
    # numpy scalars in args must serialize as plain JSON numbers
    mark = next(e for e in evs if e["name"] == "mark")
    assert mark["args"]["slot"] == 3


def test_validator_flags_malformed_events():
    bad = {"traceEvents": [
        {"ph": "X", "name": "no-dur", "ts": 0.0, "pid": 1, "tid": 1},
        {"ph": "??", "name": "bad-ph", "ts": 0.0, "pid": 1, "tid": 1},
        {"ph": "b", "name": "no-id", "ts": 0.0, "pid": 1, "tid": 1},
    ]}
    problems = trace.validate_chrome_trace(bad)
    assert len(problems) == 3
    assert trace.validate_chrome_trace({"nope": []})
    assert trace.validate_chrome_trace({"traceEvents": []}) == []


def test_trace_cli_validates(tmp_path):
    with obs.enabled_scope(True):
        with trace.span("s"):
            pass
        good = trace.export_trace(tmp_path / "good.json")
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert trace.main(["--validate", str(good)]) == 0
    assert trace.main(["--validate", str(good), str(bad)]) == 1


# ---------------------------------------------------------------------------
# metrics: labeled series, snapshot schema
# ---------------------------------------------------------------------------


def test_metrics_labeled_series_and_snapshot():
    with obs.enabled_scope(True):
        metrics.counter_inc("engine.dispatch", op="matmul", backend="exact")
        metrics.counter_inc("engine.dispatch", op="matmul", backend="exact")
        metrics.counter_inc("engine.dispatch", 3, backend="exact", op="conv2d")
        metrics.gauge_set("frac", 0.25, kind="wait")
        for v in (1.0, 2.0, 3.0, 4.0):
            metrics.observe("lat", v, op="x")
    snap = metrics.snapshot()
    # label order in the call does not matter: keys sort labels
    assert snap["counters"]["engine.dispatch{backend=exact,op=matmul}"] == 2
    assert snap["counters"]["engine.dispatch{backend=exact,op=conv2d}"] == 3
    assert snap["gauges"]["frac{kind=wait}"] == 0.25
    h = snap["histograms"]["lat{op=x}"]
    assert h["count"] == 4 and h["sum"] == 10.0
    assert h["min"] == 1.0 and h["max"] == 4.0 and h["p50"] == 2.5
    # the snapshot is JSON-serializable as-is (the gate reads it as JSON)
    json.dumps(snap)


def test_metrics_export_and_reset(tmp_path):
    with obs.enabled_scope(True):
        metrics.counter_inc("a")
    p = metrics.export_metrics(tmp_path / "m.json")
    doc = json.loads(p.read_text())
    assert set(doc) == {"counters", "gauges", "histograms"}
    assert doc["counters"]["a"] == 1
    metrics.reset()
    assert metrics.snapshot()["counters"] == {}


def test_metrics_series_cap_collapses_to_overflow():
    reg = metrics.MetricsRegistry(series_cap=3)
    for i in range(3):
        reg.counter_inc("hot", rid=i)
    with pytest.warns(RuntimeWarning, match="hot"):
        reg.counter_inc("hot", rid=99)
    reg.counter_inc("hot", rid=100)  # warns once, keeps collapsing
    snap = reg.snapshot()["counters"]
    assert snap["hot{__overflow__=true}"] == 2
    assert sum(k.startswith("hot{rid=") for k in snap) == 3
    # existing series keep accumulating past the cap
    reg.counter_inc("hot", rid=0)
    assert reg.get_counter("hot", rid=0) == 2
    # other metric names are unaffected by one name's overflow
    reg.gauge_set("cold", 1.0, k="v")
    assert reg.snapshot()["gauges"]["cold{k=v}"] == 1.0
    reg.reset()
    reg.counter_inc("hot", rid=0)  # cap state resets with the data
    assert reg.snapshot()["counters"] == {"hot{rid=0}": 1}


def test_export_paths_are_pid_tagged_for_multiprocess(tmp_path):
    import os

    with obs.enabled_scope(True):
        metrics.counter_inc("a")
        with trace.span("s"):
            pass
        pm = metrics.export_metrics(tmp_path / "metrics_x.json")
        pt = trace.export_trace(tmp_path / "trace_x.json")
        pe = metrics.export_metrics(tmp_path / "metrics_x.json", tag="")
        pg = metrics.export_metrics(tmp_path / "metrics_x.json", tag="w3")
    pid = os.getpid()
    assert pm.name == f"metrics_x_{pid}.json"
    assert pt.name == f"trace_x_{pid}.json"
    assert pe.name == "metrics_x.json"  # tag="" keeps the exact name
    assert pg.name == "metrics_x_w3.json"
    # the CI validator's globs still match the tagged names
    assert pm in tmp_path.glob("metrics_*.json")
    assert pt in tmp_path.glob("trace_*.json")


def test_validate_metrics_snapshot_schema():
    with obs.enabled_scope(True):
        metrics.counter_inc("c", op="a")
        metrics.gauge_set("g", 1.5)
        metrics.observe("h", 2.0, tier="x")
    assert metrics.validate_metrics_snapshot(metrics.snapshot()) == []
    assert metrics.validate_metrics_snapshot([]) != []
    assert metrics.validate_metrics_snapshot({}) != []
    bad = {"counters": {"c{op=a}": 1, "c{tier=b}": "NaN?"},
           "gauges": {"g{": 0}, "histograms": {"h": {"count": 1}}}
    errs = metrics.validate_metrics_snapshot(bad)
    assert any("non-numeric" in e for e in errs)
    assert any("malformed" in e for e in errs)
    assert any("unstable label set" in e for e in errs)
    assert any("expected keys" in e for e in errs)
    # __overflow__ series are exempt from the stable-label-set rule
    ok = {"counters": {"c{op=a}": 1, "c{__overflow__=true}": 2},
          "gauges": {}, "histograms": {}}
    assert metrics.validate_metrics_snapshot(ok) == []


def test_trace_cli_validates_metrics_snapshots(tmp_path):
    with obs.enabled_scope(True):
        metrics.counter_inc("c", op="a")
        good = metrics.export_metrics(tmp_path / "metrics_good.json", tag="")
    bad = tmp_path / "metrics_bad.json"
    bad.write_text(json.dumps({"counters": {"c{op=a}": 1, "c{x=y}": 2},
                               "gauges": {}, "histograms": {}}))
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"neither": 1}))
    assert trace.main(["--validate", str(good)]) == 0
    assert trace.main(["--validate", str(good), str(bad)]) == 1
    assert trace.main(["--validate", str(junk)]) == 1


# ---------------------------------------------------------------------------
# stats_dataclass: the EvalStats/IslandStats dict contract (satellite:
# deduplicated as_dict/merge — shapes must not have changed)
# ---------------------------------------------------------------------------


def test_eval_stats_dict_shape_unchanged():
    s = nsga2.EvalStats(batch_calls=2, genomes_requested=10,
                        genomes_scored=7, cache_hits=3)
    d = s.as_dict()
    assert list(d) == ["batch_calls", "genomes_requested", "genomes_scored",
                       "cache_hits", "cache_hit_rate"]
    assert d["cache_hit_rate"] == pytest.approx(0.3)
    t = nsga2.EvalStats(batch_calls=1, genomes_requested=2, genomes_scored=2)
    s.merge(t)
    assert s.batch_calls == 3 and s.genomes_requested == 12


def test_island_stats_dict_shape_unchanged_and_merge_skips_island():
    s = nsga2.IslandStats(island=1, evals=4, cache_hits=2, eval_seconds=1.5)
    d = s.as_dict()
    assert list(d) == ["island", "evals", "cache_hits", "cache_hit_rate",
                       "eval_seconds", "queue_wait_seconds",
                       "migration_wait_seconds", "migrants_in",
                       "migrants_out"]
    assert d["cache_hit_rate"] == pytest.approx(0.5)
    other = nsga2.IslandStats(island=2, evals=6, eval_seconds=0.5)
    s.merge(other)
    assert s.island == 1  # identity field: never summed
    assert s.evals == 10 and s.eval_seconds == 2.0


def test_stats_dataclass_rejects_unknown_keys():
    import dataclasses

    with pytest.raises(TypeError, match="neither a field nor a property"):
        @stats_dataclass(dict_keys=("a", "nope"))
        @dataclasses.dataclass
        class Bad:
            a: int = 0


def test_eval_stats_zero_division_guard():
    assert nsga2.EvalStats().as_dict()["cache_hit_rate"] == 0.0
    assert nsga2.IslandStats(island=0).as_dict()["cache_hit_rate"] == 0.0


# ---------------------------------------------------------------------------
# satellite: queue_wait_fraction with zero dispatched-busy time
# ---------------------------------------------------------------------------


def test_async_queue_wait_fraction_zero_busy_is_zero(monkeypatch):
    """A frozen clock makes every (t_done - t_ready) zero — the pre-guard
    spelling divided 0/0 into NaN; the result must be exactly 0.0."""
    monkeypatch.setattr(nsga2.time, "monotonic", lambda: 5.0)

    def evaluate(genome, island):
        return np.asarray(genome, float)[:2], None

    res = nsga2.optimize_async(
        evaluate_fn=evaluate, genome_len=4,
        init_genome_fn=lambda rng: rng.integers(0, 4, size=4).astype(np.int32),
        crossover_fn=lambda a, b, rng: (a, b),
        mutate_fn=lambda g, rng: g,
        pop_size=2, steps=2, n_workers=1, seed=0)
    assert res["queue_wait_fraction"] == 0.0
    assert np.isfinite(res["queue_wait_fraction"])


# ---------------------------------------------------------------------------
# watchdog: trace counting, budgets, and the stale-jit-cache repro
# ---------------------------------------------------------------------------


def test_watch_jit_counts_traces_not_calls():
    calls = []
    f = watchdog.watch_jit(lambda x: x * 2, name="wd.double")
    for _ in range(5):
        calls.append(int(f(jnp.int32(3))))
    assert calls == [6] * 5
    assert watchdog.retrace_count(f) == 1  # one shape -> one trace
    f(jnp.zeros(4))  # new shape -> retrace
    assert watchdog.retrace_count(f) == 2
    assert watchdog.counts()["wd.double"] >= 2
    watchdog.assert_max_retraces(f, 2)
    with pytest.raises(AssertionError, match="re-traced"):
        watchdog.assert_retraces(f, 1)


def test_watchdog_catches_stale_jit_cache():
    """The PR-4 bug class. A jitted consumer closing over a registry table
    bakes it in as a trace-time constant: after the table changes (same
    shape), the cached executable keeps serving the OLD values, and the
    retrace count fails to grow — exactly what assert_retraces flags."""
    table = np.array([1.0, 2.0, 3.0], np.float32)

    def stale(x):
        return x + jnp.asarray(table)  # closure: baked at trace time

    f_stale = watchdog.watch_jit(stale, name="wd.stale")
    one = jnp.ones(3, jnp.float32)
    first = np.asarray(f_stale(one))
    table[:] = [10.0, 20.0, 30.0]  # registry update, shape unchanged
    second = np.asarray(f_stale(one))
    np.testing.assert_array_equal(first, second)  # served stale values!
    with pytest.raises(AssertionError, match="stale"):
        watchdog.assert_retraces(f_stale, 2)  # the watchdog catches it

    # The fix: the table travels as a traced operand.
    f_fixed = watchdog.watch_jit(lambda x, t: x + t, name="wd.fixed")
    fresh = np.asarray(f_fixed(one, jnp.asarray(table)))
    np.testing.assert_array_equal(fresh, [11.0, 21.0, 31.0])


def test_watchdog_flags_per_call_retracing():
    """The opposite failure: an unstable trace-time constant (here a fresh
    shape per call) recompiles every call and blows the budget."""
    f = watchdog.watch_jit(jnp.sum, name="wd.churn")
    for n in (1, 2, 3):
        f(jnp.zeros(n))
    with pytest.raises(AssertionError, match="budget"):
        watchdog.assert_max_retraces(f, 2)


def test_watchdog_name_resolution_and_reset():
    a = watchdog.watch_jit(lambda x: x, name="wd.shared")
    b = watchdog.watch_jit(lambda x: x + 1, name="wd.shared")
    a(jnp.int32(1))
    b(jnp.int32(1))
    assert watchdog.retrace_count("wd.shared") == 2  # names sum records
    watchdog.reset()
    with pytest.raises(KeyError):
        watchdog.retrace_count("wd.shared")
    assert watchdog.retrace_count(a) == 1  # live handle keeps its record


# ---------------------------------------------------------------------------
# retrace budgets on the real hot paths
# ---------------------------------------------------------------------------


def test_serve_step_traces_exactly_twice():
    """The jitted serve step must compile exactly twice per Server: once at
    T=prefill_chunk, once at T=1 (decode). A third trace means shape churn;
    staying at one would mean decode reused the prefill executable."""
    cfg = R.get("xlstm-125m").smoke
    server = Server(cfg, meshlib.make_host_mesh(), slots=2, ctx=16, seed=0,
                    prefill_chunk=4)
    rng = np.random.default_rng(0)
    for i in range(3):
        server.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, 5).astype(np.int32),
            max_new=3))
    finished = server.run()
    assert sum(r.status == "done" for r in finished) == 3
    watchdog.assert_retraces(server._jit_step, 2)
    watchdog.assert_retraces(server._jit_reset, 1)


def test_batched_evaluator_traces_once_per_block_count():
    from repro.experiments import paper_cnn
    from repro.models import cnn

    params = cnn.init_params(jax.random.PRNGKey(0))
    ev = paper_cnn.make_batched_evaluator(params, 16)
    rng = np.random.default_rng(0)
    before = watchdog.counts().get("paper_cnn.batched_evaluator", 0)
    g = rng.integers(0, 9, size=(4, paper_cnn.N_SLOTS)).astype(np.int32)
    key = jax.random.PRNGKey(1)
    ev(g, key)
    ev(g[:3], key)  # pops 4 and 3 pad to the same block count: cached
    assert watchdog.counts()["paper_cnn.batched_evaluator"] - before == 1
    ev(np.concatenate([g, g]), key)  # pop 8: a new block count, one trace
    assert watchdog.counts()["paper_cnn.batched_evaluator"] - before == 2


# ---------------------------------------------------------------------------
# instrumentation publishes to the registry (spot checks)
# ---------------------------------------------------------------------------


def test_engine_dispatch_counter_labels():
    from repro.core import engine

    with obs.enabled_scope(True):
        eng = engine.AMEngine("exact")
        eng.matmul(jnp.ones((4, 5)), jnp.ones((5, 3)))
    assert metrics.REGISTRY.get_counter(
        "engine.dispatch", op="matmul", backend="exact") == 1


def test_serve_tokens_counter_by_tier():
    cfg = R.get("xlstm-125m").smoke
    server = Server(cfg, meshlib.make_host_mesh(), slots=2, ctx=16, seed=0)
    rng = np.random.default_rng(0)
    with obs.enabled_scope(True):
        server.submit(Request(
            rid=0, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
            max_new=3))
        server.run()
    assert metrics.REGISTRY.get_counter("serve.tokens", tier="exact") == 3
