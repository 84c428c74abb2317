"""benchmarks/serve_phases.py: the readings of the server's spans
(round_host_ms, queue_wait_ms, prefill_wait_ms) on synthetic events, and on
a small run of the serving cell's whole path on the CPU, where each
request's waits on its serve.request track add up to the harness's own
time to first token."""
import importlib.util
import pathlib
import sys
import types

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "serve_phases", ROOT / "benchmarks" / "serve_phases.py")
sp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sp)
harness = sp.harness

# The serving cell's pattern at small widths (as benchmarks/chip/tests).
XLSTM_SMALL = {"n_layers": 4, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
               "d_head": 32, "vocab": 256, "slots": 4}
SEED = 3_000_000_019


def _x(name, ts_s, dur_s):
    return {"name": name, "ph": "X", "ts": ts_s * 1e6, "dur": dur_s * 1e6}


def _track(rid, ph, ts_s, phase=None):
    ev = {"name": "serve.request", "cat": "serve.request", "ph": ph,
          "id": str(rid), "ts": ts_s * 1e6}
    if phase is not None:
        ev["args"] = {"phase": phase}
    return ev


def _rec(window, requests=(), rounds=()):
    return types.SimpleNamespace(
        window=window, trace=None,
        events={"requests": list(requests), "rounds": list(rounds)})


def test_round_host_ms_subtracts_syncs_inside_the_window():
    origin = 100.0
    events = [
        _x("serve.round", 1.0, 0.050), _x("serve.sync", 1.010, 0.040),
        _x("serve.round", 2.0, 0.300), _x("serve.sync", 2.001, 0.290),
        _x("serve.sync", 2.500, 0.001),  # outside both rounds: not counted
        _x("serve.round", 9.0, 0.010),  # after the window
    ]
    spans = sp.spans_on_clock(events, origin)
    got = sp.round_host_ms(spans, 100.5, 103.0)
    assert got == pytest.approx((10.0 + 10.0) / 2)
    assert sp.round_host_ms(spans, 200.0, 300.0) is None
    assert sp.round_host_ms([], 0.0, 1e9) is None


def test_request_waits_filter_by_due_and_count_open_waits_to_the_close():
    origin = 10.0
    events = [
        _track(0, "b", 0.0), _track(0, "n", 0.001, "admit"),
        _track(0, "n", 1.2, "prefill_done"), _track(0, "e", 2.0),
        _track(1, "b", 3.0), _track(1, "n", 3.5, "admit"),
        _track(2, "b", 4.0),
        _track(3, "b", 20.0),
    ]
    requests = [{"due": 9.9}, {"due": 12.9}, {"due": 13.9}, {"due": 40.0}]
    marks = sp.request_marks(events, origin)
    assert marks[0] == {"submit": 10.0, "admit": 10.001,
                        "prefill_done": 11.2, "end": 12.0}
    waits = sp.request_waits(marks, requests, 9.0, 14.5)
    assert [x for w in waits for x in w] == pytest.approx(
        [0.001, 1.199, 0.5, 1.0, 0.5, 0.0])
    assert sp.request_waits({}, requests, 9.0, 14.5) is None
    assert sp.request_waits(marks, requests, 50.0, 60.0) is None


def test_phases_read_nothing_without_spans():
    rec = _rec((0.0, 10.0), [{"due": 1.0}], [(1.0, 1.1, "decode", 1, 1)])
    out = sp.phases(rec, [], 0.0)
    for name in ("round_host_ms", "queue_wait_ms", "prefill_wait_ms",
                 "round_ms", "spans_per_round", "phase_ms"):
        assert out[name] is None
    assert out["harness_round_ms"] == pytest.approx(100.0)
    assert "idle_gaps" not in out


@pytest.fixture(scope="module")
def small_run():
    sys.path.insert(0, str(harness.SRC))
    cell = harness.load_cell("xlstm125m-chat.tiers")
    cell.config.update(XLSTM_SMALL)
    cell.traffic.update({"rate_per_s": 4.0, "check_requests": 4})
    cell.limits = {"logit_gap_mean": 0.01}
    rec, events, origin = sp.run_traced(
        cell, jax.devices()[:1], seed=SEED, seconds=3.0, trace=False,
        t_start=0.0)
    return rec, events, origin


def test_waits_add_up_to_the_harness_ttft(small_run):
    """due -> submit (the harness's lateness) + queue wait + prefill wait +
    (end of the emitting round - first token mark) is the harness's time
    to first token, within 1 ms, for every request due in the window."""
    rec, events, origin = small_run
    lo, hi = rec.window
    marks = sp.request_marks(events, origin)
    reqs = rec.events["requests"]
    waits = sp.request_waits(marks, reqs, lo, hi)
    due = [r for r in reqs if lo <= r["due"] <= hi]
    assert waits is not None and len(waits) == len(due) >= 8
    checked = 0
    for rid, r in enumerate(reqs):
        if not r["tokens"]:
            continue
        q, p = sp.request_waits({0: marks[rid]}, [r], lo, hi)[0]
        tail = r["tokens"][0] - marks[rid]["prefill_done"]
        assert 0.0 <= tail
        ttft = r["tokens"][0] - r["due"]
        assert rec.events["late"][rid] + q + p + tail == pytest.approx(
            ttft, abs=1e-3)
        checked += 1
    assert checked >= 8


def test_round_spans_inside_the_harness_rounds(small_run):
    rec, events, origin = small_run
    out = sp.phases(rec, events, origin)
    lo, hi = rec.window
    spans = [(a, b) for n, a, b in sp.spans_on_clock(events, origin)
             if n == "serve.round"]
    rounds = [(a, b) for a, b, *_ in rec.events["rounds"]]
    assert len(spans) == len(rounds)
    for (a, b), (ra, rb) in zip(spans, rounds):
        assert ra <= a <= b <= rb
    assert 0 < out["round_host_ms"] < out["round_ms"] <= out[
        "harness_round_ms"]
    # round, admit, two packs, dispatch, sync, emit (batched mode).
    assert out["spans_per_round"] == pytest.approx(7.0)
    phase = out["phase_ms"]
    assert phase["serve.round"] == pytest.approx(out["round_ms"])
    assert phase["serve.round"] - phase["serve.sync"] == pytest.approx(
        out["round_host_ms"])
    assert all(v >= 0 for v in phase.values())
    assert out["queue_wait_ms"] >= 0 and out["prefill_wait_ms"] > 0
