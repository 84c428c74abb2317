"""The server's phase spans and request tracks (repro.obs): one serve.round
per scheduling round holding its admit, then the next step's pack /
dispatch, then the previous step's sync / emit; each request's
serve.request track on the caller's perf_counter clock, the same tokens
with tracing on and off, and the spans on a JAX profiler timeline under
their bare names."""
import collections
import glob
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.launch import mesh as meshlib
from repro.launch.serve import Request, Server
from repro.models import registry as R
from repro.obs import config as obs_config, metrics, trace

PHASES = ("serve.admit", "serve.pack", "serve.dispatch", "serve.sync",
          "serve.emit")
MODES = ("batched", "per_slot")


def _requests(cfg):
    """More requests than slots, prompts across several prefill chunks."""
    rng = np.random.default_rng(3)
    return [Request(rid=i, max_new=2 + i % 3,
                    prompt=rng.integers(0, cfg.vocab, 3 + 3 * i
                                        ).astype(np.int32))
            for i in range(5)]


def _drive(server, reqs):
    """Submit everything, then one run(max_steps=1) at a time, as a load
    generator does; returns each round's perf_counter bounds and the
    submit bounds by rid."""
    submits = {}
    for r in reqs:
        a = time.perf_counter()
        server.submit(r)
        submits[r.rid] = (a, time.perf_counter())
    rounds = []
    while server.queue or any(r is not None for r in server.active):
        a = time.perf_counter()
        server.run(max_steps=1)
        rounds.append((a, time.perf_counter()))
    return rounds, submits


@pytest.fixture(scope="module")
def served():
    """Per mode: a warmed server, its traced pass (events, rounds, submits,
    stats, tokens) and the same requests served with tracing off."""
    cfg = R.get("xlstm-125m").smoke
    prior = obs_config.enabled()
    out = {}
    try:
        for mode in MODES:
            server = Server(cfg, meshlib.make_host_mesh(), slots=3, ctx=32,
                            seed=0, mode=mode, prefill_chunk=4)
            obs_config.set_enabled(False)
            off = _requests(cfg)
            _drive(server, off)
            server.reset_metrics()
            obs_config.set_enabled(True)
            trace.reset()
            metrics.reset()
            on = _requests(cfg)
            rounds, submits = _drive(server, on)
            out[mode] = {
                "server": server, "events": trace.events(), "rounds": rounds,
                "submits": submits, "stats": dict(server.stats),
                "counters": metrics.snapshot()["counters"],
                "off": [r.out for r in off], "on": [r.out for r in on]}
    finally:
        obs_config.set_enabled(prior)
        trace.reset()
        metrics.reset()
    return out


def _spans(events, name):
    return [(e["ts"], e["ts"] + e["dur"]) for e in events
            if e["ph"] == "X" and e["name"] == name]


@pytest.mark.parametrize("mode", MODES)
def test_one_round_span_per_round_holding_its_phases(served, mode):
    s = served[mode]
    rounds = _spans(s["events"], "serve.round")
    assert len(rounds) == len(s["rounds"]) == (
        s["stats"]["prefill_rounds"] + s["stats"]["decode_ticks"])
    inside = collections.Counter()
    where = collections.defaultdict(list)
    for name in PHASES:
        for a, b in _spans(s["events"], name):
            hits = [i for i, (ra, rb) in enumerate(rounds)
                    if ra <= a and b <= rb]
            assert len(hits) == 1, (name, a, b)
            inside[name, hits[0]] += 1
            where[name, hits[0]].append((a, b))
    owed = 0  # rounds dispatched and not yet read
    for i in range(len(rounds)):
        dispatch, sync, emit, pack = (inside[n, i] for n in (
            "serve.dispatch", "serve.sync", "serve.emit", "serve.pack"))
        assert inside["serve.admit", i] == 1
        admit_end = where["serve.admit", i][0][1]
        assert all(admit_end <= a for n in PHASES if n != "serve.admit"
                   for a, _ in where[n, i])
        # Round t+1 is packed and dispatched before round t is read.
        assert all(b <= a for _, b in where["serve.dispatch", i]
                   for a, _ in where["serve.sync", i])
        if mode == "per_slot":
            assert sync == dispatch >= 1 and emit == 1
            assert pack == 1 + dispatch
            continue
        assert dispatch <= 1 and pack == 2 * dispatch and sync == emit
        if owed:
            assert sync >= 1  # the round in flight is read
        else:
            assert sync in (0, dispatch)  # a busy period's first round
        owed += dispatch - sync
        assert owed in (0, 1)
    assert owed == 0
    syncs = len(_spans(s["events"], "serve.sync"))
    assert syncs == s["stats"]["dispatches"]
    if mode == "batched":
        assert syncs == len(rounds)
        assert inside["serve.sync", 0] == 0 < inside["serve.dispatch", 0]
        assert s["stats"]["overlapped"] > 0
    else:
        assert syncs > len(rounds)  # one per busy row
        assert s["stats"]["overlapped"] == 0


@pytest.mark.parametrize("mode", MODES)
def test_round_spans_on_the_callers_clock(served, mode):
    """origin() + ts puts each serve.round inside the caller's timing of
    the run(max_steps=1) that ran it."""
    s = served[mode]
    t0 = trace.origin()
    rounds = _spans(s["events"], "serve.round")
    for (a, b), (ca, cb) in zip(rounds, s["rounds"]):
        assert ca <= t0 + a * 1e-6 <= t0 + b * 1e-6 <= cb


@pytest.mark.parametrize("mode", MODES)
def test_request_track_begin_admit_first_token_end(served, mode):
    s = served[mode]
    t0 = trace.origin()
    tracks = collections.defaultdict(list)
    for e in s["events"]:
        if e.get("cat") == "serve.request":
            tracks[int(e["id"])].append(e)
    assert sorted(tracks) == sorted(s["submits"])
    rounds = s["rounds"]
    for rid, evs in tracks.items():
        steps = [e["ph"] if e["ph"] != "n" else e["args"]["phase"]
                 for e in evs]
        assert steps == ["b", "admit", "prefill_done", "e"], (rid, steps)
        ts = [t0 + e["ts"] * 1e-6 for e in evs]
        assert ts == sorted(ts)
        lo, hi = s["submits"][rid]
        assert lo <= ts[0] <= hi
        # The first token is marked inside the round that emitted it.
        assert any(a <= ts[2] <= b for a, b in rounds)


@pytest.mark.parametrize("mode", MODES)
def test_tokens_equal_with_tracing_on_and_off(served, mode):
    s = served[mode]
    assert all(s["off"]) and s["on"] == s["off"]


@pytest.mark.parametrize("mode", MODES)
def test_dispatches_counted_by_stats_alone(served, mode):
    s = served[mode]
    assert s["stats"]["dispatches"] > 0
    assert any(k.startswith("serve.tokens") for k in s["counters"])
    assert not any(k.startswith("serve.dispatches") for k in s["counters"])


def test_profiler_timeline_holds_round_and_sync(served, tmp_path):
    server = served["batched"]["server"]
    cfg = server.cfg
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab
    with obs.enabled_scope(True):
        try:
            server.submit(Request(rid=100, prompt=prompt, max_new=2))
            # The second round: it dispatches the prompt's last chunk,
            # then reads the first (the first round only dispatches).
            server.run(max_steps=1)
            with jax.profiler.trace(str(tmp_path)):
                server.run(max_steps=1)
            server.run()
        finally:
            trace.reset()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    names = collections.Counter(
        ev.name for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events)
    assert names["serve.round"] == 1
    assert names["serve.sync"] == 1
    assert not any("#" in n for n in names if n.startswith("serve."))
