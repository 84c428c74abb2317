"""The serving generator: open-loop arrivals into the program's
continuous-batching server (`Server.submit`, then `Server.run(max_steps=1)`
one scheduling round at a time).

Reads from the traffic file:
  rate_per_s            offered load, requests per second
  prompt / output       lognormal lengths: median, sigma, min, max (tokens)
  tiers                 share of requests on each tier
  check_requests        finished requests compared with the reference

Every seed gets the same work in another order: for the window's
N = rate x seconds requests, the prompt lengths, output lengths and gaps
between arrivals are the quantiles (i + 1/2) / N of their distributions
(lognormal; exponential gaps of mean 1 / rate), and the tiers come in fixed
counts; `--seed` shuffles each list and draws the prompt tokens. A request
is timed from when it was due: time to first token from its due time, and
the gaps between its tokens from the ends of the rounds that emitted them.
"""
from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np

import harness


def _quantiles(n: int, inv_cdf) -> np.ndarray:
    return np.asarray([inv_cdf((i + 0.5) / n) for i in range(n)])


def _lengths(n: int, spec: dict) -> np.ndarray:
    nd = statistics.NormalDist()
    q = _quantiles(n, lambda u: math.exp(
        math.log(spec["median"]) + spec["sigma"] * nd.inv_cdf(u)))
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def schedule(tr: dict, seconds: float, seed: int, vocab: int) -> list[dict]:
    """The window's requests: due (s from the window's start), prompt,
    max_new, tier."""
    rate = float(tr["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    gaps = _quantiles(n, lambda u: -math.log(1.0 - u) / rate)
    prompts = _lengths(n, tr["prompt"])
    outs = _lengths(n, tr["output"])
    shares = tr["tiers"]
    counts = {t: int(math.floor(s * n)) for t, s in shares.items()}
    order = sorted(shares, key=lambda t: -(shares[t] * n - counts[t]))
    for t in order[: n - sum(counts.values())]:
        counts[t] += 1
    tiers = [t for t in shares for _ in range(counts[t])]
    gaps, prompts, outs = (rng.permutation(a) for a in (gaps, prompts, outs))
    tiers = [tiers[i] for i in rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    return [{"due": float(due[i]),
             "prompt": rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
             "max_new": int(outs[i]), "tier": tiers[i]} for i in range(n)]


def _warm(server, Request, cfg: dict, rng):
    """Runs the reset, the prefill program and the decode program once."""
    for rid, tier in enumerate(cfg["tiers"]):
        server.submit(Request(
            rid=-1 - rid, max_new=2, tier=tier,
            prompt=rng.integers(0, cfg["vocab"], cfg["prefill_chunk"] + 1
                                ).astype(np.int32)))
    server.run()
    server.reset_metrics()


def window(server, plan: list[dict], seconds: float, flops: dict,
           prof=None) -> dict:
    """Drive `plan` into the server for `seconds`; returns the window's
    bounds and events. Rounds that have begun finish; the window closes at
    the end of the first round that ends after `seconds`."""
    from repro.launch.serve import Request

    reqs: list = []  # (plan entry, Request, submit time)
    tok_times: dict[int, list[float]] = {}
    rounds: list[tuple] = []  # (t0, t1, kind, tokens, flops)
    backlog: list[tuple] = []  # (t, queued + active) after each round
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if prof is not None:
        prof.arm(t0, seconds, plan[-1]["due"] if plan else seconds)
    nxt = 0
    while True:
        now = time.perf_counter()
        if prof is not None:
            prof.poll(now)
        if now >= t_end:
            break
        while nxt < len(plan) and t0 + plan[nxt]["due"] <= now:
            e = plan[nxt]
            r = Request(rid=nxt, prompt=e["prompt"], max_new=e["max_new"],
                        tier=e["tier"])
            with harness.span("bench.submit"):
                server.submit(r)
            reqs.append((e, r, time.perf_counter()))
            tok_times[nxt] = []
            nxt += 1
        if not server.queue and not any(server.active):
            wake = t0 + plan[nxt]["due"] if nxt < len(plan) else t_end
            with harness.span("bench.wait"):
                time.sleep(max(0.0, min(wake, t_end) - time.perf_counter()))
            continue
        before = {r.rid: int(server.pos[i])
                  for i, r in enumerate(server.active) if r is not None}
        for r in server.queue:
            before[r.rid] = 0
        n_out = {rid: len(reqs[rid][1].out) for rid in before}
        prefills = server.stats["prefill_rounds"]
        ta = time.perf_counter()
        with harness.span("bench.round"):
            server.run(max_steps=1)
        tb = time.perf_counter()
        kind = ("prefill" if server.stats["prefill_rounds"] > prefills
                else "decode")
        slot = {r.rid: i for i, r in enumerate(server.active)
                if r is not None}
        toks = work = 0.0
        for rid, p0 in before.items():
            r = reqs[rid][1]
            if rid in slot:
                p1 = int(server.pos[slot[rid]])
            elif r.done:
                p1 = len(r.prompt) + len(r.out) - 1
            else:
                p1 = 0
            toks += p1 - p0
            work += (p1 - p0) * flops[r.tier]
            tok_times[rid] += [tb] * (len(r.out) - n_out[rid])
        rounds.append((ta, tb, kind, toks, work))
        backlog.append((tb, len(server.queue)
                        + sum(r is not None for r in server.active)))
    t1 = max(t_end, rounds[-1][1] if rounds else t_end)
    return {"t0": t0, "t1": t1, "reqs": reqs, "rounds": rounds,
            "backlog": backlog,
            "requests": [{"due": t0 + e["due"], "tier": e["tier"],
                          "tokens": tok_times[r.rid]} for e, r, _ in reqs],
            "late": [sub - (t0 + e["due"]) for e, _, sub in reqs]}


def setup(cell, seed: int):
    """The server with its weights from the seed, every program warmed."""
    from repro.core import surrogate
    from repro.launch.serve import Request

    # The AM moments are calibrated on first use and cached in the
    # checkout; a first use inside the tiered step's trace fails, so it
    # happens here, on the host.
    surrogate.moment_tables()
    cfg = cell.config
    s_model, s_traffic, s_sample = harness.subseeds(seed, 3)
    server = cell.config_module.build_server(cfg, s_model)
    _warm(server, Request, cfg, np.random.default_rng(s_traffic))
    flops = {t: cell.config_module.flops_per_token(cfg, t)
             for t in cfg["tiers"]}
    return server, flops, (s_model, s_traffic, s_sample)


def run(cell, devs, *, seed: int, seconds: float, trace: bool,
        t_start: float, control: bool = False) -> harness.Record:
    cfg, tr = cell.config, cell.traffic
    server, flops, (s_model, s_traffic, s_sample) = setup(cell, seed)
    plan = schedule(tr, seconds, s_traffic, cfg["vocab"])
    compiles = harness.CompileCounter()
    prof = harness.Profiler(trace, cell.name)
    setup_s = time.perf_counter() - t_start
    with compiles.counting():
        w = window(server, plan, seconds, flops, prof)
    summary = prof.summary()
    memory_peak = harness.memory_peak_bytes(devs)

    reqs, rounds = w["reqs"], w["rounds"]
    done = [(e, r) for e, r, _ in reqs if r.status == "done"]
    rejected = sum(r.status == "rejected" for _, r, _ in reqs)
    sample = [(r.prompt, r.out) for _, r in
              _sample(done, int(tr["check_requests"]), s_sample)]
    del server  # the reference runs on a chip the program has let go
    gc.collect()
    t_ref = time.perf_counter()
    numbers = cell.reference.compare(cfg, sample, s_model)
    counters = {
        "requests_due": len(reqs), "requests_done": len(done),
        "rejected": rejected,
        "rounds": {k: sum(1 for x in rounds if x[2] == k)
                   for k in ("prefill", "decode")},
        "tokens": sum(len(x["tokens"]) for x in w["requests"]),
        "late_ms_p50_p95_max": [
            1e3 * harness.percentile(w["late"], q) for q in (50, 95, 100)]
        if w["late"] else None,
        "checked_requests": len(sample),
        "checked_tokens": sum(len(o) for _, o in sample),
        "compared": numbers,
        "compiles_in_window": compiles.compiles,
        "traces_in_window": compiles.traces,
        "reference_s": time.perf_counter() - t_ref,
    }
    if control:
        counters["control"] = cell.reference.control(cfg, sample, s_model)
    return harness.Record(
        cell=cell, setup_s=setup_s, window=(w["t0"], w["t1"]),
        events={"rounds": rounds, "requests": w["requests"],
                "late": w["late"]},
        counters=counters, attempted=len(reqs), failed=rejected,
        checks=[harness.Check(n, numbers[n], float(v))
                for n, v in cell.limits.items()],
        device=harness.device_info(devs), memory_peak=memory_peak,
        trace=summary)


def _sample(done: list, n: int, seed: int) -> list:
    """The longest finished request, then a seeded draw that holds each
    tier, up to n requests."""
    if not done:
        return []
    rng = np.random.default_rng(seed)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][1].prompt) + len(done[i][1].out))
    pick = [longest]
    by_tier: dict[str, list[int]] = {}
    for i in rng.permutation(len(done)):
        by_tier.setdefault(done[i][0]["tier"], []).append(int(i))
    while len(pick) < min(n, len(done)):
        for idx in by_tier.values():
            while idx and idx[0] in pick:
                idx.pop(0)
            if idx and len(pick) < n:
                pick.append(idx.pop(0))
    return [done[i] for i in pick]
