"""Batched continuous-batching server: results, prefill parity, admission,
one-dispatch ticks, per-request tiers, and the engine's row-tier routing."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.launch import loadgen, mesh as meshlib
from repro.launch.serve import DEFAULT_TIER_POLICIES, Request, Server
from repro.models import registry as R, transformer


def _mesh():
    return meshlib.make_host_mesh()


def _prompts(cfg, n, length, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, length).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# run() returns everything that was submitted (the lost-results bugfix)
# ---------------------------------------------------------------------------


def test_run_returns_all_submitted_requests():
    cfg = R.get("smollm-360m").smoke  # attn_full: bounded context
    server = Server(cfg, _mesh(), slots=2, ctx=16, seed=0)
    good = [Request(rid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts(cfg, 3, 4))]
    too_long = Request(rid=99, prompt=_prompts(cfg, 1, 12, seed=9)[0],
                       max_new=12)  # 12 + 12 > 16
    for r in [*good, too_long]:
        server.submit(r)
    finished = server.run()
    assert {r.rid for r in finished} == {0, 1, 2, 99}
    for r in good:
        assert r.status == "done" and len(r.out) == 3
        assert r.finished_at >= r.submitted_at
    assert too_long.status == "rejected" and too_long.out == []
    assert "context budget exceeded" in too_long.error


# ---------------------------------------------------------------------------
# Prefill off-by-one: slot decode == full-sequence forward greedy rollout
# ---------------------------------------------------------------------------


def test_slot_decode_matches_full_forward_rollout():
    """The prediction from the LAST prompt position must be the first decode
    token, with every prompt token cached exactly once — so the served
    output equals a greedy rollout where each next token is the argmax of a
    full-sequence forward pass (no cache at all)."""
    cfg = dataclasses.replace(R.get("smollm-360m").smoke, dtype="float32")
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    prompt = _prompts(cfg, 1, 5, seed=2)[0]
    max_new = 4

    seq = list(prompt)
    for _ in range(max_new):
        logits = transformer.forward(
            params, {"tokens": jnp.asarray(seq)[None]}, cfg)
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    want = seq[len(prompt):]

    for chunk in (1, 3, 8):  # chunk boundaries must not move the off-by-one
        server = Server(cfg, _mesh(), slots=2, ctx=16, seed=0,
                        prefill_chunk=chunk)
        req = Request(rid=0, prompt=prompt.copy(), max_new=max_new)
        server.submit(req)
        server.run()
        assert req.out == want, (chunk, req.out, want)


# ---------------------------------------------------------------------------
# Admission control: context budget
# ---------------------------------------------------------------------------


def test_context_budget_boundary_full_attention():
    cfg = R.get("smollm-360m").smoke
    server = Server(cfg, _mesh(), slots=1, ctx=16, seed=0)
    prompt = _prompts(cfg, 1, 8)[0]
    fits = Request(rid=0, prompt=prompt.copy(), max_new=8)    # 8 + 8 == 16
    spills = Request(rid=1, prompt=prompt.copy(), max_new=9)  # 8 + 9 > 16
    server.submit(fits)
    server.submit(spills)
    assert fits.status == "queued"
    assert spills.status == "rejected"
    assert "16 cache positions" in spills.error
    server.run()
    assert fits.status == "done" and len(fits.out) == 8


def test_recurrent_arch_serves_past_ctx():
    """Pure-recurrent archs carry O(1) state: no position limit, so a
    request longer than the nominal ctx is admitted and completes."""
    cfg = R.get("xlstm-125m").smoke
    server = Server(cfg, _mesh(), slots=1, ctx=8, seed=0)
    req = Request(rid=0, prompt=_prompts(cfg, 1, 6)[0], max_new=8)  # 14 > 8
    server.submit(req)
    server.run()
    assert req.status == "done" and len(req.out) == 8


def test_degenerate_requests_rejected():
    cfg = R.get("xlstm-125m").smoke
    server = Server(cfg, _mesh(), slots=1, ctx=8, seed=0)
    empty = server.submit(Request(rid=0, prompt=np.zeros(0, np.int32)))
    none = server.submit(Request(rid=1, prompt=_prompts(cfg, 1, 3)[0],
                                 max_new=0))
    assert empty.status == "rejected" and "empty prompt" in empty.error
    assert none.status == "rejected" and "max_new" in none.error
    assert server.run() == [empty, none]


def test_unknown_tier_rejected():
    cfg = R.get("xlstm-125m").smoke
    server = Server(cfg, _mesh(), slots=1, ctx=8, seed=0,
                    tiers=dict(DEFAULT_TIER_POLICIES))
    req = server.submit(Request(rid=0, prompt=_prompts(cfg, 1, 3)[0],
                                max_new=2, tier="premium"))
    assert req.status == "rejected" and "unknown tier" in req.error


# ---------------------------------------------------------------------------
# Batched == per-slot (the tentpole's bitwise contract) + dispatch counting
# ---------------------------------------------------------------------------


def _serve_tokens(cfg, mode, *, tiers=None, n=3, max_new=4, seed=7):
    server = Server(cfg, _mesh(), slots=2, ctx=32, seed=0, tiers=tiers,
                    mode=mode, prefill_chunk=4)
    names = tuple(tiers) if tiers else ("exact",)
    reqs = [Request(rid=i, prompt=p, max_new=max_new,
                    tier=names[i % len(names)])
            for i, p in enumerate(_prompts(cfg, n, 5, seed=seed))]
    for r in reqs:
        server.submit(r)
    server.run()
    return [tuple(r.out) for r in reqs], server.stats


@pytest.mark.parametrize("arch", ["xlstm-125m", "smollm-360m"])
def test_batched_matches_per_slot_exact(arch):
    """One jitted dispatch advancing all live rows must produce the same
    tokens as the same executable driven one live row at a time (every
    decode op is row-local)."""
    cfg = R.get(arch).smoke
    batched, _ = _serve_tokens(cfg, "batched")
    per_slot, _ = _serve_tokens(cfg, "per_slot")
    assert batched == per_slot


def test_batched_matches_per_slot_tiered():
    """The row-tier surrogate path keys noise on the request-local position,
    so batched and per-slot schedules see identical noise per row too."""
    cfg = R.get("xlstm-125m").smoke
    tiers = dict(DEFAULT_TIER_POLICIES)
    batched, _ = _serve_tokens(cfg, "batched", tiers=tiers)
    per_slot, _ = _serve_tokens(cfg, "per_slot", tiers=tiers)
    assert batched == per_slot


def test_one_dispatch_per_tick():
    """Batched mode issues exactly ONE jitted step per scheduling round
    regardless of how many slots are live; per_slot issues one per busy
    slot (staggered max_new keeps the live count varying)."""
    cfg = R.get("xlstm-125m").smoke
    for mode, n in (("batched", 4), ("per_slot", 4)):
        server = Server(cfg, _mesh(), slots=4, ctx=32, seed=0, mode=mode,
                        prefill_chunk=4)
        reqs = [Request(rid=i, prompt=p, max_new=2 + i)
                for i, p in enumerate(_prompts(cfg, n, 3))]
        for r in reqs:
            server.submit(r)
        server.run()
        assert all(r.status == "done" for r in reqs)
        rounds = server.stats["decode_ticks"] + server.stats["prefill_rounds"]
        if mode == "batched":
            assert server.stats["dispatches"] == rounds
        else:
            assert server.stats["dispatches"] > rounds  # one per busy slot


# ---------------------------------------------------------------------------
# Mixed-tier determinism: output independent of slot, schedule, neighbors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tier", sorted(DEFAULT_TIER_POLICIES))
def test_mixed_tier_request_isolation(tier):
    """Per tier: a request decodes the same tokens served alone as it does
    admitted late into a recycled slot beside different-tier neighbors —
    slot reset, masked merge, and position-keyed noise make the output a
    function of the request alone."""
    cfg = R.get("xlstm-125m").smoke
    tiers = dict(DEFAULT_TIER_POLICIES)
    prompt = _prompts(cfg, 1, 5, seed=11)[0]

    solo = Server(cfg, _mesh(), slots=2, ctx=32, seed=3, tiers=tiers)
    r_solo = Request(rid=0, prompt=prompt.copy(), max_new=4, tier=tier)
    solo.submit(r_solo)
    solo.run()

    busy = Server(cfg, _mesh(), slots=2, ctx=32, seed=3, tiers=tiers)
    other = [t for t in sorted(DEFAULT_TIER_POLICIES) if t != tier]
    neighbors = [Request(rid=i + 1, prompt=p, max_new=2 + i, tier=other[i])
                 for i, p in enumerate(_prompts(cfg, 2, 4, seed=12))]
    r_busy = Request(rid=0, prompt=prompt.copy(), max_new=4, tier=tier)
    for r in [*neighbors, r_busy]:  # r_busy queues behind both neighbors
        busy.submit(r)
    busy.run()

    assert r_solo.status == r_busy.status == "done"
    assert r_solo.out == r_busy.out, (tier, r_solo.out, r_busy.out)


def test_exact_tier_matches_exact_server():
    """The exact tier rides the shared tier dispatch with zero moments and
    zero variance: its tokens match a plain exact-numerics server."""
    cfg = R.get("xlstm-125m").smoke
    prompt = _prompts(cfg, 1, 5, seed=21)[0]
    outs = []
    for tiers in (None, dict(DEFAULT_TIER_POLICIES)):
        server = Server(cfg, _mesh(), slots=2, ctx=32, seed=0, tiers=tiers)
        req = Request(rid=0, prompt=prompt.copy(), max_new=4, tier="exact")
        server.submit(req)
        server.run()
        outs.append(req.out)
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Engine row-tier routing (unit level)
# ---------------------------------------------------------------------------


def test_register_tier_set_validation():
    engine.register_tier_set("t_unit", (None, "uniform:pm_csi"))
    engine.register_tier_set("t_unit", (None, "uniform:pm_csi"))  # same: ok
    with pytest.raises(ValueError):
        engine.register_tier_set("t_unit", ("rr:8",))  # different content
    engine.register_tier_set("t_unit", ("rr:8",), overwrite=True)
    engine.register_tier_set("t_unit", (None, "uniform:pm_csi"),
                             overwrite=True)  # restore
    with pytest.raises(ValueError):
        engine.register_tier_set("t_nested", ("tiers:t_unit",))
    with pytest.raises(ValueError):
        engine.tier_set("no_such_tier_set")
    assert "t_unit" in engine.list_tier_sets()


def _fold_gather_moments(x, w, policies, tiers, tile):
    """The row-tier moments as first written: each tier's moments folded
    into a full (T, K, N) weight copy, gathered per row as (B, K, N), and
    each row contracted against its own copy. Oracle only."""
    k, n = w.shape
    vids = np.stack([engine.canonical_matmul_map(
        p, k, n, tile_k=tile, tile_n=tile).vids for p in policies])
    wm, wv = engine.fold_matmul_weights(w, engine.CanonicalMap(vids, True))
    xf = np.asarray(x, np.float32)
    t = np.asarray(tiers)
    mean = np.einsum("bk,bkn->bn", xf, wm[t])
    var = np.einsum("bk,bkn->bn", xf * xf, wv[t])
    return mean, var


_ORACLE_TIERS = (None, "uniform:pm_csi", "rr:8")


@pytest.mark.parametrize("case", [
    "per_policy",
    "oracle-16x8-t8",
    "oracle-200x300-t128",
    "oracle-256x384-t128",
])
def test_row_tier_moments_match_per_policy_maps(rng, case):
    """Row r's tier-routed moments equal the plain surrogate moments under
    row r's own policy; the None tier is exact-mean zero-variance. The
    tile-factored contraction also matches the fold-and-gather formula on
    a mixed batch, at tile-aligned and padded (non-dividing) shapes."""
    if case == "per_policy":
        k, n = 16, 8
        x = jnp.asarray(rng.standard_normal((2, k)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        engine.register_tier_set("t_mom", (None, "uniform:pm_csi"),
                                 overwrite=True)
        eng = engine.AMEngine(backend="surrogate_xla", tile_k=8, tile_n=8)
        tiers = jnp.asarray([0, 1], jnp.int32)
        pos = jnp.asarray([0, 0], jnp.int32)
        with engine.row_tier_context(tiers, pos):
            mean, var = eng.matmul(x, w, "tiers:t_mom",
                                   key=jax.random.PRNGKey(0),
                                   return_moments=True)
        np.testing.assert_allclose(np.asarray(mean[0]), np.asarray(x[0] @ w),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(var[0]), 0.0, atol=1e-7)
        m1, v1 = eng.matmul(x[1:], w, "uniform:pm_csi",
                            key=jax.random.PRNGKey(0), return_moments=True)
        np.testing.assert_allclose(np.asarray(mean[1]), np.asarray(m1[0]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(var[1]), np.asarray(v1[0]),
                                   rtol=1e-5, atol=1e-8)
        assert float(jnp.max(var[1])) > 0.0
        return
    shape, tile = case.split("-")[1:]
    k, n = (int(d) for d in shape.split("x"))
    tile = int(tile[1:])
    b = 6
    x = jnp.asarray(rng.standard_normal((b, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    engine.register_tier_set("t_oracle", _ORACLE_TIERS, overwrite=True)
    eng = engine.AMEngine(tile_k=tile, tile_n=tile)
    tiers = jnp.asarray(np.arange(b) % len(_ORACLE_TIERS), jnp.int32)
    pos = jnp.arange(b, dtype=jnp.int32)
    with engine.row_tier_context(tiers, pos):
        mean, var = eng.matmul(x, w, "tiers:t_oracle",
                               key=jax.random.PRNGKey(0),
                               return_moments=True)
    ref_m, ref_v = _fold_gather_moments(x, w, _ORACLE_TIERS, tiers, tile)
    # Mean entries near zero cancel: scale their tolerance by |x| @ |w|.
    scale = float(np.max(np.abs(np.asarray(x)) @ np.abs(np.asarray(w))))
    np.testing.assert_allclose(np.asarray(mean), ref_m, rtol=1e-5,
                               atol=1e-5 * scale)
    # Variance terms are all >= 0: no cancellation, relative error only.
    np.testing.assert_allclose(np.asarray(var), ref_v, rtol=1e-5, atol=0)
    assert np.all(np.asarray(var)[tiers == 0] == 0.0)
    assert np.all(np.asarray(var)[tiers != 0] > 0.0)


def _constant_bytes(hlo_text: str) -> list[int]:
    """Bytes of every constant in a lowered module's text."""
    width = {"f32": 4, "i32": 4, "ui32": 4, "bf16": 2, "f16": 2, "i8": 1,
             "ui8": 1, "i1": 1, "i64": 8, "ui64": 8, "f64": 8}
    sizes = []
    for m in re.finditer(r"stablehlo\.constant .*?: tensor<([^>]*)>",
                         hlo_text):
        *dims, dtype = m.group(1).split("x")
        sizes.append(int(np.prod([int(d) for d in dims])) * width[dtype])
    return sizes


def test_tiered_step_lowers_without_weight_copies():
    """The tiered decode step at the published xlstm-125m widths (16
    slots, one token) carries no folded weight copies: no constant over
    1 MiB, no f32 (3|16, 768, 50304) value, and one row-tier dispatch per
    AM projection of the traced step (20 in the block scan body, plus the
    head). Lowered only, never compiled."""
    from repro import obs
    from repro.obs import metrics

    cfg = R.get("xlstm-125m").config
    slots = 16
    server = Server(cfg, _mesh(), slots=slots, ctx=512, seed=0,
                    tiers=dict(DEFAULT_TIER_POLICIES))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    args = (jax.eval_shape(lambda: server.params),
            jax.eval_shape(lambda: server.cache),
            jax.ShapeDtypeStruct((slots, 1), jnp.int32), vec, vec, vec)
    labels = {"op": "matmul", "backend": "row_tier"}
    with obs.enabled_scope(True):
        before = metrics.REGISTRY.get_counter("engine.dispatch", **labels)
        lowered = server._jit_step.lower(*args)
        dispatches = (metrics.REGISTRY.get_counter("engine.dispatch", **labels)
                      - before)
    assert dispatches == 21
    text = lowered.as_text()
    sizes = _constant_bytes(text)
    assert sizes and max(sizes) <= 1 << 20, max(sizes)
    for lead in (3, slots):
        assert f"tensor<{lead}x768x50304xf32>" not in text


def test_row_tier_requires_context_and_row_match(rng):
    k, n = 8, 4
    x = jnp.asarray(rng.standard_normal((3, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    engine.register_tier_set("t_ctx", (None,), overwrite=True)
    eng = engine.AMEngine(backend="surrogate_xla", tile_k=8, tile_n=8)
    with pytest.raises(ValueError, match="row_tier_context"):
        eng.matmul(x, w, "tiers:t_ctx", key=jax.random.PRNGKey(0))
    two = jnp.zeros(2, jnp.int32)
    with engine.row_tier_context(two, two):
        with pytest.raises(ValueError, match="rows"):
            eng.matmul(x, w, "tiers:t_ctx", key=jax.random.PRNGKey(0))


def test_bitexact_backend_rejects_tiers():
    cfg = R.get("xlstm-125m").smoke
    with pytest.raises(ValueError, match="bit-exact"):
        Server(cfg, _mesh(), slots=1, ctx=8, am_backend="bitexact_ref",
               tiers=dict(DEFAULT_TIER_POLICIES))


# ---------------------------------------------------------------------------
# Vector-pos decode == scalar-pos decode (the layer-level enabler)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen2.5-3b"])
def test_vector_pos_decode_matches_scalar(arch):
    cfg = dataclasses.replace(R.get(arch).smoke, dtype="float32")
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    B, ctx, p = 3, 16, 5
    rng = np.random.default_rng(0)
    cache_s = R.init_cache(cfg, B, ctx)
    cache_v = jax.tree.map(jnp.copy, cache_s)
    dec = R.decode_fn(cfg)
    for t in range(p):
        toks = jnp.asarray(rng.integers(0, cfg.vocab, B), jnp.int32)
        lg_s, cache_s = dec(params, cache_s, toks, jnp.int32(t), cfg)
        lg_v, cache_v = dec(params, cache_v, toks,
                            jnp.full((B,), t, jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(lg_v), np.asarray(lg_s),
                                   rtol=1e-6, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6),
        cache_v, cache_s)


# ---------------------------------------------------------------------------
# Load generator
# ---------------------------------------------------------------------------


def test_loadgen_run_load_metrics():
    cfg = R.get("xlstm-125m").smoke
    reqs = loadgen.make_requests(cfg, 4, max_new=3, seed=0)
    assert [r.tier for r in reqs] == ["exact", "conservative", "aggressive",
                                     "exact"]
    again = loadgen.make_requests(cfg, 4, max_new=3, seed=0)
    assert all(np.array_equal(a.prompt, b.prompt)
               for a, b in zip(reqs, again))  # deterministic stream
    server = Server(cfg, _mesh(), slots=2, ctx=32, seed=0,
                    tiers=dict(DEFAULT_TIER_POLICIES))
    m = loadgen.run_load(server, reqs)
    assert m["completed"] == 4 and m["rejected"] == 0
    assert m["generated"] == 12 and m["tokens_per_sec"] > 0
    assert m["dispatches"] == m["decode_ticks"] + m["prefill_rounds"]
    assert 0 < m["p50_latency_s"] <= m["p99_latency_s"] <= m["wall_s"]
