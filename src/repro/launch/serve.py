"""Serving driver: continuous-batched decode over the sharded KV cache.

A production-shaped continuous-batching tier over fixed decode slots:

* **One jitted step per tick.** Every live slot advances in a single
  dispatch — per-slot positions go in as a (B,) vector (the decode path is
  row-local, see models/layers.py::attention_decode), per-slot liveness as
  a mask on the cache merge. The same executable, driven with single-row
  masks, is the per-slot reference mode (``mode="per_slot"``) — N dispatches
  per tick, the baseline the batched mode is measured (and bitwise-checked)
  against.
* **Chunked batched prefill.** Prompts stream through the decode path
  ``prefill_chunk`` tokens per dispatch (a lax.scan inside the same jitted
  step), all prefilling slots together; the prediction from the LAST prompt
  position is the request's first decode token, so the final prompt token is
  written to the cache exactly once.
* **Admission control.** Requests that cannot fit the cache
  (`prompt + max_new` past registry.serve_position_limit — full-attention
  archs; recurrent/windowed archs are unbounded), empty prompts, and unknown
  tiers are rejected at submit with a clear error and surfaced in the
  returned results instead of silently overflowing the KV cache.
* **Per-request AM policy tiers.** Each request carries a tier name mapped
  to a NumericsConfig slot-map policy (None = exact); the engine's
  `tiers:<name>` policy contracts every projection once per 128x128 tile
  for the whole batch and scales each row's tiles by its own tier's
  moments inside the one dispatch (core/engine.py::register_tier_set /
  row_tier_context / AMEngine._row_tier_matmul) — premium traffic decodes
  exact while bulk traffic rides aggressive interleaves, in the same batch.
* **One round in flight.** Each round dispatches the next step before it
  reads the previous one's tokens: the host's admission, packing and
  emission overlap the step on the device. A decode tick takes each row's
  token from ``last``, the step's own previous prediction kept on the
  device, so nothing the host has not read yet is needed to enqueue it.
* **A round's phases are spans.** With observability on (repro.obs), each
  scheduling round is one ``serve.round`` span holding, in order:
  ``serve.admit`` (queue pops, and the slot-reset dispatch when a slot is
  filled), ``serve.pack`` (the next step's token rows, then its arguments
  as device arrays) and ``serve.dispatch`` (the step enqueued), then
  ``serve.sync`` (the host waiting for the previous step's tokens) and
  ``serve.emit`` (tokens appended, requests finished). The first round of
  a busy period has no sync or emit; a round with nothing left to
  dispatch next also syncs and emits its own step. In per_slot mode each
  busy row gets its own pack/dispatch/sync, read within the round. Each
  request's ``serve.request`` async track runs submit -> ``admit`` ->
  ``prefill_done`` (its first token read) -> end.

Slot isolation: stepping any set of slots updates ONLY those slots' cache
slices (masked merge per batch row), an admitted request starts from a
pristine slice, and surrogate noise is keyed per row by the request-local
position — never the slot index, schedule, or neighbors. A request's output
is therefore independent of where/when it runs and what runs beside it,
per tier (tests/test_serving_batched.py asserts it).

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m \
      --requests 6 --slots 4 --tiers exact,conservative,aggressive
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import compile_cache, obs
from repro.core import amlinear, engine
from repro.launch import mesh as meshlib
from repro.models import registry as R
from repro.obs import watchdog

# The shipped tier menu: accuracy-ranked alphabet positions (interleave.py)
# ground the conservative/aggressive split — conservative is the paper's
# best single variant everywhere, aggressive round-robins the full top-8
# alphabet (the Ristretto-style layer-wise trade-off as a request knob).
DEFAULT_TIER_POLICIES: dict[str, str | None] = {
    "exact": None,
    "conservative": "uniform:pm_csi",
    "aggressive": "rr:8",
}


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int = 16
    tier: str = "exact"
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "new"  # new | queued | active | done | rejected
    error: str | None = None
    submitted_at: float = 0.0
    finished_at: float = 0.0

    @property
    def latency(self) -> float:
        return self.finished_at - self.submitted_at


@dataclasses.dataclass
class _Round:
    """A dispatched round: its step outputs (rows, next tokens still on the
    device) and the requests its tokens go to, fixed at dispatch."""
    kind: str  # prefill | decode
    nxt: list[tuple[np.ndarray | list[int], jax.Array]]
    emits: list[tuple[int, Request]]
    fed: int  # tokens the round fed


class Server:
    """Fixed-slot continuous batching server (greedy decode).

    Numerics selection:
      * ``tiers`` (dict tier-name -> slot-map policy or None): per-request
        tier routing through the engine's `tiers:<name>` policy.
      * ``am_backend`` surrogate_*: a single-tier set over ``am_policy`` —
        same row-routed moment path, so surrogate noise is keyed by the
        request-local position (slot/schedule independent) here too.
      * ``am_backend`` bitexact_*: whole-batch bit-level emulation
        (validation scale; incompatible with ``tiers``).
      * default: exact.

    ``mode="batched"`` advances all live slots in ONE jitted dispatch per
    tick; ``mode="per_slot"`` drives the same executable one live slot at a
    time (the measured baseline, bitwise identical per row).
    """

    def __init__(self, cfg, mesh, slots: int = 4, ctx: int = 128, seed: int = 0,
                 am_backend: str | None = None,
                 am_policy: str = "uniform:pm_csi",
                 tiers: dict[str, str | None] | None = None,
                 mode: str = "batched", prefill_chunk: int = 8,
                 audit_fraction: float = 0.0):
        if mode not in ("batched", "per_slot"):
            raise ValueError(f"mode must be 'batched' or 'per_slot', got {mode!r}")
        if tiers is not None and am_backend and am_backend.startswith("bitexact"):
            raise ValueError(
                "per-request tiers ride the surrogate moment path; bit-exact "
                "backends emulate the whole batch under one map")
        if tiers is None and am_backend and am_backend != "exact" and \
                not am_backend.startswith("bitexact"):
            tiers = {"default": am_policy}  # single-tier surrogate serving
        if tiers:
            tiers = dict(tiers)
            set_name = "serve/" + "|".join(f"{t}={p}" for t, p in tiers.items())
            engine.register_tier_set(set_name, tuple(tiers.values()))
            cfg = cfg.with_numerics(amlinear.NumericsConfig.for_tier_set(set_name))
            self._tier_names: tuple[str, ...] | None = tuple(tiers)
            self._tier_index = {t: i for i, t in enumerate(tiers)}
        else:
            if am_backend and am_backend != "exact":
                cfg = cfg.with_numerics(
                    amlinear.NumericsConfig.for_backend(am_backend, policy=am_policy))
            self._tier_names = None
            self._tier_index = {}
        self.cfg = cfg
        # Shadow-exact audits replay sampled finished requests under this
        # exact-numerics twin of the serving config (same arch/params).
        self._cfg_exact = cfg.with_numerics(amlinear.EXACT)
        self.audit_fraction = min(1.0, max(0.0, float(audit_fraction)))
        self._audit_salt = seed
        self._audit_pending: list[Request] = []
        self.audit_results: list[dict] = []
        self._jit_audit_tier = None
        self._jit_audit_exact = None
        self.mesh = mesh
        self.slots = slots
        self.ctx = ctx
        self.mode = mode
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.params = R.init_params(cfg, jax.random.PRNGKey(seed))
        # Committed to the mesh from the start, as the step's outputs are, so
        # the first reset sees the same input shardings as every later one
        # (one trace, not two).
        self.cache = jax.device_put(R.init_cache(cfg, slots, ctx),
                                    NamedSharding(mesh, PartitionSpec()))
        # Pristine per-slot state for slot recycling. Distinct device buffers
        # (the live cache is donated to the jitted step/reset calls).
        self._fresh = jax.tree.map(jnp.copy, self.cache)
        self._batch_axes = R.cache_batch_axes(cfg)
        # Position budget: None for recurrent/rolling-window archs (O(1)
        # state / position-correct masks); ctx for full attention, where
        # overflowing would roll the cache over live entries.
        self._limit = R.serve_position_limit(cfg, ctx)
        self.active: list[Request | None] = [None] * slots
        self.pos = np.zeros(slots, np.int32)       # tokens written per slot
        self._fed = np.zeros(slots, np.int32)      # prompt tokens consumed
        self._tier_rows = np.zeros(slots, np.int32)
        # Each row's most recent prediction, on the device: a decode tick
        # feeds it back without the host reading it first.
        self._last = jax.device_put(np.zeros(slots, np.int32),
                                    NamedSharding(mesh, PartitionSpec()))
        # The dispatched round whose tokens the host has not read yet.
        self._inflight: _Round | None = None
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.stats = {"dispatches": 0, "decode_ticks": 0, "prefill_rounds": 0,
                      "generated": 0, "prefill_tokens": 0, "overlapped": 0}
        # Surrogate noise: ONE key for the whole server, closed over by the
        # jitted step (concrete, so callsite fold_in chains constant-fold).
        # The engine folds in each row's request-local position (never the
        # slot index or schedule) — see engine.row_tier_context.
        self._needs_key = cfg.numerics.mode == "surrogate"
        self._noise_key = jax.random.PRNGKey(seed + 1)
        self._jit_step = self._build_step()
        self._jit_reset = self._build_reset()

    def _build_step(self):
        dec = R.decode_fn(self.cfg)
        cfg = self.cfg
        tiered = self._tier_names is not None
        needs_key = self._needs_key
        noise_key = self._noise_key
        batch_axes = self._batch_axes

        def step(params, cache, last, tokens, feed_last, pos0, lens, tiers):
            """Advance row r through tokens[r, :lens[r]] (lens[r]=0: idle).

            tokens (B, T) i32; last (B,) i32, each row's most recent
            prediction; feed_last (B,) bool: the row's token 0 is
            last[r], not tokens[r, 0]; pos0/lens/tiers (B,) i32. Returns
            (next_token (B,), last, cache): next_token[r] is the greedy
            prediction from row r's LAST fed token (-1 for idle rows), and
            last keeps a row's old value where next_token is -1. T=1 with
            lens=live is one decode tick; T=prefill_chunk is batched
            prefill. One dispatch either way.
            """
            t_chunk = tokens.shape[1]
            tokens = tokens.at[:, 0].set(
                jnp.where(feed_last, last, tokens[:, 0]))

            def body(carry, t):
                cache, nxt = carry
                live = t < lens
                pos = pos0 + t
                key = noise_key if needs_key else None
                if tiered:
                    with engine.row_tier_context(tiers, pos):
                        logits, new_cache = dec(
                            params, cache, tokens[:, t], pos, cfg, key=key)
                else:
                    logits, new_cache = dec(
                        params, cache, tokens[:, t], pos, cfg, key=key)

                def merge(ax, new, old):
                    if ax < 0:
                        return new
                    m = live.reshape(
                        (1,) * ax + (-1,) + (1,) * (new.ndim - ax - 1))
                    return jnp.where(m, new, old)

                merged = jax.tree.map(merge, batch_axes, new_cache, cache)
                pred = jnp.argmax(logits, -1).astype(jnp.int32)
                nxt = jnp.where(t == lens - 1, pred, nxt)
                return (merged, nxt), None

            init = (cache, jnp.full((tokens.shape[0],), -1, jnp.int32))
            (cache, nxt), _ = jax.lax.scan(body, init, jnp.arange(t_chunk))
            return nxt, jnp.where(nxt >= 0, nxt, last), cache

        # Exactly 2 traces per instance: T=prefill_chunk and T=1. More means
        # shape churn; fewer after a numerics change means a stale cache.
        return watchdog.watch_jit(step, name="serve.step",
                                  donate_argnums=(1, 2))

    # --- request lifecycle -------------------------------------------------

    def submit(self, req: Request) -> Request:
        """Queue a request, or reject it (status/error set, surfaced in the
        results run() returns) when it cannot be served."""
        req.submitted_at = time.perf_counter()
        err = self._admission_error(req)
        if err is not None:
            req.status, req.error, req.done = "rejected", err, True
            req.finished_at = req.submitted_at
            self.finished.append(req)
            obs.instant("serve.reject", rid=req.rid, tier=req.tier)
            obs.metrics.counter_inc("serve.rejected", tier=req.tier)
            return req
        req.status = "queued"
        self.queue.append(req)
        obs.async_begin("serve.request", req.rid, tier=req.tier,
                        prompt_len=len(req.prompt), max_new=req.max_new)
        return req

    def _admission_error(self, req: Request) -> str | None:
        if len(req.prompt) == 0:
            return "empty prompt: prefill needs at least one token"
        if req.max_new < 1:
            return f"max_new must be >= 1, got {req.max_new}"
        if (self._tier_names is not None and len(self._tier_names) > 1
                and req.tier not in self._tier_index):
            return (f"unknown tier {req.tier!r}; this server serves "
                    f"{self._tier_names}")
        if self._limit is not None and len(req.prompt) + req.max_new > self._limit:
            return (f"context budget exceeded: prompt {len(req.prompt)} + "
                    f"max_new {req.max_new} > {self._limit} cache positions "
                    "(the full-attention KV cache would roll over and attend "
                    "to overwritten entries)")
        return None

    def _tier_id(self, req: Request) -> int:
        if self._tier_names is None or len(self._tier_names) == 1:
            return 0
        return self._tier_index[req.tier]

    def _build_reset(self):
        """One jitted masked merge restoring admitted slots' cache slices to
        the pristine init state — a single dispatch per admission wave (the
        per-slot ``.at[].set`` host loop this replaces cost more than the
        decode ticks it fed)."""
        batch_axes = self._batch_axes

        def reset(cache, fresh, mask):
            def leaf(ax, cur, fr):
                if ax < 0:
                    return cur
                m = mask.reshape(
                    (1,) * ax + (-1,) + (1,) * (cur.ndim - ax - 1))
                return jnp.where(m, fr, cur)

            return jax.tree.map(leaf, batch_axes, cache, fresh)

        return watchdog.watch_jit(reset, name="serve.reset",
                                  donate_argnums=(0,))

    def _admit(self):
        with obs.span("serve.admit"):
            fresh: list[int] = []
            for i in range(self.slots):
                if self.active[i] is None and self.queue:
                    req = self.queue.pop(0)
                    self.active[i] = req
                    req.status = "active"
                    obs.async_instant("serve.request", req.rid, "admit",
                                      slot=i)
                    self.pos[i] = 0
                    self._fed[i] = 0
                    self._tier_rows[i] = self._tier_id(req)
                    fresh.append(i)
            if fresh:
                mask = np.zeros(self.slots, bool)
                mask[fresh] = True
                with jax.set_mesh(self.mesh):
                    self.cache = self._jit_reset(self.cache, self._fresh,
                                                 jnp.asarray(mask))

    # --- dispatch ----------------------------------------------------------

    def _pending(self, i: int) -> str | None:
        """What row i still has to dispatch, from host state alone: the
        rest of its prompt, one more decode tick, or nothing (its last
        token is dispatched, maybe not yet read)."""
        req = self.active[i]
        if req is None:
            return None
        if self._fed[i] < len(req.prompt):
            return "prefill"
        if self.pos[i] < len(req.prompt) + req.max_new - 1:
            return "decode"
        return None

    def _has_work(self) -> bool:
        """Whether the next round will have something to dispatch."""
        return (any(self._pending(i) for i in range(self.slots))
                or (bool(self.queue)
                    and any(r is None for r in self.active)))

    def _enqueue(self, tokens: np.ndarray, lens: np.ndarray,
                 feed_last: np.ndarray) -> jax.Array:
        """One step over the rows `lens` selects: its arguments packed into
        device arrays (fresh host copies, so advancing `pos` after the
        enqueue cannot reach the step), the step enqueued. Returns the
        step's next tokens, still on the device."""
        if self._inflight is not None:
            self.stats["overlapped"] += 1
        with jax.set_mesh(self.mesh):
            with obs.span("serve.pack"):
                args = (jnp.asarray(tokens), jnp.asarray(feed_last),
                        jnp.asarray(self.pos.copy()), jnp.asarray(lens),
                        jnp.asarray(self._tier_rows.copy()))
            with obs.span("serve.dispatch", mode=self.mode,
                          chunk=tokens.shape[1]):
                nxt, self._last, self.cache = self._jit_step(
                    self.params, self.cache, self._last, *args)
        self.stats["dispatches"] += 1
        return nxt

    def _dispatch(self) -> _Round | None:
        """Pack and enqueue the next round from host state alone: a prefill
        round while any row has prompt left (decode rows sit idle), else a
        decode tick whose rows take their token from the device's `last`.
        Advances `pos` and the prompt cursor at dispatch. Batched: ONE
        dispatch advances every busy row. per_slot: the same executable
        once per busy row, single-row lens mask (the reference; bitwise
        identical per row since every decode op is row-local)."""
        want = [self._pending(i) for i in range(self.slots)]
        if not any(want):
            return None
        kind = "prefill" if "prefill" in want else "decode"
        t = self.prefill_chunk if kind == "prefill" else 1
        with obs.span("serve.pack"):
            tokens = np.zeros((self.slots, t), np.int32)
            lens = np.zeros(self.slots, np.int32)
            emits: list[tuple[int, Request]] = []
            for i, w in enumerate(want):
                if w != kind:
                    continue
                req = self.active[i]
                if kind == "decode":
                    lens[i] = 1
                    emits.append((i, req))
                    continue
                lo = int(self._fed[i])
                nloc = min(len(req.prompt) - lo, t)
                tokens[i, :nloc] = req.prompt[lo:lo + nloc]
                lens[i] = nloc
                if lo + nloc == len(req.prompt):
                    # The prediction from the last prompt position IS the
                    # first decode token: the final prompt token is cached
                    # exactly once (prefill's last step), never re-fed.
                    emits.append((i, req))
            feed_last = np.full(self.slots, kind == "decode")
        if self.mode == "batched":
            nxt = [(np.flatnonzero(lens), self._enqueue(tokens, lens,
                                                        feed_last))]
        else:
            nxt = []
            for i in np.flatnonzero(lens):
                solo = np.zeros_like(lens)
                solo[i] = lens[i]
                nxt.append(([i], self._enqueue(tokens, solo, feed_last)))
        if kind == "prefill":
            self._fed += lens
        self.pos += lens
        return _Round(kind, nxt, emits, int(lens.sum()))

    def _read(self, rnd: _Round):
        """Wait for a dispatched round's tokens, then emit each to the
        request it was dispatched for and count the round."""
        out = np.full(self.slots, -1, np.int32)
        for rows, nxt in rnd.nxt:
            with obs.span("serve.sync"):
                out[rows] = np.asarray(nxt)[rows]
        with obs.span("serve.emit"):
            if rnd.kind == "prefill":
                self.stats["prefill_rounds"] += 1
                self.stats["prefill_tokens"] += rnd.fed
            else:
                self.stats["decode_ticks"] += 1
            for i, req in rnd.emits:
                # A request holds its slot until its last token is read, so
                # the slot still holds the request the round was for.
                assert self.active[i] is req, (i, req.rid)
                if rnd.kind == "prefill":
                    obs.async_instant("serve.request", req.rid,
                                      "prefill_done", slot=i,
                                      prompt_len=len(req.prompt))
                self._emit(i, int(out[i]))

    def _emit(self, i: int, tok: int):
        req = self.active[i]
        req.out.append(tok)
        self.stats["generated"] += 1
        obs.metrics.counter_inc("serve.tokens", tier=req.tier)
        if len(req.out) >= req.max_new:
            req.done = True
            req.status = "done"
            req.finished_at = time.perf_counter()
            self.finished.append(req)
            self.active[i] = None
            if self._audit_sampled(req):
                # Defer the trace-lifecycle end: run_audits() appends the
                # audit span/instant to this request's async track and
                # closes it. The hot path only queues the reference.
                self._audit_pending.append(req)
                obs.async_instant("serve.request", req.rid, "audit_pending")
            else:
                obs.async_end("serve.request", req.rid, tokens=len(req.out))

    def reset_metrics(self) -> None:
        """Zero the counters and drop finished requests (benchmark warmup:
        the jitted step is cached per Server instance, so a measured pass
        must reuse the instance a warmup pass compiled)."""
        self.finished.clear()
        self._audit_pending.clear()
        self.audit_results.clear()
        self.stats = {k: 0 for k in self.stats}

    # --- shadow-exact audits (off the hot path) ----------------------------
    #
    # A deterministic fraction of finished requests — sampled by a pure
    # hash of (server seed, request id), never the slot, schedule, or
    # admission time — is replayed teacher-forced through two jitted scans:
    # once under the serving numerics (which, by the slot-isolation + CRN
    # position-keying contract, bitwise reproduces the served logits) and
    # once under the exact-numerics twin config. Per-tier token agreement
    # (did exact greedy decoding pick the served token?) and max logit
    # divergence go out as metrics; an `audit` phase lands on the request's
    # async trace track. run() NEVER calls this — callers invoke
    # run_audits() after the serving burst, so audits cost the hot path
    # nothing beyond the sampling hash (gated ≤5% in CI by loadgen).

    def _audit_sampled(self, req: Request) -> bool:
        if self.audit_fraction <= 0.0 or not obs.enabled():
            return False
        from repro.obs import numerics as obs_numerics

        u = obs_numerics.request_sample_u(self._audit_salt, str(req.rid))
        return u < self.audit_fraction

    def _build_audit_step(self, exact: bool):
        """Teacher-forced replay step: feed tokens[r, t] at position t for
        t < lens[r], returning the stacked per-step logits (T, B, V).

        Same masked-merge scan as the serving step (padded steps cannot
        corrupt the cache) at the serving batch width, so the tier replay
        runs the bitwise-identical row arithmetic the live dispatch ran.
        """
        cfg = self._cfg_exact if exact else self.cfg
        dec = R.decode_fn(cfg)
        tiered = (not exact) and self._tier_names is not None
        needs_key = (not exact) and self._needs_key
        noise_key = self._noise_key
        batch_axes = self._batch_axes

        def audit_step(params, cache, tokens, lens, tiers):
            def body(cache, t):
                live = t < lens
                pos = jnp.zeros_like(lens) + t
                key = noise_key if needs_key else None
                if tiered:
                    with engine.row_tier_context(tiers, pos):
                        logits, new_cache = dec(
                            params, cache, tokens[:, t], pos, cfg, key=key)
                else:
                    logits, new_cache = dec(
                        params, cache, tokens[:, t], pos, cfg, key=key)

                def merge(ax, new, old):
                    if ax < 0:
                        return new
                    m = live.reshape(
                        (1,) * ax + (-1,) + (1,) * (new.ndim - ax - 1))
                    return jnp.where(m, new, old)

                merged = jax.tree.map(merge, batch_axes, new_cache, cache)
                return merged, logits

            _, seq = jax.lax.scan(body, cache, jnp.arange(tokens.shape[1]))
            return seq  # (T, B, vocab)

        name = "serve.audit_exact" if exact else "serve.audit_tier"
        return watchdog.watch_jit(audit_step, name=name)

    def _shadow_rescore(self, req: Request) -> dict:
        served = np.asarray(req.out, np.int64)
        fed = np.concatenate([np.asarray(req.prompt, np.int32),
                              served[:-1].astype(np.int32)])
        t_in = len(fed)
        tpad = 1 << max(0, (t_in - 1).bit_length())  # pow2: bounded retraces
        tokens = np.zeros((self.slots, tpad), np.int32)
        tokens[0, :t_in] = fed
        lens = np.zeros(self.slots, np.int32)
        lens[0] = t_in
        tiers = np.zeros(self.slots, np.int32)
        tiers[0] = self._tier_id(req)
        if self._jit_audit_tier is None:
            self._jit_audit_tier = self._build_audit_step(exact=False)
            self._jit_audit_exact = self._build_audit_step(exact=True)
        with jax.set_mesh(self.mesh):
            # self._fresh is never donated or mutated here: both replays
            # start from the pristine cache a fresh admission would get.
            lg_t = np.asarray(self._jit_audit_tier(
                self.params, self._fresh, jnp.asarray(tokens),
                jnp.asarray(lens), jnp.asarray(tiers)), np.float64)
            lg_e = np.asarray(self._jit_audit_exact(
                self.params, self._fresh, jnp.asarray(tokens),
                jnp.asarray(lens), jnp.asarray(tiers)), np.float64)
        # Predictive positions: the logits that produced each served token
        # (last prompt position through the second-to-last output).
        sl = slice(len(req.prompt) - 1, t_in)
        replay_pred = np.argmax(lg_t[sl, 0, :], axis=-1)
        exact_pred = np.argmax(lg_e[sl, 0, :], axis=-1)
        return {
            "rid": req.rid,
            "tier": req.tier,
            "tokens": int(served.size),
            "token_agreement": float(np.mean(exact_pred == served)),
            "max_logit_divergence": float(
                np.max(np.abs(lg_t[sl, 0, :] - lg_e[sl, 0, :]))),
            "replay_mismatches": int(np.sum(replay_pred != served)),
        }

    def run_audits(self) -> list[dict]:
        """Run the deferred shadow-exact audits; returns per-request dicts.

        Call after the serving burst (run()) — never interleaved with it.
        """
        out: list[dict] = []
        while self._audit_pending:
            req = self._audit_pending.pop(0)
            t0 = time.perf_counter()
            with obs.span("serve.audit", rid=req.rid, tier=req.tier):
                res = self._shadow_rescore(req)
            res["seconds"] = time.perf_counter() - t0
            obs.async_instant(
                "serve.request", req.rid, "audit",
                token_agreement=res["token_agreement"],
                max_logit_divergence=res["max_logit_divergence"])
            obs.async_end("serve.request", req.rid, tokens=len(req.out))
            obs.metrics.counter_inc("serve.audit.requests", tier=req.tier)
            obs.metrics.observe("serve.audit.token_agreement",
                                res["token_agreement"], tier=req.tier)
            obs.metrics.observe("serve.audit.max_logit_divergence",
                                res["max_logit_divergence"], tier=req.tier)
            if res["replay_mismatches"]:
                obs.metrics.counter_inc("serve.audit.replay_mismatch",
                                        res["replay_mismatches"],
                                        tier=req.tier)
            self.audit_results.append(res)
            out.append(res)
        return out

    def audit_summary(self) -> dict:
        """Aggregate audit_results per tier (token-weighted agreement)."""
        tiers: dict[str, dict] = {}
        for r in self.audit_results:
            t = tiers.setdefault(r["tier"], {
                "requests": 0, "tokens": 0, "agree_tokens": 0.0,
                "max_logit_divergence": 0.0, "replay_mismatches": 0})
            t["requests"] += 1
            t["tokens"] += r["tokens"]
            t["agree_tokens"] += r["token_agreement"] * r["tokens"]
            t["max_logit_divergence"] = max(t["max_logit_divergence"],
                                            r["max_logit_divergence"])
            t["replay_mismatches"] += r["replay_mismatches"]
        for t in tiers.values():
            t["token_agreement"] = t.pop("agree_tokens") / max(t["tokens"], 1)
        return {
            "audited_requests": len(self.audit_results),
            "tiers": dict(sorted(tiers.items())),
        }

    # --- schedule ----------------------------------------------------------

    def run(self, max_steps: int | None = None) -> list[Request]:
        """Drive the schedule until all submitted work finishes (or
        ``max_steps`` scheduling rounds elapse). Returns every finished
        request — completed AND rejected, in finish order; results also
        live on the Request objects (out/status/error).

        A round admits into free slots, dispatches the next round from
        host state, then reads the previous round's tokens, so the host's
        work overlaps the step in flight. A round that leaves nothing to
        dispatch next reads its own round too: run() never returns owing
        tokens with the queue and every slot empty. A request holds its
        slot until its last token is read; per_slot mode reads each round
        within it. Rounds are counted in ``stats`` when read."""
        rounds = 0
        while max_steps is None or rounds < max_steps:
            if not self.queue and not any(r is not None for r in self.active):
                break
            with obs.span("serve.round"):
                self._admit()
                rnd = self._dispatch()
                if self._inflight is not None:
                    self._read(self._inflight)
                self._inflight = rnd
                if rnd is not None and (self.mode == "per_slot"
                                        or not self._has_work()):
                    self._read(rnd)
                    self._inflight = None
            rounds += 1
        return list(self.finished)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Continuous-batching AM serving smoke driver")
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--ctx", type=int, default=64)
    ap.add_argument("--mode", default="batched", choices=("batched", "per_slot"))
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--am-backend", default=None,
                    choices=(None, *engine.BACKEND_NAMES),
                    help="AM engine backend for every projection matmul "
                         "(bitexact_* are validation-scale only)")
    ap.add_argument("--am-policy", default="uniform:pm_csi",
                    help="tile->variant policy (uniform:<v> | rr:<K> | seq:<name>)")
    ap.add_argument("--tiers", default=None,
                    help="comma-separated tier names from "
                         f"{tuple(DEFAULT_TIER_POLICIES)} — enables "
                         "per-request tier routing; requests cycle through "
                         "the listed tiers")
    ap.add_argument("--obs", dest="obs", action="store_true", default=None,
                    help="enable tracing/metrics (default: env REPRO_OBS)")
    ap.add_argument("--no-obs", dest="obs", action="store_false")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="write trace_serve.json + metrics_serve.json here "
                         "(implies --obs)")
    ap.add_argument("--audit-fraction", type=float, default=0.0,
                    help="shadow-exact audit fraction of finished requests "
                         "(implies --obs; 0 disables)")
    args = ap.parse_args()
    compile_cache.enable()
    if (args.trace_out is not None or args.audit_fraction > 0) \
            and args.obs is None:
        args.obs = True
    if args.obs is not None:
        obs.set_enabled(args.obs)

    tiers = None
    tier_cycle = ("exact",)
    if args.tiers:
        names = tuple(t.strip() for t in args.tiers.split(","))
        unknown = [t for t in names if t not in DEFAULT_TIER_POLICIES]
        if unknown:
            ap.error(f"unknown tiers {unknown}; have {tuple(DEFAULT_TIER_POLICIES)}")
        tiers = {t: DEFAULT_TIER_POLICIES[t] for t in names}
        tier_cycle = names

    spec = R.get(args.arch)
    cfg = spec.smoke
    server = Server(cfg, meshlib.make_host_mesh(), slots=args.slots,
                    ctx=args.ctx, am_backend=args.am_backend,
                    am_policy=args.am_policy, tiers=tiers, mode=args.mode,
                    prefill_chunk=args.prefill_chunk,
                    audit_fraction=args.audit_fraction)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new=args.max_new, tier=tier_cycle[i % len(tier_cycle)])
            for i in range(args.requests)]
    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    server.run()
    wall = time.perf_counter() - t0
    backend = args.am_backend or ("tiers" if tiers else "exact")
    tps = server.stats["generated"] / max(wall, 1e-9)
    print(f"[serve] arch={args.arch} mode={args.mode} am={backend} "
          f"slots={args.slots} gen={server.stats['generated']} "
          f"dispatches={server.stats['dispatches']} tok/s={tps:.1f}")
    for r in reqs:
        if r.status == "rejected":
            print(f"req {r.rid} [{r.tier}] REJECTED: {r.error}")
        else:
            print(f"req {r.rid} [{r.tier}] prompt={r.prompt.tolist()} -> "
                  f"out={r.out}")
    if args.audit_fraction > 0:
        server.run_audits()
        summary = server.audit_summary()
        print(f"[serve] shadow audits: {summary['audited_requests']} "
              f"request(s)")
        for tier, agg in summary["tiers"].items():
            print(f"  {tier:14s} agreement={agg['token_agreement']:.3f} "
                  f"max_div={agg['max_logit_divergence']:.3e} "
                  f"replay_mismatch={agg['replay_mismatches']}")
    if args.trace_out is not None:
        import pathlib

        out_dir = pathlib.Path(args.trace_out)
        out_dir.mkdir(parents=True, exist_ok=True)
        obs.export_trace(out_dir / "trace_serve.json")
        obs.export_metrics(out_dir / "metrics_serve.json")
        print(f"[serve] trace + metrics written to {out_dir}/")


if __name__ == "__main__":
    main()
