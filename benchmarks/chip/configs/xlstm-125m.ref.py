"""Plain reference of xlstm-125m: the recurrent form of the xLSTM blocks
(arXiv:2405.04517, Sec. 2) in straightforward jax.numpy and float32, every
contraction at HIGHEST precision, from the configuration file alone. It
imports nothing of the program and makes its own weights from the seed by
the rule the configuration states.

A block is x + mixer(rms_norm(x)); rms_norm(x, w) = x / sqrt(mean(x^2) +
1e-6) * (1 + w). The embedding is scaled by sqrt(d_model); the head reads
rms_norm of the last block's output.

  mLSTM (matrix memory per head; k scaled by 1/sqrt(d_head)):
    m_t = max(log f_t + m_{t-1}, log i_t),  log f = -softplus(-x W_f),
    log i = x W_i;  f' = exp(log f_t + m_{t-1} - m_t), i' = exp(log i_t - m_t)
    C_t = f' C_{t-1} + i' k v^T,  n_t = f' n_{t-1} + i' k
    h_t = (C_t^T q) / max(|n_t . q|, exp(-m_t)) * sigmoid(x W_o), then W_out
  sLSTM (scalar memory, recurrent matrices R):
    z = tanh(x W_z + h R_z), log i = x W_i + h R_i,
    log f = -softplus(-(x W_f + h R_f)), o = sigmoid(x W_o + h R_o)
    m, i', f' as above; c = f' c + i' z; n = f' n + i'
    h = o c / max(n, 1e-6), then W_out

Every request tier is computed as exact: the approximate tiers change a
product by |mu| + sigma <= 2.1e-7 of itself (the configuration's numerics),
under a thousandth of the bfloat16 rounding the served step makes.

`quant="fp8"` is the control: each contraction's operands are rounded to
float8_e4m3fn with a per-tensor scale (weights) or a per-row scale
(activations), one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math

import numpy as np


def _param_defs(cfg: dict) -> dict:
    """The parameter tree: leaf -> (shape, fan-in; None for zeros)."""
    d, h, dh, v = cfg["d_model"], cfg["n_heads"], cfg["d_head"], cfg["vocab"]
    reps = cfg["n_layers"] // len(cfg["pattern"])
    # leaf: (shape without the block axis, fan-in)
    mlstm = {"wq": ((d, h, dh), d), "wk": ((d, h, dh), d),
             "wv": ((d, h, dh), d), "w_i": ((d, h), d), "w_f": ((d, h), d),
             "w_o": ((d, h, dh), d), "wo": ((h, dh, d), h * dh)}
    slstm = {n: ((d, d), d) for n in ("w_z", "w_i", "w_f", "w_o", "r_z",
                                      "r_i", "r_f", "r_o", "w_out")}
    blocks = {}
    for j, kind in enumerate(cfg["pattern"]):
        mix = mlstm if kind == "mlstm" else slstm
        blocks[f"l{j}"] = {
            "ln1": ((reps, d), None),
            "mixer": {n: ((reps,) + s, f) for n, (s, f) in mix.items()},
        }
    return {"embed": ((v, d), v), "head": ((d, v), d),
            "norm_f": ((d,), None), "blocks": blocks}


def _leaves(tree: dict, prefix=()):
    for k in sorted(tree):
        node = tree[k]
        if isinstance(node, dict):
            yield from _leaves(node, prefix + (k,))
        else:
            yield prefix + (k,), node


def make_weights(cfg: dict, seed: int) -> dict:
    """Weights by the configuration's rule (one jitted call, as the served
    weights are made), returned as float32 holding bfloat16 values."""
    import jax
    import jax.numpy as jnp

    leaves = list(_leaves(_param_defs(cfg)))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        vals = []
        for (_, (shape, fan_in)), k in zip(leaves, keys):
            if fan_in is None:
                vals.append(jnp.zeros(shape, jnp.float32))
            else:
                v = jax.random.normal(k, shape, jnp.float32) * (
                    1.0 / math.sqrt(fan_in))
                vals.append(v.astype(cfg["dtype"]).astype(jnp.float32))
        return vals

    out: dict = {}
    for (path, _), val in zip(leaves, make(jax.random.PRNGKey(seed))):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = val
    return out


def _quant_fp8(x, axis):
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def logits_at(cfg: dict, weights: dict, tokens: np.ndarray,
              positions: np.ndarray, quant: str | None = None) -> np.ndarray:
    """Logits (N, vocab) at (row, position) pairs `positions` (N, 2) of a
    teacher-forced pass over `tokens` (B, T)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    d, h, dh = cfg["d_model"], cfg["n_heads"], cfg["d_head"]
    reps = cfg["n_layers"] // len(cfg["pattern"])

    def mm(x, w):  # x (..., K) @ w (K, N)
        if quant == "fp8":
            x = _quant_fp8(x, -1)
            w = _quant_fp8(w, None)
        return jnp.matmul(x, w, precision=hi)

    def norm(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)

    def mlstm(p, x, st):
        C, n, m = st
        b = x.shape[0]
        q = mm(x, p["wq"].reshape(d, h * dh)).reshape(b, h, dh)
        k = mm(x, p["wk"].reshape(d, h * dh)).reshape(b, h, dh) / math.sqrt(dh)
        v = mm(x, p["wv"].reshape(d, h * dh)).reshape(b, h, dh)
        lf = -jax.nn.softplus(-mm(x, p["w_f"]))
        li = mm(x, p["w_i"])
        m_new = jnp.maximum(lf + m, li)
        fg = jnp.exp(lf + m - m_new)[..., None]
        ig = jnp.exp(li - m_new)[..., None]
        C = fg[..., None] * C + ig[..., None] * (k[..., :, None] * v[..., None, :])
        n = fg * n + ig * k
        num = jnp.einsum("bhkv,bhk->bhv", C, q, precision=hi)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, q, precision=hi)),
                          jnp.exp(-m_new))
        og = jax.nn.sigmoid(mm(x, p["w_o"].reshape(d, h * dh)))
        out = (num / den[..., None]).reshape(b, h * dh) * og
        return mm(out, p["wo"].reshape(h * dh, d)), (C, n, m_new)

    def slstm(p, x, st):
        c, n, hp, m = st
        z = jnp.tanh(mm(x, p["w_z"]) + mm(hp, p["r_z"]))
        li = mm(x, p["w_i"]) + mm(hp, p["r_i"])
        lf = -jax.nn.softplus(-(mm(x, p["w_f"]) + mm(hp, p["r_f"])))
        o = jax.nn.sigmoid(mm(x, p["w_o"]) + mm(hp, p["r_o"]))
        m_new = jnp.maximum(lf + m, li)
        ig = jnp.exp(li - m_new)
        fg = jnp.exp(lf + m - m_new)
        c = fg * c + ig * z
        n = fg * n + ig
        hn = o * (c / jnp.maximum(n, 1e-6))
        return mm(hn, p["w_out"]), (c, n, hn, m_new)

    layers = [(r, j, kind) for r in range(reps)
              for j, kind in enumerate(cfg["pattern"])]

    def init_state(b):
        st = []
        for _, _, kind in layers:
            if kind == "mlstm":
                st.append((jnp.zeros((b, h, dh, dh)), jnp.zeros((b, h, dh)),
                           jnp.full((b, h), -1e30)))
            else:
                st.append((jnp.zeros((b, d)),) * 3 + (jnp.full((b, d), -1e30),))
        return st

    @jax.jit
    def hidden(w, toks):
        def step(st, tok):
            x = w["embed"][tok] * math.sqrt(d)
            new = []
            for (r, j, kind), s in zip(layers, st):
                blk = w["blocks"][f"l{j}"]
                p = {k: v[r] for k, v in blk["mixer"].items()}
                y, s = (mlstm if kind == "mlstm" else slstm)(
                    p, norm(x, blk["ln1"][r]), s)
                x = x + y
                new.append(s)
            return new, norm(x, w["norm_f"])

        _, hs = jax.lax.scan(step, init_state(toks.shape[0]), toks.T)
        return jnp.swapaxes(hs, 0, 1)  # (B, T, d)

    @jax.jit
    def head(w, hsel):
        return mm(hsel, w["head"])

    hs = hidden(weights, jnp.asarray(tokens, jnp.int32))
    pos = np.asarray(positions)
    hsel = hs[jnp.asarray(pos[:, 0]), jnp.asarray(pos[:, 1])]
    return np.asarray(head(weights, hsel), np.float64)


def served_batch(requests: list) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Teacher-forced inputs of finished requests: each prompt followed by
    its served tokens but the last; the (row, position) whose logits chose
    each served token; and the served tokens."""
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(o[:-1], np.int32)])
            for p, o in requests]
    t = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), t), np.int32)
    pos, served = [], []
    for r, ((p, o), s) in enumerate(zip(requests, seqs)):
        tokens[r, : len(s)] = s
        for i, tok in enumerate(o):
            pos.append((r, len(p) - 1 + i))
            served.append(int(tok))
    return tokens, np.asarray(pos, np.int64), np.asarray(served, np.int64)


def compare(cfg: dict, requests: list, seed: int) -> dict:
    """The numbers that decide `correct`: the widest gap by which a served
    token's reference logit lies below the reference's best at that
    position, and the share of served tokens that are not the reference's
    first choice. `requests` holds (prompt, served tokens) pairs."""
    weights = make_weights(cfg, seed)
    tokens, pos, served = served_batch(requests)
    ref = logits_at(cfg, weights, tokens, pos)
    return _gaps(ref, served)


def _gaps(ref: np.ndarray, chosen: np.ndarray) -> dict:
    gap = ref.max(-1) - ref[np.arange(len(chosen)), chosen]
    return {"logit_gap": float(gap.max()),
            "logit_gap_mean": float(gap.mean()),
            "token_mismatch_share": float(np.mean(gap > 0))}


def control(cfg: dict, requests: list, seed: int) -> dict:
    """The control's reading at the same prompts and tokens: the gap, under
    the reference, of the token the fp8 computation puts first."""
    weights = make_weights(cfg, seed)
    tokens, pos, served = served_batch(requests)
    ref = logits_at(cfg, weights, tokens, pos)
    low = logits_at(cfg, weights, tokens, pos, quant="fp8")
    return _gaps(ref, low.argmax(-1))
