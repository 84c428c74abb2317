"""Bit-exact emulation of the paper's approximate FP32 multipliers.

Pipeline (paper Sec. II): sign XOR | exponent add with bias correction |
24x24 mantissa multiply via radix-8 modified Booth PP generation and a 3-stage
4:2-compressor reduction tree, approximate in columns 0..23 (core/schemes.py),
followed by normalization and truncation.

Numerics contract (see DESIGN.md Sec. 2):
  * exact-compressor configuration reproduces the integer mantissa product
    bit-for-bit; the packed FP32 result is the truncating-multiplier result
    (<= 1 ulp below IEEE-754 RNE);
  * subnormal inputs honored (implicit bit 0, exp -126); subnormal outputs
    flushed to zero; overflow -> signed Inf; NaN/Inf/zero propagate per IEEE;
  * the 48-bit datapath wraps mod 2^48, as the hardware tree would.

Everything is jnp-traceable (jit / vmap / Pallas kernel bodies). The
``scheme_codes`` argument is an int32 (..., 3, 48) array broadcastable against
the inputs, so a single call can interleave different multiplier variants
per element — the paper's core mechanism.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import booth
from repro.core import compressors as C
from repro.core import schemes
from repro.core.compressors import compress42, cout42

_U32 = jnp.uint32
_I32 = jnp.int32


# ---------------------------------------------------------------------------
# FP32 pack/unpack
# ---------------------------------------------------------------------------


def unpack(x):
    """float32 -> (sign, biased_exp, man24, eff_exp) int32 fields.

    man24 includes the implicit leading bit (0 for subnormals), eff_exp is the
    unbiased exponent of the 1.M / 0.M fixed point (paper Eq. 1).
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), _U32)
    s = (bits >> 31).astype(_I32)
    e = ((bits >> 23) & 0xFF).astype(_I32)
    m = (bits & 0x7FFFFF).astype(_I32)
    man24 = jnp.where(e > 0, m | (1 << 23), m)
    eff_exp = jnp.where(e > 0, e - 127, -126)
    return s, e, m, man24, eff_exp


def pack(sign, biased_exp, man23):
    """(sign, biased exponent in [1,254], 23-bit mantissa) -> float32."""
    bits = (
        (sign.astype(_U32) << 31)
        | (biased_exp.astype(_U32) << 23)
        | man23.astype(_U32)
    )
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# ---------------------------------------------------------------------------
# Compressor tree
# ---------------------------------------------------------------------------


def _shift_left_1(bits):
    """Column shift toward higher significance: out[j] = in[j-1], out[0] = 0."""
    return jnp.concatenate(
        [jnp.zeros_like(bits[..., :1]), bits[..., :-1]], axis=-1
    )


def _compress_stage(r1, r2, r3, r4, codes):
    """One 4:2 stage over all 48 columns. codes: (..., 48) broadcastable."""
    cout = cout42(r1, r2, r3)
    cin = _shift_left_1(cout)
    s, c, _ = compress42(r1, r2, r3, r4, cin, codes)
    return s, _shift_left_1(c)


def mantissa_multiply_bits(a24, b24, scheme_codes):
    """24x24 mantissa multiply through the (possibly approximate) tree.

    Args:
      a24, b24: int32 (...,) in [0, 2^24).
      scheme_codes: int32 (..., 3, 48) compressor-code map (broadcastable).
    Returns:
      (..., 48) {0,1} bit array of the product, little-endian columns.
    """
    ppm = booth.booth_ppm(a24, b24)  # (..., 10, 48)
    rows = [ppm[..., i, :] for i in range(booth.N_ROWS)]

    c0 = scheme_codes[..., 0, :]
    c1 = scheme_codes[..., 1, :]
    c2 = scheme_codes[..., 2, :]

    # Stage 0: rows 0-3 and rows 4-7 through compressors; rows 8,9 pass.
    sA, cA = _compress_stage(rows[0], rows[1], rows[2], rows[3], c0)
    sB, cB = _compress_stage(rows[4], rows[5], rows[6], rows[7], c0)
    # Stage 1: the four stage-0 outputs; PP rows 8,9 pass.
    s1, k1 = _compress_stage(sA, cA, sB, cB, c1)
    # Stage 2: down to two rows.
    s2, k2 = _compress_stage(s1, k1, rows[8], rows[9], c2)

    # Exact final addition (mod 2^48), as the hardware's final adder.
    lo1, hi1 = booth.bits_to_limbs(s2)
    lo2, hi2 = booth.bits_to_limbs(k2)
    lo, hi = booth.limbs_add_mod48(lo1, hi1, lo2, hi2)
    return booth.limbs_to_bits(lo, hi)


# ---------------------------------------------------------------------------
# Full FP32 multiply
# ---------------------------------------------------------------------------


def fp32_multiply(a, b, scheme_codes=None):
    """Emulated FP32 multiply a*b under a compressor scheme.

    Args:
      a, b: float32 arrays (same shape).
      scheme_codes: int32 (..., 3, 48) map; None means the exact multiplier.
    Returns:
      float32 array, bit-accurate w.r.t. the modeled hardware.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if scheme_codes is None:
        scheme_codes = jnp.asarray(schemes.scheme_map("exact"))
    scheme_codes = jnp.asarray(scheme_codes, _I32)

    sa, ea, ma, man_a, ea_eff = unpack(a)
    sb, eb, mb, man_b, eb_eff = unpack(b)
    sign = sa ^ sb

    prod_bits = mantissa_multiply_bits(man_a, man_b, scheme_codes)  # (..., 48)

    # Normalize: leading-one position (47 or 46 for normal inputs; lower for
    # subnormal operands).
    rev = prod_bits[..., ::-1]
    msb = (booth.N_COLS - 1) - jnp.argmax(rev, axis=-1).astype(_I32)
    is_zero_prod = jnp.sum(prod_bits, axis=-1) == 0

    # Extract the 23 bits below the leading one (truncation rounding).
    k = jnp.arange(23, dtype=_I32)  # k=0 -> mantissa LSB
    col = msb[..., None] - 23 + k  # (..., 23)
    valid = col >= 0
    col_c = jnp.clip(col, 0, booth.N_COLS - 1)
    mbits = jnp.take_along_axis(prod_bits, col_c, axis=-1) * valid.astype(_I32)
    man23 = jnp.sum(mbits * (1 << k), axis=-1)

    # Exponent: product value = P * 2^(ea_eff + eb_eff - 46); normalized
    # mantissa is P / 2^msb.
    e_unbiased = ea_eff + eb_eff + (msb - 46)
    e_biased = e_unbiased + 127

    overflow = e_biased >= 255
    underflow = (e_biased <= 0) | is_zero_prod  # FTZ on subnormal outputs

    result = pack(sign, jnp.clip(e_biased, 1, 254), man23)
    result = jnp.where(underflow, pack(sign, jnp.zeros_like(e_biased), jnp.zeros_like(man23)), result)
    inf = pack(sign, jnp.full_like(e_biased, 255), jnp.zeros_like(man23))
    result = jnp.where(overflow, inf, result)

    # IEEE specials.
    a_nan = (ea == 255) & (ma != 0)
    b_nan = (eb == 255) & (mb != 0)
    a_inf = (ea == 255) & (ma == 0)
    b_inf = (eb == 255) & (mb == 0)
    a_zero = (ea == 0) & (ma == 0)
    b_zero = (eb == 0) & (mb == 0)

    nan_out = a_nan | b_nan | (a_inf & b_zero) | (b_inf & a_zero)
    inf_out = (a_inf | b_inf) & ~nan_out
    zero_out = (a_zero | b_zero) & ~nan_out

    result = jnp.where(zero_out, pack(sign, jnp.zeros_like(e_biased), jnp.zeros_like(man23)), result)
    result = jnp.where(inf_out, inf, result)
    qnan = jnp.full(result.shape, jnp.nan, jnp.float32)
    result = jnp.where(nan_out, qnan, result)
    return result


def fp32_multiply_variant(a, b, variant: str):
    """Convenience wrapper: multiply under a named variant (schemes.VARIANTS)."""
    return fp32_multiply(a, b, jnp.asarray(schemes.scheme_map(variant)))


def fp32_multiply_interleaved(a, b, variant_ids):
    """Multiply with a *per-element* variant assignment.

    Args:
      a, b: float32 (...,).
      variant_ids: int32 (...,) in [0, N_VARIANTS) broadcastable to a's
        shape; 0 means exact, 1..8 the paper's AMs, 9.. foundry-registered
        variants (schemes.VARIANTS order).
    Returns:
      float32 (...,).

    This is the paper's interleaving mechanism: each multiplier slot carries
    its own variant. Implemented as a gather of (3, 48) code maps from the
    live registry (the XLA oracle's spelling; the Pallas kernels use the
    packed emulation below with per-slot column masks).
    """
    scheme_stack = jnp.asarray(schemes.scheme_stack())  # (N_VARIANTS, 3, 48)
    codes = scheme_stack[jnp.asarray(variant_ids, _I32)]  # (..., 3, 48)
    return fp32_multiply(a, b, codes)


# ---------------------------------------------------------------------------
# Packed emulation: the Pallas kernels' spelling
# ---------------------------------------------------------------------------
#
# The same datapath with each 48-column row held as two int32 limbs of 24
# columns (lo = columns 0..23, hi = 24..47) instead of a trailing 48-wide
# bit axis. Every column operation of the tree is bitwise, so one limb pair
# carries all 48 columns at once, and a variant's per-column compressor
# choice becomes a column mask per (stage, approximate code): 3 stages x 4
# codes x 2 limbs = 24 int32 words (code_masks). All ops are elementwise
# int32 on arrays of the operands' shape — no gather, scatter or minor bit
# axis — which is what Mosaic lowers, at ~1/16 of the bit tensor's live
# bytes per multiply. Bit-identical to fp32_multiply (tests/test_fp32_mul.py).

_M24 = (1 << 24) - 1
_M23 = (1 << 23) - 1
_APPROX_CODES = (C.PC1, C.PC2, C.NC1, C.NC2)
N_MASK_WORDS = schemes.N_STAGES * len(_APPROX_CODES) * 2


def code_masks(scheme_maps) -> np.ndarray:
    """(..., 3, 48) compressor-code maps -> (..., 24) int32 column masks.

    Word ``(stage * 4 + j) * 2 + limb`` holds the columns of limb ``limb``
    whose stage-``stage`` compressor is ``_APPROX_CODES[j]``.
    """
    maps = np.asarray(scheme_maps, np.int64)
    weights = np.int64(1) << np.arange(24, dtype=np.int64)
    words = []
    for stage in range(schemes.N_STAGES):
        for code in _APPROX_CODES:
            hit = maps[..., stage, :] == code  # (..., 48)
            words.append((hit[..., :24] * weights).sum(-1))
            words.append((hit[..., 24:] * weights).sum(-1))
    return np.stack(words, axis=-1).astype(np.int32)


def mask_stack() -> np.ndarray:
    """(N_VARIANTS, 24) code masks of the live registry, by variant id."""
    return code_masks(schemes.scheme_stack())


def _srl(x, n):
    return jax.lax.shift_right_logical(x, jnp.asarray(n, _I32))


def _shl1_packed(lo, hi):
    """_shift_left_1 on a limb pair (bit 47 falls off)."""
    return (lo << 1) & _M24, ((hi << 1) | (lo >> 23)) & _M24


def booth_packed(a, b):
    """Operand half of the packed emulation: IEEE fields of a and b and the
    10 Booth partial-product rows of man(a) * man(b) as limb pairs. It does
    not read the scheme, so a caller emulating several variants on one
    operand stream computes it once (kernels/bitexact_emulator.py)."""
    ba = jax.lax.bitcast_convert_type(jnp.asarray(a, jnp.float32), _I32)
    bb = jax.lax.bitcast_convert_type(jnp.asarray(b, jnp.float32), _I32)
    ea, eb = (ba >> 23) & 0xFF, (bb >> 23) & 0xFF
    ma, mb = ba & _M23, bb & _M23
    man_a = jnp.where(ea > 0, ma | (1 << 23), ma)
    man_b = jnp.where(eb > 0, mb | (1 << 23), mb)

    def bit(j):
        return (man_b >> j) & 1 if 0 <= j <= 23 else 0

    rows = []
    n_neg = 0
    for i in range(booth.N_DIGITS):
        d = bit(3 * i - 1) + bit(3 * i) + 2 * bit(3 * i + 1) - 4 * bit(3 * i + 2)
        neg = d < 0
        mag = jnp.abs(d) * man_a  # < 2^27
        sh = 3 * i  # <= 24: the low limb's bits wrap off the top harmlessly
        lo = (mag << sh) & _M24
        hi = (mag >> (24 - sh)) & _M24
        rows.append((jnp.where(neg, lo ^ _M24, lo), jnp.where(neg, hi ^ _M24, hi)))
        n_neg = n_neg + neg.astype(_I32)
    rows.append((n_neg + jnp.zeros_like(man_a), jnp.zeros_like(man_a)))
    return (ba, ea, ma, bb, eb, mb), rows


def _stage_packed(r1, r2, r3, r4, masks):
    out = []
    for limb in range(2):
        x1, x2, x3, x4 = r1[limb], r2[limb], r3[limb], r4[limb]
        out.append(C.cout42(x1, x2, x3))
    cin = _shl1_packed(*out)
    s, c = [], []
    for limb in range(2):
        sl, cl = C.compress42_masked(
            r1[limb], r2[limb], r3[limb], r4[limb], cin[limb],
            {k: masks[j][limb] for j, k in enumerate(_APPROX_CODES)}, _M24)
        s.append(sl)
        c.append(cl)
    return tuple(s), _shl1_packed(*c)


def _ilog2_24(x):
    """floor(log2(x)) for 0 < x < 2^24 (exact in f32), via its exponent."""
    f = jax.lax.bitcast_convert_type(x.astype(jnp.float32), _I32)
    return (f >> 23) - 127


def multiply_packed(fields, rows, masks):
    """Scheme half of the packed emulation: the compressor tree, final add,
    normalization and IEEE specials for one variant's ``masks`` (24 words,
    each broadcastable against the operands). Returns float32."""
    ba, ea, ma, bb, eb, mb = fields
    m = [(masks[2 * j], masks[2 * j + 1]) for j in range(N_MASK_WORDS // 2)]
    sA, cA = _stage_packed(rows[0], rows[1], rows[2], rows[3], m[0:4])
    sB, cB = _stage_packed(rows[4], rows[5], rows[6], rows[7], m[0:4])
    s1, k1 = _stage_packed(sA, cA, sB, cB, m[4:8])
    s2, k2 = _stage_packed(s1, k1, rows[8], rows[9], m[8:12])
    lo, hi = booth.limbs_add_mod48(s2[0], s2[1], k2[0], k2[1])

    is_zero_prod = (lo == 0) & (hi == 0)
    msb = jnp.where(hi > 0, 24 + _ilog2_24(jnp.maximum(hi, 1)),
                    _ilog2_24(jnp.maximum(lo, 1)))
    msb = jnp.where(is_zero_prod, booth.N_COLS - 1, msb)
    # The 23 product bits below the leading one (truncation rounding).
    sr = msb - 23  # in [-23, 24]
    man23 = jnp.where(
        sr >= 24, hi,
        jnp.where(sr >= 0,
                  _srl(lo, jnp.clip(sr, 0, 23)) | (hi << jnp.clip(24 - sr, 1, 24)),
                  lo << jnp.clip(-sr, 0, 23))) & _M23

    sign = _srl(ba ^ bb, 31)
    ea_eff = jnp.where(ea > 0, ea - 127, -126)
    eb_eff = jnp.where(eb > 0, eb - 127, -126)
    e_biased = ea_eff + eb_eff + (msb - 46) + 127
    overflow = e_biased >= 255
    underflow = (e_biased <= 0) | is_zero_prod  # FTZ on subnormal outputs

    sbit = sign << 31
    zero = sbit
    inf = sbit | (255 << 23)
    out = sbit | (jnp.clip(e_biased, 1, 254) << 23) | man23
    out = jnp.where(underflow, zero, out)
    out = jnp.where(overflow, inf, out)

    a_nan = (ea == 255) & (ma != 0)
    b_nan = (eb == 255) & (mb != 0)
    a_inf = (ea == 255) & (ma == 0)
    b_inf = (eb == 255) & (mb == 0)
    a_zero = (ea == 0) & (ma == 0)
    b_zero = (eb == 0) & (mb == 0)
    nan_out = a_nan | b_nan | (a_inf & b_zero) | (b_inf & a_zero)
    inf_out = (a_inf | b_inf) & ~nan_out
    zero_out = (a_zero | b_zero) & ~nan_out
    out = jnp.where(zero_out, zero, out)
    out = jnp.where(inf_out, inf, out)
    out = jnp.where(nan_out, _QNAN_BITS, out)
    return jax.lax.bitcast_convert_type(out, jnp.float32)


# Bits of jnp.nan as float32: the NaN fp32_multiply returns.
_QNAN_BITS = 0x7FC00000


def fp32_multiply_packed(a, b, masks):
    """fp32_multiply(a, b, codes) with ``masks = code_masks(codes)`` given as
    24 words broadcastable against a and b (a sequence, or an array whose
    leading axis is the word index)."""
    fields, rows = booth_packed(a, b)
    return multiply_packed(fields, rows, masks)


# jit'd conveniences for benchmarking / batch evaluation --------------------

_fp32_multiply_jit = jax.jit(fp32_multiply)


def fp32_multiply_batch(a, b, variant, chunk: int = 1 << 16):
    """Chunked jit evaluation over large 1-D batches (error-analysis runs).

    ``variant`` is a registered variant name or an explicit (3, 48) scheme
    map — the latter lets the foundry characterize candidate placements
    before they are registered.
    """
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    if isinstance(variant, str):
        codes = jnp.asarray(schemes.scheme_map(variant))
    else:
        codes = jnp.asarray(schemes.validate_scheme_map(variant))
    outs = []
    for i in range(0, a.size, chunk):
        outs.append(
            np.asarray(_fp32_multiply_jit(a[i : i + chunk], b[i : i + chunk], codes))
        )
    return np.concatenate(outs)
