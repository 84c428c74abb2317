"""Radix-8 modified-Booth partial-product generation for 24x24 mantissa multiply.

The 24-bit multiplier (23-bit mantissa + implicit leading bit) is recoded into
9 radix-8 digits d_i in [-4, 4]:

    d_i = -4*b[3i+2] + 2*b[3i+1] + b[3i] + b[3i-1],   b[-1] = b[>=24] = 0

so that  B = sum_i d_i * 8^i  for any unsigned 24-bit B (the 9th digit absorbs
the would-be sign of bit 23). Each partial product |d_i| * A fits in 27 bits
(A < 2^24, |d_i| <= 4); the 3A "hard multiple" is computed exactly, as in the
paper's exact-adder PP generation stage (approximation lives only in the
compressor tree).

Negative digits are represented as the full-width 48-bit one's complement of
the shifted magnitude plus a +1 correction; the per-row +1 corrections are
accumulated into a single extra correction row (the count of negative digits,
<= 9, encoded in bits 0..3). The 10-row PPM therefore satisfies

    sum(rows) mod 2^48 == A * B            (exact, by construction)

which the exact-compressor reduction preserves bit-for-bit
(tests/test_fp32_mul.py::test_exact_tree_matches_integer_product).

Everything is int32 {0,1} bit matrices with a trailing 48-wide column axis, so
it traces under jit/vmap and inside Pallas kernel bodies.
"""
from __future__ import annotations

import jax.numpy as jnp

N_COLS = 48  # 24x24 product width
N_DIGITS = 9  # radix-8 digits for an unsigned 24-bit multiplier
N_ROWS = N_DIGITS + 1  # + correction row
PP_BITS = 27  # |d|*A < 2^27


def booth_digits(b24):
    """Recode unsigned 24-bit integers into 9 radix-8 digits in [-4, 4].

    Args:
      b24: int32 array, values in [0, 2^24).
    Returns:
      int32 array shaped (..., 9).
    """
    b24 = b24.astype(jnp.int32)

    def bit(j):
        if j < 0 or j > 23:
            return jnp.zeros_like(b24)
        return (b24 >> j) & 1

    digits = []
    for i in range(N_DIGITS):
        d = bit(3 * i - 1) + bit(3 * i) + 2 * bit(3 * i + 1) - 4 * bit(3 * i + 2)
        digits.append(d)
    return jnp.stack(digits, axis=-1)


def booth_ppm(a24, b24):
    """Build the 10-row x 48-col partial-product bit matrix for a24 * b24.

    Args:
      a24, b24: int32 arrays (same shape ...), values in [0, 2^24).
    Returns:
      int32 {0,1} array shaped (..., 10, 48) whose row-sum mod 2^48 equals
      a24 * b24.
    """
    a24 = a24.astype(jnp.int32)
    digits = booth_digits(b24)  # (..., 9)
    neg = (digits < 0).astype(jnp.int32)  # (..., 9)
    mag = jnp.abs(digits) * a24[..., None]  # (..., 9), < 2^27, fits int32

    cols = jnp.arange(N_COLS, dtype=jnp.int32)  # (48,)
    shifts = 3 * jnp.arange(N_DIGITS, dtype=jnp.int32)  # (9,)
    rel = cols[None, :] - shifts[:, None]  # (9, 48)
    in_range = ((rel >= 0) & (rel < PP_BITS)).astype(jnp.int32)
    rel_c = jnp.clip(rel, 0, PP_BITS - 1)

    # (..., 9, 48): bit `rel` of each shifted magnitude.
    bits = ((mag[..., None] >> rel_c) & 1) * in_range
    # Negative digits: full-width one's complement (mod-2^48 two's complement
    # minus the +1, which goes to the correction row).
    rows = jnp.where(neg[..., None] == 1, 1 - bits, bits)

    # Correction row: binary count of negative digits at columns 0..3.
    neg_count = jnp.sum(neg, axis=-1)  # (...,), <= 9
    corr = ((neg_count[..., None] >> jnp.arange(4, dtype=jnp.int32)) & 1).astype(
        jnp.int32
    )
    lead = rows.shape[:-2]
    corr_row = jnp.concatenate(
        [jnp.broadcast_to(corr, lead + (4,)),
         jnp.zeros(lead + (N_COLS - 4,), jnp.int32)], axis=-1)

    return jnp.concatenate([rows, corr_row[..., None, :]], axis=-2)


def bits_to_limbs(bits):
    """(..., 48) {0,1} -> (lo24, hi24) int32 limb pair."""
    w_lo = (1 << jnp.arange(24, dtype=jnp.int32)).astype(jnp.int32)
    lo = jnp.sum(bits[..., :24] * w_lo, axis=-1)
    hi = jnp.sum(bits[..., 24:] * w_lo, axis=-1)
    return lo, hi


def limbs_add_mod48(lo1, hi1, lo2, hi2):
    """48-bit add (two 24-bit limbs), discarding carry-out of bit 47."""
    lo = lo1 + lo2
    carry = lo >> 24
    lo = lo & 0xFFFFFF
    hi = (hi1 + hi2 + carry) & 0xFFFFFF
    return lo, hi


def limbs_to_bits(lo, hi):
    """(lo24, hi24) -> (..., 48) {0,1} int32."""
    j = jnp.arange(24, dtype=jnp.int32)
    blo = (lo[..., None] >> j) & 1
    bhi = (hi[..., None] >> j) & 1
    return jnp.concatenate([blo, bhi], axis=-1).astype(jnp.int32)
