"""Exact and approximate 4:2 compressors (bit-level, vectorized).

A 4:2 compressor takes four partial-product bits ``x1..x4`` of one column plus a
carry-in ``cin`` from the previous column and emits

    x1 + x2 + x3 + x4 + cin  =  sum + 2*(carry + cout)

``cout`` depends only on ``x1..x3`` so the per-stage column chain is
non-recursive (cout of column j feeds cin of column j+1 *within* the stage).

The paper builds its eight FP32 multipliers from *positive* compressors (PCs,
error >= 0) and *negative* compressors (NCs, error <= 0) taken from its ref [9]
(ISQED'23), whose gate-level tables are not reproduced in the paper text. We
design compressors to the same spec — single-direction, low-rate error, exact
``cout`` so error stays local to the column pair — and validate that the eight
assembled FP32 multipliers land in the paper's reported metric ranges
(see tests/test_error_metrics.py).

Truth-table error summary (derived in tests):
  PC1: +1 when (x1^x2^x3^x4^cin)==0 and x3&x4        (p = 1/8 on iid bits)
  PC2: +2 when x1^x2 and x3&x4 and cin==0            (p = 1/16)
  NC1: -1 when cin==1 (cin ignored)                  (p = P[cin])
  NC2: NC1 plus -2 when x1&x2&x3&x4                  (extra p = 1/16)

All functions operate on int32 {0,1} arrays of any broadcastable shape; the
``code`` argument selects the compressor per element, enabling per-column /
per-stage / per-slot interleaving in a single vectorized pass.
"""
from __future__ import annotations

import jax.numpy as jnp

# Compressor codes (order matters: used by jnp indexed selection).
EXACT = 0
PC1 = 1
PC2 = 2
NC1 = 3
NC2 = 4
N_COMPRESSORS = 5

CODE_NAMES = {EXACT: "EXACT", PC1: "PC1", PC2: "PC2", NC1: "NC1", NC2: "NC2"}


def cout42(x1, x2, x3):
    """Exact cout (carry of the first embedded full-adder). Exact in all designs."""
    return (x1 & x2) | ((x1 ^ x2) & x3)


def _candidates(x1, x2, x3, x4, cin, one):
    """(sum, carry) of every compressor design, keyed by code.

    ``one`` is the all-ones word of the operands' encoding: 1 for {0,1} bit
    arrays, 0xFFFFFF for the packed 24-column limbs of core/fp32_mul.py —
    every term is bitwise, so one truth table serves both spellings.
    """
    t = x1 ^ x2 ^ x3
    sx = t ^ x4
    sum_exact = sx ^ cin
    carry_exact = (sx & cin) | (t & x4)
    return {
        EXACT: (sum_exact, carry_exact),
        # PC1: or an extra positive term into sum.
        PC1: (sum_exact | (x1 & x2) | (x3 & x4), carry_exact),
        # PC2: or an extra positive term into carry.
        PC2: (sum_exact, carry_exact | ((x1 ^ x2) & x3 & x4)),
        # NC1: drop the carry-in entirely.
        NC1: (sx, t & x4),
        # NC2: NC1 plus a dropped carry on the all-ones pattern.
        NC2: (sx, (t & x4) & (one ^ (x1 & x2 & x3 & x4))),
    }


def compress42(x1, x2, x3, x4, cin, code):
    """Vectorized 4:2 compression with per-element compressor selection.

    Args:
      x1..x4, cin: int32 {0,1} arrays (broadcastable).
      code: int32 array of compressor codes (broadcastable against the bits).

    Returns:
      (sum, carry, cout) int32 {0,1} arrays.
    """
    cand = _candidates(x1, x2, x3, x4, cin, 1)
    s, c = cand[EXACT]
    # Branch-free selection (codes are data, may vary per element).
    for k in (PC1, PC2, NC1, NC2):
        s = jnp.where(code == k, cand[k][0], s)
        c = jnp.where(code == k, cand[k][1], c)
    return s, c, cout42(x1, x2, x3)


def compress42_masked(x1, x2, x3, x4, cin, masks, one):
    """compress42 over packed columns: ``masks`` maps each approximate code
    (PC1, PC2, NC1, NC2) to the word of columns that use it; the remaining
    columns are exact. Returns (sum, carry) words (cout is ``cout42``)."""
    cand = _candidates(x1, x2, x3, x4, cin, one)
    s, c = cand[EXACT]
    for k, m in masks.items():
        s = s ^ ((s ^ cand[k][0]) & m)
        c = c ^ ((c ^ cand[k][1]) & m)
    return s, c


def compressor_value_error(x1, x2, x3, x4, cin, code):
    """Signed value error (approx - exact) of one compressor application.

    Used by property tests to assert PC errors >= 0 and NC errors <= 0.
    """
    s, c, co = compress42(x1, x2, x3, x4, cin, code)
    exact = x1 + x2 + x3 + x4 + cin
    return (s + 2 * (c + co)) - exact
