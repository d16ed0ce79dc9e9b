"""Operations and bytes of one interior-point iteration, from shapes alone.

The count is the benchmark's own, so it stays the same whichever kernel
or precision does the work.  It is that of the linear algebra each IPM
iteration must do on the no-front-end LP (arXiv:1902.01994 Sec 3.2)
of one scenario at its own, unpadded (N, M): factor the normal
equations once and solve with the factor twice (predictor and
corrector).

In the column-reduced program the rows that touch processor ``j`` form
one diagonal block: ``N-1`` source-order rows (Eq 8), ``N-1``
processor-order rows (Eq 9) and one finish-time row (Eq 13), so
``s = 2N - 1``; blocks ``j-1`` and ``j`` couple, and the normalisation
row (Eq 14) is a border of ``p = 1`` rows against every block.  The
normal matrix is block tridiagonal with ``K = M`` blocks and an
arrowhead border.  Block 0's extra release rows (Eqs 11-12) and the
forming of the matrix are left out, so the count is a lower bound of
the work, and a share of the roofline built on it cannot overstate.

One block step of the factor: the Schur update by the previous block
(``s^3``), the block's Cholesky (``s^3/3``), the sub-diagonal's
triangular solve (``s^3``), the border's update and solve
(``3 p s^2``) and the border's Schur update (``p^2 s``); then the
border's own Cholesky (``p^3/3``).  One solve with the factor is a
forward and a backward sweep: per block a triangular solve (``s^2``),
the coupling product (``2 s^2``) and the border (``2 p s``), each way.

Bytes are float64 words (8 bytes): the factor reads the matrix and
writes the factor, and each of the two solves reads the factor again.
"""

from __future__ import annotations

WORD_BYTES = 8
BORDER_ROWS = 1


def ipm_iteration(n: int, m: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one IPM iteration of an ``n x m`` scenario."""
    s, k, p = 2 * n - 1, m, BORDER_ROWS
    factor = k * (7 / 3 * s**3 + 3 * p * s**2 + p**2 * s) + p**3 / 3
    solve = k * 2 * (3 * s**2 + 2 * p * s)
    flops = factor + 2 * solve
    words = k * (2 * s**2 + p * s) + p**2     # the matrix, or its factor
    return flops, float(WORD_BYTES * words * (2 + 2))
