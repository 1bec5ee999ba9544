"""Block-shape rules shared by the Pallas kernels.

Mosaic (the TPU kernel compiler) accepts a block only when its last two
dims are multiples of (8, 128) or equal to the full array dims, and a
dynamic-offset DMA into a tiled HBM operand only when the row offset is a
provable multiple of the operand's row tiling.
"""

from __future__ import annotations

# Row tiling of an HBM operand (bf16 and fp32 alike): the gather/scatter
# kernels' dynamic row offsets must be multiples of it, and the dispatch
# layouts that produce those offsets start every bucket segment on one.
ROW_ALIGN = 8


def round_up(n):
    """``n`` rounded up to a multiple of ``ROW_ALIGN`` (ints or int arrays)."""
    return -(-n // ROW_ALIGN) * ROW_ALIGN


def fit_block(n: int, pref: int, align: int = ROW_ALIGN) -> int:
    """Block size for an axis of length ``n``: ``n`` itself when it fits
    under ``pref``, else the largest divisor ``<= pref`` that is a multiple
    of ``align``, else the largest plain divisor (interpret-mode shapes,
    which the registry's ``can_*`` gates keep off the compiled path)."""
    if n <= pref:
        return n
    for t in range(pref, 0, -1):
        if n % t == 0 and t % align == 0:
            return t
    t = pref
    while n % t:
        t -= 1
    return t
