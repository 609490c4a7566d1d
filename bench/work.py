"""The work a call asks for, counted from its shapes alone.

These counts follow the operation, not its implementation: a layout
that pads limbs to the lane width moves more bytes, but it is judged
against the same interface bytes, so cutting the padding shows as a
gain and not as a changed denominator.
"""
from __future__ import annotations

LIMB_BYTES = 4          # a 16-bit limb travels as uint32


def interface_bytes(products: int, la: int, lb: int) -> int:
    """Bytes of the operands read and the products written, as ``mul``
    takes and returns them: ``(B, LA)`` and ``(B, LB)`` in,
    ``(B, LA + LB)`` out."""
    return products * (la + lb + (la + lb)) * LIMB_BYTES


def limb_products(products: int, la: int, lb: int) -> int:
    """16x16-bit multiplications of a schoolbook product."""
    return products * la * lb
