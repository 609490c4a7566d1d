"""Plain host reference of the limb multiplication, and its control.

Operands are unsigned integers held as 16-bit limbs in uint32, least
significant limb first: ``(B, LA) x (B, LB) -> (B, LA + LB)``.  Nothing
here imports the program under test.
"""
from __future__ import annotations

import numpy as np

RADIX_BITS = 16
_MASK = np.uint64(0xFFFF)
_SHIFT = np.uint64(RADIX_BITS)


def products(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Schoolbook limb products in uint64 column sums (exact)."""
    a = a.astype(np.uint64)
    b = b.astype(np.uint64)
    n, la = a.shape
    lb = b.shape[1]
    cols = np.zeros((n, la + lb + 1), np.uint64)
    for i in range(la):
        for j in range(lb):
            p = a[:, i] * b[:, j]
            cols[:, i + j] += p & _MASK
            cols[:, i + j + 1] += p >> _SHIFT
    out = np.empty((n, la + lb), np.uint32)
    carry = np.zeros(n, np.uint64)
    for k in range(la + lb):
        tot = cols[:, k] + carry
        out[:, k] = tot & _MASK
        carry = tot >> _SHIFT
    return out


def as_int(limbs) -> int:
    """One row of limbs as a Python int."""
    return int.from_bytes(np.asarray(limbs).astype("<u2").tobytes(),
                          "little")


def checked_products(a: np.ndarray, b: np.ndarray,
                     rows: int = 256) -> np.ndarray:
    """:func:`products`, checked against Python ints on its first rows."""
    want = products(a, b)
    for r in range(min(rows, len(a))):
        if as_int(a[r]) * as_int(b[r]) != as_int(want[r]):
            raise AssertionError(
                f"host schoolbook disagrees with Python ints on row {r}")
    return want


def control_products(a, b):
    """The reference computed in float32 column sums, on the device.

    float32 holds 24 bits of mantissa, so a 16x16-bit limb product loses
    its low bits: this breaks the bit-exactness every configuration
    guarantees, and ``correct`` has to come out false with it in the
    program's place.
    """
    import jax.numpy as jnp
    la, lb = a.shape[-1], b.shape[-1]
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cols = [jnp.zeros(a.shape[:-1], jnp.float32)
            for _ in range(la + lb + 1)]
    for i in range(la):
        for j in range(lb):
            cols[i + j] = cols[i + j] + af[..., i] * bf[..., j]
    out = []
    carry = jnp.zeros(a.shape[:-1], jnp.float32)
    for k in range(la + lb):
        tot = cols[k] + carry
        hi = jnp.floor(tot / 65536.0)
        out.append((tot - hi * 65536.0).astype(jnp.uint32))
        carry = hi
    return jnp.stack(out, axis=-1)
