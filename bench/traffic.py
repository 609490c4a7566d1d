"""The one generator of operands, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) gives:

* ``products_per_call_per_chip``: products in one ``mul`` call, per
  chip the cell holds;
* ``pad_to_power_of_two`` (default false): the call's rows are padded
  with zero operands to the next power of two, as the serving worker
  buckets a round; only the products count as work;
* ``resident``: ``"device"`` (operands made and kept on the device, the
  products left there) or ``"host"`` (numpy operands, copied to the
  device in each call, and the products copied back, as the serving
  worker does);
* ``operand_sets``: distinct operand pairs made in set-up, which the
  window cycles through in order;
* ``callers`` and ``loop``: one caller in a closed loop, which issues
  its next call when the previous one is ready;
* ``rehearse_products_per_call_per_chip``: the batch of a rehearsal
  on the CPU, which is never a measurement;
* ``trace_max_calls`` (optional): a traced window closes after this many
  calls, if its seconds have not run out first, so that a mix of short
  calls leaves a trace that can be read within the run's time.

Operands are uniform random integers at the configuration's full
width, made on the device in one jitted call from the seed.
"""
from __future__ import annotations

import numpy as np

RADIX_BITS = 16
RESIDENT = ("device", "host")


def n_limbs(bits: int) -> int:
    return -(-bits // RADIX_BITS)


def batch(traffic: dict, chips: int, rehearse: bool = False) -> int:
    """Products in one call across all the cell's chips."""
    if traffic.get("loop") != "closed" or traffic.get("callers") != 1:
        raise ValueError("the generator drives one caller in a closed loop")
    if traffic.get("resident") not in RESIDENT:
        raise ValueError(f"resident must be one of {RESIDENT}")
    key = ("rehearse_products_per_call_per_chip" if rehearse
           else "products_per_call_per_chip")
    return int(traffic[key]) * chips


def rows(traffic: dict, chips: int, rehearse: bool = False) -> int:
    """Rows of one call across all the cell's chips: the products, and
    the zero padding after them."""
    n = batch(traffic, chips, rehearse) // chips
    if traffic.get("pad_to_power_of_two", False):
        n = 1 << (n - 1).bit_length()
    return n * chips


def key_from_seed(seed: int):
    """A PRNG key from any whole-number seed (more than 32 bits too)."""
    import jax
    state = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(state))


def make_operands(seed: int, n_sets: int, rows: int, bits_a: int,
                  bits_b: int, sharding=None, products: int | None = None,
                  chips: int = 1):
    """``n_sets`` operand pairs ``(a, b)`` of ``rows`` rows each: in
    each chip's ``rows // chips``, the first ``products // chips`` are
    the products and the rest zero padding.

    One jitted call on the device; ``sharding`` places every array
    (e.g. split over a mesh axis by rows).
    """
    import jax
    import jax.numpy as jnp
    products = rows if products is None else products

    def limbs(key, bits):
        n = n_limbs(bits)
        x = jax.random.bits(key, (rows, n), jnp.uint16).astype(jnp.uint32)
        top = bits - RADIX_BITS * (n - 1)
        x = x.at[:, -1].set(x[:, -1] & jnp.uint32((1 << top) - 1))
        live = (jnp.arange(rows)[:, None] % (rows // chips)
                < products // chips)
        return jnp.where(live, x, jnp.uint32(0))

    def bench_operands(key):
        keys = jax.random.split(key, 2 * n_sets)
        return (tuple(limbs(keys[2 * k], bits_a) for k in range(n_sets)),
                tuple(limbs(keys[2 * k + 1], bits_b)
                      for k in range(n_sets)))

    out = None if sharding is None else (
        (sharding,) * n_sets, (sharding,) * n_sets)
    fn = jax.jit(bench_operands, out_shardings=out)
    a, b = fn(key_from_seed(seed))
    return list(zip(a, b))
