"""The host reference against Python ints, and its control."""
import numpy as np
import pytest

from bench import reference


@pytest.mark.parametrize("la,lb", [(1, 1), (2, 2), (8, 8), (2, 8)])
def test_schoolbook_matches_python_ints(la, lb):
    rng = np.random.default_rng(la * 10 + lb)
    a = rng.integers(0, 1 << 16, size=(300, la), dtype=np.uint32)
    b = rng.integers(0, 1 << 16, size=(300, lb), dtype=np.uint32)
    a[0] = b[0] = 0
    a[1], b[1] = 0xFFFF, 0xFFFF          # the largest column sums
    got = reference.products(a, b)
    for r in range(len(a)):
        assert reference.as_int(got[r]) == \
            reference.as_int(a[r]) * reference.as_int(b[r])


def test_checked_products_rejects_a_wrong_schoolbook(monkeypatch):
    a = np.full((4, 2), 0xFFFF, np.uint32)
    wrong = reference.products(a, a)
    wrong[2, 0] ^= 1
    monkeypatch.setattr(reference, "products", lambda a, b: wrong)
    with pytest.raises(AssertionError, match="row 2"):
        reference.checked_products(a, a)


@pytest.mark.parametrize("la", [2, 8])
def test_control_breaks_bit_exactness(la):
    import jax.numpy as jnp
    rng = np.random.default_rng(la)
    a = rng.integers(0, 1 << 16, size=(512, la), dtype=np.uint32)
    b = rng.integers(0, 1 << 16, size=(512, la), dtype=np.uint32)
    got = np.asarray(reference.control_products(jnp.asarray(a),
                                                jnp.asarray(b)))
    assert got.shape == (512, 2 * la)
    wrong = (got != reference.products(a, b)).any(axis=1)
    assert wrong.mean() > 0.9
