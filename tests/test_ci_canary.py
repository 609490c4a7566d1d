"""CI canary: the jax version pinned in the workflow is the one tested.

``launch/hlo_cost.py`` parses *optimized HLO text*, a surface with no
stability guarantee -- dialect drift across jax releases silently breaks
FLOP accounting.  The CI workflow therefore pins ``jax[cpu]`` to one
tested version; this canary fails loudly when either side moves without
the other:

  * the workflow pin must equal the jax that is actually running the
    suite (bump ci.yml and re-validate, don't let them diverge), and
  * the running jax's optimized HLO must still parse into nonzero FLOPs
    (the drift the pin exists to prevent).
"""
import os
import re

import pytest
import jax
import jax.numpy as jnp

CI_YML = os.path.join(os.path.dirname(__file__), "..", ".github",
                      "workflows", "ci.yml")


def _pinned_version() -> str:
    with open(CI_YML) as f:
        text = f.read()
    m = re.search(r'JAX_PINNED_VERSION:\s*"([0-9][0-9a-z.]*)"', text)
    assert m, "ci.yml no longer declares JAX_PINNED_VERSION"
    return m.group(1)


def test_workflow_pin_matches_running_jax():
    pin = _pinned_version()
    if jax.__version__ != pin:
        pytest.fail(
            f"ci.yml pins jax=={pin} but the suite is running "
            f"jax=={jax.__version__}; bump the pin and re-validate "
            f"hlo_cost against the new release")


def test_pinned_jax_hlo_dialect_parses():
    """The fragile surface itself: optimized HLO from the pinned jax must
    yield a sane FLOP count through hlo_cost.analyze."""
    from repro.launch import hlo_cost
    m, k, n = 32, 64, 16
    a = jax.ShapeDtypeStruct((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32)
    txt = jax.jit(lambda x, y: x @ y).lower(a, b).compile().as_text()
    res = hlo_cost.analyze(txt)
    assert res["flops"] == 2 * m * k * n, (
        "hlo_cost no longer parses this jax's optimized HLO dialect")


def test_pinned_jax_hlo_dialect_parses_chained_dots():
    """Second dialect probe (re-validated at the 0.9.0 pin): chained
    contractions must each be found -- a parser that silently drops
    every dot but the first would still pass the single-dot probe."""
    from repro.launch import hlo_cost
    m = 32
    a = jax.ShapeDtypeStruct((m, m), jnp.float32)
    txt = jax.jit(lambda x: (x @ x) @ x).lower(a).compile().as_text()
    res = hlo_cost.analyze(txt)
    assert res["flops"] == 2 * (2 * m * m * m), (
        "hlo_cost missed a contraction in this jax's optimized HLO")
