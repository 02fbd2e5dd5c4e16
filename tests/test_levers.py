"""MoE capacity bins and the bf16 gradient cast: ``expert_capacity`` may only
shrink as a dispatch call covers fewer tokens, and ``grad_cast`` is the
identity forward and casts the cotangent backward."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.common import grad_cast
from repro.models.moe import expert_capacity


def test_moe_group_capacity_scales():
    cfg = get_config("mixtral-8x7b").reduced()
    c_global = expert_capacity(1024, cfg)
    c_group = expert_capacity(1024 // 4, cfg)
    assert c_group <= c_global


def test_grad_cast_casts_cotangent():
    x = jnp.ones(4, jnp.bfloat16)

    def f(x):
        return (grad_cast(x).astype(jnp.float32) ** 2).sum()

    g = jax.grad(f)(x)
    assert g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g, np.float32), 2.0 * np.ones(4))


def test_grad_cast_is_identity_forward():
    x = jax.random.normal(jax.random.key(0), (8,))
    np.testing.assert_array_equal(np.asarray(grad_cast(x)), np.asarray(x))
