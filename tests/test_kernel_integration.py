"""Integration: the model trunk with Pallas kernels (interpreted off the
TPU) must match the pure-jnp reference path — the exact swap that happens
on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ops import kernel_set
from repro.models.registry import build_model, train_loss


# multi-minute model/kernel path: runs in the full CI job only
pytestmark = pytest.mark.slow



@pytest.mark.parametrize("arch", ["mixtral-8x7b", "falcon-mamba-7b", "jamba-v0.1-52b"])
def test_trunk_with_pallas_kernels_matches_reference(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    B, L = 2, 32
    toks = jax.random.randint(jax.random.key(1), (B, L), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    loss_ref, _ = train_loss(model, params, batch, kernels=None)
    loss_krn, _ = train_loss(
        model, params, batch, kernels=kernel_set(use_pallas=True)
    )
    assert float(loss_ref) == pytest.approx(float(loss_krn), rel=2e-4), arch


def test_flash_attention_op_jit_wrapper():
    from repro.kernels.ops import flash_attention_op
    from repro.kernels.ref import reference_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 128, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    out = flash_attention_op(q, k, v, causal=True, block_q=64, block_kv=64)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
