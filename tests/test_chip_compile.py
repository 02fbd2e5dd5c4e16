"""Compile the main path's kernels and the stablelm-1.6b decode step for one
TPU v5e chip, at full width, without a chip: the TPU compiler refuses what
the chip would refuse (tiling, scoped VMEM, unsupported primitives), which
interpret mode never shows.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _kernel_case(name):
    """(fn, [(shape, dtype)]) for one kernel at the widths of a repo config."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_decode import flash_decode
    from repro.kernels.hash_tree import hash_tree_state
    from repro.kernels.mamba_scan import mamba_scan
    from repro.kernels.moe_gmm import moe_gmm

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    lm = get_config("stablelm-1.6b")
    H, Dh = lm.n_heads, lm.head_dim
    if name == "flash_attention":
        return (
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            [((1, 2048, H, Dh), bf)] * 3,
        )
    if name == "flash_decode":
        kv = ((4, 2048, lm.n_kv_heads, Dh), bf)
        return (
            lambda *a: flash_decode(*a, interpret=False),
            [((4, 1, H, Dh), bf), kv, kv, ((4, 2048), i32), ((4,), i32), ((4,), i32)],
        )
    if name == "hash_tree":
        return (lambda w: hash_tree_state(w, interpret=False), [((1 << 24,), jnp.uint32)])
    if name == "moe_gmm":
        mx = get_config("mixtral-8x7b")
        E, D, F = mx.n_experts, mx.d_model, mx.d_ff
        return (
            lambda *a: moe_gmm(*a, interpret=False),
            [((E, 256, D), bf), ((E, D, F), bf), ((E, D, F), bf), ((E, F, D), bf)],
        )
    fm = get_config("falcon-mamba-7b")
    Di, N = fm.d_inner, fm.ssm_state
    return (
        lambda *a: mamba_scan(*a, interpret=False),
        [((1, 512, Di), bf), ((1, 512, Di), f32), ((1, 512, N), f32),
         ((1, 512, N), f32), ((Di, N), f32)],
    )


@pytest.mark.parametrize(
    "name", ["flash_attention", "flash_decode", "hash_tree", "moe_gmm", "mamba_scan"]
)
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), f"{name}: no Mosaic kernel in the HLO"


def test_stablelm_decode_step_compiles_for_v5e(topo, no_persistent_cache):
    from repro.dist.step import make_serve_fns, param_specs
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model

    cfg = get_config("stablelm-1.6b")
    model = build_model(cfg)
    mesh = make_host_mesh(devices=topo.devices[:1])
    B, max_len = 4, 2048
    _, decode, st_shapes, shards = make_serve_fns(
        model, mesh, max_len=max_len, global_batch=B
    )
    place = lambda tree, sh: jax.tree.map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d), tree, sh
    )
    params = place(param_specs(model)[0], shards["params"])
    state = place(st_shapes, shards["state"])
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=NamedSharding(mesh, P()))
    compiled = decode.lower(params, tok, state).compile()
    mem = compiled.memory_analysis()
    # bf16 params (3.3 GB) plus a 4 x 2048 KV cache (1.6 GB) fit one 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
