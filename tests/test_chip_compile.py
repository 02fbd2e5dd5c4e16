"""Compile the main path's kernels and the serve decode step for one TPU
v5e chip, at full width, without a chip: the TPU compiler refuses what the
chip would refuse (tiling, scoped VMEM, unsupported primitives), which
interpret mode never shows. The decode step is also held to updating its
KV cache in place: no copy, re-lay or slice of a cache-sized buffer.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""

import dataclasses
import math
import os
import re

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


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)(.*)$"
)


def cache_sized_moves(hlo: str, nbytes: int) -> list:
    """Instructions of a compiled module that move ``nbytes`` or more of a
    buffer without computing on it: a copy, copy-start or transpose anywhere
    (a re-lay, fused or not), a dynamic-update-slice writing that much, and
    a dynamic-slice, or a fusion that ends in one of these, whose result is
    put in memory. A slice fused into the op that reads it is a read where
    the buffer lies, and passes."""
    comps: dict = {}
    instrs: dict = {}
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            instrs = comps.setdefault(m.group(1), {})
            continue
        m = _INSTRUCTION.match(line)
        if m:
            root, name, dt, dims, op, operands, attrs = m.groups()
            size = math.prod(int(d) for d in dims.split(",") if d) * _DTYPE_BYTES.get(dt, 4)
            callee = re.search(r"calls=%([\w.\-]+)", attrs)
            instrs[name] = (op, size, re.findall(r"%([\w.\-]+)", operands),
                            callee and callee.group(1), bool(root))
    fused = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", hlo))

    def root_op(comp):
        body = comps.get(comp, {})
        for op, _, args, _, is_root in body.values():
            if is_root:
                while op == "bitcast" and args[0] in body:
                    op, _, args, _, _ = body[args[0]]
                return op

    found = []
    for comp, body in comps.items():
        for name, (op, size, args, callee, _) in body.items():
            if op in ("copy", "copy-start", "transpose") and size >= nbytes:
                found.append(f"{op} {name}: {size} B")
            if op == "dynamic-update-slice" and body.get(args[1], ("", 0))[1] >= nbytes:
                found.append(f"{name} writes {body[args[1]][1]} B")
            if comp in fused:
                continue
            if op == "dynamic-slice" and size >= nbytes:
                found.append(f"{op} {name}: {size} B")
            if op == "fusion" and size >= nbytes and root_op(callee) in (
                "copy", "dynamic-slice", "transpose"
            ):
                found.append(f"fusion {name}: {size} B, a {root_op(callee)}")
    return found


def aliased_params(hlo: str) -> set:
    """Entry parameter numbers the module's outputs alias."""
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    return {int(p) for p in re.findall(r": \((\d+),", m.group(1))} if m else set()


@pytest.mark.parametrize(
    "arch,layers",
    [("stablelm-1.6b", 24), ("internlm2-20b", 12)],
    ids=["stablelm-1.6b", "internlm2-20b-stage12"],
)
def test_decode_step_updates_cache_in_place_on_v5e(arch, layers, topo, no_persistent_cache):
    """The decode step as the chip benchmark serves it (one request, 2,080
    cache slots) writes each layer's new key and value where the stacked
    cache lies: no copy, re-lay or slice as large as one layer's K cache,
    and the donated cache is the step's output."""
    from repro.dist.step import make_serve_fns, param_specs
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model

    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    model = build_model(cfg)
    mesh = make_host_mesh(devices=topo.devices[:1])
    max_len = 2080
    _, decode, st_shapes, shards = make_serve_fns(model, mesh, max_len=max_len, global_batch=1)
    place = lambda tree, sh: jax.tree.map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d), tree, sh
    )
    params = place(param_specs(model)[0], shards["params"])
    state = place(st_shapes, shards["state"])
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=NamedSharding(mesh, P()))
    hlo = decode.lower(params, tok, state).compile().as_text()

    layer_k = max_len * cfg.n_kv_heads * cfg.head_dim * 2  # bf16, batch 1
    assert cache_sized_moves(hlo, layer_k) == []

    n_params = len(jax.tree.leaves(params)) + 1
    entry = hlo[hlo.index("\nENTRY "):]
    assert len(re.findall(r" parameter\(\d+\)", entry)) == n_params + len(jax.tree.leaves(state))
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    cache_params = {
        n_params + i for i, (path, leaf) in enumerate(leaves) if len(leaf.shape) >= 4
    }
    assert len(cache_params) == 2 and cache_params <= aliased_params(hlo)


def test_jamba2_mini_serve_steps_fit_one_v5e(topo, no_persistent_cache):
    """Jamba2-Mini cut as the chip benchmark serves it (one 8-layer period,
    8 of 16 experts held, 8,192 vocabulary rows, 2,080 slots): the prefill of
    a 2,048-token prompt and the decode step compile for one v5e, each with
    its arguments (14.39 GB of weights) and temporaries inside 16 GB, and
    the decode step writes the attention layer's KV cache in place."""
    from repro.dist.step import make_serve_fns, param_specs
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model

    cfg = dataclasses.replace(get_config("jamba2-mini"), n_layers=8, experts_held=8, vocab=8192)
    model = build_model(cfg)
    mesh = make_host_mesh(devices=topo.devices[:1])
    max_len = 2080
    prefill, decode, st_shapes, shards = make_serve_fns(
        model, mesh, max_len=max_len, global_batch=1
    )
    place = lambda tree, sh: jax.tree.map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d), tree, sh
    )
    params = place(param_specs(model)[0], shards["params"])
    state = place(st_shapes, shards["state"])
    rep = NamedSharding(mesh, P())
    for n in (2048, 1):
        step = prefill if n > 1 else decode
        compiled = step.lower(params, jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=rep), state).compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes > 14.3e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9, n
    layer_k = max_len * cfg.n_kv_heads * cfg.head_dim * 2
    assert cache_sized_moves(compiled.as_text(), layer_k) == []
