"""The serve steps keep the module names that the model-step metrics look
for in the trace: a renamed step would leave ``decode_roofline.serve`` and
``mfu.serve`` with nothing to read, in silence."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

import bench
import serve
from conftest import TINY_CONFIG, TINY_MIX


def _module(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_serve_steps_keep_their_module_names():
    from repro.dist.step import param_specs

    prog = serve.build_program(TINY_CONFIG, TINY_MIX, jax.devices()[0])
    params, _ = param_specs(prog.model)
    state = jax.eval_shape(prog.new_state)
    prompt = jax.ShapeDtypeStruct((1, TINY_MIX["prompt_lengths"][0]), jnp.int32)
    token = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    prefill = _module(prog.prefill.lower(params, prompt, state))
    decode = _module(prog.decode.lower(params, token, state))
    assert (prefill, decode) == ("jit_prefill_fn", "jit_decode_fn")
    metrics = bench.HERE / "metrics"
    roofline = bench.load_module(metrics / "decode_roofline.serve.py").PROGRAM
    mfu = bench.load_module(metrics / "mfu.serve.py").PROGRAMS
    assert re.search(roofline, decode) and not re.search(roofline, prefill)
    assert re.search(mfu, decode) and re.search(mfu, prefill)
