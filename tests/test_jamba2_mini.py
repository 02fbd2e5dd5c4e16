"""Jamba2-Mini's layers against the plain float32 reference of the chip
benchmark (``benchmarks/chip/reference_hybrid.py``), on seeded random
weights at a tiny Jamba-shaped size: one 8-layer period (7 Mamba, 1
attention, 4 MoE layers), a router of 16 experts of which 8 are held."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.common import ParamBuilder
from repro.models.registry import build_model, decode_step, init_serve_state, prefill

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip"))
import reference_hybrid  # noqa: E402


def _tiny(**changes):
    sizes = dict(n_layers=8, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96, vocab=128,
                 experts_held=8, dtype="float32", remat="none")
    return dataclasses.replace(get_config("jamba2-mini"), **{**sizes, **changes})


def _reference_config(cfg) -> dict:
    """The reference's configuration keys for an ``ArchConfig``."""
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
        "router_outputs": cfg.n_experts, "num_experts": cfg.experts_here,
        "num_experts_per_tok": cfg.top_k, "held_experts": [cfg.experts_first],
        "mamba_expand": cfg.ssm_expand, "mamba_d_state": cfg.ssm_state,
        "mamba_dt_rank": cfg.dt_rank, "mamba_d_conv": cfg.ssm_conv,
        "attn_layer_offset": 4, "attn_layer_period": 8,
        "expert_layer_offset": 1, "expert_layer_period": 2, "rms_norm_eps": cfg.norm_eps,
    }


def _params(model, seed=0):
    """Seeded random parameters; norms, biases and D drawn too, so a norm
    or bias the program left out would show."""
    params, _ = model.init(jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 1000))

    def vary(path, a):
        name = getattr(path[-1], "key", "")
        if name in ("ln1", "ln2", "final_norm", "dt_norm", "b_norm", "c_norm", "d_skip"):
            return a * (1 + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype))
        if name == "conv_b":
            return 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(vary, params)


def _weights(params) -> dict:
    """The program's parameters in the reference's layout (the same tree)."""
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"], "layers": list(params["blocks"])}


def test_prefill_then_decode_match_the_reference(monkeypatch):
    """Prefill, then decode through the hybrid cache (KV of the attention
    layer, scan state and conv window of the Mamba layers), against the
    reference's full forward pass over the same tokens, routed as the
    program routed; the program's experts are the reference's own. The
    prefill's scan runs in chunks of 8 steps, its state carried across."""
    from repro.models import mamba

    cfg = _tiny()
    monkeypatch.setattr(mamba, "SCAN_TILE_BYTES", 8 * cfg.d_inner * cfg.ssm_state * 4)
    assert mamba.scan_chunk(cfg, 20) == 8
    model = build_model(cfg)
    params = _params(model)
    c = _reference_config(cfg)
    B, n_prompt, n_steps, max_len = 2, 20, 6, 32
    seq = np.asarray(jax.random.randint(jax.random.key(7), (B, n_prompt + n_steps), 0, cfg.vocab))
    state = init_serve_state(model, B, max_len)
    logits, state = prefill(model, params, jnp.asarray(seq[:, :n_prompt]), state)
    got = [logits]
    for t in range(n_prompt, n_prompt + n_steps - 1):
        logits, state = decode_step(model, params, jnp.asarray(seq[:, t : t + 1]), state)
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], 1)  # (B, n_steps, V)

    fed = n_prompt + n_steps - 1
    slots = [j for j, s in enumerate(cfg.layout) if s.ffn == "moe"]
    for b in range(B):
        chosen = np.stack([np.asarray(state["caches"][j]["choice"])[0, b] for j in slots])
        assert (chosen[:, :fed] >= 0).all() and (chosen[:, fed:] == -1).all()
        want, own, margin = reference_hybrid.logits(
            _weights(params), c, seq[b, :fed], np.arange(n_prompt - 1, fed),
            forced=chosen[:, :fed],
        )
        assert reference_hybrid.logit_err(got[b], want) < 1e-4
        wide, _, _ = reference_hybrid.route_mismatch(chosen[:, :fed], own, margin, 1e-3)
        assert wide == 0
    route = np.stack([np.asarray(state["caches"][j]["route"])[0] for j in slots])
    held, absent, dropped, hit = np.moveaxis(route, -1, 0)  # (layers, prefill | decode)
    assert (held + absent == [B * n_prompt * cfg.top_k, B * (n_steps - 1) * cfg.top_k]).all()
    assert (dropped == 0).all() and held.all()
    # held experts reached: once by the prefill, summed over the decode steps
    assert (hit[:, 0] <= cfg.experts_here).all() and (hit[:, 1] <= (n_steps - 1) * B * 2).all()


def _moe_case(cfg, T=40, seed=3):
    from repro.models.moe import init_moe

    p, _ = zip(*init_moe(ParamBuilder(jax.random.key(seed), jnp.float32), cfg).values())
    p = dict(zip(("router", "w_gate", "w_up", "w_down"), p))
    x = jax.random.normal(jax.random.key(seed + 1), (1, T, cfg.d_model))
    return p, x


@pytest.mark.parametrize("drop_free", [True, False], ids=["grouped", "capacity-bins"])
def test_expert_shares_sum_to_the_uncut_layer(drop_free):
    """The two halves of the 16 experts, each held by one chip, give MoE
    outputs that add up to the uncut layer's; their counters split the
    token-expert rows between them."""
    from repro.models.moe import moe_ffn

    whole = _tiny(experts_held=0)
    p, x = _moe_case(whole)
    y, aux = moe_ffn(p, whole, x, drop_free=drop_free)
    parts, held = 0, 0
    for first in (0, 8):
        cut = dataclasses.replace(whole, experts_held=8, experts_first=first)
        mine = {**p, **{k: p[k][first : first + 8] for k in ("w_gate", "w_up", "w_down")}}
        y_part, a = moe_ffn(mine, cut, x, drop_free=drop_free)
        parts = parts + y_part
        held += int(a["route"][0])
        assert int(a["route"][0] + a["route"][1]) == 40 * whole.top_k
        np.testing.assert_array_equal(a["choice"], aux["choice"])
    np.testing.assert_allclose(parts, y, rtol=1e-5, atol=1e-6)
    assert held == 40 * whole.top_k == int(aux["route"][0])


def test_no_routed_row_dropped_above_the_exact_dispatch_size():
    """Past ``moe_exact_tokens``, where capacity bins would drop rows, the
    drop-free (serving) layer computes every routed row: its output is each held
    expert's SwiGLU weighted by the token's softmax share, and its dropped
    counter reads 0. The binned layer drops rows at the same size."""
    from repro.models.moe import moe_ffn

    cfg = _tiny(moe_exact_tokens=16, capacity_factor=0.5)
    p, x = _moe_case(cfg, T=64)
    y, aux = moe_ffn(p, cfg, x, drop_free=True)
    assert int(aux["route"][2]) == 0 and int(aux["route"][0]) > 0

    h = np.asarray(x[0], np.float64)
    probs = jax.nn.softmax(h @ np.asarray(p["router"], np.float64), -1)
    top = np.argsort(-np.asarray(probs), -1)[:, : cfg.top_k]
    want = np.zeros_like(h)
    for e in range(cfg.experts_here):
        share = np.where((top == e).any(-1), np.asarray(probs)[:, e], 0.0)
        g = h @ np.asarray(p["w_gate"][e], np.float64)
        u = h @ np.asarray(p["w_up"][e], np.float64)
        want += share[:, None] * ((g / (1 + np.exp(-g)) * u) @ np.asarray(p["w_down"][e], np.float64))
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=2e-4, atol=2e-5)

    _, binned = moe_ffn(p, cfg, x)
    assert int(binned["route"][2]) > 0


@pytest.mark.parametrize("arch, order_free", [("jamba2-mini", True), ("stablelm-1.6b", False)])
def test_attention_without_positions_sees_keys_as_a_set(arch, order_free):
    """Jamba's attention (``rope_theta`` 0) has no positions: the last
    query's output stays the same when the tokens before it are put in
    another order. stablelm's rotary attention tells the orders apart."""
    from repro.models.attention import attention_block, init_attention

    cfg = _tiny() if arch == "jamba2-mini" else get_config(arch).reduced()
    p, _ = zip(*init_attention(ParamBuilder(jax.random.key(0), jnp.float32), cfg).values())
    p = dict(zip(("wq", "wk", "wv", "wo"), p))
    x = jax.random.normal(jax.random.key(1), (1, 12, cfg.d_model))
    shuffled = jnp.concatenate([x[:, 10::-1], x[:, 11:]], axis=1)
    pos = jnp.arange(12)[None]
    a, _ = attention_block(p, cfg, x, pos)
    b, _ = attention_block(p, cfg, shuffled, pos)
    assert bool(jnp.allclose(a[:, -1], b[:, -1], rtol=1e-5, atol=1e-6)) is order_free


@pytest.mark.parametrize("arch, norms", [("jamba2-mini", True), ("falcon-mamba-7b", False)])
def test_dt_b_c_norms_only_where_the_mixer_has_them(arch, norms):
    """Jamba's mixer RMS-normalises dt, B and C with weights of their own,
    which change its output; falcon-mamba's mixer has none."""
    from repro.models.mamba import init_mamba, mamba_block

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    tree = init_mamba(ParamBuilder(jax.random.key(0), jnp.float32), cfg)
    p = {k: v[0] for k, v in tree.items()}
    assert {"dt_norm", "b_norm", "c_norm"} <= set(p) if norms else not {"dt_norm"} & set(p)
    if norms:
        x = jax.random.normal(jax.random.key(1), (1, 9, cfg.d_model))
        y, _ = mamba_block(p, cfg, x, None)
        for name in ("dt_norm", "b_norm", "c_norm"):
            y2, _ = mamba_block({**p, name: 2 * p[name]}, cfg, x, None)
            assert not bool(jnp.allclose(y, y2))
