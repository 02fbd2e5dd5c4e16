"""Model-level behaviour: decode==teacher-forced, SWA ring wraparound,
MoE dispatch invariants, Mamba prefill continuation, MLA cache compression."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.sharding import cache_logical_axes
from repro.models.common import ArchConfig, LayerSpec
from repro.models.registry import (
    build_model,
    decode_step,
    greedy_generate,
    init_serve_state,
    prefill,
)


# multi-minute model/kernel path: runs in the full CI job only
pytestmark = pytest.mark.slow


DECODE_ARCHS = [
    "internlm2-20b",
    "qwen2.5-32b",
    "mixtral-8x7b",
    "minicpm3-4b",
    "falcon-mamba-7b",
    "jamba-v0.1-52b",
    "seamless-m4t-medium",
    "internvl2-1b",
]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_teacher_forced(arch):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params, _ = m.init(jax.random.key(0))
    B, L = 2, 24
    toks = jax.random.randint(jax.random.key(1), (B, L), 0, cfg.vocab)
    frames = (
        jax.random.normal(jax.random.key(2), (B, cfg.frontend_len, cfg.d_model))
        if cfg.encoder_layers
        else None
    )
    x = m.embed(params, toks)
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    mem = m.encode(params, frames) if cfg.encoder_layers else None
    xt, _, _ = m.trunk(params, x, pos, memory=mem)
    full = m.logits(params, xt)

    state = init_serve_state(m, B, max_len=64)
    lg, state = prefill(m, params, toks[:, :16], state, frames=frames)
    errs = [float(jnp.abs(lg - full[:, 15]).max())]
    for t in range(16, L):
        lg, state = decode_step(m, params, toks[:, t : t + 1], state)
        errs.append(float(jnp.abs(lg - full[:, t]).max()))
    assert max(errs) < 5e-3, f"{arch}: decode diverges from teacher forcing"


def test_swa_ring_buffer_wraparound():
    """Generating past the window: ring cache must equal a full-cache run."""
    cfg = get_config("mixtral-8x7b").reduced()
    cfg = dataclasses.replace(cfg, window=16)  # small window, forces wrap
    m = build_model(cfg)
    params, _ = m.init(jax.random.key(0))
    B, L = 1, 40  # generate well past window=16
    toks = jax.random.randint(jax.random.key(1), (B, L), 0, cfg.vocab)

    # teacher-forced reference (full attention with SWA masking)
    x = m.embed(params, toks)
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    xt, _, _ = m.trunk(params, x, pos)
    full = m.logits(params, xt)

    # ring-cache decode (cache size == window == 16 < L)
    state = init_serve_state(m, B, max_len=64)
    seq_axis = cache_logical_axes(cfg, 64)[0]["k"].index("kv_seq")
    assert state["caches"][0]["k"].shape[seq_axis] == 16  # ring allocated at window
    lg, state = prefill(m, params, toks[:, :8], state)
    errs = [float(jnp.abs(lg - full[:, 7]).max())]
    for t in range(8, L):
        lg, state = decode_step(m, params, toks[:, t : t + 1], state)
        errs.append(float(jnp.abs(lg - full[:, t]).max()))
    assert max(errs) < 5e-3, f"ring cache diverges after wraparound: {max(errs)}"


def test_moe_dispatch_invariants():
    from repro.models.moe import expert_capacity, init_moe, moe_ffn
    from repro.models.common import ParamBuilder

    cfg = get_config("mixtral-8x7b").reduced()
    pb = ParamBuilder(jax.random.key(0), jnp.float32)
    p = jax.tree.map(
        lambda x: x[0],
        init_moe(pb, cfg),
        is_leaf=lambda x: isinstance(x, tuple) and hasattr(x[0], "dtype"),
    )
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    y, aux = moe_ffn(p, cfg, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(aux["aux_loss"]))
    assert 0.0 <= float(aux["dropped_frac"]) <= 1.0
    # generous capacity => zero drops
    cfg2 = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    y2, aux2 = moe_ffn(p, cfg2, x)
    assert float(aux2["dropped_frac"]) == 0.0
    # with zero drops the MoE output must match the dense per-token expert mix
    logits = jnp.einsum("td,de->te", x.reshape(-1, cfg.d_model), p["router"])
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / w.sum(-1, keepdims=True)
    xt = x.reshape(-1, cfg.d_model)
    ref = jnp.zeros_like(xt)
    for e in range(cfg.n_experts):
        g = jnp.einsum("td,df->tf", xt, p["w_gate"][e])
        u = jnp.einsum("td,df->tf", xt, p["w_up"][e])
        h = jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, p["w_down"][e])
        wt = ((idx == e) * w).sum(-1)
        ref = ref + h * wt[:, None]
    np.testing.assert_allclose(
        np.asarray(y2.reshape(-1, cfg.d_model)), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_mamba_prefill_continuation():
    """Two-stage prefill (8 then 8 tokens) == one 16-token prefill."""
    cfg = get_config("falcon-mamba-7b").reduced()
    m = build_model(cfg)
    params, _ = m.init(jax.random.key(0))
    B = 2
    toks = jax.random.randint(jax.random.key(1), (B, 16), 0, cfg.vocab)
    s1 = init_serve_state(m, B, max_len=32)
    lg_a, s1 = prefill(m, params, toks, s1)
    s2 = init_serve_state(m, B, max_len=32)
    _, s2 = prefill(m, params, toks[:, :8], s2)
    lg_b, s2 = prefill(m, params, toks[:, 8:], s2)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b), rtol=2e-4, atol=2e-4)


def test_mla_cache_is_latent_compressed():
    cfg = get_config("minicpm3-4b").reduced()
    m = build_model(cfg)
    state = init_serve_state(m, batch=1, max_len=64)
    c = state["caches"][0]
    latent_bytes = c["c_kv"].nbytes + c["k_rope"].nbytes
    full_kv_bytes = 2 * 1 * 64 * cfg.n_heads * 16 * c["c_kv"].dtype.itemsize * cfg.n_groups
    # latent cache strictly smaller than per-head KV would be
    assert latent_bytes < full_kv_bytes


def test_greedy_generate_deterministic():
    cfg = get_config("stablelm-1.6b").reduced()
    m = build_model(cfg)
    params, _ = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    g1 = greedy_generate(m, params, prompt, n_steps=8, max_len=32)
    g2 = greedy_generate(m, params, prompt, n_steps=8, max_len=32)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    assert g1.shape == (2, 8)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "minicpm3-4b"])
def test_deep_model_is_not_chaotic_at_init(arch):
    """Attention weights are drawn at 1/sqrt(contracted dims). With the
    fan-in of a 3-D weight read as shape[-2], q/k/v came out several times
    too large and a 24-layer model amplified a 1e-6 input change into O(1)
    logits, so no decode-vs-teacher check of a deep model could pass."""
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=24)
    m = build_model(cfg)
    params, _ = m.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 16), 0, cfg.vocab)
    pos = jnp.arange(16)[None]

    @jax.jit
    def logits(eps):
        x, _, _ = m.trunk(params, m.embed(params, toks) * (1 + eps), pos)
        return m.logits(params, x)

    a, b = logits(0.0), logits(1e-6)
    assert float(jnp.abs(a - b).max() / jnp.abs(a).max()) < 1e-4


CACHE_KINDS = {
    # kind: (arch, config changes, prompt length, decode steps)
    "linear-mha": ("stablelm-1.6b", {}, 8, 6),
    "linear-gqa": ("internlm2-20b", {}, 8, 6),
    "swa-ring": ("mixtral-8x7b", {"window": 16}, 8, 14),  # 22 tokens wrap a 16-slot ring
    "mla-latent": ("minicpm3-4b", {}, 8, 6),
    "mamba-state": ("falcon-mamba-7b", {}, 8, 6),
    "hybrid": ("jamba-v0.1-52b", {}, 8, 6),
}


@pytest.mark.parametrize("kind", list(CACHE_KINDS))
def test_stacked_cache_written_in_place(kind):
    """The serve step writes each layer's new rows into the stacked cache at
    its layer's slot: after a prefill and N decode steps every cache equals a
    reference that runs the layers one at a time, each on a cache of its own,
    and stacks what they wrote (to float32 rounding: the reference's layers
    run outside the scan, so XLA fuses them differently). Slots no token
    reached, and the zero lanes that pad a head dim, still hold 0 (ring
    positions -1)."""
    from repro.models.transformer import apply_layer

    arch, changes, n_prompt, n_steps = CACHE_KINDS[kind]
    cfg = dataclasses.replace(get_config(arch).reduced(), **changes)
    m = build_model(cfg)
    params, _ = m.init(jax.random.key(0))
    B, max_len = 2, 32
    toks = jax.random.randint(jax.random.key(1), (B, n_prompt + n_steps), 0, cfg.vocab)

    def one_layer(tree, g):
        return jax.tree.map(lambda a: a[g : g + 1], tree)

    def reference_step(ref, tokens, t):
        """Layer after layer, each writing into its own single-slot cache."""
        x = m.embed(params, tokens)
        L = tokens.shape[1]
        pos = t + jnp.broadcast_to(jnp.arange(L)[None], (B, L))
        out = [[None] * cfg.n_groups for _ in cfg.layout]
        for g in range(cfg.n_groups):
            for j, spec in enumerate(cfg.layout):
                p = jax.tree.map(lambda a: a[g], params["blocks"][j])
                x, out[j][g], _ = apply_layer(
                    p, cfg, spec, x, pos, ref[j][g], None, None, jnp.int32(0)
                )
        return out

    def stacked(ref):
        return [
            jax.tree.map(lambda *a: jnp.concatenate(a), *per_layer) for per_layer in ref
        ]

    state = init_serve_state(m, B, max_len)
    ref = [[one_layer(c, g) for g in range(cfg.n_groups)] for c in state["caches"]]
    _, state = prefill(m, params, toks[:, :n_prompt], state)
    ref = reference_step(ref, toks[:, :n_prompt], 0)
    _assert_cache_state(cfg, state["caches"], stacked(ref), n_prompt, max_len)
    for t in range(n_prompt, n_prompt + n_steps):
        _, state = decode_step(m, params, toks[:, t : t + 1], state)
        ref = reference_step(ref, toks[:, t : t + 1], t)
    _assert_cache_state(cfg, state["caches"], stacked(ref), n_prompt + n_steps, max_len)


def _assert_cache_state(cfg, caches, reference, written, max_len):
    axes = cache_logical_axes(cfg, max_len)
    for c, r, ax in zip(caches, reference, axes):
        assert jax.tree.structure(c) == jax.tree.structure(r)
        for name in c:
            np.testing.assert_allclose(
                np.asarray(c[name]), np.asarray(r[name]), rtol=2e-4, atol=1e-5,
                err_msg=f"{cfg.name}: cache {name!r} differs from the layer-by-layer writes",
            )
        if "index" in c:
            np.testing.assert_array_equal(np.asarray(c["index"]), written)
        if "kv_seq" not in ax.get("k", ax.get("c_kv", ())):
            continue  # a mamba state has no slots
        for name in ("k", "v", "c_kv", "k_rope"):
            if name not in c:
                continue
            a = np.moveaxis(np.asarray(c[name]), ax[name].index("kv_seq"), 1)
            S = a.shape[1]
            if written < S:  # slots no token reached
                assert not a[:, written:].any(), f"{cfg.name}: {name} written past token {written}"
            assert a[:, : min(written, S)].any(axis=tuple(range(2, a.ndim))).all()
        if "k" in c:  # zero lanes padding the head dim
            assert not np.asarray(c["k"])[..., cfg.head_dim :].any()
            assert not np.asarray(c["v"])[..., cfg.head_dim :].any()
        if "pos" in c:
            pos = np.asarray(c["pos"])
            S = pos.shape[-1]
            want = np.full(S, -1)
            for p in range(written):
                want[p % S] = p
            np.testing.assert_array_equal(pos, np.broadcast_to(want, pos.shape))
