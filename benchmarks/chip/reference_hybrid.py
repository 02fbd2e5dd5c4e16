"""Plain float32 reference of the hybrid decoder, and its int8 control.

The model as the configuration states it (Jamba, arXiv:2403.19887, HF
``modeling_jamba``): token embedding; per layer an RMSNorm, a mixer and a
residual, an RMSNorm, a feed-forward and a residual; a final RMSNorm and the
output head. Mixers:

- Mamba-1: in_proj to x and z; a causal depthwise conv with bias and SiLU;
  x_proj to dt, B and C, each RMS-normalised with its weight; dt_proj plus
  its bias through softplus; the selective scan h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t B_t, y_t = C_t . h_t, run as a plain sequential ``lax.scan`` over
  time; y + D x, gated by SiLU(z); out_proj.
- attention: grouped-query causal softmax attention with no positional
  encoding.

Feed-forwards: SwiGLU, or a router's softmax over all ``router_outputs``
experts, top ``num_experts_per_tok`` taken and not renormalised, and the
SwiGLU of each held expert weighted by its share of the token's weights;
slots on experts this chip does not hold add nothing.

It reads the benchmark's weights (``weights_hybrid.py``) and nothing of the
program under test. Matrix products are float32 at ``HIGHEST`` precision on
the exactly widened bf16 weights; a layer runs in a jitted call of its own
and an MoE layer widens one expert at a time, so the reference fits beside
the weights. ``control="int8"`` rounds each weight matrix per output channel
and each activation entering a matrix product per row, as ``reference.py``
does for the dense cells.

The router can be made to follow given expert choices (``forced``, -1 where
it picks its own) wherever its own margin (the router logit of its last pick
less the best left out) is at most ``tau``, so that a served answer is
compared with the reference on the same routing where the two routers may
tie; the reference reports its own top experts and their margins beside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import _matmul, _rms, _sequence, logit_err  # noqa: F401  (re-exported)
from weights_hybrid import period
from work_hybrid import dims, layout

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mamba(k, eps, control, x, w):
    S = x.shape[0]
    h = _rms(x, w["ln1"], eps)
    xz = _matmul(h, w["in_proj"], control)
    xr, z = xz[:, : k["Di"]], xz[:, k["Di"] :]
    Kc = w["conv_w"].shape[0]
    xp = jnp.pad(xr, ((Kc - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][j].astype(F32) * xp[j : j + S] for j in range(Kc))
    xc = jax.nn.silu(conv + w["conv_b"].astype(F32))
    proj = _matmul(xc, w["x_proj"], control)
    R, N = k["R"], k["N"]
    dt = _rms(proj[:, :R], w["dt_norm"], eps)
    B = _rms(proj[:, R : R + N], w["b_norm"], eps)
    C = _rms(proj[:, R + N :], w["c_norm"], eps)
    dt = jax.nn.softplus(_matmul(dt, w["dt_proj"], control) + w["dt_bias"])
    A = -jnp.exp(w["a_log"])

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * A) * state + (dt_t * x_t)[:, None] * b_t[None]
        return state, jnp.dot(state, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros(A.shape, F32), (dt, xc, B, C))
    y = (y + xc * w["d_skip"].astype(F32)) * jax.nn.silu(z)
    return x + _matmul(y, w["out_proj"], control)


def _attention(k, eps, control, x, w):
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rms(x, w["ln1"], eps)
    q = _matmul(h, w["wq"].reshape(k["D"], -1), control).reshape(n, k["KVH"], -1, k["Dh"])
    kk = _matmul(h, w["wk"].reshape(k["D"], -1), control).reshape(n, k["KVH"], k["Dh"])
    v = _matmul(h, w["wv"].reshape(k["D"], -1), control).reshape(n, k["KVH"], k["Dh"])
    s = jnp.einsum("qhgd,khd->hgqk", q, kk, precision=HI) * k["Dh"] ** -0.5
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return x + _matmul(o.reshape(n, -1), w["wo"].reshape(-1, k["D"]), control)


def _swiglu(h, w_gate, w_up, w_down, control):
    g = _matmul(h, w_gate, control)
    u = _matmul(h, w_up, control)
    return _matmul(jax.nn.silu(g) * u, w_down, control)


def _moe(k, first, control, h, w, forced, tau):
    """(output, own choices (S, K), margin (S,)) of one MoE feed-forward,
    routed by ``forced`` where it is given and the margin is at most ``tau``."""
    logits = _matmul(h, w["router"], control)
    probs = jax.nn.softmax(logits, axis=-1)
    top, own = jax.lax.top_k(logits, k["K"] + 1)
    margin = top[:, k["K"] - 1] - top[:, k["K"]]
    own = own[:, : k["K"]]
    chosen = jnp.where((forced >= 0) & (margin <= tau)[:, None], forced, own)
    weight = jnp.take_along_axis(probs, chosen, axis=-1)  # not renormalised

    def one(acc, xs):
        e, wg, wu, wd = xs
        share = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        return acc + share[:, None] * _swiglu(h, wg, wu, wd, control), None

    experts = (jnp.arange(k["Eh"]), w["w_gate"], w["w_up"], w["w_down"])
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), experts)
    return out, own, margin


@functools.partial(jax.jit, static_argnames=("c_items", "at", "mixer", "ffn", "control"))
def _layer(x, stacked, forced, tau, *, c_items, at, mixer, ffn, control):
    """One layer, on its weights at period ``at`` of ``stacked`` (sliced
    here, where the slice is a view)."""
    c = dict(c_items)
    k, eps = dims(c), c["rms_norm_eps"]
    w = jax.tree.map(lambda a: a[at], stacked)
    mix = _mamba if mixer == "mamba" else _attention
    x = mix(k, eps, control, x, {**w["mixer"], "ln1": w["ln1"]})
    h = _rms(x, w["ln2"], eps)
    if ffn == "moe":
        y, own, margin = _moe(k, c["held_experts"][0], control, h, w["ffn"], forced, tau)
    else:
        y = _swiglu(h, w["ffn"]["w_gate"], w["ffn"]["w_up"], w["ffn"]["w_down"], control)
        own, margin = forced, jnp.zeros(forced.shape[:1], F32)
    return x + y, own, margin


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, at, final_norm, lm_head, *, eps, control):
    return _matmul(_rms(x[at], final_norm, eps), lm_head, control)


def _items(c: dict) -> tuple:
    return tuple(sorted(
        (key, tuple(v) if isinstance(v, list) else v)
        for key, v in c.items()
        if isinstance(v, (int, float)) or key == "held_experts"
    ))


def logits(weights, c: dict, tokens, at, *, forced=None, tau=np.inf, control=None):
    """float32 logits (len(at), V) at positions ``at`` of the causal sequence
    ``tokens``, with the reference's own top experts (M, S, K) and margins
    (M, S) of its M MoE layers. ``forced`` (M, S, K) fixes the experts where
    it is not -1 and the reference's margin is at most ``tau``. Pad ``tokens`` to one length for every call: positions
    after ``max(at)`` change nothing before it, and one length compiles once."""
    tokens = jnp.asarray(tokens)
    S, K = tokens.shape[0], c["num_experts_per_tok"]
    lay = layout(c)
    moe = [i for i, (_, f) in enumerate(lay) if f == "moe"]
    if forced is None:
        forced = np.full((len(moe), S, K), -1, np.int32)
    forced = jnp.asarray(forced)
    items, p = _items(c), period(c)
    x = weights["embed"][tokens].astype(F32)
    own, margin = [], []
    for i, (mixer, ffn) in enumerate(lay):
        m = moe.index(i) if ffn == "moe" else 0
        x, o, g = _layer(x, weights["layers"][i % p], forced[m], jnp.float32(tau),
                         c_items=items, at=i // p,
                         mixer=mixer, ffn=ffn, control=control)
        if ffn == "moe":
            own.append(o)
            margin.append(g)
    out = _head(x, jnp.asarray(at), weights["final_norm"], weights["lm_head"],
                eps=c["rms_norm_eps"], control=control)
    return np.asarray(out), np.stack(own), np.stack(margin)


def served(weights, c: dict, prompt, tokens, pad_to: int, at_len: int, *, forced=None,
           tau=np.inf, control=None):
    """The reference at the positions that produced each served token, fed
    the prompt and the served tokens before it, padded to ``pad_to`` tokens
    and read at ``at_len`` positions so that every request runs one program:
    (logits (len(tokens), V), own experts (M, n, K), margins (M, n)) over the
    n = len(prompt) + len(tokens) - 1 positions fed."""
    n, g = len(prompt), len(tokens)
    seq = _sequence(prompt, tokens, pad_to)
    at = np.minimum(np.arange(n - 1, n - 1 + at_len), n + g - 2)
    if forced is not None:
        full = np.full((forced.shape[0], pad_to, forced.shape[2]), -1, np.int32)
        full[:, : n + g - 1] = forced[:, : n + g - 1]
        forced = full
    out, own, margin = logits(weights, c, seq, at, forced=forced, tau=tau, control=control)
    return out[:g], own[:, : n + g - 1], margin[:, : n + g - 1]


def route_mismatch(got, own, margin, tau: float) -> tuple:
    """(positions where the experts ``got`` differ from the reference's own
    and its margin exceeds ``tau``, positions where they differ at all, the
    widest margin at which they differ). Experts compare as sets."""
    differ = np.any(np.sort(got, -1) != np.sort(own, -1), -1)
    wide = differ & (margin > tau)
    return int(wide.sum()), int(differ.sum()), float(margin[differ].max()) if differ.any() else 0.0
