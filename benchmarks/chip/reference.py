"""Plain float32 reference of the served model, and its int8 control.

A dense pre-norm decoder as the configuration states it: token embedding;
per layer RMSNorm, grouped-query causal attention with rotary positions
(half-split pairs, base ``rope_theta``), a residual, RMSNorm, a SwiGLU
feed-forward and a residual; a final RMSNorm and the output head. It reads
the benchmark's weights (``weights.py``) and nothing of the program under
test. Every matrix product is float32 at ``HIGHEST`` precision; the bf16
weights are widened exactly. Layers run one at a time in a scan, each
widened only while it runs, so the reference fits beside the weights.

``control`` ("int8" or "fp8") computes the same in a precision below the
served bf16: each weight matrix rounded per output channel, and each
activation entering a matrix product rounded per row, to int8 or to float8
e4m3. A configuration names its control; put in the program's place, the
control has to come out as not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from work import dims

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _low(x, axis, control):
    """``x`` rounded to the control's precision, one scale per slice along
    ``axis``: symmetric int8, or float8 e4m3 with the slice's largest
    magnitude at the format's largest."""
    top = 127.0 if control == "int8" else 448.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if control == "int8":
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _matmul(x, w, control):
    """x (N, K) float32 times w (K, M) bf16, in float32."""
    w = w.astype(F32)
    if control:
        x, w = _low(x, -1, control), _low(w, 0, control)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, pos, theta):
    """x (N, heads, Dh); rotate the two halves of each head by position."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = pos[:, None, None].astype(F32) * freqs
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1
    )


def _layer(k, eps, theta, control, x, w):
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rms(x, w["ln1"], eps)
    q = _matmul(h, w["wq"].reshape(k["D"], -1), control).reshape(n, k["H"], k["Dh"])
    kk = _matmul(h, w["wk"].reshape(k["D"], -1), control).reshape(n, k["KVH"], k["Dh"])
    v = _matmul(h, w["wv"].reshape(k["D"], -1), control).reshape(n, k["KVH"], k["Dh"])
    q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
    g = k["H"] // k["KVH"]
    qg = q.reshape(n, k["KVH"], g, k["Dh"])
    s = jnp.einsum("qhgd,khd->hgqk", qg, kk, precision=HI) * k["Dh"] ** -0.5
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI).reshape(n, -1)
    x = x + _matmul(o, w["wo"].reshape(-1, k["D"]), control)
    h = _rms(x, w["ln2"], eps)
    gate = _matmul(h, w["w_gate"], control)
    up = _matmul(h, w["w_up"], control)
    return x + _matmul(jax.nn.silu(gate) * up, w["w_down"], control), None


@functools.partial(jax.jit, static_argnames=("c_items", "control"))
def _logits(weights, tokens, at, *, c_items, control):
    c = dict(c_items)
    k = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = weights["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(
        functools.partial(_layer, k, eps, theta, control), x, weights["layers"]
    )
    h = _rms(x[at], weights["final_norm"], eps)
    return _matmul(h, weights["lm_head"], control)


def logits(weights, c: dict, tokens: np.ndarray, at: np.ndarray, *, control=None):
    """float32 logits (len(at), V) at positions ``at`` of the causal sequence
    ``tokens``. Pad ``tokens`` to one length for every call: positions after
    ``max(at)`` change nothing before it, and one length compiles once."""
    items = tuple(sorted((key, v) for key, v in c.items() if isinstance(v, (int, float))))
    return _logits(weights, jnp.asarray(tokens), jnp.asarray(at), c_items=items, control=control)


def served_logits(weights, c: dict, prompt, served, pad_to: int, at_len: int, *,
                  control=None) -> np.ndarray:
    """float32 logits (len(served), V) at the positions that produced each
    served token, the prompt and the served tokens before it as input: the
    reference's, or with ``control`` ("int8", "fp8") the control's. Sequences are padded
    to ``pad_to`` tokens and the positions read to ``at_len``, so that every
    request runs one program."""
    n, g = len(prompt), len(served)
    seq = _sequence(prompt, served, pad_to)
    at = np.minimum(np.arange(n - 1, n - 1 + at_len), n + g - 2)
    return np.asarray(logits(weights, c, seq, at, control=control))[:g]


def logit_err(got: np.ndarray, ref: np.ndarray) -> float:
    """The widest error over positions: per position the root mean square of
    ``got - ref`` over the vocabulary, over that of ``ref``."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    num = np.sqrt(np.mean((got - ref) ** 2, -1))
    return float(np.max(num / np.sqrt(np.mean(ref**2, -1))))


def _sequence(prompt, served, pad_to: int) -> np.ndarray:
    """The prompt and every served token but the last, padded."""
    n, g = len(prompt), len(served)
    seq = np.zeros(pad_to, np.int32)
    seq[:n] = prompt
    seq[n : n + g - 1] = np.clip(served[: g - 1], 0, None)
    return seq
