"""Operations and bytes a step needs, computed from the configuration's
shapes: the work of the algorithm, not of one implementation of it, so a
roofline reads the same however the step is computed.

Dense decoder with grouped-query attention and a SwiGLU feed-forward, as in
``configs/*.json`` (keys named as in the models' published ``config.json``).
A multiply-add counts 2 operations. Causal attention counts only the keys a
query may see. Weights are bf16 (2 bytes), as served.
"""

from __future__ import annotations

BF16 = 2
SIZE_KEYS = (
    "num_hidden_layers", "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size",
)


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {
        "L": c["num_hidden_layers"],
        "D": d,
        "H": h,
        "KVH": c["num_key_value_heads"],
        "Dh": c.get("head_dim") or d // h,
        "F": c["intermediate_size"],
        "V": c["vocab_size"],
    }


def layer_params(c: dict) -> int:
    k = dims(c)
    attn = k["D"] * (k["H"] + 2 * k["KVH"]) * k["Dh"] + k["H"] * k["Dh"] * k["D"]
    return attn + 3 * k["D"] * k["F"] + 2 * k["D"]


def token_flops(c: dict, keys: int, logits: bool) -> int:
    """One token through the model, attending to ``keys`` positions."""
    k = dims(c)
    per_layer = 2 * (layer_params(c) - 2 * k["D"]) + 4 * k["H"] * k["Dh"] * keys
    return k["L"] * per_layer + (2 * k["D"] * k["V"] if logits else 0)


def prefill_flops(c: dict, n: int) -> int:
    """A prompt of ``n`` tokens, causal, with the logits of its last."""
    k = dims(c)
    linear = 2 * (layer_params(c) - 2 * k["D"])
    attn = 4 * k["H"] * k["Dh"] * n * (n + 1) // 2
    return k["L"] * (n * linear + attn) + 2 * k["D"] * k["V"]


def decode_flops(c: dict, pos: int) -> int:
    """The token at position ``pos`` (0-based), attending to ``pos + 1`` keys."""
    return token_flops(c, pos + 1, logits=True)


def decode_bytes(c: dict, pos: int) -> int:
    """Bytes a decode step at ``pos`` must move: every weight it uses (one
    row of the embedding), the cache's ``pos`` earlier keys and values, the
    new key and value, and the logits."""
    k = dims(c)
    weights = k["L"] * layer_params(c) + k["D"] * k["V"] + k["D"] + k["D"]
    kv_row = k["L"] * 2 * k["KVH"] * k["Dh"]
    return BF16 * (weights + kv_row * (pos + 1) + k["V"])


def decode_positions(prompt_len: int, gen: int) -> range:
    """Positions of the tokens a request decodes: its first output comes
    from prefill, each later one from a step fed the previous output."""
    return range(prompt_len, prompt_len + gen - 1)


def request_work(c: dict, prompt_len: int, gen: int) -> dict:
    """Operations and bytes of one request's prefill and decode steps; for
    the decode steps also the least time the chip could take, summed per
    step, as a function of the peaks."""
    steps = [(decode_flops(c, p), decode_bytes(c, p)) for p in decode_positions(prompt_len, gen)]
    return {
        "prefill_flops": prefill_flops(c, prompt_len),
        "decode_flops": sum(f for f, _ in steps),
        "decode_bytes": sum(b for _, b in steps),
        "decode_steps": steps,
    }


def decode_floor_s(steps: list, peak: dict) -> float:
    """Least time the chip could take for these decode steps: per step the
    larger of operations over peak FLOP/s and bytes over peak bandwidth."""
    return sum(
        max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"]) for f, b in steps
    )
