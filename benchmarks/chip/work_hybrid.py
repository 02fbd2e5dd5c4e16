"""Operations and bytes of the hybrid Mamba / attention / MoE decoder
(``configs/jamba2-mini-period8.json``), from its shapes and the routing
counters the served steps return: the work of the algorithm, so a roofline
reads the same however the step computes it.

Layer ``i`` holds attention where ``i % attn_layer_period ==
attn_layer_offset`` and Mamba elsewhere, and experts where ``i %
expert_layer_period == expert_layer_offset`` and a SwiGLU FFN elsewhere.
The experts counted are the ones this chip holds (``num_experts``); the
router scores ``router_outputs``. Routed work counts the token-expert rows
that land on held experts (``held``) and, for bytes, the held experts a
call reaches (``hit_experts``), both read from the program's counters, so
capacity bins or a dense read of every expert count as waste. A
multiply-add counts 2 operations; causal attention counts only the keys a
query may see; the selective scan counts 3 multiply-adds per state element
and token (decay, input, output). Weights are bf16 (2 bytes), the scan's
state float32 (4), as served.
"""

from __future__ import annotations

from work import BF16, decode_positions

F32 = 4

SIZE_KEYS = (
    "num_hidden_layers", "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "vocab_size", "num_experts", "router_outputs",
    "num_experts_per_tok", "mamba_d_state", "mamba_d_conv", "mamba_dt_rank", "mamba_expand",
    "attn_layer_offset", "attn_layer_period", "expert_layer_offset", "expert_layer_period",
)


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {
        "L": c["num_hidden_layers"],
        "D": d,
        "H": h,
        "KVH": c["num_key_value_heads"],
        "Dh": d // h,
        "F": c["intermediate_size"],
        "V": c["vocab_size"],
        "E": c["router_outputs"],
        "Eh": c["num_experts"],
        "K": c["num_experts_per_tok"],
        "Di": c["mamba_expand"] * d,
        "N": c["mamba_d_state"],
        "R": c["mamba_dt_rank"],
        "Kc": c["mamba_d_conv"],
    }


def layout(c: dict) -> list:
    """Per layer (mixer, ffn): ("attention" | "mamba", "moe" | "dense")."""
    return [
        (
            "attention" if i % c["attn_layer_period"] == c["attn_layer_offset"] else "mamba",
            "moe" if i % c["expert_layer_period"] == c["expert_layer_offset"] else "dense",
        )
        for i in range(c["num_hidden_layers"])
    ]


def counts(c: dict) -> dict:
    """Layers of each kind: mamba, attention, moe, dense."""
    lay = layout(c)
    return {
        "mamba": sum(m == "mamba" for m, _ in lay),
        "attention": sum(m == "attention" for m, _ in lay),
        "moe": sum(f == "moe" for _, f in lay),
        "dense": sum(f == "dense" for _, f in lay),
    }


def mamba_params(c: dict) -> int:
    k = dims(c)
    D, Di, N, R = k["D"], k["Di"], k["N"], k["R"]
    return (
        D * 2 * Di  # in_proj
        + k["Kc"] * Di + Di  # depthwise conv and its bias
        + Di * (R + 2 * N) + R + 2 * N  # x_proj and the dt, B, C norms
        + R * Di + Di  # dt_proj and its bias
        + Di * N + Di  # A_log, D
        + Di * D  # out_proj
    )


def attention_params(c: dict) -> int:
    k = dims(c)
    return k["D"] * (k["H"] + 2 * k["KVH"]) * k["Dh"] + k["H"] * k["Dh"] * k["D"]


def expert_params(c: dict) -> int:
    """One expert's SwiGLU, the dense FFN's size too."""
    k = dims(c)
    return 3 * k["D"] * k["F"]


def router_params(c: dict) -> int:
    k = dims(c)
    return k["D"] * k["E"]


def model_params(c: dict) -> int:
    """Every parameter this chip holds."""
    k, n = dims(c), counts(c)
    return (
        n["mamba"] * mamba_params(c)
        + n["attention"] * attention_params(c)
        + n["dense"] * expert_params(c)
        + n["moe"] * (router_params(c) + k["Eh"] * expert_params(c))
        + k["L"] * 2 * k["D"]  # two norms a layer
        + 2 * k["V"] * k["D"] + k["D"]  # embedding, head, final norm
    )


def _dense_token_flops(c: dict) -> int:
    """Work of one token outside the experts, attention's scores and the
    scan: every matmul weight of the Mamba, attention, dense FFN and router
    layers, and the depthwise conv."""
    n, k = counts(c), dims(c)
    D, Di = k["D"], k["Di"]
    mamba = D * 2 * Di + Di * (k["R"] + 2 * k["N"]) + k["R"] * Di + Di * D
    weights = (
        n["mamba"] * mamba
        + n["attention"] * attention_params(c)
        + n["dense"] * expert_params(c)
        + n["moe"] * router_params(c)
    )
    return 2 * weights + n["mamba"] * 2 * k["Kc"] * Di


def scan_flops(c: dict, tokens: int) -> int:
    """One layer's selective scan over ``tokens``: 3 multiply-adds per state
    element and token."""
    k = dims(c)
    return 6 * tokens * k["Di"] * k["N"]


def scan_bytes(c: dict, tokens: int) -> int:
    """Least bytes one layer's scan moves: its inputs (x bf16; dt, B, C
    float32) and output y (float32) once, A, and the state in and out."""
    k = dims(c)
    Di, N = k["Di"], k["N"]
    return tokens * (Di * (BF16 + F32 + F32) + 2 * N * F32) + 3 * Di * N * F32


def moe_flops(c: dict, tokens: int, held: int) -> int:
    """One MoE call: the router over ``tokens`` and ``held`` routed rows."""
    return 2 * tokens * router_params(c) + held * 2 * expert_params(c)


def moe_bytes(c: dict, tokens: int, hit: int) -> int:
    """Least bytes of one MoE call: the router, the ``hit`` held experts it
    reaches, its input and output."""
    k = dims(c)
    return BF16 * (router_params(c) + hit * expert_params(c) + 2 * tokens * k["D"])


def _attention_flops(c: dict, q_from: int, n: int) -> int:
    """Scores and values of ``n`` queries at positions ``q_from..``, causal."""
    k = dims(c)
    keys = n * q_from + n * (n + 1) // 2
    return counts(c)["attention"] * 4 * k["H"] * k["Dh"] * keys


def prefill_flops(c: dict, n: int, held: int) -> int:
    """A prompt of ``n`` tokens with ``held`` routed rows on held experts
    over all its MoE layers, and the logits of its last token."""
    k, nl = dims(c), counts(c)
    return (
        n * _dense_token_flops(c)
        + nl["mamba"] * scan_flops(c, n)
        + _attention_flops(c, 0, n)
        + held * 2 * expert_params(c)
        + 2 * k["D"] * k["V"]
    )


def decode_flops(c: dict, pos: int, held: int) -> int:
    """The token at position ``pos`` with ``held`` routed rows."""
    k, nl = dims(c), counts(c)
    return (
        _dense_token_flops(c)
        + nl["mamba"] * scan_flops(c, 1)
        + _attention_flops(c, pos, 1)
        + held * 2 * expert_params(c)
        + 2 * k["D"] * k["V"]
    )


def decode_bytes(c: dict, pos: int, hit: int) -> int:
    """Bytes a decode step at ``pos`` must move when its token reaches
    ``hit`` held experts over all MoE layers: every other weight (one row of
    the embedding), those experts, the attention cache's ``pos`` rows and
    the new one, each Mamba state and conv window read and written, and the
    logits."""
    k, n = dims(c), counts(c)
    weights = (
        n["mamba"] * mamba_params(c)
        + n["attention"] * attention_params(c)
        + n["dense"] * expert_params(c)
        + n["moe"] * router_params(c)
        + k["L"] * 2 * k["D"]
        + k["D"] * k["V"] + k["D"] + k["D"]
    )
    kv = n["attention"] * 2 * k["KVH"] * k["Dh"] * (pos + 1)
    state = n["mamba"] * 2 * (k["Di"] * k["N"] * F32 + (k["Kc"] - 1) * k["Di"] * BF16)
    wide = n["mamba"] * (k["Di"] * k["N"] + k["Di"]) * (F32 - BF16)  # A_log, dt_bias: float32
    return BF16 * (weights + hit * expert_params(c) + kv + k["V"]) + state + wide


def request_work(c: dict, prompt_len: int, gen: int, route: dict) -> dict:
    """Operations and bytes of one request's prefill and decode steps, from
    its routing counters ``route``: per MoE layer a prefill and a decode row
    of (held, absent, dropped, hit_experts), the decode row summed over its
    steps. The decode steps' FLOPs and bytes are summed; their floor is
    exact while every step is bound by bandwidth (at one token a step by
    about a hundred times)."""
    steps = list(decode_positions(prompt_len, gen))
    pre = [r[0] for r in route]
    dec = [r[1] for r in route]
    d_held = sum(r[0] for r in dec)
    d_hit = sum(r[3] for r in dec)
    calls = len(steps) * len(dec)  # decode calls of the MoE layers
    return {
        "prefill_flops": prefill_flops(c, prompt_len, sum(r[0] for r in pre)),
        "decode_flops": sum(decode_flops(c, p, 0) for p in steps) + d_held * 2 * expert_params(c),
        "decode_bytes": sum(decode_bytes(c, p, 0) for p in steps) + d_hit * BF16 * expert_params(c),
        "moe_prefill": [(moe_flops(c, prompt_len, r[0]), moe_bytes(c, prompt_len, r[3])) for r in pre],
        "moe_decode": (
            calls * 2 * router_params(c) + d_held * 2 * expert_params(c),
            calls * moe_bytes(c, 1, 0) + d_hit * BF16 * expert_params(c),
        ),
        "scan_prefill": (scan_flops(c, prompt_len), scan_bytes(c, prompt_len)),
    }


def floor_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time for work of ``flops`` and ``nbytes``."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
