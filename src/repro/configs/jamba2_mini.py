"""AI21 Jamba2-Mini (the Jamba 1.5/1.6/1.7 Mini family, 52B-A12B).

[hf:ai21labs/AI21-Jamba2-Mini config.json; arXiv:2403.19887] 32 layers,
d_model 4096, vocab 65536, untied head, RMSNorm eps 1e-6. One GQA attention
layer (32 query / 8 KV heads of 128) with no positional encoding at offset 4
of every 8 (``attn_layer_offset 4 / period 8``); Mamba-1 in the other seven
(d_inner 8192, d_state 16, d_conv 4, dt_rank 256, conv bias, no projection
bias, RMSNorms on dt, B and C); 16 experts of width 14336, top-2 of the
softmax over all 16 and not renormalised, on every odd layer
(``expert_layer_offset 1 / period 2``), a SwiGLU FFN on the even ones: the
layout of ``jamba_v0_1_52b``.
"""

import dataclasses

from repro.configs.jamba_v0_1_52b import CONFIG as _JAMBA

CONFIG = dataclasses.replace(
    _JAMBA,
    name="jamba2-mini",
    rope_theta=0.0,
    norm_eps=1e-6,
    ssm_bcdt_norm=True,
    moe_renorm=False,
)
