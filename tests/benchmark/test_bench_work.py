"""Operations and bytes from shapes, against hand counts."""

from __future__ import annotations

import pytest

import bench
import work


def _config(name):
    man = bench.manifest()
    return bench.config_file(bench.find(man["configs"], name, "configuration"))


def test_stablelm_hand_counts():
    c = _config("stablelm-1.6b")
    # per layer: q,k,v 2048*(32+2*32)*64, o 32*64*2048, SwiGLU 3*2048*5632, two norms
    layer = 2048 * 96 * 64 + 32 * 64 * 2048 + 3 * 2048 * 5632 + 2 * 2048
    assert work.layer_params(c) == layer == 51_384_320
    assert 24 * layer + 2 * 100352 * 2048 + 2048 == 1_644_267_520  # 1.64 B as published
    # decode at position 999: 2 flops a weight of every matmul, 4*H*Dh per key
    flops = 24 * (2 * (layer - 4096) + 4 * 32 * 64 * 1000) + 2 * 2048 * 100352
    assert work.decode_flops(c, 999) == flops
    # bytes: bf16 layers, head, one embedding row, final norm, 999 cached
    # positions read and one written (k and v, 32 heads of 64), the logits
    kv = 24 * 2 * 32 * 64
    assert work.decode_bytes(c, 999) == 2 * (24 * layer + 2048 * 100352 + 2 * 2048 + kv * 1000 + 100352)


def test_internlm2_stage_hand_counts():
    c = _config("internlm2-20b-stage12")
    layer = 6144 * (48 + 16) * 128 + 48 * 128 * 6144 + 3 * 6144 * 16384 + 2 * 6144
    assert work.layer_params(c) == layer == 390_082_560
    assert 12 * layer + 2 * 92544 * 6144 + 6144 == 5_818_177_536  # the stage's 5.8 B
    # GQA: the cache holds 8 heads, queries attend with 48
    kv = 12 * 2 * 8 * 128
    assert work.decode_bytes(c, 0) == 2 * (12 * layer + 6144 * 92544 + 2 * 6144 + kv + 92544)
    assert work.decode_flops(c, 0) - work.decode_flops(c, 1) == -12 * 4 * 48 * 128


@pytest.mark.parametrize("name", ["stablelm-1.6b", "internlm2-20b-stage12"])
@pytest.mark.parametrize("n", [1, 7, 256])
def test_prefill_is_its_tokens(name, n):
    c = _config(name)
    k = work.dims(c)
    tokens = sum(work.token_flops(c, i + 1, logits=False) for i in range(n))
    assert work.prefill_flops(c, n) == tokens + 2 * k["D"] * k["V"]


def test_request_work_and_floor():
    c = _config("stablelm-1.6b")
    w = work.request_work(c, 512, 20)
    assert len(w["decode_steps"]) == 19
    assert w["decode_flops"] == sum(work.decode_flops(c, p) for p in range(512, 531))
    peak = bench.peaks("TPU v5 lite")
    floor = work.decode_floor_s(w["decode_steps"], peak)
    # decode is bound by bandwidth: 19 reads of about 2.9 GB (all weights
    # but the embedding table, of which one row) at 819 GB/s
    assert floor == pytest.approx(w["decode_bytes"] / 819e9)
    assert 0.065 < floor < 0.07
