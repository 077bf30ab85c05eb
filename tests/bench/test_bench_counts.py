"""Operations and bytes of one decode launch, against hand-worked
numbers at the cell's sizes."""
import pytest

from bench import manifest
from bench.models import llama


def test_deepseek_layer_and_head_params():
    s = manifest.config("deepseek-7b.L1")["model"]
    # q, k, v, o: 4 x 4096 x 4096; gate, up, down: 3 x 4096 x 11008
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008      # 202,375,168
    head = 4096 * 102400                             # 419,430,400
    assert llama.matmul_params(s) == layer + head == 621_805_568
    assert llama.vector_params(s) == 3 * 4096


def test_deepseek_decode_launch():
    s = manifest.config("deepseek-7b.L1")["model"]
    positions = [99, 199, 299, 399, 499, 599, 699, 799]   # live 100..800
    flops, nbytes = llama.decode_launch(s, positions)
    live = sum(p + 1 for p in positions)                  # 3,600
    want_flops = 8 * 2 * 621_805_568 + 4 * live * 32 * 128
    assert flops == want_flops == 9_948_889_088 + 58_982_400
    weights = (621_805_568 + 12_288) * 2                  # 1,243,635,712
    embed = 8 * 4096 * 2
    kv = 2 * 4096 * 4 * (live + 8)                        # 118,226,944
    logits = 8 * 102400 * 4
    assert nbytes == weights + embed + kv + logits == 1_365_204_992
    # least time on a v5e: bound by bytes, 1.667 ms
    p = manifest.peaks()["TPU v5 lite"]
    assert nbytes / p["hbm_bytes_per_s"] == pytest.approx(1.6668e-3,
                                                          rel=1e-3)
    assert nbytes / p["hbm_bytes_per_s"] > flops / p["bf16_flops_per_s"]


def test_token_flops_grow_with_position_for_attention_only():
    ds = manifest.config("deepseek-7b.L1")["model"]
    assert llama.token_flops(ds, 10) - llama.token_flops(ds, 9) == \
        4 * 32 * 128
    # the weights' share does not depend on the position
    assert llama.token_flops(ds, 0) == 2 * 621_805_568 + 4 * 32 * 128
