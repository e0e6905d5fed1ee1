"""Operation and byte counts on hand-computed sizes, and the peak table."""

import pytest

from bench import counts as C
from bench import peaks as P

TINY = {
    "num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
    "num_key_value_heads": 2, "intermediate_size": 16, "vocab_size": 10,
    "ffn_gated": True, "skip_lora_rank": 2,
}


def test_matmul_params():
    # per layer: q,k,v 8*(2+4)*4 = 192; o 2*4*8 = 64; ffn 3*8*16 = 384
    assert C.layer_matmul_params(TINY) == 640
    assert C.matmul_params(TINY) == 1280
    ungated = dict(TINY, ffn_gated=False)
    assert C.layer_matmul_params(ungated) == 640 - 128


def test_token_flops():
    # attention over 5 keys: 4 * heads 2 * hd 4 * 5 keys * 2 layers
    assert C.attention_flops(TINY, 5) == 320
    assert C.readout_flops(TINY) == 160              # 2 * 8 * 10
    assert C.skip_flops(TINY) == 128                 # 4 * 2 * 8 * 2
    # decode at position 4 attends 5 keys
    assert C.decode_token_flops(TINY, 4) == 2 * 1280 + 320 + 128 + 160
    # prefill of 3 tokens: 6 causal pairs
    assert C.prefill_flops(TINY, 3) == 2 * 1280 * 3 + 4 * 2 * 4 * 6 * 2 + 128 + 160
    assert C.adapt_token_flops(TINY) == 3 * 128 + 2 * 160


def test_kernel_calls():
    f, b = C.skip_serve_call(TINY, rows=3, slots=2)
    assert f == 4 * 2 * 8 * 2 * 3
    assert b == 2 * 2 * 3 * 8 + 4 * 2 * 2 * 2 * 8 * 2 + 2 * 3 * 8
    f, b = C.skip_adapt_bwd_call(TINY, rows=4, tenants=1)
    assert f == 6 * 2 * 8 * 2 * 4
    assert b == 2 * 2 * 4 * 8 + 2 * 4 * 8 + 2 * 4 * 1 * 2 * 2 * 8 * 2


def test_peaks_table():
    p = P.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert P.least_seconds(197e12, 0, "TPU v5 lite") == pytest.approx(1.0)
    assert P.least_seconds(0, 819e9 * 2, "TPU v5 lite") == pytest.approx(2.0)
    with pytest.raises(KeyError):
        P.peaks("cpu")
