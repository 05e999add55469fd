"""The byte and operation counts against numbers worked by hand at GPT-J
widths (d_model 4096, d_ff 16384, vocab 50400, 28 layers, heads of 256)."""

import pytest

from benchmarks.harness import rooflines

GPTJ = dict(d_model=4096, d_ff=16384, vocab_size=50400, n_layers=28, n_heads=16)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_weights_a_decode_step_reads():
    w = rooflines.weight_count(GPTJ)
    assert w["per_layer"] == 4 * 4096 * 4096 + 2 * 4096 * 16384 == 201_326_592
    assert w["head"] == 206_438_400
    assert w["total"] == 28 * 201_326_592 + 206_438_400 == 5_843_582_976


def test_paged_decode_step_bytes_flops_and_least_time():
    # 8 sequences that hold 300 positions each
    need = rooflines.paged_decode_step(GPTJ, 8, 2400.0)
    kv_row = 2 * 4096 * 2 * 28  # K and V of one position over 28 layers, bf16: 458,752 B
    assert need["bytes"] == 5_843_582_976 * 2 + 2400 * kv_row + 8 * kv_row == 12_791_840_768
    assert need["flops"] == 2 * 5_843_582_976 * 8 + 4 * 4096 * 28 * 2400 == 94_598_332_416
    least = rooflines.least_time_s(need["flops"], need["bytes"], V5E)
    assert least["bound"] == "bytes" and least["seconds"] == pytest.approx(0.0156189, rel=1e-5)


def test_flash_attention_call_at_the_training_shape():
    call = rooflines.flash_attention_call(8, 16, 2048, 256)
    assert call["fwd"]["flops"] == 4 * 8 * 16 * 2048 * 2048 * 256 / 2 == 274_877_906_944
    assert call["bwd"]["flops"] == 2.5 * call["fwd"]["flops"]
    tensor = 8 * 16 * 2048 * 256 * 2
    assert call["fwd"]["bytes"] == 4 * tensor and call["bwd"]["bytes"] == 8 * tensor
    fwd = rooflines.least_time_s(call["fwd"]["flops"], call["fwd"]["bytes"], V5E)
    assert fwd["bound"] == "flops" and fwd["seconds"] == pytest.approx(1.39532e-3, rel=1e-5)
    assert rooflines.flash_attention_call(1, 1, 128, 128, causal=False)["fwd"]["flops"] == 4 * 128**3
