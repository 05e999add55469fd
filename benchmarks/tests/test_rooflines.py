"""The byte and operation counts against numbers worked by hand at GPT-J
widths (d_model 4096, d_ff 16384, vocab 50400, 28 layers, heads of 256)."""

import pytest

from benchmarks.families import gptj as family
from benchmarks.harness import rooflines

GPTJ = dict(d_model=4096, d_ff=16384, vocab_size=50400, n_layers=28, n_heads=16)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_weights_a_decode_step_reads():
    w = family.weight_count(GPTJ)
    assert w["per_layer"] == 4 * 4096 * 4096 + 2 * 4096 * 16384 == 201_326_592
    assert w["head"] == 206_438_400
    assert w["total"] == 28 * 201_326_592 + 206_438_400 == 5_843_582_976


def test_paged_decode_step_bytes_flops_and_least_time():
    # 8 sequences that hold 300 positions each
    need = family.decode_step_need(GPTJ, 8, 2400.0)
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


def test_paged_attention_bytes_are_the_whole_blocks_the_kernel_copies():
    # 8 sequences whose tables hold 112 blocks of 16 rows in all (14 each: 209-224 positions)
    need = family.paged_attention_need(GPTJ, 112.0, 16, 8.0)
    rows = 112 * 16
    kv = rows * 2 * 4096 * 2 * 28  # K and V of every copied row over 28 layers, bf16
    q_and_o = 8 * 2 * 4096 * 2 * 28
    assert need["bytes"] == kv + q_and_o == 825_753_600
    assert need["flops"] == 4 * rows * 4096 * 28 == 822_083_584
    least = rooflines.least_time_s(need["flops"], need["bytes"], V5E)
    assert least["bound"] == "bytes" and least["seconds"] == pytest.approx(1.008246e-3, rel=1e-5)


def test_the_paged_kernels_share_from_loop_records_and_kernel_events(monkeypatch, tmp_path):
    """``paged_attn_roofline`` by hand: 10 traced steps whose kernel events
    took 14.4 ms, steps of the window that copied 112 blocks for 8 sequences."""
    import json

    import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
    from benchmarks.harness import loops

    read = tiny.reader("paged_attn_roofline")
    steps = [{"kind": "llm_step", "t_loop": int((100 + i) * 1e9), "live": live, "kv_blocks": blocks}
             for i, (live, blocks) in enumerate([(8, 110), (8, 114), (0, 0), (8, 112)])]  # one iteration dispatched nothing
    (tmp_path / "llm-llm-1.jsonl").write_text("".join(json.dumps(r) + "\n" for r in steps))
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))
    ctx = {
        "config": {}, "model": GPTJ, "engine": {"block_size": 16}, "peaks": V5E, "window": (100.0, 200.0),
        "trace": {"modules": {"jit_decode_step_greedy": {"count": 10, "total_s": 0.22}},
                  "ops_s": {"jit_decode_step_greedy/paged_decode_attention.7": 0.0144,
                            "jit_decode_step_greedy/fusion.103": 0.05, "jit_prefill/paged_decode_attention": 1.0}},
    }
    assert read(ctx) == pytest.approx(100 * 1.008246e-3 / 1.44e-3, rel=1e-5)  # 70.0%
    # nothing to read: no kernel events (off the chip), or a program whose records carry no blocks
    assert read({**ctx, "trace": {**ctx["trace"], "ops_s": {"jit_decode_step_greedy/fusion.103": 0.05}}}) is None
    (tmp_path / "llm-llm-1.jsonl").write_text("".join(
        json.dumps({k: v for k, v in r.items() if k != "kv_blocks"}) + "\n" for r in steps))
    assert read(ctx) is None
