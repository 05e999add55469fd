"""The hybrid reasoning cell's tiny twin: Olmo-Hybrid's family (gated
delta-rule layers with a recurrent state a sequence beside full-attention
layers over paged K/V, a state row in the block table's last column) through
the harness at a CPU's size. The real files of the family are the ones under
test; only the configuration and the mix are made up. With a planted fault in
the reference (beta not doubled, the decay left out, the short convolution left
out, the recurrent mixers' output dropped, rotary put on the full layers) the
same cell ends ``correct: false``."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.families import olmo_hybrid as family

CELL = "serve-olmohybrid16l-reasoning"
TWIN = "tiny-hybrid-reasoning"
# the listed readers that read nothing off the chip: three need the chip's peaks and a program's device time the
# chip's "XLA Modules" line, and no ``gated_delta_update`` or ``paged_decode_attention`` event exists where no kernel runs
OFF_THE_CHIP = {"paged_decode_roofline", "paged_attn_roofline", "prefill_device_ms.reasoning", "state_update_roofline"}
CONFIG = {
    "family": "olmo_hybrid", "source": "made up for the tests", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 6, "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "layer_types": ["linear_attention", "linear_attention", "full_attention"] * 2, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}, "dtype": "float32", "reduced": [],
    "chips": 1,
    "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 4, "max_blocks_per_seq": 17},
    # float32 on the CPU: the prefill's chunkwise form against the token recurrence reads ~1e-5; a fault 1e-2 and more
    "limits": {"logits_rel_err_max": 1e-3, "logits_rel_err_mean": 1e-3, "served_token_mismatches": 0},
}
TRAFFIC = {**tiny.TRAFFIC["tiny-batch"], "callers": 5, "prompt_len": {"lo": 6, "hi": 24, "count": 4},
           "output_len": {"lo": 5, "hi": 12, "count": 4}}
FAULTS = {
    "beta_not_doubled": '''

def strength(b, hy):  # the fault: beta in (0, 1)
    return jax.nn.sigmoid(b)
''',
    "no_decay": '''

def decay(a, a_log, dt_bias):  # the fault: alpha = 1, a state that forgets nothing
    return jnp.zeros_like(a)
''',
    "no_short_conv": '''

def short_conv(u, w):  # the fault: a token sees its own projection alone
    return silu(u)
''',
    "no_recurrent_mixer": '''

def linear_mixer(x, params, ll, hy, precision):  # the fault: the recurrent layers add nothing
    return jnp.zeros_like(x)
''',
    "rotary_on_full_layers": '''

def rotate(x, positions):  # the fault: a rotary where the published config has none
    from benchmarks.reference.longcat import rope

    return rope(x, positions, 10000.0)
''',
}
RETURNS = "from benchmarks.reference import olmo_hybrid\n\n    return olmo_hybrid"


def real_entries():
    bench = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
    return bench, [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]


@pytest.fixture(scope="module", params=["sound", *FAULTS])
def tree(request, tmp_path_factory):
    """The copy with the twin; a faulty one gets a reference of its own (the
    family's file with the fault appended) under another family name."""
    extra_files, config = {}, dict(CONFIG)
    if request.param in FAULTS:
        here = os.path.join(tiny.ROOT, "benchmarks")
        fam = open(os.path.join(here, "families", "olmo_hybrid.py")).read()
        assert RETURNS in fam
        extra_files = {
            "families/olmo_hybrid_faulty.py": fam.replace(RETURNS, RETURNS.replace("olmo_hybrid", "olmo_hybrid_faulty")),
            "reference/olmo_hybrid_faulty.py": open(os.path.join(here, "reference", "olmo_hybrid.py")).read()
            + FAULTS[request.param],
        }
        config["family"] = "olmo_hybrid_faulty"
    _, mine = real_entries()
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=[(TWIN, "tiny-hybrid", "tiny-hybrid-reasoning", 1)],
        extra_configs={"tiny-hybrid": config}, extra_traffic={"tiny-hybrid-reasoning": TRAFFIC},
        extra_files=extra_files, extra_per_layer=[{**m, "workloads": [TWIN]} for m in mine], extra_twins={CELL: [TWIN]},
    )
    return request.param, dest


def test_the_twin_runs_to_correct_and_a_reference_with_a_planted_fault_does_not(tree):
    which, dest = tree
    proc = tiny.run_cell(dest, TWIN, trace=0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["correct"] is (which == "sound"), proc.stdout[-3000:]


def test_every_listed_reader_but_those_that_need_the_chip_returns_a_number_on_the_twins_line(tree):
    which, dest = tree
    if which != "sound":
        pytest.skip("the sound tree's traced line is the one read")
    proc = tiny.run_cell(dest, TWIN, trace=1, seconds=4.0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    bench, mine = real_entries()
    assert [m["name"] for m in mine] == ["state_update_roofline"]
    want = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert OFF_THE_CHIP < want
    assert set(line["metrics"]) == want - OFF_THE_CHIP == {"batch_occupancy", "decode_step_ms.reasoning"}, proc.stdout[-3000:]
    assert all(isinstance(m["value"], float) for m in line["metrics"].values())
    assert line["correct"] is True


def test_the_state_updates_share_is_read_from_the_kernels_events_and_the_loops_live_rows(tmp_path, monkeypatch):
    from benchmarks.harness import loops

    read = tiny.reader("state_update_roofline")
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "olmo-hybrid-7b-16l.json")))
    model = family.model_kwargs(config)
    recs = [{"kind": "llm_step", "t_loop": int(1e9 * t), "live": 48, "kv_blocks": 2000} for t in (1, 2, 3)]
    (tmp_path / "llm-a.jsonl").write_text("\n".join(json.dumps(r) for r in recs))
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"modules": {"jit_decode_step_greedy(7)": {"count": 10, "total_s": 0.2}},
             "ops_s": {"jit_decode_step_greedy(7)/gated_delta_update.3": 0.040, "jit_decode_step_greedy(7)/fusion.1": 0.1,
                       "jit_decode_step(9)/gated_delta_update.3": 0.5}}
    ctx = {"config": config, "model": model, "trace": trace, "peaks": peaks, "window": (0.5, 3.5)}
    need = family.state_update_need(model, 48)
    assert read(ctx) == pytest.approx(100 * (need["bytes"] / 819e9) / 0.004)  # 4 ms of the kernel a step
    # nothing to read: no kernel event (a program off the chip), no trace, a family that counts no state
    assert read({**ctx, "trace": {**trace, "ops_s": {"jit_decode_step_greedy(7)/fusion.1": 0.1}}}) is None
    assert read({**ctx, "trace": None}) is None
    gptj = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "gptj-6b.json")))
    assert read({**ctx, "config": gptj}) is None


def test_the_real_configuration_is_the_catalogs_with_the_cut_written_down():
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "olmo-hybrid-7b-16l.json")))
    model = family.model_kwargs(config)
    assert (model["kind"], model["num_hidden_layers"], model["rope_theta"]) == ("olmo_hybrid", 16, None)
    assert model["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 4
    assert config["reduced"] == ["num_hidden_layers", "layer_types"] and config["published"]["num_hidden_layers"] == 32
    assert (config["hidden_size"], config["intermediate_size"], config["vocab_size"]) == (3840, 11008, 100352)
    assert (config["linear_key_head_dim"], config["linear_value_head_dim"], config["linear_num_value_heads"]) == (96, 192, 30)
    assert config["layer_chips"] == 1 and config["rope_parameters"] == {"rope_theta": None}
    engine, mix = config["engine"], json.load(open(os.path.join(tiny.ROOT, "benchmarks", "traffic", "reasoning.json")))
    worst = -(-(mix["prompt_len"]["hi"] + mix["output_len"]["hi"]) // engine["block_size"])
    # the table's last column is the state row's: 96 columns of blocks and one more
    assert worst + 1 == engine["max_blocks_per_seq"] and engine["num_blocks"] == engine["max_batch"] * worst + 1
    assert (engine["max_batch"], mix["callers"]) == (48, 60)
    with pytest.raises(NotImplementedError):
        family.train_config(model)


def test_the_needs_by_hand_at_the_published_numbers():
    """The real configuration at its 48 slots holding 30,000 positions."""
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "olmo-hybrid-7b-16l.json")))
    m = family.model_kwargs(config)
    w = family.weight_count(m)
    assert w["mlp"] == 3 * 3840 * 11008 == 126_812_160
    mixer = 3840 * (11520 + 5760 + 60) + 5760 * 3840 + 4 * 11520 + 60 + 192
    assert w["linear_mixer"] == mixer and 88.74e6 < mixer < 88.76e6  # ISSUE 36's 88.75 M
    assert w["full_mixer"] == 4 * 3840 * 3840 + 2 * 3840
    assert 215.5e6 < w["linear_layer"] < 215.7e6 and 185.7e6 < w["full_layer"] < 185.9e6
    assert w["total"] == 12 * w["linear_layer"] + 4 * w["full_layer"] + 3840 * 100352
    # every weight the chip holds, with the embedding a step gathers from: 8.20 GB at 2 bytes
    assert 8.19e9 < 2 * (w["total"] + 3840 * 100352) < 8.21e9
    row = family.state_row_bytes(m)
    assert row == {"state": 96 * 30 * 192 * 4, "window": 4 * 11520 * 2} and row["state"] == 2_211_840
    need = family.state_update_need(m, 48)
    assert need["bytes"] == 48 * 12 * (2 * 2_211_840 + (2 * 30 * 96 + 4 * 30 * 192) * 4)
    assert 2.61e9 < need["bytes"] < 2.63e9 and need["flops"] == 7.0 * 552_960 * 48 * 12
    step = family.decode_step_need(m, 48, 30_000.0, 2)
    kv_row = 2 * 3840 * 2 * 4
    want = w["total"] * 2 + 48 * 12 * 2 * (2_211_840 + 92_160) + (30_000 + 48) * kv_row
    assert step["bytes"] == pytest.approx(want) and 11.9e9 < want < 12.1e9  # ISSUE 36's 12.1 GB counts 32 stored heads
    assert step["flops"] == pytest.approx(2.0 * w["total"] * 48 + 4.0 * 3840 * 4 * 30_000 + need["flops"])
    attn = family.paged_attention_need(m, 2000.0, 16, 48.0)
    assert family.kv_heads_stored(m) == 32
    assert attn["bytes"] == (2000 * 16 * 2 * 4096 + 48 * 2 * 4096) * 2 * 4 and attn["flops"] == 4.0 * 32_000 * 4096 * 4
    # the pool as the configuration's file states it: 4.83 GB of blocks, 1.35 GB of state rows
    e = config["engine"]
    assert 4.83e9 < e["num_blocks"] * e["block_size"] * 2 * 4 * 32 * 128 * 2 < 4.84e9
    assert 1.35e9 < (e["max_batch"] + 1) * 12 * (row["state"] + row["window"]) < 1.36e9
