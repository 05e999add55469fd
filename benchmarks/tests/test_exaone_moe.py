"""The long-prompt reasoning cell's tiny twin for K-EXAONE's family (grouped
K/V with a per-head norm, three window layers with a rotary to every full layer
without, a leading dense layer, then sigmoid-routed experts beside a shared
expert, a share of the routed experts) through the harness at a CPU's size. The
real files of the family are the ones under test; only the configuration and
the mix are made up. With a planted fault in the reference's place (a window one
position off, a rotary on the full layers, no norm on q and k, the shared or the
held experts left out, weights not renormalised, the scaling factor 1) the same
cell ends ``correct: false``."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.families import exaone_moe as family

CELL = "serve-kexaone8l-longreason"
TWIN = "tiny-kexaone"
CONFIG = {
    "family": "exaone_moe", "source": "made up for the tests", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 32, "num_hidden_layers": 8, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_experts": 6, "num_experts_published": 16,
    "expert_offset": 4, "num_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "routed_scaling_factor": 2.5, "sliding_window": 8,
    "sliding_window_pattern": "LLLG", "num_nextn_predict_layers": 0, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 100.0, "rope_type": "default"},
    "tie_word_embeddings": False, "dtype": "float32", "reduced": [], "chips": 1,
    "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 4, "max_blocks_per_seq": 17},
    "limits": {"logits_rel_err_max": 1e-3, "logits_rel_err_mean": 1e-3, "served_token_mismatches": 0},
}
# prompts past the window of 8, so every ring wraps in its prefill and again while decoding
TRAFFIC = {**tiny.TRAFFIC["tiny-batch"], "callers": 5, "prompt_len": {"lo": 10, "hi": 24, "count": 4},
           "output_len": {"lo": 6, "hi": 14, "count": 4}}
FAULTS = {
    "a_window_of_7": "def window_of(hy):\n    return hy['sliding_window'] - 1\n",
    "a_window_of_9": "def window_of(hy):\n    return hy['sliding_window'] + 1\n",
    "a_rotary_on_the_full_layers": "def rotates(i):\n    return True\n",
    "no_norm_on_q_and_k": "def head_norm(x, weight, eps):\n    return x\n",
    "no_shared_expert": "def shared_part(u, w, at, precision):\n    return jnp.zeros_like(u)\n",
    "no_held_experts": "def routed_part(u, weights, chosen, w, at, hy, precision):\n    return jnp.zeros_like(u)\n",
    "weights_not_renormalised": "def renormalised(picked):\n    return picked\n",
    "a_scaling_factor_of_1": "def scaling(hy):\n    return 1.0\n",
}
# the readers that need the chip's peaks, its kernels' events or a program's device time in the trace's modules
OFF_THE_CHIP = {"paged_decode_roofline", "paged_attn_roofline", "window_attn_roofline", "expert_matmul_roofline",
                "prefill_device_ms.reasoning"}
RETURN = "from benchmarks.reference import exaone_moe\n\n    return exaone_moe"


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
        fam = open(os.path.join(here, "families", "exaone_moe.py")).read()
        assert RETURN in fam
        extra_files = {
            "families/exaone_moe_faulty.py": fam.replace(RETURN, RETURN.replace("exaone_moe", "exaone_moe_faulty")),
            "reference/exaone_moe_faulty.py": (open(os.path.join(here, "reference", "exaone_moe.py")).read() + "\n\n"
                                               + FAULTS[request.param]),
        }
        config["family"] = "exaone_moe_faulty"
    _, mine = real_entries()
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=[(TWIN, "tiny-kexaone", "tiny-longreason", 1)],
        extra_configs={"tiny-kexaone": config}, extra_traffic={"tiny-longreason": TRAFFIC}, extra_files=extra_files,
        extra_per_layer=[{**m, "workloads": [TWIN]} for m in mine], extra_twins={CELL: [TWIN]},
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
    assert [m["name"] for m in mine] == ["expert_matmul_roofline"]
    want = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert OFF_THE_CHIP < want
    # the names a line carries are not pinned (PERF.md section 7 (14)): those that need the chip are subtracted by name
    assert {"batch_occupancy", "decode_step_ms.reasoning", "experts_touched.reasoning",
            "expert_rows_peak.reasoning"} <= set(line["metrics"]) <= want - OFF_THE_CHIP, proc.stdout[-3000:]
    assert all(isinstance(m["value"], float) for m in line["metrics"].values())
    assert 0 < line["metrics"]["experts_touched.reasoning"]["value"] <= CONFIG["num_experts"]
    assert line["correct"] is True


def test_the_grouped_matmuls_share_is_read_from_the_kernels_events_and_the_routing_counts(tmp_path, monkeypatch):
    from benchmarks.harness import loops

    read = tiny.reader("expert_matmul_roofline")
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "k-exaone-236b-8l.json")))
    model = family.model_kwargs(config)
    # 10 steps of 7 expert layers between the records: 15 touched experts and 48 held rows a layer a step
    recs = [{"kind": "llm_moe", "t": int(1e9 * t), "step": 10 * t, "held": 48 * 70 * t, "zero": 0, "absent": 336 * 70 * t,
             "touched": 15 * 70 * t, "peak": 9 * 70 * t, "windows": 70 * t, "layers": 7} for t in (1, 2, 3)]
    (tmp_path / "llm-a.jsonl").write_text("\n".join(json.dumps(r) for r in recs))
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"modules": {"jit_decode_step_greedy(7)": {"count": 10, "total_s": 0.2}},
             "ops_s": {"jit_decode_step_greedy(7)/gmm.3": 0.050, "jit_decode_step_greedy(7)/gmm": 0.070,
                       "jit_decode_step_greedy(7)/fusion.1": 0.1, "jit_decode_step(9)/gmm.3": 0.5,
                       "jit_decode_step_greedy(7)/gmm_like.2": 0.3}}
    ctx = {"config": config, "model": model, "trace": trace, "peaks": peaks, "window": (0.5, 3.5)}
    need = family.expert_matmul_need(model, 15.0, 48.0)
    assert need["bytes"] == 7 * (15 * 3 * 6144 * 2048 * 2 + 48 * (2 * 6144 * 2 + 3 * 2048 * 2 + 6144 * 4))
    assert need["flops"] == 2.0 * 3 * 6144 * 2048 * 48 * 7 and 7.9e9 < need["bytes"] < 8.0e9
    assert read(ctx) == pytest.approx(100 * (need["bytes"] / 819e9) / 0.012)  # 12 ms of the kernel a step
    # nothing to read: no kernel event (a program off the chip), no trace, no routing records, a family without the count
    assert read({**ctx, "trace": {**trace, "ops_s": {"jit_decode_step_greedy(7)/fusion.1": 0.1}}}) is None
    assert read({**ctx, "trace": None}) is None
    kimi = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "kimi-k2-7l.json")))
    assert read({**ctx, "config": kimi}) is None
    (tmp_path / "llm-a.jsonl").write_text("")
    assert read(ctx) is None


def test_the_real_configuration_is_the_catalogs_with_the_cut_written_down():
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "k-exaone-236b-8l.json")))
    kept = {"first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144,
            "intermediate_size": 18432, "max_position_embeddings": 262144, "model_type": "exaone_moe",
            "moe_intermediate_size": 2048, "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0], "n_group": 1,
            "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8, "num_key_value_heads": 8,
            "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
            "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "sliding_window": 128, "sliding_window_pattern": "LLLG",
            "tie_word_embeddings": False, "topk_group": 1}
    assert {k: config[k] for k in kept} == kept
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows", "num_experts",
                                 "vocab_size", "num_nextn_predict_layers"]
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"], config["num_nextn_predict_layers"]) == (8, 16, 19200, 0)
    assert config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 7 and config["sliding_windows"] == [128, 128, 128, 0] * 2
    published = config["published"]
    assert (published["num_hidden_layers"], published["num_experts"], published["vocab_size"],
            published["num_nextn_predict_layers"]) == (48, 128, 153600, 1)
    assert config["num_experts_published"] == 128 and config["layer_chips"] == 8 and config["expert_offset"] == 0
    assert all(config.get(k) for k in ("assumed", "deployment", "departures", "engine", "limits"))
    model = family.model_kwargs(config)
    assert (model["kind"], model["num_experts"], model["experts_held"], model["rope_theta"]) == ("exaone_moe", 128, 16, 1000000)
    engine, mix = config["engine"], json.load(open(os.path.join(tiny.ROOT, "benchmarks", "traffic", "longreason.json")))
    worst = -(-(mix["prompt_len"]["hi"] + mix["output_len"]["hi"]) // engine["block_size"])
    # the table's last column is the state row's; the pool holds 60 requests at their worst
    assert engine["max_blocks_per_seq"] == worst + 1 == 129 and engine["num_blocks"] == mix["callers"] * worst + 1
    assert (engine["max_batch"], mix["callers"]) == (48, 60)
    # every ring has wrapped by the end of its prompt
    assert mix["prompt_len"]["lo"] > config["sliding_window"]
    with pytest.raises(NotImplementedError):
        family.train_config(model)


def test_the_needs_by_hand_at_the_published_numbers():
    """The published count, the cut's bytes, and a decode step of 48 slots
    holding 48,000 positions (ISSUE 43's arithmetic)."""
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "k-exaone-236b-8l.json")))
    m = family.model_kwargs(config)
    w = family.weight_count(m)
    assert w["attention"] == 6144 * 10240 + 8192 * 6144 == 113_246_208
    assert w["expert"] == 3 * 6144 * 2048 == 37_748_736 and w["dense_layer"] == 113_246_208 + 3 * 6144 * 18432 == 452_984_832
    assert w["expert_layer"] == 113_246_208 + 37_748_736 + 6144 * 128 == 151_781_376
    # the whole model by the same counts: the published 236 B, of which 23 B a token
    whole = 47 * (w["expert_layer"] + 128 * w["expert"]) + w["dense_layer"] + 2 * 153_600 * 6144
    active = 47 * (w["expert_layer"] + 8 * w["expert"]) + w["dense_layer"] + 2 * 153_600 * 6144
    assert 236e9 < whole < 237e9 and 23.6e9 < active < 23.8e9
    # every weight this chip holds: 11.96 GB at 2 bytes (with the embedding, which a step gathers from)
    assert w["head"] == 6144 * 19200 and w["held"] == 7 * 16 * w["expert"]
    assert 11.95e9 < 2 * (w["total"] + w["held"] + w["head"]) < 11.97e9
    touched = 16 * (1 - (1 - 8 / 128) ** 48)
    assert family.experts_touched(m, 48) == pytest.approx(touched) and 15.2 < touched < 15.3
    assert family.layers_of(m) == {"full": 2, "window": 6, "dense": 1, "expert": 7}
    ring = family.window_attention_need(m, 48 * 128, 48)
    assert ring["bytes"] == 6 * 48 * 128 * 4096 and ring["flops"] == 4.0 * 6144 * 8192 * 6  # the rings' rows alone
    attn = family.paged_attention_need(m, 3000.0, 16, 48.0)
    assert attn["bytes"] == (48_000 * 4096 + 48 * 2 * 8192 * 2) * 2 and attn["flops"] == 4.0 * 48_000 * 8192 * 2
    step = family.decode_step_need(m, 48, 48_000.0, 2)
    want = ((w["total"] + 7 * touched * w["expert"]) * 2 + (48_000 + 48) * 4096 * 2 + (48 * 128 + 48) * 4096 * 6)
    assert step["bytes"] == pytest.approx(want) and 11.7e9 < want < 11.9e9  # ISSUE 43's ~11.8 GB
    assert 0.67 < 7 * touched * w["expert"] * 2 / want < 0.69  # the touched experts: 68% of a step
    assert 0.04 < ((48_000 + 48) * 4096 * 2 + (48 * 128 + 48) * 4096 * 6) / want < 0.05  # the two caches: 4.6%
    # the pool as the configuration's file states it: 1.01 GB of blocks, 0.15 GB of state rows
    e = config["engine"]
    assert 1.00e9 < e["num_blocks"] * e["block_size"] * 4096 * 2 < 1.01e9
    assert 0.15e9 < (e["max_batch"] + 1) * 6 * 128 * 4096 < 0.16e9
