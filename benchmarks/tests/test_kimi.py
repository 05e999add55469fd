"""The reasoning cell's tiny twin: Kimi-K2's family (a leading dense layer, then
expert layers of sigmoid-routed experts beside a shared expert, latent
attention under YaRN, a share of the routed experts) through the harness at a
CPU's size. The real files of the family are the ones under test; only the
configuration and the mix are made up. With the held experts' routed part, the
shared expert, or YaRN (its frequencies and its score scale) left out of the
reference the same cell ends ``correct: false``."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.families import kimi as family

CELL = "serve-kimik2-7l-reasoning"
TWIN = "tiny-reasoning"
CONFIG = {
    "family": "kimi", "source": "made up for the tests", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8, "qk_nope_head_dim": 8,
    "v_head_dim": 8, "n_routed_experts": 6, "n_routed_experts_published": 16, "expert_offset": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "routed_scaling_factor": 2.827,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6, "rope_theta": 100.0,
    "rope_scaling": {"type": "yarn", "factor": 4, "original_max_position_embeddings": 16, "beta_fast": 2,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "dtype": "float32", "reduced": [], "chips": 1,
    "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 4, "max_blocks_per_seq": 16},
    "limits": {"logits_rel_err_max": 1e-3, "logits_rel_err_mean": 1e-3, "served_token_mismatches": 0},
}
TRAFFIC = {**tiny.TRAFFIC["tiny-batch"], "callers": 5, "prompt_len": {"lo": 6, "hi": 24, "count": 4},
           "output_len": {"lo": 5, "hi": 12, "count": 4}}
FAULTS = {
    "no_routed": '''

def routed_part(u, weights, chosen, params, ei, hy, precision):  # the fault: the held experts add nothing
    return jnp.zeros_like(u)
''',
    "no_shared": '''

def shared_part(u, params, ei, precision):  # the fault: the shared expert adds nothing
    return jnp.zeros_like(u)
''',
    "no_yarn": '''

_hyper = hyper


def hyper(params):  # the fault: the plain rotary and the plain score scale
    return {**_hyper(params), "factor": 1.0}
''',
}


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
        fam = open(os.path.join(here, "families", "kimi.py")).read()
        assert "from benchmarks.reference import kimi\n\n    return kimi" in fam
        extra_files = {
            "families/kimi_faulty.py": fam.replace("from benchmarks.reference import kimi\n\n    return kimi",
                                                   "from benchmarks.reference import kimi_faulty\n\n    return kimi_faulty"),
            "reference/kimi_faulty.py": open(os.path.join(here, "reference", "kimi.py")).read() + FAULTS[request.param],
        }
        config["family"] = "kimi_faulty"
    _, mine = real_entries()
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=[(TWIN, "tiny-kimi", "tiny-reasoning", 1)],
        extra_configs={"tiny-kimi": config}, extra_traffic={"tiny-reasoning": TRAFFIC}, extra_files=extra_files,
        extra_per_layer=[{**m, "workloads": [TWIN]} for m in mine], extra_twins={CELL: [TWIN]},
    )
    return request.param, dest


def test_the_twin_runs_to_correct_and_a_reference_without_a_part_of_the_model_does_not(tree):
    which, dest = tree
    proc = tiny.run_cell(dest, TWIN, trace=0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["correct"] is (which == "sound"), proc.stdout[-3000:]


def test_every_new_reader_returns_a_number_on_the_twins_line(tree):
    which, dest = tree
    if which != "sound":
        pytest.skip("the sound tree's traced line is the one read")
    proc = tiny.run_cell(dest, TWIN, trace=1, seconds=4.0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    bench, mine = real_entries()
    assert sorted(m["name"] for m in mine) == ["decode_step_ms.reasoning", "expert_rows_peak.reasoning",
                                               "experts_touched.reasoning", "prefill_device_ms.reasoning"]
    want = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    # the roofline share needs the chip's peaks and a program's device time the chip's "XLA Modules"
    # line (the reader is held to a made-up one below); every other reader finds its number here
    assert set(line["metrics"]) == want - {"paged_decode_roofline", "prefill_device_ms.reasoning"}, proc.stdout[-3000:]
    assert all(isinstance(m["value"], float) for m in line["metrics"].values())
    assert 0 < line["metrics"]["experts_touched.reasoning"]["value"] <= CONFIG["n_routed_experts"]
    # the fullest held expert has at least a touched expert's mean rows, and at most every row
    assert 1.0 <= line["metrics"]["expert_rows_peak.reasoning"]["value"] <= CONFIG["n_routed_experts"]
    assert line["correct"] is True


def test_the_prefills_device_time_is_read_from_the_traces_modules():
    read = tiny.reader("prefill_device_ms.reasoning")
    mods = {"jit_prefill(1)": {"count": 3, "total_s": 0.045}, "jit_prefill(2)": {"count": 1, "total_s": 0.025},
            "jit_decode_step_greedy(3)": {"count": 200, "total_s": 3.0}}
    assert read({"trace": {"modules": mods}}) == pytest.approx(17.5)  # 70 ms over four calls, whatever their bucket
    assert read({"trace": {"modules": {}}}) is None and read({"trace": None}) is None


def test_the_peak_reader_finds_nothing_in_records_without_the_field(tmp_path, monkeypatch):
    """A program older than the ``peak`` count writes ``llm_moe`` records
    without it: the reader returns None and the line leaves the metric out."""
    from benchmarks.harness import loops

    recs = [{"kind": "llm_moe", "t": int(1e9 * t), "step": 10 * t, "held": 80 * t, "zero": 0, "absent": 400 * t,
             "touched": 50 * t, "layers": 6} for t in (1, 2, 3)]
    (tmp_path / "llm-a.jsonl").write_text("\n".join(json.dumps(r) for r in recs))
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))
    ctx = {"window": (0.5, 3.5)}
    assert tiny.reader("expert_rows_peak.reasoning")(ctx) is None
    assert tiny.reader("experts_touched.reasoning")(ctx) == pytest.approx(100 / (20 * 6))
    with_peak = [{**r, "peak": 15 * (i + 1)} for i, r in enumerate(recs)]
    (tmp_path / "llm-a.jsonl").write_text("\n".join(json.dumps(r) for r in with_peak))
    # 30 peak rows over 120 layer-steps, where a touched expert got 160 / 100 rows in the mean
    assert tiny.reader("expert_rows_peak.reasoning")(ctx) == pytest.approx((30 / 120) / (160 / 100))


def test_the_real_configuration_is_the_catalogs_with_the_cut_written_down():
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "kimi-k2-7l.json")))
    model = family.model_kwargs(config)
    assert (model["n_routed_experts"], model["experts_held"], model["expert_offset"]) == (384, 12, 0)
    assert (model["kind"], model["num_hidden_layers"], model["first_k_dense_replace"]) == ("kimi_k2", 7, 1)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 61, "n_routed_experts": 384, "vocab_size": 163840}
    assert config["rope_scaling"]["factor"] == 32 and config["layer_chips"] == 32
    engine, mix = config["engine"], json.load(open(os.path.join(tiny.ROOT, "benchmarks", "traffic", "reasoning.json")))
    worst = -(-(mix["prompt_len"]["hi"] + mix["output_len"]["hi"]) // engine["block_size"])
    assert worst == engine["max_blocks_per_seq"] and engine["num_blocks"] > mix["callers"] * worst and (engine["max_batch"], mix["callers"]) == (48, 60)
    with pytest.raises(NotImplementedError):
        family.train_config(model)


def test_decode_step_need_by_hand():
    """The real configuration at its 48 slots holding 45,000 positions."""
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "kimi-k2-7l.json")))
    m = family.model_kwargs(config)
    w = family.weight_count(m)
    attention = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 64 * 128 * 7168
    assert w["attention"] == attention == 101_122_048
    assert w["dense_layer"] == attention + 3 * 7168 * 18432 == 497_483_776
    assert w["expert_layer"] == attention + 3 * 7168 * 2048 + 7168 * 384 == 147_914_752
    assert w["expert"] == 3 * 7168 * 2048 and w["head"] == 7168 * 20480
    assert w["total"] == 497_483_776 + 6 * 147_914_752 + 7168 * 20480
    # every weight the chip holds: ISSUE 34's 9.70 GB at 2 bytes (with the embedding, which a step gathers from)
    assert 9.69e9 < 2 * (w["total"] + w["held"] + 7168 * 20480) < 9.71e9
    touched = 12 * (1 - (1 - 8 / 384) ** 48)
    assert family.experts_touched(m, 48) == pytest.approx(touched) and 7.6 < touched < 7.7
    need = family.decode_step_need(m, 48, 45_000.0, 2)
    want_bytes = (w["total"] + 6 * touched * w["expert"]) * 2 + (45_000 + 48) * 1152 * 7
    assert need["bytes"] == pytest.approx(want_bytes) and 7.4e9 < want_bytes < 7.6e9
    want_flops = 2 * w["total"] * 48 + 2 * w["expert"] * 6 * 48 * 8 * 12 / 384 + 2 * 64 * (576 + 512) * 7 * 45_000
    assert need["flops"] == pytest.approx(want_flops)
