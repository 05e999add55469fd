"""The Nemotron 3 Super cell's tiny twin: the family (layers of one norm and one
part, ``MEMEMEM*EME``: Mamba-2 mixers at eight B/C groups, attention without
positions on two K/V heads, sigmoid-routed experts of two matrices in a latent
beside a shared expert over the whole width, a quarter of the experts held, an
untied head) through the harness at a CPU's size. The real files of the family
are the ones under test; only the configuration and the mix are made up. With a
planted fault in the reference's place (relu in relu squared's place; one B/C
group in eight's place) the same cell ends ``correct: false``."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.families import nemotron_h as family

CELL = "serve-nemotron3s11l-longreason"
GRANITE = "serve-granite4h10l-longreason"
TWIN = "tiny-nemotron3s-longreason"
CONFIG_FILE = os.path.join(tiny.ROOT, "benchmarks", "configs", "nemotron-3-super-120b-11l.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the listed readers that read nothing off the chip: they need the chip's peaks, a program's device time from the
# chip's "XLA Modules" line, or the event of a kernel that runs nowhere else
OFF_THE_CHIP = {"paged_decode_roofline", "paged_attn_roofline", "prefill_device_ms.reasoning", "ssm_update_roofline",
                "expert_matmul_roofline"}
REAL = json.load(open(CONFIG_FILE))
PATTERN = "MEMEMEM*EME"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
CONFIG = {
    **{k: REAL[k] for k in family.PUBLISHED if k in REAL},  # the switches, the scale and the names as published
    "family": "nemotron_h", "source": "made up for the tests", "vocab_size": 384, "hidden_size": 64, "num_hidden_layers": 11,
    "hybrid_override_pattern": PATTERN, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 64, "n_groups": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
    "expert_offset": 8, "published": {"n_routed_experts": 16}, "max_position_embeddings": 256, "dtype": "float32",
    "reduced": [], "chips": 1,
    "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 4, "max_blocks_per_seq": 17},
    # float32 on the CPU: the SSD form's matrix products, the grouped matmuls and the fused projection against the
    # plain sums read ~1e-5; either fault 1e-2 and more
    "limits": {"logits_rel_err_max": 5e-4, "logits_rel_err_mean": 5e-4, "served_token_mismatches": 0},
}
TRAFFIC = {**tiny.TRAFFIC["tiny-batch"], "callers": 5, "prompt_len": {"lo": 8, "hi": 30, "count": 4},
           "output_len": {"lo": 5, "hi": 12, "count": 4}}
FAULTS = {
    "relu_in_relu_squareds_place": '''

def expert_act(h):  # the fault: relu, not its square
    return jnp.maximum(h, 0.0)
''',
    "one_bc_group_in_eights_place": '''

def group_of(head, heads, groups):  # the fault: every head reads the first group's B and C
    return 0
''',
}
RETURN = "from benchmarks.reference import nemotron_h\n\n    return nemotron_h"


def real_entries():
    return json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))


@pytest.fixture(scope="module", params=["sound", *FAULTS])
def tree(request, tmp_path_factory):
    """The copy with the twin; a faulty one gets a reference of its own (the
    family's file with the fault appended) under another family name."""
    extra_files, config = {}, CONFIG
    if request.param in FAULTS:
        here = os.path.join(tiny.ROOT, "benchmarks")
        fam = open(os.path.join(here, "families", "nemotron_h.py")).read()
        assert RETURN in fam
        extra_files = {
            "families/nemotron_h_faulty.py": fam.replace(RETURN, RETURN.replace("nemotron_h", "nemotron_h_faulty")),
            "reference/nemotron_h_faulty.py": open(os.path.join(here, "reference", "nemotron_h.py")).read()
            + FAULTS[request.param],
        }
        config = {**CONFIG, "family": "nemotron_h_faulty"}
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=[(TWIN, "tiny-nemotron3s", "tiny-longreason", 1)],
        extra_configs={"tiny-nemotron3s": config}, extra_traffic={"tiny-longreason": TRAFFIC}, extra_files=extra_files,
        extra_twins={CELL: [TWIN]},
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


def test_every_listed_reader_returns_a_number_or_is_named_as_unreadable_off_the_chip(tree):
    which, dest = tree
    if which != "sound":
        pytest.skip("the sound tree's traced line is the one read")
    proc = tiny.run_cell(dest, TWIN, trace=1, seconds=4.0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    bench = real_entries()
    # no kernel and no counter of its own, so no reader of its own: the cell rides on the lists that Granite's is on
    assert not [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    want = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert want == {m["name"] for m in bench["per_layer"] if GRANITE in m.get("workloads", [])} and len(want) == 22
    assert OFF_THE_CHIP < want
    counted = {"batch_occupancy", "decode_step_ms.reasoning", "compiles_in_window", "experts_touched.reasoning",
               "expert_rows_peak.reasoning", "expert_rows_held.reasoning"}
    # the names a line carries are not pinned (PERF.md section 7 (14)): those that need the chip are subtracted by name
    assert counted <= set(line["metrics"]) <= want - OFF_THE_CHIP, proc.stdout[-3000:]
    assert all(isinstance(m["value"], float) for m in line["metrics"].values())
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < metrics["experts_touched.reasoning"] <= CONFIG["n_routed_experts"]
    # a quarter of the experts held: of a step's live tokens x 3 choices about a quarter are held rows, an expert layer
    assert 0 < metrics["expert_rows_held.reasoning"] <= CONFIG["engine"]["max_batch"] * CONFIG["num_experts_per_tok"]
    assert line["correct"] is True


def test_the_real_configuration_is_the_catalogs_with_the_cut_written_down():
    config = REAL
    assert config["reduced"] == REDUCED and config["family"] == "nemotron_h"
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert config["source"] == row["source_url"]
        assert {k: config[k] for k in row["config"] if k not in REDUCED} == {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert config["published"] == {k: row["config"][k] for k in REDUCED}
        assert row["config"]["hybrid_override_pattern"].startswith(config["hybrid_override_pattern"])
    assert (config["num_hidden_layers"], config["hybrid_override_pattern"], config["n_routed_experts"], config["vocab_size"],
            config["num_nextn_predict_layers"]) == (11, PATTERN, 128, 32768, 0)
    assert (config["published"]["num_hidden_layers"], config["published"]["n_routed_experts"], config["published"]["vocab_size"],
            config["published"]["num_nextn_predict_layers"]) == (88, 512, 131072, 1)
    # the floors: a whole period's ratio, at least 8 routed experts a layer, at least an eighth of the vocabulary
    assert sorted(PATTERN) == sorted("EMEMEMEMEM*") and config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert (config["layer_chips"], config["expert_offset"]) == (4, 0)
    assert all(config.get(k) for k in ("assumed", "deployment", "departures", "engine", "limits", "limits_why"))
    entry = next(c for c in real_entries()["configs"] if c["name"] == "nemotron-3-super-120b-11l")
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    cell = next(w for w in real_entries()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("nemotron-3-super-120b-11l", "longreason", 1)
    model = family.model_kwargs(config)
    assert (model["kind"], model["n_routed_experts"], model["experts_held"], model["expert_offset"],
            model["num_experts_per_tok"], model["moe_latent_size"]) == ("nemotron_h", 512, 128, 0, 22, 1024)
    engine, mix = config["engine"], json.load(open(os.path.join(tiny.ROOT, "benchmarks", "traffic", "longreason.json")))
    worst = -(-(mix["prompt_len"]["hi"] + mix["output_len"]["hi"]) // engine["block_size"])
    # the table's last column is the state row's; the pool holds every slot at its worst, and a block a slot to spare
    assert engine["max_blocks_per_seq"] == worst + 1 == 33 and engine["num_blocks"] == 1585 >= engine["max_batch"] * worst + 1
    assert (engine["max_batch"], engine["block_size"], mix["callers"]) == (48, 64, 60)
    with pytest.raises(NotImplementedError):
        family.train_config(model)


def test_the_needs_by_hand_at_the_published_numbers():
    """The cut's bytes and a decode step of 48 slots holding 57,600 positions
    (1,200 each): ISSUE 57's arithmetic."""
    m = family.model_kwargs(REAL)
    w = family.weight_count(m)
    assert w["ssm_mixer"] == 4096 * 18560 + 4 * 10240 + 10240 + 3 * 128 + 8192 + 8192 * 4096 == 109_635_968
    assert w["attention"] == 4096 * (4096 + 2 * 256) + 4096 * 4096 == 35_651_584
    assert (w["router"], w["latent"], w["shared"], w["expert"]) == (4096 * 512 + 512, 2 * 4096 * 1024, 2 * 4096 * 5376, 2 * 1024 * 2688)
    assert w["head"] == 32_768 * 4096 + 4096 and family.layers_of(m) == {"mamba": 5, "attention": 1, "expert": 5}
    assert w["held"] == 640 * w["expert"] and w["total"] + w["held"] + w["embed"] == 4_648_163_712  # 9.30 GB at 2 bytes
    row = family.state_row_bytes(m)
    assert row == {"state": 128 * 8192 * 4, "window": 4 * 10240 * 2} and family.kv_row_bytes(m) == 1024
    slots, positions = 48, 48 * 1200.0
    touched = 128 * (1 - (1 - 22 / 512) ** slots)
    assert family.experts_touched(m, slots) == pytest.approx(touched) and 112 < touched < 113
    update = family.ssm_update_need(m, float(slots))
    assert update["bytes"] == (2 * 128 * 8192 + 2 * 8192 + 2 * 1024 + 128) * 4.0 * slots * 5
    assert update["flops"] == 6.0 * 128 * 8192 * slots * 5
    attn = family.paged_attention_need(m, positions / 64, 64, float(slots))
    assert attn["bytes"] == positions * 1024 + slots * 2 * 4096 * 2 and attn["flops"] == 4.0 * positions * 4096
    held_rows = slots * 22 / 4
    rows = family.expert_matmul_need(m, touched, held_rows)
    # two matrices an expert, latent-wide rows in (bfloat16) and out (float32), the hidden rows written once and read once
    assert rows["bytes"] == pytest.approx(5 * (touched * w["expert"] * 2 + held_rows * (1024 * 2 + 2 * 2688 * 2 + 1024 * 4)))
    assert rows["flops"] == 2.0 * w["expert"] * held_rows * 5
    step = family.decode_step_need(m, slots, positions, 2)
    experts, state = 5 * touched * w["expert"] * 2, slots * 5 * 2 * (row["state"] + row["window"])
    want = w["total"] * 2 + experts + state + (positions + slots) * 1024
    assert step["bytes"] == pytest.approx(want) and 10.2e9 < want < 10.3e9  # 10.28 GB: 12.6 ms at 819 GB/s
    assert 0.59 < experts / want < 0.61 and 0.19 < state / want < 0.21 and (positions + slots) * 1024 / want < 0.01
    assert 0.02 < w["head"] * 2 / want < 0.03
    # the pool as the configuration's file states it: 0.10 GB of blocks, 1.05 GB of state rows
    e = REAL["engine"]
    assert 0.10e9 < e["num_blocks"] * e["block_size"] * 1024 < 0.11e9
    assert 1.04e9 < (e["max_batch"] + 1) * 5 * (row["state"] + row["window"] + 4) < 1.05e9
