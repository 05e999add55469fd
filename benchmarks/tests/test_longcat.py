"""The long-answer cell's tiny twin: LongCat-Flash's family (latent
attention, the shortcut-connected expert layer with identity experts, a share
of the routed experts) through the harness at a CPU's size. The real files of
the family are the ones under test; only the configuration and the mix are
made up. With the identity experts, or the held experts' routed part, left out
of the reference the same cell ends ``correct: false``."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.families import longcat as family

CELL = "serve-longcat4l-longanswer"
TWIN = "tiny-longanswer"
CONFIG = {
    "family": "longcat", "source": "made up for the tests", "vocab_size": 384, "hidden_size": 64,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 4, "qk_nope_head_dim": 8, "v_head_dim": 8,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "expert_offset": 2, "zero_expert_num": 4, "moe_topk": 3,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5, "rope_theta": 1e7, "dtype": "float32",
    "reduced": [], "chips": 1,
    "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 4, "max_blocks_per_seq": 16},
    "limits": {"logits_rel_err_max": 1e-3, "logits_rel_err_mean": 1e-3, "served_token_mismatches": 0},
}
TRAFFIC = {**tiny.TRAFFIC["tiny-batch"], "callers": 5, "prompt_len": {"lo": 6, "hi": 24, "count": 4},
           "output_len": {"lo": 5, "hi": 12, "count": 4}}
FAULTS = {
    "no_identity": '''

def identity_part(u, weights, chosen, hy):  # the fault: zero-compute experts add nothing
    return jnp.zeros_like(u)
''',
    "no_routed": '''

def routed_part(u, weights, chosen, params, li, hy, precision):  # the fault: the held experts add nothing
    return jnp.zeros_like(u)
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
        fam = open(os.path.join(here, "families", "longcat.py")).read()
        assert "from benchmarks.reference import longcat\n\n    return longcat" in fam
        extra_files = {
            "families/longcat_faulty.py": fam.replace("from benchmarks.reference import longcat\n\n    return longcat",
                                                      "from benchmarks.reference import longcat_faulty\n\n    return longcat_faulty"),
            "reference/longcat_faulty.py": open(os.path.join(here, "reference", "longcat.py")).read() + FAULTS[request.param],
        }
        config["family"] = "longcat_faulty"
    _, mine = real_entries()
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=[(TWIN, "tiny-longcat", "tiny-longanswer", 1)],
        extra_configs={"tiny-longcat": config}, extra_traffic={"tiny-longanswer": TRAFFIC}, extra_files=extra_files,
        extra_per_layer=[{**m, "workloads": [TWIN]} for m in mine], extra_twins={CELL: [TWIN]},
    )
    return request.param, dest


def test_the_twin_runs_to_correct_and_a_reference_without_a_part_of_the_expert_layer_does_not(tree):
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
    assert len(mine) == 5
    want = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    # the roofline share needs the chip's peaks; every other reader finds its number here
    assert set(line["metrics"]) == want - {"paged_decode_roofline"}, proc.stdout[-3000:]
    assert all(isinstance(m["value"], float) for m in line["metrics"].values())
    assert 0 < line["metrics"]["experts_touched.longanswer"]["value"] <= CONFIG["n_routed_experts"]
    assert line["correct"] is True


def test_the_real_configuration_is_the_catalogs_with_the_cut_written_down():
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "longcat-flash-omni-4l.json")))
    model = family.model_kwargs(config)
    assert (model["n_routed_experts"], model["experts_held"], model["expert_offset"]) == (512, 16, 0)
    assert config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    with pytest.raises(NotImplementedError):
        family.train_config(model)


def test_decode_step_need_by_hand():
    """The real configuration at 32 sequences holding 32,000 positions."""
    config = json.load(open(os.path.join(tiny.ROOT, "benchmarks", "configs", "longcat-flash-omni-4l.json")))
    m = family.model_kwargs(config)
    w = family.weight_count(m)
    attention = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144
    assert attention == 90_570_752
    assert w["per_layer"] == 2 * attention + 2 * 3 * 6144 * 12288 + 6144 * 768 == 638_844_928
    assert w["expert"] == 3 * 6144 * 2048 and w["head"] == 6144 * 16384
    touched = 16 * (1 - (1 - 12 / 768) ** 32)
    assert family.experts_touched(m, 32) == pytest.approx(touched) and 6.3 < touched < 6.4
    need = family.decode_step_need(m, 32, 32_000.0, 2)
    dense = 4 * 638_844_928 + 6144 * 16384
    want_bytes = (dense + 4 * touched * w["expert"]) * 2 + (32_000 + 32) * 1152 * 8
    assert need["bytes"] == pytest.approx(want_bytes) and 7.4e9 < want_bytes < 7.7e9
    want_flops = 2 * dense * 32 + 2 * w["expert"] * 4 * 32 * 12 * 16 / 768 + 2 * 64 * (576 + 512) * 8 * 32_000
    assert need["flops"] == pytest.approx(want_flops)
