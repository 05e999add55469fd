"""The Granite 4.0-H cell's tiny twin: the family (nine Mamba-2 layers to one
attention layer without positions, softmax-over-the-chosen experts beside a
shared expert in every layer, half the experts held, a tied head under
``logits_scaling``) through the harness at a CPU's size, with prompts past one
chunk of the prefill's SSD form. The real files of the family are the ones
under test; only the configuration and the mix are made up. With a planted
fault in the reference's place (the weights not renormalised over the chosen,
1/sqrt(d) in ``attention_multiplier``'s place, a rotary applied, the norm ahead
of the gate, the shared expert left out, ``residual_multiplier`` left off the
expert branch, the state rounded to bfloat16 after every token) the same cell
ends ``correct: false``."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.families import granite_hybrid as family

CELL = "serve-granite4h10l-longreason"
TWIN = "tiny-granite4h-longreason"
CONFIG_FILE = os.path.join(tiny.ROOT, "benchmarks", "configs", "granite-4.0-h-small-10l.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the listed readers that read nothing off the chip: they need the chip's peaks, a program's device time from the
# chip's "XLA Modules" line, or the event of a kernel that runs nowhere else
OFF_THE_CHIP = {"paged_decode_roofline", "paged_attn_roofline", "prefill_device_ms.reasoning", "ssm_update_roofline",
                "expert_matmul_roofline"}
REAL = json.load(open(CONFIG_FILE))
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CONFIG = {
    **{k: REAL[k] for k in family.PUBLISHED if k in REAL},  # the multipliers, the switches and the names as published
    "family": "granite_hybrid", "source": "made up for the tests", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 32, "shared_intermediate_size": 48, "num_hidden_layers": 10, "layer_types": PERIOD,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts_per_tok": 3, "num_local_experts": 4, "expert_offset": 2,
    "published": {"num_local_experts": 8}, "max_position_embeddings": 256, "mamba_d_state": 64, "mamba_d_head": 32,
    "mamba_n_heads": 4, "mamba_chunk_size": 8, "dtype": "float32", "reduced": [], "chips": 1,
    "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 4, "max_blocks_per_seq": 17},
    # float32 on the CPU: the SSD form's matrix products, the grouped matmuls and the fused projections against the
    # plain sums read ~1e-5; the state kept in bfloat16 reads 1e-3 and more, every other fault 2e-2 and more
    "limits": {"logits_rel_err_max": 5e-4, "logits_rel_err_mean": 5e-4, "served_token_mismatches": 0},
}
# every prompt at least a chunk (8) and up to four: a prefill carries the state between chunks
TRAFFIC = {**tiny.TRAFFIC["tiny-batch"], "callers": 5, "prompt_len": {"lo": 8, "hi": 30, "count": 4},
           "output_len": {"lo": 5, "hi": 12, "count": 4}}
FAULTS = {
    "weights_not_renormalised_over_the_chosen": '''

def chosen_weights(logits, chosen):  # the fault: the softmax over every output, not renormalised
    return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen, axis=-1)
''',
    "inverse_root_of_the_head_in_place_of_attention_multiplier": '''

def score_scale(hy, d):  # the fault: the scale every other model has
    return d ** -0.5
''',
    "a_rotary_applied": '''

def positioned(q, k, hy):  # the fault: position_embedding_type "rope"
    from benchmarks.reference.exaone_moe import rope
    at = jnp.arange(q.shape[0])
    return rope(q, at, 10000.0), rope(k, at, 10000.0)
''',
    "norm_ahead_of_the_gate": '''

def gated_norm(y, z, w, groups, eps):  # the fault: norm_before_gate true
    s = y.shape[0]
    g = y.reshape(s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(s, -1) * w.astype(jnp.float32) * silu(z)
''',
    "shared_expert_left_out": '''

def shared_part(u, params, li, precision):  # the fault: the routed experts alone
    return jnp.zeros_like(u)
''',
    "residual_multiplier_left_off_the_expert_branch": '''

def block(x, params, li, hy, precision):  # the fault: the experts' branch at full size
    eps, m_r = hy["rms_norm_eps"], m(hy, "residual_multiplier")
    h = x + m_r * mix(rms_norm(x, params["in_norm"][li], eps), params, li, hy, precision)
    return h + moe(rms_norm(h, params["post_norm"][li], eps), params, li, hy, precision)
''',
    # ``reduce_precision``: a convert there and back is what a compiler may remove (excess precision), a fault unseen
    "state_in_bfloat16": '''

def kept(state):  # the fault: the state rounded to bfloat16 after every token
    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
''',
}
RETURN = "from benchmarks.reference import granite_hybrid\n\n    return granite_hybrid"


def real_entries():
    return json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))


@pytest.fixture(scope="module", params=["sound", *FAULTS])
def tree(request, tmp_path_factory):
    """The copy with the twin; a faulty one gets a reference of its own (the
    family's file with the fault appended) under another family name."""
    extra_files, config = {}, CONFIG
    if request.param in FAULTS:
        here = os.path.join(tiny.ROOT, "benchmarks")
        fam = open(os.path.join(here, "families", "granite_hybrid.py")).read()
        assert RETURN in fam
        extra_files = {
            "families/granite_hybrid_faulty.py": fam.replace(RETURN, RETURN.replace("granite_hybrid", "granite_hybrid_faulty")),
            "reference/granite_hybrid_faulty.py": open(os.path.join(here, "reference", "granite_hybrid.py")).read()
            + FAULTS[request.param],
        }
        config = {**CONFIG, "family": "granite_hybrid_faulty"}
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=[(TWIN, "tiny-granite4h", "tiny-longreason", 1)],
        extra_configs={"tiny-granite4h": config}, extra_traffic={"tiny-longreason": TRAFFIC}, extra_files=extra_files,
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
    own = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in own] == ["expert_rows_held.reasoning"]  # no new kernel: the one reader of its own is a counter's
    assert (own[0]["source"], own[0]["moves"], own[0]["layer"]) == ("program_counter", "serve_tokens_per_s", "device programs")
    want = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert OFF_THE_CHIP < want
    counted = {"batch_occupancy", "decode_step_ms.reasoning", "compiles_in_window", "experts_touched.reasoning",
               "expert_rows_peak.reasoning", "expert_rows_held.reasoning"}
    # the names a line carries are not pinned (PERF.md section 7 (14)): those that need the chip are subtracted by name
    assert counted <= set(line["metrics"]) <= want - OFF_THE_CHIP, proc.stdout[-3000:]
    assert all(isinstance(m["value"], float) for m in line["metrics"].values())
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < metrics["experts_touched.reasoning"] <= CONFIG["num_local_experts"]
    # half the experts held: of a step's live tokens x 3 choices about half are held rows, an expert layer
    assert 0 < metrics["expert_rows_held.reasoning"] <= CONFIG["engine"]["max_batch"] * CONFIG["num_experts_per_tok"]
    assert line["correct"] is True


def test_the_real_configuration_is_the_catalogs_with_the_cut_written_down():
    config = REAL
    reduced = ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert config["reduced"] == reduced and config["family"] == "granite_hybrid"
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "granite-4.0-h-small")
        assert config["source"] == row["source_url"]
        assert {k: config[k] for k in row["config"] if k not in reduced} == {k: v for k, v in row["config"].items() if k not in reduced}
        assert config["published"] == {k: row["config"][k] for k in reduced}
    assert (config["num_hidden_layers"], config["num_local_experts"], config["vocab_size"]) == (10, 36, 50176)
    assert config["layer_types"] == PERIOD and config["published"]["layer_types"] == PERIOD * 4
    assert (config["published"]["num_hidden_layers"], config["published"]["num_local_experts"],
            config["published"]["vocab_size"]) == (40, 72, 100352)
    assert all(config.get(k) for k in ("assumed", "deployment", "departures", "engine", "limits", "limits_why"))
    entry = next(c for c in real_entries()["configs"] if c["name"] == "granite-4.0-h-small-10l")
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    model = family.model_kwargs(config)
    assert (model["kind"], model["num_local_experts"], model["experts_held"], model["expert_offset"]) == ("granite_hybrid", 72, 36, 0)
    engine, mix = config["engine"], json.load(open(os.path.join(tiny.ROOT, "benchmarks", "traffic", "longreason.json")))
    worst = -(-(mix["prompt_len"]["hi"] + mix["output_len"]["hi"]) // engine["block_size"])
    # the table's last column is the state row's; the pool holds every slot at its worst
    assert engine["max_blocks_per_seq"] == worst + 1 == 129 and engine["num_blocks"] == engine["max_batch"] * worst + 1
    assert (engine["max_batch"], mix["callers"]) == (48, 60)
    with pytest.raises(NotImplementedError):
        family.train_config(model)


def test_the_needs_by_hand_at_the_published_numbers():
    """The cut's bytes and a decode step of 48 slots holding 57,600 positions
    (1,200 each): ISSUE 55's arithmetic."""
    m = family.model_kwargs(REAL)
    w = family.weight_count(m)
    assert w["ssm_mixer"] == 4096 * 16768 + 4 * 8448 + 8448 + 3 * 128 + 8192 + 8192 * 4096 == 102_286_976
    assert w["attention"] == 4096 * (4096 + 2 * 1024) + 4096 * 4096 == 41_943_040
    assert (w["shared"], w["router"], w["expert"]) == (18_874_368, 294_912, 9_437_184)
    assert w["head"] == 50_176 * 4096 + 4096 and family.layers_of(m) == {"attention": 1, "mamba": 9, "expert": 10}
    assert w["held"] == 360 * w["expert"] and 9.51e9 < 2 * (w["total"] + w["held"]) < 9.52e9  # 9.51 GB at 2 bytes
    # the whole model by the same counts: the published 32 B, of which 9 B a token
    whole = 36 * w["ssm_mixer"] + 4 * w["attention"] + 40 * (w["shared"] + w["router"] + 72 * w["expert"]) + 100_352 * 4096
    active = 36 * w["ssm_mixer"] + 4 * w["attention"] + 40 * (w["shared"] + w["router"] + 10 * w["expert"]) + 100_352 * 4096
    assert 32.0e9 < whole < 32.5e9 and 8.8e9 < active < 9.2e9
    row = family.state_row_bytes(m)
    assert row == {"state": 128 * 8192 * 4, "window": 4 * 8448 * 2} and family.kv_row_bytes(m) == 4096
    slots, positions = 48, 48 * 1200.0
    touched = 36 * (1 - (1 - 10 / 72) ** slots)
    assert family.experts_touched(m, slots) == pytest.approx(touched) and 35.9 < touched < 36
    update = family.ssm_update_need(m, float(slots))
    assert update["bytes"] == (2 * 128 * 8192 + 2 * 8192 + 2 * 128 + 128) * 4.0 * slots * 9
    assert update["flops"] == 6.0 * 128 * 8192 * slots * 9
    attn = family.paged_attention_need(m, positions / 16, 16, float(slots))
    assert attn["bytes"] == positions * 4096 + slots * 2 * 4096 * 2 and attn["flops"] == 4.0 * positions * 4096
    rows = family.expert_matmul_need(m, touched, slots * 10 / 2)
    assert rows["bytes"] == pytest.approx(10 * (touched * w["expert"] * 2 + 240 * (2 * 4096 * 2 + 3 * 768 * 2 + 4096 * 4)))
    assert rows["flops"] == 2.0 * w["expert"] * 240 * 10
    step = family.decode_step_need(m, slots, positions, 2)
    experts, state = 10 * touched * w["expert"] * 2, slots * 9 * 2 * (row["state"] + row["window"])
    want = w["total"] * 2 + experts + state + (positions + slots) * 4096
    assert step["bytes"] == pytest.approx(want) and 13.3e9 < want < 13.5e9  # 13.4 GB: 16.3 ms at 819 GB/s
    assert 0.50 < experts / want < 0.52 and 0.26 < state / want < 0.28 and (positions + slots) * 4096 / want < 0.02
    assert 0.03 < w["head"] * 2 / want < 0.035
    # the pool as the configuration's file states it: 0.40 GB of blocks, 1.88 GB of state rows
    e = REAL["engine"]
    assert 0.40e9 < e["num_blocks"] * e["block_size"] * 4096 < 0.41e9
    assert 1.87e9 < (e["max_batch"] + 1) * 9 * (row["state"] + row["window"] + 4) < 1.89e9
