"""The Falcon-H1 cell's tiny twin: the family (a Mamba-2 mixer and grouped-query
attention side by side in every layer under µP multipliers, K/V rows and a state
row a layer a sequence behind one block table) through the harness at a CPU's
size, with prompts past one chunk of the prefill's SSD form. The real files of
the family are the ones under test; only the configuration and the mix are made
up. With a planted fault in the reference (a multiplier dropped, ``B``/``C`` of
the other group, the norm ahead of the gate, the mixers in series, a mixer left
out, the state rounded to bfloat16 after every token) the same cell ends
``correct: false``."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.families import falcon_h1 as family

CELL = "serve-falconh1-6l-longreason"
TWIN = "tiny-falconh1-longreason"
CONFIG_FILE = os.path.join(tiny.ROOT, "benchmarks", "configs", "falcon-h1-34b-6l.json")
# the listed readers that read nothing off the chip: they need the chip's peaks, a program's device time from the
# chip's "XLA Modules" line, or the event of a kernel that runs nowhere else
OFF_THE_CHIP = {"paged_decode_roofline", "paged_attn_roofline", "prefill_device_ms.reasoning", "ssm_update_roofline"}
MULTIPLIERS = {k: v for k, v in json.load(open(CONFIG_FILE)).items() if "multiplier" in k}  # the published ones
CONFIG = {
    "family": "falcon_h1", "source": "made up for the tests", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5, "rope_theta": 100000000000, "rope_scaling": None,
    "attn_layer_indices": None, "tie_word_embeddings": False, "attention_bias": False, "mlp_bias": False,
    "projectors_bias": False, "mamba_d_ssm": 256, "mamba_d_state": 64, "mamba_d_head": 64, "mamba_n_heads": 4,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False, **MULTIPLIERS, "dtype": "float32", "reduced": [], "chips": 1,
    "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 4, "max_blocks_per_seq": 17},
    # float32 on the CPU: the SSD form's matrix products and the fused projections against the plain sums read ~1e-6;
    # the state kept in bfloat16 reads 2e-3 and more, every other fault 2e-2 and more
    "limits": {"logits_rel_err_max": 5e-4, "logits_rel_err_mean": 5e-4, "served_token_mismatches": 0},
}
# every prompt at least a chunk (8) and up to four: a prefill carries the state between chunks
TRAFFIC = {**tiny.TRAFFIC["tiny-batch"], "callers": 5, "prompt_len": {"lo": 8, "hi": 30, "count": 4},
           "output_len": {"lo": 5, "hi": 12, "count": 4}}
FAULTS = {
    "ssm_out_multiplier_dropped": '''

def m(hy, name, index=None, m=m):  # the fault: the mixer's output at full size
    return 1.0 if name == "ssm_out_multiplier" else m(hy, name, index)
''',
    "key_multiplier_dropped": '''

def m(hy, name, index=None, m=m):  # the fault: the keys 90 times too large
    return 1.0 if name == "key_multiplier" else m(hy, name, index)
''',
    "b_and_c_of_the_other_group": '''

def group_of(head, heads, groups):  # the fault: a head reads the other group's B and C
    return groups - 1 - head // (heads // groups)
''',
    "norm_ahead_of_the_gate": '''

def gated_norm(y, z, w, groups, eps):  # the fault: norm_before_gate true
    s = y.shape[0]
    g = y.reshape(s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(s, -1) * w.astype(jnp.float32) * silu(z)
''',
    "mixers_in_series": '''

def mixers(x, u, params, li, hy, precision):  # the fault: attention reads the stream after the state-space mixer
    h = x + m(hy, "ssm_out_multiplier") * ssm_mixer(m(hy, "ssm_in_multiplier") * u, params, li, hy, precision)
    u = rms_norm(h, params["in_norm"][li], hy["rms_norm_eps"])
    return h + m(hy, "attention_out_multiplier") * attention(m(hy, "attention_in_multiplier") * u, params, li, hy, precision)
''',
    "attention_left_out": '''

def mixers(x, u, params, li, hy, precision):  # the fault: the state-space mixer alone
    return x + m(hy, "ssm_out_multiplier") * ssm_mixer(m(hy, "ssm_in_multiplier") * u, params, li, hy, precision)
''',
    "state_in_bfloat16": '''

def kept(state):  # the fault: the state rounded after every token (reduce_precision: a convert there and back is what the TPU's compiler removes)
    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
''',
}
RETURNS = "from benchmarks.reference import falcon_h1\n\n    return falcon_h1"


def real_entries():
    bench = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
    return bench, [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]


@pytest.fixture(scope="module", params=["sound", *FAULTS])
def tree(request, tmp_path_factory):
    """The copy with the twin; a faulty one gets a reference of its own (the
    family's file with the fault appended) under another family name."""
    extra_files, config = {}, dict(CONFIG)
    if request.param in FAULTS:
        here = os.path.join(tiny.ROOT, "benchmarks")
        fam = open(os.path.join(here, "families", "falcon_h1.py")).read()
        assert RETURNS in fam
        extra_files = {
            "families/falcon_h1_faulty.py": fam.replace(RETURNS, RETURNS.replace("falcon_h1", "falcon_h1_faulty")),
            "reference/falcon_h1_faulty.py": open(os.path.join(here, "reference", "falcon_h1.py")).read()
            + FAULTS[request.param],
        }
        config["family"] = "falcon_h1_faulty"
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=[(TWIN, "tiny-falconh1", TWIN, 1)],
        extra_configs={"tiny-falconh1": config}, extra_traffic={TWIN: TRAFFIC},
        extra_files=extra_files, extra_twins={CELL: [TWIN]},
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
    _, mine = real_entries()
    want = {m["name"] for m in mine}
    assert OFF_THE_CHIP < want and len(want) == 12
    assert {"batch_occupancy", "decode_step_ms.reasoning", "compiles_in_window"} <= set(line["metrics"]) <= want - OFF_THE_CHIP, (
        proc.stdout[-3000:])
    assert all(isinstance(m["value"], float) for m in line["metrics"].values())
    assert line["correct"] is True


def test_the_update_kernels_share_is_read_from_its_events_and_this_familys_need(tmp_path, monkeypatch):
    """``ssm_update_roofline`` is the reader Phi's cell brought; over this
    family it divides Mamba-2's need (``ssm_update_need``) by the same events."""
    from benchmarks.harness import loops

    read = tiny.reader("ssm_update_roofline")
    config = json.load(open(CONFIG_FILE))
    model = family.model_kwargs(config)
    recs = [{"kind": "llm_step", "t_loop": int(1e9 * t), "live": 48, "kv_blocks": 2700} for t in (1, 2, 3)]
    (tmp_path / "llm-a.jsonl").write_text("\n".join(json.dumps(r) for r in recs))
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"modules": {"jit_decode_step_greedy(7)": {"count": 10, "total_s": 0.2}},
             "ops_s": {"jit_decode_step_greedy(7)/selective_scan_update.3": 0.040, "jit_decode_step_greedy(7)/fusion.1": 0.1,
                       "jit_decode_step(9)/selective_scan_update.3": 0.5}}
    ctx = {"config": config, "model": model, "trace": trace, "peaks": peaks, "window": (0.5, 3.5)}
    need = family.ssm_update_need(model, 48)
    assert read(ctx) == pytest.approx(100 * (need["bytes"] / 819e9) / 0.004)  # 4 ms of the kernel a step
    assert read({**ctx, "trace": {**trace, "ops_s": {"jit_decode_step_greedy(7)/fusion.1": 0.1}}}) is None


def test_the_real_configuration_is_the_catalogs_row_with_only_the_depth_cut():
    config = json.load(open(CONFIG_FILE))
    catalog = {
        "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504, "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
        "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128, "mamba_d_ssm": 4096,
        "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32, "mamba_norm_before_gate": False,
        "mamba_proj_bias": False, "mamba_rms_norm": True, "mamba_use_mlp": True, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_expansion_factor": 8, "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4,
        "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False, "vocab_size": 261120,
    }
    cut = {"num_hidden_layers": 6}
    assert {k: config[k] for k in catalog} == {**catalog, **cut}
    assert config["reduced"] == list(cut) and config["published"] == {k: catalog[k] for k in cut} and config["layer_chips"] == 1
    assert config["source"] == "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json"
    model = family.model_kwargs(config)
    assert model["kind"] == "falcon_h1" and model["num_hidden_layers"] == 6 and model["dtype"] == "bfloat16"
    assert all(config.get(k) for k in ("assumed", "deployment", "departures", "engine", "limits"))
    engine, mix = config["engine"], json.load(open(os.path.join(tiny.ROOT, "benchmarks", "traffic", "longreason.json")))
    worst = -(-(mix["prompt_len"]["hi"] + mix["output_len"]["hi"]) // engine["block_size"])
    # the table's last column is the state row's: 128 columns of blocks (2,048 positions: the mix's longest request)
    # and one more; the pool holds every slot at its worst, and the null block
    assert engine["max_blocks_per_seq"] == worst + 1 == 129 and engine["num_blocks"] == engine["max_batch"] * worst + 1 == 6145
    assert (engine["max_batch"], mix["callers"]) == (48, 60)
    assert mix["prompt_len"]["hi"] // config["mamba_chunk_size"] == 8  # eight SSD chunks in the longest prefill
    bench, mine = real_entries()
    assert [w for w in bench["workloads"] if w["name"] == CELL] == [
        {"name": CELL, "config": "falcon-h1-34b-6l", "traffic": "longreason", "chips": 1, "why": bench["workloads"][-1]["why"]}]
    with pytest.raises(NotImplementedError):
        family.train_config(model)


def test_the_needs_by_hand_at_the_published_numbers():
    """The real configuration at its 48 slots holding 43,200 positions (a mean context of 900)."""
    config = json.load(open(CONFIG_FILE))
    m = family.model_kwargs(config)
    w = family.weight_count(m)
    assert w["attention"] == 5120 * (2560 + 2 * 512) + 2560 * 5120 == 31_457_280
    assert w["ssm_mixer"] == 5120 * 9248 + 4 * 5120 + 5120 + 3 * 32 + 4096 + 4096 * 5120 == 68_351_072
    assert w["mlp"] == 3 * 5120 * 21504 == 330_301_440
    assert w["layer"] == 430_120_032 and 0.860e9 < 2 * w["layer"] < 0.861e9  # 430.1 M a layer, 0.860 GB
    assert w["head"] == 261120 * 5120 + 5120 and w["total"] == 6 * w["layer"] + w["head"]
    assert 10.50e9 < 2 * (w["total"] + 261120 * 5120) < 10.52e9  # with the embedding: 10.51 GB of bf16 weights
    row = family.state_row_bytes(m)
    assert row == {"state": 256 * 4096 * 4, "window": 4 * 5120 * 2} and row["state"] == 4_194_304
    assert family.kv_row_bytes(m) * 6 == 12_288  # a position's K and V over six layers
    need = family.ssm_update_need(m, 48)
    assert need["bytes"] == 48 * 6 * (2 * 4_194_304 + (2 * 4096 + 2 * 512 + 32) * 4) and need["flops"] == 6.0 * 1_048_576 * 48 * 6
    assert 2.42e9 < need["bytes"] < 2.44e9  # ISSUE 47's 2.44 GB of states read and written a step
    attn = family.paged_attention_need(m, 2700.0, 16, 48.0)
    assert attn["bytes"] == (2700 * 16 * 2048 + 48 * 2 * 2560 * 2) * 6 and attn["flops"] == 4.0 * 43_200 * 2560 * 6
    step = family.decode_step_need(m, 48, 43_200.0, 2)
    want = w["total"] * 2 + 48 * 6 * 2 * (4_194_304 + 40_960) + (43_200 + 48) * 2048 * 6
    assert step["bytes"] == pytest.approx(want) and 10.7e9 < want < 10.9e9  # ISSUE 47's 10.8 GB
    assert 0.22 < 48 * 6 * 2 * 4_194_304 / want < 0.23 and 0.24 < 2 * w["head"] / want < 0.26  # the states 23%, the head 25%
    # the pool as the configuration's file states it: 1.21 GB of blocks, 1.25 GB of state rows
    e = config["engine"]
    assert 1.20e9 < e["num_blocks"] * e["block_size"] * 12_288 < 1.21e9
    assert 1.24e9 < (e["max_batch"] + 1) * 6 * (row["state"] + row["window"]) < 1.25e9
