"""The seam a new model family comes in by: in a temporary copy of the
benchmark (``tiny.py``) a made-up second family is added by new files and
appended entries alone (``made_up_family/``: its module, its plain reference,
two configurations that name it, two mixes, a serving and a training cell),
and its cells run at tiny size on the CPU to ``correct: true``. The check is
live on both sides: with GPT-J's residual path in the reference's place, or
in the program's (the timed path broken underneath the sound reference), the
same cells end ``correct: false``. And every configuration of ``BENCHMARK.json`` resolves to
a family that has the names the harness calls."""

import filecmp
import inspect
import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks import families

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "made_up_family")
FAMILY = "swiglu_gqa"
MODEL = {
    "family": FAMILY, "source": "made up for the tests", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 96,
    "max_position_embeddings": 256, "vocab_size": 384, "dtype": "float32", "reduced": [], "chips": 1,
}
CONFIGS = {
    "made-up-serve": {
        **MODEL, "engine": {"block_size": 4, "num_blocks": 128, "max_batch": 3, "max_blocks_per_seq": 16},
        "limits": {"logits_rel_err_max": 1e-3, "served_token_mismatches": 0},
    },
    "made-up-train": {
        **MODEL, "model_extra": {"remat_policy": "dots"},
        "train": {"batch": 4, "seq": 64, "learning_rate": 1e-4, "mesh": {"data": -1},
                  "adamw": {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}},
        # leaves GPT-J's layout does not have, or has in another shape
        "limits": {"step_loss_rel_err": 1e-5, "step_mu_rel_err.mlp_norm": 1e-3, "step_mu_rel_err.w_gate": 1e-3,
                   "step_mu_rel_err.wk": 1e-3, "step_update_rel_err.mlp_norm": 0.05},
    },
}
TRAFFIC = {
    "made-up-batch": {**tiny.TRAFFIC["tiny-batch"], "callers": 4,
                      "prompt_len": {"lo": 6, "hi": 24, "count": 4}, "output_len": {"lo": 3, "hi": 9, "count": 4}},
    "made-up-fixed": {**tiny.TRAFFIC["tiny-fixed"], "check": {"samples": 256, "leaves": ["mlp_norm", "w_gate", "wk"]}},
}
CELLS = [("made-up-serve-cell", "made-up-serve", "made-up-batch", 1),
         ("made-up-train-cell", "made-up-train", "made-up-fixed", 1)]


def text(name):
    with open(os.path.join(HERE, name)) as f:
        return f.read()


@pytest.fixture(scope="module", params=["sound", "wrong_reference", "wrong_program"])
def tree(request, tmp_path_factory):
    """The copy with the made-up family; ``wrong_reference`` has the parallel
    block in the reference's place, ``wrong_program`` has the program run it."""
    reference = text("reference.py") + (text("wrong_block.py") if request.param == "wrong_reference" else "")
    family = text("family.py")
    if request.param == "wrong_program":
        family = family.replace("parallel_block=False", "parallel_block=True")
        assert family != text("family.py")
    dest = tiny.build(
        str(tmp_path_factory.mktemp(request.param)), extra_cells=CELLS, extra_configs=CONFIGS,
        extra_traffic=TRAFFIC,
        extra_files={f"families/{FAMILY}.py": family, f"reference/{FAMILY}.py": reference},
        extra_twins={"serve-gptj6b-batch": [CELLS[0][0]], "train-gptj4l-ingest": [CELLS[1][0]]},
    )
    return request.param, dest


def unchanged(tree):
    """Every file of ``benchmarks/`` that was there is in the copy, byte for byte."""
    real = os.path.join(tiny.ROOT, "benchmarks")
    for folder, dirs, files in os.walk(real):
        dirs[:] = [d for d in dirs if d not in ("tests", "__pycache__")]
        for name in files:
            path = os.path.join(folder, name)
            assert filecmp.cmp(path, os.path.join(tree, "benchmarks", os.path.relpath(path, real)), shallow=False), path
    return True


@pytest.mark.parametrize("workload,metric", [(CELLS[0][0], "serve_tokens_per_s"), (CELLS[1][0], "train_tokens_per_s")])
def test_a_family_is_added_by_files_and_entries_alone(tree, workload, metric):
    which, dest = tree
    proc = tiny.run_cell(dest, workload, trace=0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0 and set(line["metrics"]) == {metric, "setup_s"}
    assert line["correct"] is (which == "sound"), proc.stdout[-3000:]
    assert proc.stderr.strip().splitlines()[-1].startswith(f"correct={line['correct']}: ")  # beside their limits
    assert unchanged(dest)


NAMES = ("model_kwargs", "train_config", "make_weights", "reference", "weight_count", "decode_step_need")


@pytest.mark.parametrize("entry", json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))["configs"],
                         ids=lambda e: e["name"])
def test_every_configuration_resolves_to_a_family_with_the_names_the_harness_calls(entry):
    config = json.load(open(os.path.join(tiny.ROOT, entry["file"])))
    family = families.of(config)
    assert family.__name__ == "benchmarks.families." + config.get("family", families.DEFAULT)
    assert all(callable(getattr(family, name)) for name in NAMES)
    model = family.model_kwargs(config)
    assert model["vocab_size"] == config["vocab_size"] and model["dtype"] == config["dtype"]
    reference = family.reference()
    assert list(inspect.signature(reference.logits_at).parameters)[:4] == ["params", "tokens", "rows", "precision"]
    if "train" in config:
        assert {"precision", "leaves"} <= set(inspect.signature(reference.mean_loss_and_grads).parameters)
    need = family.decode_step_need(model, 8, 2400.0, 2)
    assert need["bytes"] > family.weight_count(model)["total"] * 2 and need["flops"] > 0


def test_a_family_that_has_no_module_is_an_error_and_not_the_default():
    with pytest.raises(ImportError):
        families.of({"family": "no_such_family"})
