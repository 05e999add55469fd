"""``latent_attn_roofline`` (PR 35): the reader held to a made-up reduced trace
and loop records by hand, as ``test_rooflines.py`` holds GPT-J's, and the tiny
twins of both sparse cells with the metric listed: on the CPU no kernel runs,
the reader finds no event and the line leaves the metric out."""

import json
import os

import pytest

import test_kimi
import test_longcat
import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.harness import loops

NAME = "latent_attn_roofline"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published widths both kinds share; Kimi-K2 counts layers of one attention, LongCat-Flash layers of two
WIDTHS = {"num_attention_heads": 64, "kv_lora_rank": 512, "qk_rope_head_dim": 64}
MODELS = {"kimi": ({**WIDTHS, "num_hidden_layers": 7}, 7), "longcat": ({**WIDTHS, "num_layers": 4}, 8)}


def _records(tmp_path, monkeypatch, steps, drop=()):
    recs = [{"kind": "llm_step", "t_loop": int((100 + i) * 1e9), "live": live, "kv_blocks": blocks}
            for i, (live, blocks) in enumerate(steps)]
    (tmp_path / "llm-llm-1.jsonl").write_text("".join(
        json.dumps({k: v for k, v in r.items() if k not in drop}) + "\n" for r in recs))
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))


@pytest.mark.parametrize("kind", list(MODELS))
def test_the_latent_kernels_share_from_loop_records_and_kernel_events(monkeypatch, tmp_path, kind):
    """By hand: steps of the window that copied 2,016 blocks for 48 sequences,
    10 traced steps. Bytes: 2,016 x 16 whole rows of 640 stored values, 48 x 64
    queries of 576 read and outputs of 512 written, 2 bytes each, an attention;
    the FLOPs (2 x 64 x 1,088 a copied row) take less: the bytes bound."""
    model, attentions = MODELS[kind]
    read = tiny.reader(NAME)
    _records(tmp_path, monkeypatch, [(48, 2000), (48, 2032), (0, 0), (48, 2016)])  # one iteration dispatched nothing
    nbytes = (2016 * 16 * 640 + 48 * 64 * 576 + 48 * 64 * 512) * 2 * attentions
    flops = 2 * 64 * (576 + 512) * 2016 * 16 * attentions
    assert nbytes == 23_986_176 * 2 * attentions and flops / 197e12 < nbytes / 819e9
    least = nbytes / 819e9

    def ctx(kernel_s):
        return {
            "config": {}, "model": model, "engine": {"block_size": 16}, "peaks": V5E, "window": (100.0, 200.0),
            "trace": {"modules": {"jit_decode_step_greedy": {"count": 10, "total_s": 0.14}},
                      "ops_s": {"jit_decode_step_greedy/paged_latent_attention.13": kernel_s / 7,  # a section each
                                "jit_decode_step_greedy/paged_latent_attention.14": 6 * kernel_s / 7,
                                "jit_decode_step_greedy/fusion.354": 0.05, "jit_decode_step/paged_latent_attention": 1.0}},
        }

    assert read(ctx(10 * least)) == pytest.approx(100.0, rel=1e-9)  # a kernel that takes exactly its least time
    assert read(ctx(25 * least)) == pytest.approx(40.0, rel=1e-9)
    # nothing to read: no kernel events (the parent's program; any program off the chip), no peaks (a
    # rehearsal), or a program whose records carry no blocks
    sound = ctx(10 * least)
    assert read({**sound, "trace": {**sound["trace"], "ops_s": {"jit_decode_step_greedy/fusion.354": 0.05}}}) is None
    assert read({**sound, "peaks": None}) is None and read({**sound, "trace": None}) is None
    _records(tmp_path, monkeypatch, [(48, 2000), (48, 2032)], drop=("kv_blocks",))
    assert read(sound) is None


def test_whole_copied_blocks_keep_the_share_under_100_whatever_the_lengths(monkeypatch, tmp_path):
    """The kernel cannot take less than the copies of the blocks it is counted
    by: 48 sequences of one row each are 48 whole blocks."""
    model, attentions = MODELS["kimi"]
    _records(tmp_path, monkeypatch, [(48, 48)])
    nbytes = (48 * 16 * 640 + 48 * 64 * 576 + 48 * 64 * 512) * 2 * attentions
    ctx = {"config": {}, "model": model, "engine": {"block_size": 16}, "peaks": V5E, "window": (100.0, 200.0),
           "trace": {"modules": {"jit_decode_step_greedy": {"count": 1, "total_s": 0.01}},
                     "ops_s": {"jit_decode_step_greedy/paged_latent_attention.14": 2 * nbytes / 819e9}}}
    assert tiny.reader(NAME)(ctx) == pytest.approx(50.0)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """One copy with both sparse cells' twins (the configurations and mixes of
    ``test_longcat.py`` and ``test_kimi.py``). ``BENCHMARK.json`` lists the
    metric for both cells, so ``tiny.build`` lists it for both twins."""
    twins = {test_longcat.CELL: [test_longcat.TWIN], test_kimi.CELL: [test_kimi.TWIN]}
    return tiny.build(
        str(tmp_path_factory.mktemp("latent")),
        extra_cells=[(test_longcat.TWIN, "tiny-longcat", "tiny-longanswer", 1), (test_kimi.TWIN, "tiny-kimi", "tiny-reasoning", 1)],
        extra_configs={"tiny-longcat": test_longcat.CONFIG, "tiny-kimi": test_kimi.CONFIG},
        extra_traffic={"tiny-longanswer": test_longcat.TRAFFIC, "tiny-reasoning": test_kimi.TRAFFIC}, extra_twins=twins)


def test_the_entry_is_an_addition_and_lists_both_sparse_cells(tree):
    real = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
    entry = real["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "device_trace", "layer": "kernels",
                     "moves": "serve_tokens_per_s", "workloads": [test_longcat.CELL, test_kimi.CELL]}
    assert os.path.exists(os.path.join(tiny.ROOT, "benchmarks", "layer_metrics", NAME + ".py"))
    listed = [m for m in json.load(open(os.path.join(tree, "BENCHMARK.json")))["per_layer"] if m["name"] == NAME]
    assert [m["workloads"] for m in listed] == [[test_longcat.TWIN, test_kimi.TWIN]]


def test_tiny_build_takes_the_entry_as_an_extra_too(tmp_path):
    """As a later PR's test would bring it: beside the real entries, under another name."""
    extra = {"name": NAME + ".again", "unit": "%", "better": "higher", "source": "device_trace", "layer": "kernels",
             "moves": "serve_tokens_per_s", "workloads": ["tiny-batch"]}
    dest = tiny.build(str(tmp_path / "tree"), extra_per_layer=[extra])
    assert json.load(open(os.path.join(dest, "BENCHMARK.json")))["per_layer"][-1] == extra


@pytest.mark.parametrize("twin", [test_longcat.TWIN, test_kimi.TWIN])
def test_the_twins_traced_line_leaves_the_metric_out_on_the_cpu(tree, twin):
    proc = tiny.run_cell(tree, twin, trace=1, seconds=4.0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert NAME not in line["metrics"] and "decode_step_ms." + twin.removeprefix("tiny-") in line["metrics"]
    assert not any("paged_latent_attention" in op for op in json.dumps(line.get("breakdown") or {}).split('"'))
