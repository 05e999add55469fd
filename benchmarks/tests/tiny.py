"""A copy of the benchmark at tiny sizes in a temporary directory, for the
tests: the harness's own files as they are, with made-up configurations,
mixes and a ``BENCHMARK.json`` that the CPU can run in seconds. It also
serves as the proof that a cell is added by files and entries alone."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_MODEL = {
    "source": "made up for the tests", "n_layer": 2, "n_embd": 64, "n_head": 4, "n_inner": 128,
    "n_positions": 256, "vocab_size": 512, "dtype": "float32", "reduced": [], "chips": 1,
}
CONFIGS = {
    "tiny-serve": {
        **TINY_MODEL,
        "engine": {"block_size": 4, "num_blocks": 256, "max_batch": 4, "max_blocks_per_seq": 32},
        "limits": {"logits_rel_err_max": 1e-3, "served_token_mismatches": 0},
    },
    "tiny-train": {
        **TINY_MODEL,
        "model_extra": {"remat_policy": "dots"},
        "train": {"batch": 8, "seq": 128, "learning_rate": 1e-4, "mesh": {"data": -1},
                  "adamw": {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}},
        # float32 at this size: the step and the plain reference agree closely
        "limits": {"step_loss_rel_err": 1e-5, "step_mu_rel_err.attn_norm": 1e-3, "step_nu_rel_err.attn_norm": 2e-3,
                   "step_mu_rel_err.wq": 1e-3, "step_update_rel_err.attn_norm": 0.05},
    },
}
CONFIGS["tiny-mesh"] = {
    **CONFIGS["tiny-train"], "chips": 4,
    "train": {**CONFIGS["tiny-train"]["train"], "mesh": {"fsdp": 2, "tensor": 2}},
}
TRAFFIC = {
    "tiny-batch": {
        "kind": "closed_loop", "callers": 6, "prompt_len": {"lo": 8, "hi": 30, "count": 6},
        "output_len": {"lo": 4, "hi": 12, "count": 6}, "sampling": {"temperature": 0.0},
        "ramp_seconds": 0.5, "trace_seconds": 1, "check": {"requests": 3, "decode_steps": 6},
    },
    "tiny-online": {
        "kind": "open_loop", "arrivals": {"process": "poisson", "rate_per_s": 8.0},
        "prompt_len": {"lo": 8, "hi": 30, "count": 6}, "output_len": {"lo": 4, "hi": 12, "count": 6},
        "sampling": {"temperature": 0.0}, "ramp_seconds": 0.5, "trace_seconds": 1,
        "check": {"requests": 3, "decode_steps": 6},
    },
    "tiny-ingest": {"kind": "train_job", "ingest": True, "dataset_batches": 16, "warmup_steps": 2,
                    "trace_steps": 2, "check": {"samples": 256, "leaves": ["attn_norm", "final_norm", "wq"]}},
    "tiny-fixed": {"kind": "train_job", "ingest": False, "warmup_steps": 2, "trace_steps": 2,
                   "check": {"samples": 256, "leaves": ["attn_norm", "final_norm", "wq"]}},
}
CELLS = [
    ("tiny-batch", "tiny-serve", "tiny-batch", 1),
    ("tiny-online", "tiny-serve", "tiny-online", 1),
    ("tiny-ingest", "tiny-train", "tiny-ingest", 1),
    ("tiny-mesh", "tiny-mesh", "tiny-fixed", 4),
]


# the tiny cells that report each real cell's metrics: the open-loop and the
# four-device kinds have no cell in BENCHMARK.json yet and ride on their kind's
TWIN = {"serve-gptj6b-batch": ["tiny-batch", "tiny-online"],
        "train-gptj4l-ingest": ["tiny-ingest", "tiny-mesh"]}


def build(dest: str, extra_cells=(), extra_per_layer=(), extra_configs=None, extra_traffic=None,
          extra_files=None, extra_twins=None) -> str:
    """Copy ``benchmarks/`` to ``dest`` and add the tiny cells as new files
    and entries; nothing that is there is edited. A test of a later PR brings
    its own: ``extra_configs`` and ``extra_traffic`` (name -> the file's
    object), ``extra_files`` (path under ``benchmarks/`` -> text; a file that
    is there already is an error), ``extra_cells`` ((name, config, traffic,
    chips)), ``extra_per_layer`` (entries) and ``extra_twins`` (real cell ->
    the extra cells that report its metrics, as ``TWIN`` has the tiny ones)."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    files = {f"{kind}/{name}.json": json.dumps(obj)
             for kind, group in (("configs", {**CONFIGS, **(extra_configs or {})}),
                                 ("traffic", {**TRAFFIC, **(extra_traffic or {})}))
             for name, obj in group.items()}
    files.update(extra_files or {})
    for rel, text in files.items():
        path = os.path.join(dest, "benchmarks", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "x") as f:  # added, never written over
            f.write(text)
    cells = list(CELLS) + list(extra_cells)
    names = [c[0] for c in cells]
    extra_twins = extra_twins or {}
    twins = {w: [*TWIN.get(w, ()), *extra_twins.get(w, ())] for w in {*TWIN, *extra_twins}}
    bench = dict(real)
    bench["configs"] = [
        {"name": n, "source": "made up", "file": f"benchmarks/configs/{n}.json", "reduced": [], "why": "test"}
        for n in sorted({c[1] for c in cells})
    ]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "test"} for n, c, t, k in cells
    ]
    # every metric the real file has, read in the tiny cell of the same kind
    for group in ("end_to_end", "per_layer"):
        bench[group] = [
            {**m, **({"workloads": [t for w in m["workloads"] for t in twins.get(w, ()) if t in names]}
                     if "workloads" in m else {})}
            for m in real[group]
        ]
    bench["per_layer"] += list(extra_per_layer)
    json.dump(bench, open(os.path.join(dest, "BENCHMARK.json"), "w"), indent=1)
    return dest


def reader(name: str):
    """The ``read`` of ``layer_metrics/<name>.py``, loaded as the harness loads it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "layer_metric", os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(tree: str, workload: str, trace: int, seconds: float = 2.0, seed: int = 7,
             devices: int = 1, timeout: int = 600):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(tree, ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace), "--rehearse", "1"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=timeout, start_new_session=True,
    )
    return proc


if __name__ == "__main__":
    # python benchmarks/tests/tiny.py <dir> <workload> <trace> [devices]: a rehearsal by hand
    tree = build(sys.argv[1]) if not os.path.exists(sys.argv[1]) else sys.argv[1]
    p = run_cell(tree, sys.argv[2], int(sys.argv[3]), devices=int(sys.argv[4]) if len(sys.argv) > 4 else 1)
    print(p.stdout[-6000:], p.stderr[-3000:], "exit", p.returncode)
