"""The latent cache's decode kernel alone on the chip (PR 63):
``ops/paged_attention.py:paged_latent_attention`` at a served model's shapes,
over profiles of lengths: us a call on the device, and the least-squares fit of
what a call costs by the row it copies, by the chunk and by the sequence.

    chiprun -- python tools/latent_time.py --config benchmarks/configs/kimi-k2-7l.json
        [--out chiprun_out/latent_time.json] [--draws 6] [--seed 63]
        [--also _parent/ray_tpu/ops/paged_attention.py]

The shapes are the configuration file's (heads, the latent and the rotated
part, rows stored in whole lane tiles, the engine's block size, blocks, slots
and table width, an attention a layer or two: ``latent_attn_roofline``'s
``attentions_a_step``); a call is what ``ops/latent_attention.py:mla`` hands
the kernel in a decode step, every slot live, each sequence's blocks scattered
over the pool. The profiles:

* ``cell``: ``--draws`` seeded draws of the batch as the configuration's cell
  holds it in its steady state: the traffic mix of the first cell of
  ``BENCHMARK.json`` that runs the configuration, a slot a request of the mix's
  cycle (``harness/traffic.py:request_cycle``) drawn by how long it decodes,
  somewhere in its answer;
* ``full_k``: every length ``k`` x 512 rows (``UNIT``: the kernel's chunk until
  PR 63, two scored prefixes since); ``full_k_and_a_block``: and one block
  more; ``one_block``: every length one block.

**The time is the device's**: the ``paged_latent_attention`` events of the
profiler's "XLA Ops" line over ``RUNS`` runs of a program of one call an
attention, which is what ``latent_attn_roofline`` reads of a decode step;
``bytes_us`` is that reader's need (whole copied blocks, the queries in and the
output out) at the chip's bandwidth (``benchmarks/peaks.json``). The fit is
over every profile's line: us a call = ``ns_a_row`` x copied rows +
``us_a_chunk`` x chunks + ``us_a_sequence`` x sequences (a chunk is the timed
kernel's own, by its module's ``_LATENT_CHUNK_BYTES``; every call's fixed cost
is in the last). Off the chip there is no such line: an eighth of the
heads and four slots run in interpret mode, every call is held
against ``latent_decode_attention`` over the gathered rows as on the chip, and
us reads "not measured". ``--also``: another module's
kernel (a parent's, from ``git archive``) beside the tree's on the same chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness.common import peaks_for  # noqa: E402
from benchmarks.harness.rooflines import least_time_s  # noqa: E402
from benchmarks.harness.traffic import request_cycle  # noqa: E402
from benchmarks.layer_metrics.latent_attn_roofline import LANES, attentions_a_step, latent_attention_need  # noqa: E402
from benchmarks.trace.reduce import load_events, newest_xplane  # noqa: E402
from ray_tpu.ops import paged_attention  # noqa: E402
from ray_tpu.ops.latent_attention import latent_decode_attention  # noqa: E402

KERNEL = "paged_latent_attention"
RUNS = 10
UNIT = 512  # rows: what the synthetic profiles are multiples of, whatever the timed kernel's chunk
PEAKS = peaks_for("TPU v5 lite")  # the chip the least times are stated for, wherever this runs


def shapes_of(config_path: str, on_chip: bool) -> dict:
    """The kernel's shapes and the cell's traffic mix from a configuration
    file; off the chip the same at a size the interpreter carries."""
    with open(config_path) as f:
        model = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = next(c["name"] for c in bench["configs"] if os.path.samefile(os.path.join(ROOT, c["file"]), config_path))
    mix = next(w["traffic"] for w in bench["workloads"] if w["config"] == name)
    with open(os.path.join(ROOT, "benchmarks", "traffic", mix + ".json")) as f:
        traffic = json.load(f)
    engine = model["engine"]
    z = dict(model=model, traffic=traffic, mix=mix, heads=model["num_attention_heads"], r_kv=model["kv_lora_rank"],
             d_r=model["qk_rope_head_dim"], block=engine["block_size"], blocks=engine["num_blocks"],
             batch=engine["max_batch"], table=engine["max_blocks_per_seq"], attentions=attentions_a_step(model))
    if not on_chip:
        z.update(heads=z["heads"] // 8, batch=4, attentions=1, blocks=4 * z["table"] + 1)
    z["stored"] = -(-(z["r_kv"] + z["d_r"]) // LANES) * LANES  # as the reader's need counts a row
    return z


def chunk_rows(z: dict, module) -> int:
    """Rows of a chunk of ``module``'s latent kernel at these shapes (bfloat16)."""
    return z["block"] * module.chunk_blocks_for(z["table"], z["block"] * z["stored"] * 2, module._LATENT_CHUNK_BYTES)


def profiles(z: dict, draws: int, seed: int) -> dict:
    """name -> (batch,) lengths, none past the table."""
    batch, block, most = z["batch"], z["block"], z["table"] * z["block"]
    rng = np.random.default_rng([seed, 1])
    cycle = np.asarray(request_cycle(z["traffic"], seed))
    out = {}
    for d in range(draws):
        held = cycle[rng.choice(len(cycle), batch, p=cycle[:, 1] / cycle[:, 1].sum())]  # a request by its decode steps
        out[f"cell_{d}"] = np.minimum(held[:, 0] + rng.integers(1, held[:, 1] + 1), most)
    for k in range(1, most // UNIT + 1):
        out[f"full_{k}"] = np.full(batch, k * UNIT)
        if k * UNIT + block <= most:
            out[f"full_{k}_and_a_block"] = np.full(batch, k * UNIT + block)
    out["one_block"] = np.full(batch, block)
    return {name: lengths.astype(np.int32) for name, lengths in out.items()}


def tables_for(z: dict, lengths, rng) -> np.ndarray:
    """Each sequence's live blocks drawn without order from the pool (never the null block 0), the rest 0."""
    free = rng.permutation(np.arange(1, z["blocks"]))
    tables, at = np.zeros((z["batch"], z["table"]), np.int32), 0
    for i, n in enumerate(-(-lengths // z["block"])):
        tables[i, :n], at = free[at:at + n], at + n
    return tables


def device_us(call, args, runs: int):
    """us a ``paged_latent_attention`` event from the profiler's trace of ``runs`` runs, or None off the chip."""
    directory = tempfile.mkdtemp(prefix="latent_time_")
    try:
        with jax.profiler.trace(directory):
            for _ in range(runs):
                jax.block_until_ready(call(*args))
        for name, dev in load_events(newest_xplane(directory))["devices"].items():
            if name.startswith("/device:TPU:0"):
                kernel = [e - s for op, _, s, e in dev["ops"] if op.split(".")[0] == KERNEL]
                if kernel:
                    return sum(kernel) / len(kernel) / 1e3
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    return None


def fit(lines: list) -> dict:
    """Least squares of us a call over (copied rows, chunks, sequences)."""
    a = np.asarray([[ln["rows"], ln["chunks"], ln["sequences"]] for ln in lines], np.float64)
    us = np.asarray([ln["us_a_call"] for ln in lines], np.float64)
    (row, chunk, seq), *_ = np.linalg.lstsq(a, us, rcond=None)
    return dict(ns_a_row=1e3 * row, us_a_chunk=chunk, us_a_sequence=seq,
                residual_us_max=float(np.abs(a @ [row, chunk, seq] - us).max()), lines=len(lines))


def time_kernel(z: dict, module, named: dict, on_chip: bool, label: str, seed: int) -> list:
    """One line a profile of ``named``: ``module``'s ``paged_latent_attention`` over every attention of one pool."""
    rng = np.random.default_rng([seed, 2])
    pool = jax.random.normal(jax.random.PRNGKey(seed), (z["attentions"], z["blocks"], z["block"], z["stored"]), jnp.bfloat16)
    pool = pool.at[..., z["r_kv"] + z["d_r"]:].set(0)
    q_l = jax.random.normal(jax.random.PRNGKey(seed + 1), (z["batch"], z["heads"], z["r_kv"]), jnp.bfloat16)
    q_r = jax.random.normal(jax.random.PRNGKey(seed + 2), (z["batch"], z["heads"], z["d_r"]), jnp.bfloat16)
    scale = (128 + z["d_r"]) ** -0.5

    @jax.jit
    def program(q_l, q_r, pool, tables, lengths):
        return [module.paged_latent_attention(q_l, q_r, pool, jnp.int32(i), tables, lengths, scale=scale, interpret=not on_chip)
                for i in range(z["attentions"])]

    @jax.jit
    def gathered(q_l, q_r, pool, tables, lengths):
        rows = pool[z["attentions"] - 1, tables].reshape(z["batch"], -1, z["stored"])[..., :z["r_kv"] + z["d_r"]]
        return latent_decode_attention(q_l, q_r, rows, lengths, scale=scale)

    lines, chunk = [], chunk_rows(z, module)
    for name, lengths in named.items():
        operands = (q_l, q_r, pool, jnp.asarray(tables_for(z, lengths, rng)), jnp.asarray(lengths))
        got = jax.block_until_ready(program(*operands))[-1].astype(jnp.float32)
        err = float(jnp.abs(got - gathered(*operands).astype(jnp.float32)).max())
        blocks = int((-(-lengths // z["block"])).sum())
        need = latent_attention_need({**z["model"], "kv_lora_rank": z["r_kv"], "num_attention_heads": z["heads"]},
                                     blocks, z["block"], z["batch"])
        us = device_us(program, operands, RUNS) if on_chip else None
        line = dict(kernel=label, profile=name, rows=blocks * z["block"], chunks=int((-(-lengths // chunk)).sum()),
                    sequences=int((lengths > 0).sum()), live_rows=int(lengths.sum()),
                    us_a_call=us if us is not None else "not measured",
                    bytes_us=least_time_s(0, need["bytes"] / attentions_a_step(z["model"]), PEAKS)["seconds"] * 1e6,
                    err_to_gathered=err, device=jax.devices()[0].device_kind)
        lines.append(line)
        print(json.dumps(line), flush=True)
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--draws", type=int, default=6)
    ap.add_argument("--seed", type=int, default=63)
    ap.add_argument("--also", default="", help="another ops/paged_attention.py whose kernel is timed beside the tree's")
    args = ap.parse_args(argv)
    on_chip = jax.default_backend() == "tpu"
    z = shapes_of(os.path.abspath(args.config), on_chip)
    kernels = {"tree": paged_attention}
    if args.also:
        spec = importlib.util.spec_from_file_location("also_paged_attention", args.also)
        also = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(also)
        kernels["also"] = also
    named = profiles(z, args.draws if on_chip else 1, args.seed)
    if not on_chip:  # a rehearsal: a profile of each form
        named = {k: v for k, v in named.items() if k in ("cell_0", "full_1", "full_1_and_a_block", "one_block")}
    report = {"config": args.config, "mix": z["mix"], "kernels": {}}
    for label, module in kernels.items():
        lines = time_kernel(z, module, named, on_chip, label, args.seed)
        report["kernels"][label] = {"chunk_rows": chunk_rows(z, module), "lines": lines,
                                    "fit": fit(lines) if on_chip else "not measured"}
        print(json.dumps({"kernel": label, "fit": report["kernels"][label]["fit"]}), flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)
    return report


if __name__ == "__main__":
    main()
