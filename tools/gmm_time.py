"""One grouped matmul alone on the chip (PR 60): ``ops/grouped_matmul.py:gmm``
at a stated tiling, by expert shape, tokens, matrix, row tile and weight tile:
us a call on the device beside the call's bytes' and products' least time.

    chiprun -- python tools/gmm_time.py [--out chiprun_out/gmm_time.json]
        [--shapes granite,nemotron] [--tokens 48] [--row-tiles 256,128,64]

A call is what ``models/moe.py:expert_layer`` hands the kernel for the first
window of a call of ``--tokens`` tokens (a decode step's 48 slots, a prefill
bucket's 256 ..): ``moe.window_rows`` rows, sorted by expert, of which an even
router's draw leaves the held experts their share (each token ``top_k`` of the
router's outputs without replacement; ``DRAWS`` seeded draws, a call each, every
call reading another layer's experts out of one stack as the programs do). A
matrix is timed under each row tile that divides the window, at the weight tile
``moe._weight_tile`` states there (``cut``) and, where that cuts the
contraction, with the contraction whole (``whole``).

**The time is the device's**: the ``gmm`` events of the profiler's "XLA Ops"
line, which is what ``expert_matmul_roofline`` reads of a decode step
(``us_around`` is the rest of the program's time a call: the group metadata and
the groups' slice, in no ``gmm`` event). Beside it, from the draws and the
shapes: ``pairs``, the (row tile, expert) pairs a call visits; ``bytes_us``, the
touched experts' matrices once with the held rows in and out at 819 GB/s (the
work, as ``expert_matmul_need`` counts it; the peaks are ``benchmarks/peaks.json``'s); ``streamed_us``, what the kernel
copies as it is (a matrix a pair, the tile of rows a pair a contraction tile);
``products_us``, a tile's rows times the matrix a pair at 197 TFLOP/s. Off the
chip there is no such line: a sixteenth of the widths runs in interpret mode,
the result is held against ``jax.lax.ragged_dot``, and us reads "not measured".
"""

from __future__ import annotations

import argparse
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
from benchmarks.trace.reduce import load_events, newest_xplane  # noqa: E402
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.ops.grouped_matmul import gmm  # noqa: E402

# the published widths an expert reads and its hidden width, the experts a chip holds of a layer, the router's outputs,
# the choices a token, whether the expert is gated (gate and up: bfloat16 out) or ``e_up`` alone (float32 out: the
# square is taken ahead of the rounding), the layers of the timed stack
SHAPES = {
    "granite": dict(d=4096, f=768, held=36, n_outputs=72, top_k=10, gated=True, layers=4),
    "nemotron": dict(d=1024, f=2688, held=128, n_outputs=512, top_k=22, gated=False, layers=3),
    "lfm2": dict(d=2048, f=1536, held=64, n_outputs=64, top_k=4, gated=True, layers=4),
}
DRAWS = 8
PEAKS = peaks_for("TPU v5 lite")  # the chip the least times are stated for, wherever this runs


def draws(tokens, top_k, n_outputs, held, window, seed=60):
    """``DRAWS`` x (held,) rows of each held expert in a call's first window:
    every token's ``top_k`` of ``n_outputs`` without replacement, the held
    experts the first ``held``, clipped to the window as ``expert_layer`` does."""
    rng, out = np.random.default_rng(seed), []
    for _ in range(DRAWS):
        chosen = rng.permuted(np.tile(np.arange(n_outputs), (tokens, 1)), axis=1)[:, :top_k]
        sizes = np.bincount(chosen.reshape(-1), minlength=n_outputs)[:held]
        ends = np.cumsum(sizes)
        out.append(np.clip(ends, 0, window) - np.clip(ends - sizes, 0, window))
    return np.asarray(out, np.int32)


def pairs_of(groups, tile):
    """The (row tile, group) pairs a call visits: a group with a row, the tiles from its first row's to its last's."""
    ends = np.cumsum(groups)
    starts = ends - groups
    return int(sum((e - 1) // tile - s // tile + 1 for s, e, g in zip(starts, ends, groups) if g))


def device_us(call, args):
    """(us a ``gmm`` event, us a call of everything else in the program) from
    the profiler's trace of one run, or None off the chip."""
    directory = tempfile.mkdtemp(prefix="gmm_time_")
    try:
        with jax.profiler.trace(directory):
            jax.block_until_ready(call(*args))
        for name, dev in load_events(newest_xplane(directory))["devices"].items():
            if name.startswith("/device:TPU:0"):
                kernel = [e - s for op, _, s, e in dev["ops"] if op.split(".")[0] == "gmm"]
                whole = sum(e - s for _, s, e in dev["modules"])
                if kernel:
                    return sum(kernel) / len(kernel) / 1e3, (whole - sum(kernel)) / len(kernel) / 1e3
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    return None


def tilings(k, n, window, row_tiles):
    """(row tile, label, (tk, tn)) of every call to time: each row tile that
    divides the window at the weight tile ``moe._weight_tile`` states under it
    (``cut``) and, where that cuts the contraction, with the contraction whole."""
    for tile in row_tiles:
        if window % tile == 0:
            cut = moe._weight_tile(k, n, tile)
            yield tile, "cut", cut
            if cut[0] < k and k * 128 <= moe._WEIGHT_TILE:
                yield tile, "whole", (k, moe._widest(n, moe._WEIGHT_TILE // k))


def time_one(x, w, drawn, tiling, out_type, on_chip):
    """One matrix at one tiling: ``DRAWS`` calls in one program, a call a draw,
    each over another layer's experts of the stack ``w`` -> (the last call's
    largest error against ``ragged_dot``, ``device_us`` of the program)."""
    held = drawn.shape[1]
    layers = w.shape[0] // held

    def groups_of(i, drawn):
        return jax.lax.dynamic_update_slice(jnp.zeros((w.shape[0],), jnp.int32), drawn[i], ((i % layers) * held,))

    def program(x, w, drawn):
        total = jnp.zeros((), jnp.float32)
        for i in range(DRAWS):
            res = gmm(x, w, groups_of(i, drawn), preferred_element_type=out_type, tiling=tiling, interpret=not on_chip)
            total = total + res[0, 0].astype(jnp.float32)  # every call's result is used
        return total, res

    call, operands = jax.jit(program), (x, w, jnp.asarray(drawn))
    _, res = jax.block_until_ready(call(*operands))
    live = int(drawn[-1].sum())
    want = jax.lax.ragged_dot(x, w, groups_of(DRAWS - 1, operands[2]), preferred_element_type=jnp.float32)
    err = float(jnp.abs(res[:live].astype(jnp.float32) - want[:live]).max())
    return err, device_us(call, operands) if on_chip else None


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="")
    ap.add_argument("--shapes", default="granite,nemotron")
    ap.add_argument("--tokens", default="48")
    ap.add_argument("--row-tiles", default="256,128,64")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    row_tiles, lines = [int(t) for t in args.row_tiles.split(",")], []
    us = lambda flops, nbytes: least_time_s(flops, nbytes, PEAKS)["seconds"] * 1e6  # noqa: E731
    for name in args.shapes.split(","):
        z = dict(SHAPES[name])
        if not on_chip:  # a rehearsal: the same code at a size the interpreter carries
            z.update(d=max(128, z["d"] // 16 // 128 * 128), f=max(128, z["f"] // 16 // 128 * 128), layers=2)
        d, f, held, layers = z["d"], z["f"], z["held"], z["layers"]
        stack = (jax.random.normal(jax.random.PRNGKey(1), (layers * held, d, f), jnp.float32) * d ** -0.5).astype(jnp.bfloat16)
        matrices = {"gate" if z["gated"] else "up": (d, f, jnp.bfloat16 if z["gated"] else jnp.float32), "down": (f, d, jnp.float32)}
        for tokens in (int(t) for t in args.tokens.split(",")):
            window = moe.window_rows(tokens * z["top_k"], held, z["n_outputs"])
            drawn = draws(tokens, z["top_k"], z["n_outputs"], held, window)
            touched, rows = float((drawn > 0).sum(1).mean()), float(drawn.sum(1).mean())
            for matrix, (k, n, out_type) in matrices.items():
                x = jax.random.normal(jax.random.PRNGKey(7), (window, k), jnp.float32).astype(jnp.bfloat16)
                out_size = jnp.dtype(out_type).itemsize
                for tile, label, (tk, tn) in tilings(k, n, window, row_tiles):
                    err, timed = time_one(x, stack.reshape(layers * held, k, n), drawn, (tile, tk, tn), out_type, on_chip)
                    pairs = float(np.mean([pairs_of(g, tile) for g in drawn]))
                    row_tiles_met = float(np.mean(-(-drawn.sum(1) // tile)))
                    # the rows' block follows (row tile, contraction tile): a cut contraction copies it again a pair
                    row_copies = (pairs if tk < k else row_tiles_met) * (n // tn)
                    line = dict(
                        shape=name, tokens=tokens, matrix=matrix, k=k, n=n, window=window, row_tile=tile, weight_tile=[tk, tn],
                        contraction=label, held_rows=rows, touched=touched, pairs=pairs,
                        us_a_call=timed[0] if timed else "not measured", us_around=timed[1] if timed else "not measured",
                        bytes_us=us(0, touched * k * n * 2 + rows * (k * 2 + n * out_size)),
                        streamed_us=us(0, pairs * k * n * 2 + row_copies * tile * k * 2 + row_tiles_met * tile * n * out_size),
                        products_us=us(pairs * tile * 2 * k * n, 0),
                        err_to_ragged_dot=err, device=jax.devices()[0].device_kind)
                    lines.append(line)
                    print(json.dumps(line), flush=True)
                    if args.out:
                        with open(args.out, "w") as fh:
                            json.dump(lines, fh, indent=1)
        del stack


if __name__ == "__main__":
    main()
