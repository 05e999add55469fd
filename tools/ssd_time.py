"""A prompt's SSD form alone on the chip (PR 56): ``ops/ssd.py:ssd_chunked``, one
layer, B = 1, at the two head shapes the benchmark serves (Granite-4.0-H's 128
heads of 64, N 128, one group; Falcon-H1's 32 heads of 128, N 256, two groups)
and its three prefill buckets: ms a layer on the device, and the largest error
of ``y`` and of the last state against the float64 walk of
``tests/test_selective_scan.py:mamba2_recurrence``.

    chiprun -- python tools/ssd_time.py [--out chiprun_out/ssd_time.json]
        [--buckets 256,512,1024] [--also _parent/ray_tpu/ops/ssd.py]

``x`` goes in and ``y`` comes out (S, heads x P), as the mixer hands them over
(``models/mamba2.py``), so a form that re-lays them by head pays for it here as
it does in the program. **The time is the device's**: the profiler's "XLA
Modules" line of ``RUNS`` calls (the host's clock around one call reads 0.4 ms
of dispatch whatever the program, more than the kernel itself). Off the chip
there is no such line: a sixteenth of the heads run in the form the shapes
choose there (``jax.numpy``), the errors are printed, and ms reads "not
measured".

``--also``: another module's ``ssd_chunked`` (a parent's, from ``git archive``)
beside the tree's on the same chip; it is given the shape's published
``mamba_chunk_size`` as its tile where it takes one.
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.trace.reduce import newest_xplane  # noqa: E402
from ray_tpu.ops import ssd  # noqa: E402
from test_selective_scan import mamba2_inputs, mamba2_recurrence  # noqa: E402

SHAPES = {  # the mixers' published numbers; ``chunk``: the published mamba_chunk_size, which only ``--also`` may read
    "granite-4.0-h": dict(heads=128, p=64, groups=1, n=128, chunk=256),
    "falcon-h1": dict(heads=32, p=128, groups=2, n=256, chunk=128),
}
RUNS = 20
PADDED = 37  # positions of padding at the bucket's end, as a prompt leaves them


def device_ms(call, args, runs):
    """ms a call on the device from the profiler's trace, or None where no
    device's plane is in it (the CPU backend)."""
    from jax.profiler import ProfileData

    directory = tempfile.mkdtemp(prefix="ssd_time_")
    try:
        with jax.profiler.trace(directory):
            for _ in range(runs):
                out = call(*args)
            jax.block_until_ready(out)
        for plane in ProfileData.from_file(newest_xplane(directory)).planes:
            if plane.name.startswith("/device:TPU:0"):
                modules = [e.duration_ns for line in plane.lines if line.name == "XLA Modules" for e in line.events]
                return sum(modules) / len(modules) / 1e6 if modules else None
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    return None


def sides(also):
    """name -> f(shape) -> the jitted (x (B, S, q), dt, a, bm, cm) -> (y (B, S, q), state)."""
    def of(chunked, tiled):
        def make(k):
            def call(x, dt, a, bm, cm):
                y, state = chunked(x.reshape(*x.shape[:2], k["heads"], k["p"]), dt, a, bm, cm, *((k["chunk"],) if tiled else ()))
                return y.reshape(x.shape), state
            return jax.jit(call)
        return make

    out = {"tree": of(ssd.ssd_chunked, False)}
    if also:
        spec = importlib.util.spec_from_file_location("ssd_also", also)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        out[also] = of(module.ssd_chunked, "chunk" in inspect.signature(module.ssd_chunked).parameters)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="")
    ap.add_argument("--buckets", default="256,512,1024")
    ap.add_argument("--also", default="")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    buckets = [int(s) for s in args.buckets.split(",")]
    forms, lines = sides(args.also), []
    for name, k in SHAPES.items():
        if not on_chip:  # a rehearsal: the same code at a size the CPU and the interpreter carry
            k = dict(k, heads=k["heads"] // 16)
        inputs = mamba2_inputs(56, max(buckets), b=1, **{key: k[key] for key in ("heads", "p", "groups", "n")})
        for s in buckets:
            live = s - PADDED
            x, dt, a, bm, cm = (t if t.ndim == 1 else t[:, :s] for t in inputs)
            dt = dt.at[:, live:].set(0.0)
            want_y, want_state = mamba2_recurrence(x[:, :live], dt[:, :live], a, bm[:, :live], cm[:, :live])
            for side, make in forms.items():
                call, operands = make(k), (x.reshape(1, s, -1), dt, a, bm, cm)
                y, state = jax.block_until_ready(call(*operands))
                ms = device_ms(call, operands, RUNS) if on_chip else None
                line = dict(shape=name, s=s, side=side, ms_a_layer=ms if ms is not None else "not measured",
                            kernel=ssd.can_use_ssd_kernel(s, k["heads"], k["p"], k["groups"], k["n"]) if side == "tree" else None,
                            err_y=float(np.abs(np.asarray(y).reshape(want_y.shape[0], s, -1)[:, :live].reshape(want_y.shape) - want_y).max()),
                            err_state=float(np.abs(np.asarray(state) - want_state).max()),
                            device=jax.devices()[0].device_kind)
                lines.append(line)
                print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
