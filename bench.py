"""Benchmark: flagship LM training-step MFU on the attached TPU chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The baseline is BASELINE.json's north-star target of 35% MFU for GPT-J-style
fine-tuning on v5e (the reference publishes no number for this workload —
BASELINE.md "North-star targets"); vs_baseline = achieved_MFU / 0.35.
"""

from __future__ import annotations

import json
import os
import time

# one compile cache for every run from this checkout, placeable from outside
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
)
# a Pallas kernel's module is serialised with its Python call stack (file paths
# and line numbers, ten frames up) into an opaque string the cache key cannot
# strip, so a moved checkout or a shifted line would recompile the step
os.environ.setdefault("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "0")

# per-chip peak dense bf16 FLOP/s, keyed by ``device_kind`` exactly as JAX
# reports it on a machine this repo has run on (Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s). Add a device with its source when one is seen.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    """Peak of one chip of ``device_kind``; a device that is not in the
    table is an error, not a default."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak recorded for device_kind {device_kind!r}: add it to "
            f"PEAK_BF16_FLOPS with its source (known: {sorted(PEAK_BF16_FLOPS)})"
        ) from None


def main():
    import jax
    import numpy as np

    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.spmd import build_lm_train_step

    backend = jax.default_backend()
    n_dev = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    peak = peak_bf16_flops(device_kind) * n_dev  # no chip it knows: an error

    # GPT-J-6B LAYER GEOMETRY (d_model 4096, 16 heads x head_dim 256,
    # d_ff 16384, seq 2048, parallel block, remat on): per-layer compute is
    # identical to the 6B north-star; depth is truncated to 4 layers so
    # params + fp32 adam moments still fit one chip's 16G HBM (28 layers
    # needs the v5e-64 FSDP mesh the driver cannot attach). MFU measured on
    # these layers transfers to full depth: remat makes every layer's
    # compute/memory profile identical.
    cfg = TransformerConfig(
        vocab_size=50432,
        d_model=4096,
        n_layers=4,
        n_heads=16,
        d_ff=16384,
        max_seq_len=2048,
        parallel_block=True,
        use_swiglu=False,
        # dots-saveable selective remat: backward re-runs only cheap
        # elementwise work; matmul outputs stay in HBM (fits at batch 8)
        remat_policy="dots",
    )
    batch, seq, steps = 8, 2048, 10

    mesh = create_mesh(MeshConfig(data=n_dev))
    bundle = build_lm_train_step(cfg, mesh, learning_rate=1e-4)
    state = bundle.init_fn(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size - 1, (batch, seq), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)
    tok, tgt = bundle.shard_batch(tokens, targets)

    # warmup (compile)
    state, metrics = bundle.step_fn(state, tok, tgt)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = bundle.step_fn(state, tok, tgt)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])

    n_params = cfg.num_params()
    tokens_per_step = batch * seq
    # fwd+bwd ~= 6 * N FLOPs/token; remat re-runs fwd -> ~8 * N.
    # MFU convention counts the useful 6N (hardware utilization incl. remat
    # would be higher); report the conservative number.
    model_flops_per_step = 6 * n_params * tokens_per_step
    steps_per_sec = steps / dt
    tokens_per_sec = tokens_per_step * steps_per_sec
    achieved = model_flops_per_step * steps_per_sec
    mfu = achieved / peak

    result = {
        # honest name: GPT-J-6B LAYER GEOMETRY at truncated depth (4 layers,
        # ~1.2B params — full 6B + fp32 adam moments does not fit one v5e
        # chip's HBM); per-layer compute identical to the 6B north star
        "metric": "gptj_layer_geometry_train_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak_bf16",
        "vs_baseline": round(mfu / 0.35, 4),
        "detail": {
            "backend": backend,
            "device_kind": device_kind,
            "n_devices": n_dev,
            "n_params": n_params,
            "tokens_per_sec_per_chip": round(tokens_per_sec / n_dev, 1),
            "step_time_ms": round(1000 * dt / steps, 2),
            "loss": final_loss,
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
