"""LM decode bench: static batching vs continuous batching + KV saturation.

BASELINE.json's serving target is Llama-2-7B batched replicas on v5e; on
CPU hosts a scaled-down geometry keeps every mode runnable in CI.

Modes (``--mode``; the default ``all`` runs ``direct`` (= ``static`` and
``continuous`` with their ratio row), ``serve`` and ``saturate``, each in a
process of its own — a chip belongs to one process at a time, and the served
modes' replica is not this one):

* ``static``      — the dense KV-cache decode path (``make_decode_fns``)
  run the way static batching actually serves: fixed batches admitted
  together, every batch decodes until its LONGEST member finishes
  (padding waste included). Useful tokens / wall-clock.
* ``continuous``  — the SAME workload through the paged continuous-
  batching engine (``serve.llm.InferenceEngine``): finished sequences
  free their slot + KV blocks immediately and waiting work joins at step
  boundaries. Also emits the ``lm_decode_continuous_vs_static_floor_ratio``
  row (floor 1.0: continuous must not lose to static on its home turf).
* ``serve``       — deploy the engine behind the serve plane, drive
  streams, and quote the deployment TTFT p50/p99 from the tracing-plane
  stream spans as folded by the controller (``serve.status()['..']['ttft']``
  — the same window the ``deployment_ttft_p99`` SLO burns against, which
  this mode registers).
* ``saturate``    — >= 100 concurrent streams against one replica with a
  deliberately small KV pool: counts ok / typed sheds / untyped failures
  (must be 0) and checks sheds stay fast.

Every row appends to ``BENCH_LM_DECODE.jsonl`` (append-only ledger; the
newest row per metric is the current claim, gated by
``tools/bench_check.py`` / ``make bench-gate``).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import threading
import time

LEDGER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "BENCH_LM_DECODE.jsonl")


def _fingerprint(backend: str, device: str) -> dict:
    return {
        "host": platform.node(),
        "backend": backend,
        "device": device,
        "cpus": os.cpu_count(),
    }


def _replica_device(handle) -> dict:
    """Where the served modes' replica computed, as its engine reports it:
    this process stays off JAX so the replica can have the chip."""
    stats = handle.kv_stats.remote().result(timeout_s=60)
    return {"backend": stats["platform"], "device": stats["device_kind"]}


def _append(row: dict) -> None:
    with open(LEDGER, "a") as fh:
        fh.write(json.dumps(row) + "\n")
    print(json.dumps(row))


def _geometry():
    """(model cfg, workload) sized to the attached backend."""
    import jax

    from ray_tpu.models.transformer import TransformerConfig

    if jax.default_backend() == "tpu":
        # Llama-2-7B geometry; weights bf16 (~13.5 GB) + cache fit 16G HBM
        cfg = TransformerConfig(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
            d_ff=11008, max_seq_len=1024, remat=False,
        )
        prompt_len, lengths = 128, [64, 384, 128, 256, 64, 384, 192, 320]
    else:
        # big enough that a decode step costs real time (utilization, not
        # python overhead, decides the comparison), small enough for CI
        cfg = TransformerConfig(
            vocab_size=512, d_model=256, n_layers=4, n_heads=8,
            d_ff=512, max_seq_len=256, remat=False,
        )
        prompt_len, lengths = 8, [8, 56, 16, 48, 8, 64, 24, 56, 16, 40, 8, 48]
    return cfg, prompt_len, lengths


def _params(cfg):
    import jax

    from ray_tpu.models.transformer import init_params

    # jit the init: XLA frees the fp32 sampling intermediates instead of
    # holding a transient fp32 copy of every bf16 tensor (OOM at 7B)
    return jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))


def _prompts(cfg, prompt_len, n, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, cfg.vocab_size - 1, prompt_len).tolist()
        for _ in range(n)
    ]


# -- static batching -------------------------------------------------------


def run_static(cfg, params, prompt_len, lengths, batch=4):
    """Fixed batch-of-4 admission: each batch decodes to its longest
    member (the static-batching padding tax), batches run back-to-back."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.generation import init_kv_cache, make_decode_fns

    max_len = prompt_len + max(lengths) + 1
    prefill, decode_step = make_decode_fns(cfg, max_len)
    prompts = _prompts(cfg, prompt_len, len(lengths))

    # compile warmup (one batch shape, reused by every batch)
    cache = init_kv_cache(cfg, batch, max_len)
    warm = jnp.asarray(np.asarray(prompts[:batch], dtype=np.int32))
    logits, cache = prefill(params, warm, cache)
    tok = jnp.argmax(logits, axis=-1)
    logits, cache = decode_step(params, tok[:, None], cache)
    jax.block_until_ready(logits)

    useful = 0
    t0 = time.perf_counter()
    for start in range(0, len(lengths), batch):
        group = list(range(start, min(start + batch, len(lengths))))
        pad = group + [group[-1]] * (batch - len(group))
        cache = init_kv_cache(cfg, batch, max_len)
        pb = jnp.asarray(np.asarray([prompts[i] for i in pad], dtype=np.int32))
        logits, cache = prefill(params, pb, cache)
        tok = jnp.argmax(logits, axis=-1)
        steps = max(lengths[i] for i in group)  # longest member gates
        for _ in range(steps - 1):
            logits, cache = decode_step(params, tok[:, None], cache)
            tok = jnp.argmax(logits, axis=-1)
        jax.block_until_ready(logits)
        useful += sum(lengths[i] for i in group)
    dt = time.perf_counter() - t0
    return {
        "tokens_per_sec": round(useful / dt, 1),
        "useful_tokens": useful,
        "wall_s": round(dt, 3),
        "batch": batch,
        "padding_tax": round(
            1.0
            - useful
            / sum(
                batch * max(lengths[i] for i in g)
                for g in [
                    list(range(s, min(s + batch, len(lengths))))
                    for s in range(0, len(lengths), batch)
                ]
            ),
            3,
        ),
    }


# -- continuous batching ---------------------------------------------------


def run_continuous(cfg, params, prompt_len, lengths, max_batch=4):
    """Same workload through the paged engine: slots refill the moment a
    sequence finishes, so mixed lengths stop taxing the batch."""
    from ray_tpu.serve.llm import EngineConfig, InferenceEngine

    block_size = 16
    blocks_per_seq = -(-(prompt_len + max(lengths) + 1) // block_size) + 1
    eng = InferenceEngine(
        params,
        cfg,
        EngineConfig(
            block_size=block_size,
            num_blocks=blocks_per_seq * (max_batch + len(lengths)) + 1,
            max_batch=max_batch,
            max_blocks_per_seq=blocks_per_seq,
            max_waiting=len(lengths) + 1,
            stream_timeout_s=600.0,
        ),
        deployment="bench",
    )
    try:
        prompts = _prompts(cfg, prompt_len, len(lengths))
        # compile warmup (prefill bucket + decode step)
        eng.submit(prompts[0], max_new_tokens=2).tokens()
        t0 = time.perf_counter()
        streams = [
            eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, lengths)
        ]
        ttfts = []
        useful = 0
        for s in streams:
            useful += len(s.tokens())
            ttfts.append(s.ttft_s)
        dt = time.perf_counter() - t0
    finally:
        eng.shutdown()
    ttfts = sorted(1000.0 * t for t in ttfts if t is not None)
    return {
        "tokens_per_sec": round(useful / dt, 1),
        "useful_tokens": useful,
        "wall_s": round(dt, 3),
        "max_batch": max_batch,
        "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 1) if ttfts else None,
        "ttft_p99_ms": round(ttfts[-1], 1) if ttfts else None,
    }


# -- serve-deployed TTFT (tracing-plane spans via the controller fold) -----


def run_serve_ttft(streams_n=24):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import TINY_MODEL, llm_deployment
    from ray_tpu.util import state

    ray_tpu.init(
        num_cpus=4,
        ignore_reinit_error=True,
        _system_config={"incident_plane_enabled": True},
    )
    try:
        # the SLO this row feeds: burns against the same ray_tpu_serve_ttft_ms
        # window the controller folds into serve.status()
        state.register_slo(
            "llm-ttft", "deployment_ttft_p99", 5_000.0, severity="WARNING"
        )
        app = llm_deployment(
            TINY_MODEL,
            dict(block_size=16, num_blocks=128, max_batch=4,
                 max_blocks_per_seq=8, max_waiting=64),
            deployment_name="llm",
            health_check_period_s=0.5,
            max_ongoing_requests=64,
        )
        serve.run(app, name="bench-llm")
        h = serve.get_app_handle("bench-llm").options(stream=True)
        prompt = [7, 3, 11, 23, 5, 42, 9, 2]
        list(h.generate.remote(prompt, max_new_tokens=4))  # compile warmup

        def one():
            list(h.generate.remote(prompt, max_new_tokens=16))

        threads = [threading.Thread(target=one) for _ in range(streams_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        # the controller folds replica stream-TTFT spans on its probe tick
        snap = None
        deadline = time.time() + 20
        while time.time() < deadline:
            snap = serve.status().get("bench-llm", {}).get("llm", {}).get("ttft")
            if snap and snap.get("count", 0) >= streams_n:
                break
            time.sleep(0.25)
        slo_rows = [s for s in state.list_slos() if s.get("name") == "llm-ttft"]
        device = _replica_device(serve.get_app_handle("bench-llm"))
        serve.delete("bench-llm")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    if not snap or not snap.get("count"):
        return None
    return {
        "ttft_p50_ms": snap.get("p50"),
        "ttft_p99_ms": snap.get("p99"),
        "folded_streams": snap.get("count"),
        "source": "serve.status() controller fold of replica stream-TTFT spans",
        "slo_registered": bool(slo_rows),
        "device": device,
    }


# -- KV saturation ---------------------------------------------------------


def run_saturate(streams_n=100):
    """>= 100 concurrent streams against ONE replica with a small KV pool:
    KV-aware admission must shed typed (DeploymentOverloadedError with
    retry_after) fast, admitted streams complete, nothing fails untyped."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import TINY_MODEL, llm_deployment

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        app = llm_deployment(
            TINY_MODEL,
            dict(block_size=4, num_blocks=33, max_batch=4,
                 max_blocks_per_seq=8, max_waiting=4, retry_after_s=1.0),
            deployment_name="llm",
            health_check_period_s=0.5,
            # the ENGINE's KV admission is the layer under test
            max_ongoing_requests=4 * streams_n,
        )
        serve.run(app, name="sat-llm")
        h = serve.get_app_handle("sat-llm").options(stream=True)
        prompt = [5, 3, 1, 2, 4, 6]
        list(h.generate.remote(prompt, max_new_tokens=4))  # compile warmup

        counts = {"ok": 0, "shed": 0, "untyped": 0}
        ttfts = []
        lock = threading.Lock()

        def client():
            t0 = time.perf_counter()
            try:
                first_at = None
                n = 0
                for _ in h.generate.remote(prompt, max_new_tokens=8):
                    if first_at is None:
                        first_at = time.perf_counter() - t0
                    n += 1
                with lock:
                    counts["ok" if n == 8 else "untyped"] += 1
                    if first_at is not None:
                        ttfts.append(1000.0 * first_at)
            except serve.DeploymentOverloadedError as e:
                with lock:
                    counts["shed" if getattr(e, "retry_after_s", 0) > 0
                           else "untyped"] += 1
            except Exception:  # noqa: BLE001
                with lock:
                    counts["untyped"] += 1

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client) for _ in range(streams_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.perf_counter() - t0
        device = _replica_device(serve.get_app_handle("sat-llm"))
        serve.delete("sat-llm")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    ttfts.sort()
    return {
        "streams": streams_n,
        "ok": counts["ok"],
        "shed_typed": counts["shed"],
        "untyped": counts["untyped"],
        "wall_s": round(wall, 2),
        "admitted_ttft_p99_ms": round(ttfts[-1], 1) if ttfts else None,
        "device": device,
    }


# -- driver ----------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--mode",
        default="all",
        choices=["all", "direct", "static", "continuous", "serve", "saturate"],
    )
    ap.add_argument("--saturate-streams", type=int, default=100)
    args = ap.parse_args()

    if args.mode == "all":
        # one process for each chip: the direct modes compute in their own
        # interpreter, the served modes in a replica — and a process that
        # has touched JAX keeps the chip until it exits
        for mode in ("direct", "serve", "saturate"):
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--mode", mode,
                 "--saturate-streams", str(args.saturate_streams)],
                check=True,
            )
        return

    static = continuous = None
    if args.mode in ("direct", "static", "continuous"):
        import jax

        fp = _fingerprint(jax.default_backend(), jax.devices()[0].device_kind)
        cfg, prompt_len, lengths = _geometry()
        params = _params(cfg)
    if args.mode in ("direct", "static"):
        static = run_static(cfg, params, prompt_len, lengths)
        _append({
            "metric": "lm_decode_static_tokens_per_sec",
            "value": static["tokens_per_sec"],
            "unit": "tokens/s", "mode": "static",
            "fingerprint": fp, "detail": static,
        })
    if args.mode in ("direct", "continuous"):
        continuous = run_continuous(cfg, params, prompt_len, lengths)
        _append({
            "metric": "lm_decode_continuous_tokens_per_sec",
            "value": continuous["tokens_per_sec"],
            "unit": "tokens/s", "mode": "continuous",
            "fingerprint": fp, "detail": continuous,
        })
    if static and continuous:
        _append({
            "metric": "lm_decode_continuous_vs_static_floor_ratio",
            "value": round(
                continuous["tokens_per_sec"] / static["tokens_per_sec"], 3
            ),
            "unit": "continuous/static tokens/s (same workload, same host)",
            "floor": 1.0, "mode": "continuous",
            "fingerprint": fp,
        })
    if args.mode == "serve":
        ttft = run_serve_ttft()
        if ttft:
            _append({
                "metric": "llm_deployment_ttft_p99_ms",
                "value": ttft["ttft_p99_ms"],
                "unit": "ms", "mode": "continuous",
                "budget": 5000.0,
                "fingerprint": _fingerprint(**ttft.pop("device")), "detail": ttft,
            })
    if args.mode == "saturate":
        sat = run_saturate(args.saturate_streams)
        _append({
            "metric": "lm_decode_saturation_untyped_failures",
            "value": sat["untyped"],
            "unit": "failures (must be 0)", "mode": "continuous",
            "budget": 0,
            "fingerprint": _fingerprint(**sat.pop("device")), "detail": sat,
        })


if __name__ == "__main__":
    main()
