#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu's two main paths start on
the chip, through the entry points a user calls.

    python chip_smoke.py            one chip: serve phase, then train phase
    python chip_smoke.py --chips 4  four chips: chip isolation + sharded training
    python chip_smoke.py --tiny     rehearsal at TINY size (never a chip result)

*serve*: ``serve.run(llm_deployment(GPT-J-6B as published, 28 layers))`` on a
replica that holds ``TPU: 1``; six concurrent streaming requests through the
deployment handle and one through the HTTP proxy. *train*: ``JaxTrainer.fit``
with ``use_tpu=True`` over ``build_lm_train_step`` at ``bench.py``'s geometry.

This process never imports jax: each phase's chip work happens in the one
worker process that holds the ``TPU`` resource, and that process is gone
before the next phase starts. Any failed check, any phase that raised, or a
platform other than ``tpu`` ends the run non-zero without the last line. The
numbers printed on the way are smoke readings, not a benchmark.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import shutil
import signal
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
# one compile cache for every process of every run from this checkout,
# placeable from outside; set before ray_tpu.init() so workers inherit it
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
os.environ.setdefault("JAX_COMPILATION_CACHE_MAX_SIZE", str(128 << 20))
# a Pallas kernel's module is serialised with its Python call stack (file paths
# and line numbers, ten frames up) into an opaque string the cache key cannot
# strip: without this a moved checkout, or a line added to this file, recompiles
# the training step. No frames in locations: the key is the program alone.
os.environ.setdefault("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "0")

# GPT-J-6B as published (EleutherAI/gpt-j-6b config.json): 28 layers, d_model
# 4096, 16 heads of 256, d_ff 16384, vocab 50400, parallel block, gelu, bf16
GPTJ_6B = dict(
    vocab_size=50400, d_model=4096, n_layers=28, n_heads=16, d_ff=16384,
    max_seq_len=2048, parallel_block=True, use_swiglu=False,
    tie_embeddings=False, dtype="bfloat16",
)
# pool sized to the memory left after the 12.1 GB of weights: 384 blocks x 16
# tokens x 28 layers x 2 x 4096 x 2 B = 2.8 GB (1,024 blocks do not compile)
GPTJ_ENGINE = dict(block_size=16, num_blocks=384, max_batch=8, max_blocks_per_seq=64)
# bench.py's training geometry: GPT-J widths, 4 layers so parameters, Adam
# moments and gradients fit 16 GB at batch 8 x 2048
GPTJ_TRAIN = dict(
    vocab_size=50432, d_model=4096, n_layers=4, n_heads=16, d_ff=16384,
    max_seq_len=2048, parallel_block=True, use_swiglu=False, remat_policy="dots",
)
FULL = dict(
    model=GPTJ_6B, engine=GPTJ_ENGINE, prompt_lens=(100, 500), new_tokens=32,
    train_model=GPTJ_TRAIN, batch=8, seq=2048,
)
TINY = dict(
    model=dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
               d_ff=128, max_seq_len=256, dtype="float32"),
    engine=dict(block_size=4, num_blocks=128, max_batch=8, max_blocks_per_seq=16),
    prompt_lens=(10, 40), new_tokens=8,
    train_model=dict(vocab_size=512, d_model=128, n_layers=2, n_heads=2, d_ff=256,
                     max_seq_len=128, parallel_block=True, use_swiglu=False,
                     remat_policy="dots"),
    batch=8, seq=128,  # 8: divisible by the virtual CPU mesh the tests force
)
N_REQUESTS = 6
WARMUP_STEPS, TIMED_STEPS = 1, 5
# four-chip comparison: the same 3 steps on a one-device and a (fsdp 2, tensor
# 2) mesh. Same program, bf16 weights and activations, but every contraction
# over d_model / d_ff is split across chips and summed in another order, and
# three AdamW steps (which divide by the gradient's running RMS) compound
# it. 2% of a loss near ln(vocab) = 10.8 is far below what a mesh that lost a
# shard or doubled a gradient shows (such faults move the loss by units).
MESH_LOSS_RTOL = 0.02


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    say(f"  ok: {what}")


# -- processes -------------------------------------------------------------


def _descendants(root: int) -> dict:
    """pid -> parent pid of every process below ``root``, zombies included."""
    parent = {}
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                with open(f"/proc/{name}/stat") as f:
                    parent[int(name)] = int(f.read().rsplit(")", 1)[1].split()[1])
            except OSError:
                pass
    out, frontier = {}, [root]
    while frontier:
        p = frontier.pop()
        kids = [c for c, pp in parent.items() if pp == p]
        out.update((c, p) for c in kids)
        frontier.extend(kids)
    return out


def _libtpu_mapped(pid: int) -> bool:
    with open(f"/proc/{pid}/maps") as f:
        return "libtpu" in f.read()


def report_processes(expect_holders: int) -> None:
    """Every live process of this run and whether libtpu is mapped into it."""
    from ray_tpu.util import state

    names = {a["pid"]: a["class_name"] for a in state.list_actors() if a.get("pid")}
    holders = []
    for pid in [os.getpid()] + sorted(_descendants(os.getpid())):
        try:
            mapped = _libtpu_mapped(pid)
        except OSError:
            continue  # exited while we looked
        role = "parent (chip_smoke.py)" if pid == os.getpid() else names.get(pid, "worker/forkserver")
        say(f"  pid {pid:>7}  libtpu {'MAPPED' if mapped else 'no':<6}  {role}")
        if mapped:
            holders.append(pid)
    check(
        len(holders) == expect_holders and os.getpid() not in holders,
        f"{expect_holders} process(es) with libtpu mapped, the parent not among them: {holders}",
    )


def wait_gone(pids: list, timeout_s: float = 30.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if not any(os.path.exists(f"/proc/{p}") for p in pids):
            break
        time.sleep(0.2)
    alive = [p for p in pids if os.path.exists(f"/proc/{p}")]
    check(not alive, f"teardown ended the chip's process(es) {pids} (still alive: {alive})")


def stop_processes() -> list:
    """Every way out of this script leads through here: nothing it started
    may outlive it. ``ray_tpu.shutdown()`` tells the workers to exit and
    waits two seconds; multiprocessing's forkserver and resource tracker end
    only when they notice this process gone, which is after it has ended. So:
    the workers first (they are the forkserver's children and keep it alive;
    were it to go before them, init would inherit them and nobody here could
    wait for them), then the two helpers, each waited for. Returns the pids
    that did not go by themselves and were killed."""
    from multiprocessing import forkserver, resource_tracker

    me, killed = os.getpid(), []

    def end(live, grace_s: float) -> None:
        deadline = time.monotonic() + grace_s
        while live() and time.monotonic() < deadline:
            time.sleep(0.1)
        for pid in live():
            killed.append(pid)
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        deadline = time.monotonic() + 10.0
        while live() and time.monotonic() < deadline:
            time.sleep(0.1)

    def unreaped() -> list:
        for pid, ppid in _descendants(me).items():
            if ppid == me:
                try:
                    os.waitpid(pid, os.WNOHANG)
                except ChildProcessError:
                    pass
        return sorted(_descendants(me))

    try:
        if "ray_tpu" in sys.modules:
            import ray_tpu

            ray_tpu.shutdown()  # does nothing where a phase already did it
    finally:
        end(lambda: sorted(p for p, pp in _descendants(me).items() if pp != me), 15.0)
        forkserver._forkserver._stop()
        resource_tracker._resource_tracker._stop()
        end(unreaped, 5.0)
    return killed


def start_cluster(need_chips: int, tiny: bool) -> int:
    """ray_tpu.init(); returns the TPU count a worker may ask for. Off the
    chip only the rehearsal goes on, on workers that hold no TPU."""
    import ray_tpu
    from ray_tpu import native

    if "jax" in sys.modules:
        raise RuntimeError("the parent must stay off jax: it would hold the chip")
    ray_tpu.init()
    chips = int(ray_tpu.cluster_resources().get("TPU", 0))
    say(f"  cluster: {chips} TPU chip(s) detected; object store: {native.status()}")
    if chips < need_chips:
        if not tiny:
            raise SystemExit(f"chip_smoke: needs {need_chips} TPU chip(s), this host shows {chips}")
        return 0
    return need_chips


def cache_entries() -> int:
    d = os.environ["JAX_COMPILATION_CACHE_DIR"]
    return len(os.listdir(d)) if os.path.isdir(d) else 0


# -- serve phase -----------------------------------------------------------


def serve_phase(size: dict, seed: int, tiny: bool) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment
    from ray_tpu.util import state

    m = size["model"]
    say(f"== serve: serve.run(llm_deployment(...)) at vocab {m['vocab_size']}, d_model "
        f"{m['d_model']}, {m['n_layers']} layers, {m['n_heads']} heads, d_ff {m['d_ff']}, "
        f"{m['dtype']}; engine {size['engine']}")
    num_tpus = start_cluster(1, tiny)
    entries_before = cache_entries()
    t0 = time.perf_counter()
    serve.run(
        llm_deployment(
            model_cfg=m, engine_cfg=size["engine"],
            ray_actor_options={"num_tpus": num_tpus},
        ),
        name="llmapp", route_prefix="/llm",
    )
    say(f"  replica up (backend start + seeded weights + pool) in {time.perf_counter() - t0:.1f} s")
    h = serve.get_deployment_handle("llm", app_name="llmapp")
    report_processes(expect_holders=1 if num_tpus else 0)
    replicas = [a["pid"] for a in state.list_actors() if a["class_name"] == "Replica"]

    rng = np.random.default_rng(seed)
    lo, hi = size["prompt_lens"]
    new = size["new_tokens"]
    prompts = [
        rng.integers(1, m["vocab_size"] - 1, int(n)).tolist()
        for n in rng.integers(lo, hi + 1, N_REQUESTS)
    ]

    def stream(prompt):
        t = time.perf_counter()
        toks, ttft = [], None
        for tok in h.options(stream=True).generate.remote(prompt, max_new_tokens=new):
            if ttft is None:
                ttft = time.perf_counter() - t
            toks.append(int(tok))
        return toks, ttft, time.perf_counter() - t

    alone, ttft0, wall0 = stream(prompts[0])
    say(f"  first request alone ({len(prompts[0])} prompt tokens; compiles its prefill bucket "
        f"and the decode step): first token after {ttft0:.2f} s, all {new} after {wall0:.2f} s")

    def all_at_once():
        t = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(N_REQUESTS) as pool:
            out = [f.result() for f in [pool.submit(stream, p) for p in prompts]]
        return out, time.perf_counter() - t

    results, wall = all_at_once()
    for p, (toks, ttft, dt) in zip(prompts, results):
        say(f"  stream: prompt {len(p):>3} tokens -> {len(toks)} tokens, first after "
            f"{ttft:.3f} s, done after {dt:.2f} s")
    check(
        all(len(t) == new and all(0 <= x < m["vocab_size"] for x in t) for t, _, _ in results),
        f"all {N_REQUESTS} concurrent streams finished with {new} in-vocabulary tokens",
    )
    check(all(ttft is not None and ttft > 0 for _, ttft, _ in results),
          "every stream recorded a time to first token")
    check(results[0][0] == alone,
          "greedy tokens of prompt 0 sent alone == its tokens among the other five")
    say(f"  smoke reading (not a benchmark; includes compiling the other prefill buckets): "
        f"{N_REQUESTS * new / wall:.1f} tokens/s over {N_REQUESTS} concurrent streams")

    again, wall = all_at_once()  # the same six, everything compiled
    check([t for t, _, _ in again] == [t for t, _, _ in results],
          "the same six requests give the same tokens a second time")
    say(f"  smoke reading (not a benchmark), all programs compiled: "
        f"{N_REQUESTS * new / wall:.1f} tokens/s, median time to first token "
        f"{sorted(t for _, t, _ in again)[N_REQUESTS // 2]:.3f} s")

    req = urllib.request.Request(
        "http://127.0.0.1:8700/llm",
        data=json.dumps({"prompt": prompts[0], "max_new_tokens": new}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        via_http = json.loads(r.read())["result"]
    check(via_http == alone, "prompt 0 through the HTTP proxy gives the same tokens")

    stats = h.kv_stats.remote().result(timeout_s=60)
    check(stats["blocks_free"] == stats["blocks_total"] and stats["running"] == 0
          and stats["waiting"] == 0 and stats["blocks_committed"] == 0,
          f"kv_stats() shows the pool drained ({stats['blocks_free']}/{stats['blocks_total']} blocks free)")
    say(f"  replica device: {stats['platform']} / {stats['device_kind']} x {stats['device_count']}; "
        f"peak device bytes {stats['device_peak_bytes']}")
    say("  attention path: generation._attend_pool (decode steps: the Pallas kernel of ops/paged_attention.py; "
        "a prefill: XLA einsum over its table's gathered blocks)")
    say(f"  compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}: "
        f"{entries_before} entries before, {cache_entries()} after")

    serve.shutdown()
    ray_tpu.shutdown()
    wait_gone(replicas)
    return {k: stats[k] for k in ("platform", "device_kind", "device_count")}


# -- train phase -----------------------------------------------------------


def _make_step(model: dict, mesh_axes: dict, devices, lr: float):
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.spmd import build_lm_train_step

    cfg = TransformerConfig(**model)
    mesh = create_mesh(MeshConfig(**mesh_axes), devices=devices)
    return cfg, build_lm_train_step(cfg, mesh, learning_rate=lr)


def _batch(cfg, batch: int, seq: int, seed: int):
    import numpy as np

    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size - 1, (batch, seq), dtype=np.int32
    )
    return tokens, np.roll(tokens, -1, axis=1)


def _self_report() -> dict:
    import jax

    d = jax.devices()
    return {
        "platform": d[0].platform, "device_kind": d[0].device_kind, "device_count": len(d),
        "pid": os.getpid(), "libtpu_mapped": _libtpu_mapped(os.getpid()),
    }


def train_loop(config: dict) -> None:
    """Runs in the JaxTrainer worker: 1 warm-up + 5 timed steps on one fixed
    seeded batch, each loss reported; the last report carries the summary."""
    import jax
    import jax.monitoring

    from ray_tpu import train

    cache = {"requests": 0, "hits": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    cfg, bundle = _make_step(config["model"], {"data": -1}, jax.devices(), config["lr"])
    state = bundle.init_state(config["seed"])
    tok, tgt = bundle.shard_batch(*_batch(cfg, config["batch"], config["seq"], config["seed"]))
    losses, step_times, get_after_block = [], [], []
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = bundle.step_fn(state, tok, tgt)
        jax.block_until_ready(metrics)
        t1 = time.perf_counter()
        # what the old device_get work-around would still have waited for
        losses.append(float(jax.device_get(metrics["loss"])))
        get_after_block.append(time.perf_counter() - t1)
        step_times.append(t1 - t0)
        # Result.metrics keeps the last report only: carry the history along
        report = {"step": i, "loss": losses[-1], "losses": list(losses),
                  "step_times": list(step_times), "get_after_block": list(get_after_block)}
        if i == 0:
            cache_first_step = dict(cache)
        if i == config["steps"] - 1:
            # is the attention a kernel or XLA's own ops?
            text = bundle.step_fn.lower(state, tok, tgt).as_text()
            stats = jax.devices()[0].memory_stats() or {}
            report.update(
                _self_report(),
                cache_first_step=cache_first_step,
                flash_kernels=text.count("tpu_custom_call"),
                peak_bytes=stats.get("peak_bytes_in_use"),
                n_params=cfg.num_params(),
            )
        train.report(report)


def train_phase(size: dict, seed: int, tiny: bool) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    m = size["train_model"]
    say(f"== train: JaxTrainer.fit over build_lm_train_step at d_model {m['d_model']}, "
        f"{m['n_layers']} layers, {m['n_heads']} heads, d_ff {m['d_ff']}, batch "
        f"{size['batch']} x {size['seq']}, remat {m['remat_policy']}")
    use_tpu = bool(start_cluster(1, tiny))
    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    try:
        trainer = JaxTrainer(
            train_loop,
            train_loop_config=dict(
                model=m, batch=size["batch"], seq=size["seq"], seed=seed, lr=1e-4,
                steps=WARMUP_STEPS + TIMED_STEPS,
            ),
            scaling_config=ScalingConfig(num_workers=1, use_tpu=use_tpu),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
        )
        result = trainer.fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    last = result.metrics
    check(last.get("step") == WARMUP_STEPS + TIMED_STEPS - 1,
          f"the last report is step {WARMUP_STEPS + TIMED_STEPS - 1}")
    losses = last["losses"]
    say(f"  losses: {['%.4f' % l for l in losses]}")
    check(all(math.isfinite(l) for l in losses) and losses[-1] < losses[0],
          "losses finite and falling on the fixed batch")
    steps = last["step_times"]
    timed = sorted(steps[WARMUP_STEPS:])
    say(f"  first step (compile + run) {steps[0]:.1f} s; compile cache "
        f"{os.environ['JAX_COMPILATION_CACHE_DIR']} at the first step: "
        f"{last['cache_first_step']['hits']} hits of {last['cache_first_step']['requests']} requests "
        f"(init and step programs; hits when an earlier run left the directory)")
    say(f"  smoke reading (not a benchmark): median step {timed[len(timed) // 2]:.4f} s of "
        f"{TIMED_STEPS} timed by block_until_ready = "
        f"{size['batch'] * size['seq'] / timed[len(timed) // 2]:.0f} tokens/s, "
        f"{last['n_params'] / 1e9:.2f} B parameters")
    say(f"  device_get after block_until_ready waited {max(last['get_after_block']) * 1e3:.2f} ms "
        f"at most (it has nothing left to wait for if that is ~0)")
    say(f"  attention path: {'Pallas flash kernel' if last['flash_kernels'] else 'XLA einsum'} "
        f"({last['flash_kernels']} tpu_custom_call in the lowered step program)")
    say(f"  worker device: {last['platform']} / {last['device_kind']} x {last['device_count']}; "
        f"peak device bytes {last['peak_bytes']}; worker pid {last['pid']} libtpu mapped: "
        f"{last['libtpu_mapped']}")
    if not tiny:
        check(last["flash_kernels"] > 0, "the flash kernel is the attention path in training")
    ray_tpu.shutdown()
    wait_gone([last["pid"]])
    return {k: last[k] for k in ("platform", "device_kind", "device_count")}


# -- four chips ------------------------------------------------------------


def mesh_loop(config: dict) -> None:
    """Runs in ONE JaxTrainer worker that holds all four chips."""
    import re

    import jax

    from ray_tpu import train

    devs = jax.devices()
    out = dict(_self_report(), device_ids=[d.id for d in devs])
    runs = {
        "one_device": (config["model"], {"data": 1}, devs[:1]),
        "mesh_2x2": (config["model"], {"fsdp": 2, "tensor": 2}, devs),
        "mesh_2x2_deep": (config["deep_model"], {"fsdp": 2, "tensor": 2}, devs),
    }
    for name, (model, axes, on) in runs.items():
        cfg, bundle = _make_step(model, axes, on, config["lr"])
        state = bundle.init_state(config["seed"])
        tok, tgt = bundle.shard_batch(*_batch(cfg, config["batch"], config["seq"], config["seed"]))
        losses, times = [], []
        for _ in range(config["steps"]):
            t0 = time.perf_counter()
            state, metrics = bundle.step_fn(state, tok, tgt)
            losses.append(float(metrics["loss"]))
            times.append(time.perf_counter() - t0)
        text = bundle.step_fn.lower(state, tok, tgt).compile().as_text()
        out[name] = {
            "layers": cfg.n_layers, "n_params": cfg.num_params(), "losses": losses,
            "step_times": times,
            "mesh_devices": [d.id for d in bundle.mesh.devices.flat],
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use") for d in devs],
            "collectives": {
                op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
                for op in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                           "collective-permute")
            },
            "flash_kernels": text.count("tpu_custom_call"),
        }
        del state, tok, tgt, bundle, metrics
    train.report({"loss": out["mesh_2x2_deep"]["losses"][-1], "summary": out})


def four_chip_phase(size: dict, seed: int, tiny: bool) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    say("== four chips (a): two @ray_tpu.remote(num_tpus=1) actors alive at once")
    n = start_cluster(4, tiny)
    if not n:
        raise SystemExit("chip_smoke --chips 4: rehearse it on 4 virtual CPU devices in the tests; "
                         "this host shows no 4 chips")

    @ray_tpu.remote(num_tpus=1)
    class OneChip:
        def look(self):
            import jax
            import jax.numpy as jnp

            import ray_tpu as rt

            x = jnp.ones((1024, 1024), jnp.bfloat16)
            trace = float((x @ x).sum())  # the device does work, not just shows up
            vfio = sorted(
                os.readlink(f"/proc/self/fd/{fd}") for fd in os.listdir("/proc/self/fd")
                if os.path.islink(f"/proc/self/fd/{fd}")
                and os.readlink(f"/proc/self/fd/{fd}").startswith("/dev/vfio/")
            )
            return dict(
                _self_report(), matmul_sum=trace,
                assigned=rt.get_runtime_context().get_accelerator_ids()["TPU"],
                visible=os.environ.get("TPU_VISIBLE_CHIPS"),
                bounds=os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS"),
                coords=[getattr(d, "coords", None) for d in jax.devices()],
                vfio_open=vfio,
            )

    actors = [OneChip.remote(), OneChip.remote()]
    looks = ray_tpu.get([a.look.remote() for a in actors], timeout=300)
    for look in looks:
        say(f"  actor pid {look['pid']}: {look['device_count']} x {look['platform']} / "
            f"{look['device_kind']}, assigned chip {look['assigned']}, TPU_VISIBLE_CHIPS="
            f"{look['visible']}, bounds {look['bounds']}, coords {look['coords']}, "
            f"open {look['vfio_open']}, matmul sum {look['matmul_sum']:.0f}")
    report_processes(expect_holders=2)
    check(all(l["device_count"] == 1 and l["platform"] == "tpu" for l in looks),
          "each one-chip actor sees exactly one TPU device")
    groups = [tuple(v for v in l["vfio_open"] if v[len("/dev/vfio/"):].isdigit()) for l in looks]
    check(looks[0]["assigned"] != looks[1]["assigned"] and looks[0]["pid"] != looks[1]["pid"]
          and groups[0] and groups[1] and not set(groups[0]) & set(groups[1]),
          f"the two actors hold different chips (assigned {looks[0]['assigned']} vs "
          f"{looks[1]['assigned']}, vfio groups {groups[0]} vs {groups[1]})")
    for a in actors:
        ray_tpu.kill(a)
    wait_gone([l["pid"] for l in looks])

    m = size["train_model"]
    deep = dict(m, n_layers=2 * m["n_layers"])
    say(f"== four chips (b): one JaxTrainer worker with TPU: 4, MeshConfig(fsdp=2, tensor=2), "
        f"{m['n_layers']} layers on one device and on the mesh, then {deep['n_layers']} layers")
    storage = tempfile.mkdtemp(prefix="chip_smoke_mesh_")
    try:
        result = JaxTrainer(
            mesh_loop,
            train_loop_config=dict(model=m, deep_model=deep, batch=size["batch"],
                                   seq=size["seq"], seed=seed, lr=1e-4, steps=3),
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"CPU": 1.0, "TPU": 4.0}),
            run_config=RunConfig(name="chip_smoke_mesh", storage_path=storage),
        ).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    s = result.metrics["summary"]
    say(f"  worker pid {s['pid']}: {s['device_count']} x {s['platform']} / {s['device_kind']}, "
        f"device ids {s['device_ids']}")
    for name in ("one_device", "mesh_2x2", "mesh_2x2_deep"):
        r = s[name]
        say(f"  {name}: {r['layers']} layers, {r['n_params'] / 1e9:.2f} B parameters, mesh devices "
            f"{r['mesh_devices']}, losses {['%.4f' % l for l in r['losses']]}, step times "
            f"{['%.2f' % t for t in r['step_times']]} s (first compiles; smoke reading)")
        say(f"    bytes_in_use per device {r['bytes_in_use']}; collectives in the compiled step "
            f"{r['collectives']}; tpu_custom_call {r['flash_kernels']}")
    one, mesh, deep_r = s["one_device"], s["mesh_2x2"], s["mesh_2x2_deep"]
    check(s["device_count"] == 4 and len(set(mesh["mesh_devices"])) == 4,
          "the worker with TPU: 4 sees four devices and the mesh spans all of them")
    worst = max(abs(a - b) / abs(a) for a, b in zip(one["losses"], mesh["losses"]))
    check(worst <= MESH_LOSS_RTOL,
          f"one-device and four-device losses agree within {MESH_LOSS_RTOL:.0%} (worst {worst:.4%})")
    check(all(math.isfinite(l) for l in deep_r["losses"]) and deep_r["losses"][-1] < deep_r["losses"][0],
          f"the {deep_r['layers']}-layer model stepped 3 times with finite, falling losses")
    for name in ("mesh_2x2", "mesh_2x2_deep"):
        b = s[name]["bytes_in_use"]
        check(all(x is not None for x in b) and max(b) <= 1.5 * (sum(b) / len(b)),
              f"{name}: no device holds more than 1.5x the mean bytes_in_use")
        check(sum(s[name]["collectives"].values()) > 0, f"{name}: the compiled step has collectives")
    ray_tpu.shutdown()
    wait_gone([s["pid"]])
    return {k: s[k] for k in ("platform", "device_kind", "device_count")}


# -- main ------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse the same code at TINY size; never a chip result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    size = TINY if args.tiny else FULL

    try:
        if args.chips == 4:
            devices = [four_chip_phase(size, args.seed, args.tiny)]
        else:
            devices = [serve_phase(size, args.seed, args.tiny),
                       train_phase(size, args.seed, args.tiny)]
    finally:
        killed = stop_processes()
    left = sorted(_descendants(os.getpid()))
    check(not killed and not left,
          f"every process this run started has ended (killed: {killed}, left: {left})")
    check("jax" not in sys.modules, "the parent never imported jax")
    check(all(d == devices[0] for d in devices), f"every phase ran on the same device: {devices[0]}")
    d = devices[0]
    if args.tiny:
        say(f"rehearsal at TINY size passed on {d['platform']} / {d['device_kind']} x "
            f"{d['device_count']}: not a chip result")
        return 3
    if d["platform"] != "tpu" or d["device_count"] != args.chips:
        raise SystemExit(f"chip_smoke: ran on {d}, not on {args.chips} TPU chip(s)")
    print(json.dumps({"ok": True, "device": {
        "platform": d["platform"], "kind": d["device_kind"], "count": d["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
