"""The training cells (traffic kind ``train_job``): ``JaxTrainer.fit`` over
``build_lm_train_step``, one worker that holds every chip of the cell, a
``train.report`` every step. The loop function below is the user's loop: it
runs in the worker, does all device work there, and hands the parent the
step stamps, the counters, the reduced trace and the comparison with the
plain reference in its last report."""

from __future__ import annotations

import math
import shutil
import tempfile
import time

from benchmarks import families
from benchmarks.harness import arith, common, stepcheck, traffic as tr
from benchmarks.harness.common import say


def train_loop(config: dict) -> None:
    import jax
    import numpy as np

    from benchmarks.harness.replica import CONTROLS, CompileWatch, device_report
    from benchmarks.harness.weights import seed_words
    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.spmd import build_lm_train_step

    watch, heart = CompileWatch(), common.Heartbeat()
    family = families.of(config["config"])
    model, tc, mix = family.model_kwargs(config["config"]), config["config"]["train"], config["traffic"]
    cfg = family.train_config(model)
    mesh = create_mesh(MeshConfig(**tc["mesh"]), devices=jax.devices())
    bundle = build_lm_train_step(cfg, mesh, learning_rate=tc["learning_rate"])
    weights_of = jax.jit(lambda w: family.make_weights(w, model, jax.numpy.dtype(model["dtype"])),
                         out_shardings=bundle.param_shardings)

    def fresh_weights():
        return weights_of(seed_words(config["seed"]))

    # the program lays out its state (zero moments, shardings); the weights in
    # it are the benchmark's, made from the seed, the same the reference gets
    state = bundle.init_state(0)
    state["params"] = fresh_weights()
    batch, seq = tc["batch"], tc["seq"]

    if mix["ingest"]:
        shard = train.get_dataset_shard("train")

        def endless():
            while True:
                yield from shard.iter_jax_batches(
                    batch_size=batch, drop_last=True, sharding=bundle.batch_shard)

        batches = ((b["tokens"], b["targets"]) for b in endless())
    else:
        data = tr.token_batches(config["seed"], model["vocab_size"], batch, seq)
        fixed = bundle.shard_batch(data["tokens"], data["targets"])
        batches = iter(lambda: fixed, None)

    starts, waits, losses, parts = [], [], [], []
    first_batch = None

    def one_step():
        nonlocal state, first_batch
        starts.append(time.time())
        tok, tgt = next(batches)
        waits.append(time.time() - starts[-1])
        if first_batch is None:
            first_batch = (np.asarray(tok), np.asarray(tgt))
        t_step = time.time()
        state, metrics = bundle.step_fn(state, tok, tgt)
        losses.append(float(metrics["loss"]))  # the step ends when its loss is on the host
        t_report = time.time()
        train.report({"step": len(losses) - 1, "loss": losses[-1]})
        parts.append((t_report - t_step, time.time() - t_report))

    # the first step, from the seeded weights and zero moments on the first
    # batch, is the one `correct` judges: a sample of what it leaves behind
    chk = mix["check"]
    picks = stepcheck.draw_picks(config["seed"], state["params"], chk["leaves"], chk["samples"])
    take = stepcheck.make_take()
    before = take(state["params"], picks)
    one_step()
    sampled = stepcheck.sample_step(take, state, picks, before)
    for _ in range(int(mix["warmup_steps"]) - 1):
        one_step()
    n_warm, compile0 = len(starts), dict(watch.counts)
    t0 = time.time()
    trace, t1 = None, t0 + config["seconds"]
    trace_steps = int(mix["trace_steps"])
    while time.time() < t1:
        if config["trace"] and len(starts) - n_warm >= 3:
            # leave the end of the window to the traced steps
            if time.time() >= t1 - trace_steps * (starts[-1] - starts[-2]):
                break
        one_step()
    n_plain = len(starts)
    compile1 = dict(watch.counts)
    if config["trace"]:
        # the last few steps of the window run under the profiler
        from benchmarks.trace.reduce import reduce_directory

        jax.profiler.start_trace(config["trace_dir"])
        for _ in range(trace_steps):
            one_step()
        jax.profiler.stop_trace()
        trace = reduce_directory(config["trace_dir"], remove=not config["keep_trace"])
    starts.append(time.time())  # the end of the last step
    host_stalls = heart.within(t0, starts[-1])
    device = device_report(jax.devices()[0])

    # -- the comparison with the plain reference, after the window --------
    del state
    one = jax.devices()[0]
    params1 = jax.device_put(fresh_weights(), one)
    tok1, tgt1 = (jax.device_put(first_batch[i], one) for i in (0, 1))
    verdict = stepcheck.compare(family.reference(), sampled, losses[0], params1, tok1, tgt1, picks, tc["adamw"],
                                tc["learning_rate"], CONTROLS if config["control"] else ())
    train.report({"step": len(losses), "loss": losses[-1], "summary": {
        "t0": t0, "n_warm": n_warm, "n_plain": n_plain, "starts": starts, "waits": waits, "parts": parts,
        "losses": losses, "compile_in_window": {k: compile1[k] - compile0[k] for k in compile1},
        "compile_total": dict(watch.counts), "host_stalls": host_stalls, "device": device, "trace": trace,
        "verdict": verdict, "n_params": sum(x.size for x in jax.tree.leaves(params1)),
    }})


def run(cell: dict, args) -> int:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    mix, config = cell["traffic"], cell["config"]
    tc = config["train"]
    model = families.of(config).model_kwargs(config)
    chips = common.start_cluster(cell["chips"], args.rehearse)
    datasets = None
    if mix["ingest"]:
        from ray_tpu import data

        rows = tr.token_batches(args.seed, model["vocab_size"], tc["batch"] * int(mix["dataset_batches"]), tc["seq"])
        datasets = {"train": data.from_numpy(rows, num_blocks=int(mix["dataset_batches"]))}
    if not chips:
        scaling = ScalingConfig(num_workers=1)
    elif chips == 1:
        scaling = ScalingConfig(num_workers=1, use_tpu=True)
    else:
        scaling = ScalingConfig(num_workers=1, resources_per_worker={"CPU": 1.0, "TPU": float(chips)})
    storage = tempfile.mkdtemp(prefix="bench_train_")
    heart = common.Heartbeat()
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config=dict(
                config=config, traffic=mix, seed=args.seed, seconds=args.seconds,
                trace=args.trace, control=args.control, keep_trace=args.keep_trace,
                trace_dir=common.trace_dir(cell, args),
            ),
            scaling_config=scaling, datasets=datasets,
            run_config=RunConfig(name="bench", storage_path=storage),
        ).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    s = result.metrics["summary"]
    ray_tpu.shutdown()
    device = s["device"]
    common.check_device(device, cell["chips"], args.rehearse)

    t0 = s["t0"]
    t1 = t0 + args.seconds
    plain = slice(s["n_warm"], s["n_plain"])  # the untraced steps of the window
    step_starts, step_ends = s["starts"][plain], s["starts"][1:][plain]
    tokens_per_step = tc["batch"] * tc["seq"]
    rate, n_steps = arith.whole_step_rate(step_ends, step_starts, tokens_per_step, t0, t1)
    losses = s["losses"][plain]
    bad = sum(1 for x in losses if not math.isfinite(x))
    durations = [e - b for b, e in zip(step_starts, step_ends)]
    waits = s["waits"][plain]
    say(f"{n_steps} whole steps in the window of {args.seconds} s; step time median "
        f"{1e3 * arith.percentile(durations, 50):.2f} ms, min {1e3 * min(durations):.2f}, max "
        f"{1e3 * max(durations):.2f}; data wait mean {1e3 * arith.mean(waits):.3f} ms; "
        f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; {s['n_params'] / 1e9:.3f} B parameters")
    median = arith.percentile(durations, 50)
    slow = [(i, round(step_starts[i] - t0, 2), round(1e3 * d), round(1e3 * w),
             *(round(1e3 * x) for x in s["parts"][plain][i]))
            for i, (d, w) in enumerate(zip(durations, waits)) if d > 1.05 * median]
    slow = sorted(slow, key=lambda x: -x[2])[:8]
    say(f"{len(slow)} slowest steps over 1.05 x the median (index in the window, seconds after its start; ms: "
        f"whole, data wait, step to loss, report): {slow}")
    say(f"host stalls in the window (seconds after its start, seconds away): worker {s['host_stalls']}, "
        f"parent {heart.within(t0, t1)}")
    say(f"compilation inside the window {s['compile_in_window']}; over the whole run "
        f"{s['compile_total']}; load average {common.loadavg():.2f}")
    e2e = {"setup_s": t0 - args.t_start, "train_tokens_per_s": rate}

    v, limits = s["verdict"], config["limits"]
    compared = {k: (v[k], limits[k]) for k in limits}
    correct = bool(compared) and all(math.isfinite(x) and x <= lim for x, lim in compared.values()) and not bad
    args.verdict = (f"correct={correct}: " + "; ".join(f"{k} {x:.6g} (limit {lim})" for k, (x, lim) in compared.items())
                    + f"; all readings {v}")
    say(args.verdict)

    if args.trace:
        trace = s["trace"]
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        ctx = {"cell": cell, "config": config, "model": model, "train": tc, "trace": trace,
               "waits": waits, "durations": durations, "e2e": e2e, "chips": cell["chips"],
               "peaks": None if args.rehearse else common.peaks_for(device["kind"])}
        metrics = common.read_layer_metrics(cell, ctx)
        breakdown = trace["breakdown"]
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]} for m in cell["end_to_end"]}
        breakdown = None
    args.result = (correct, n_steps, bad, metrics, device, breakdown)
    return 0
