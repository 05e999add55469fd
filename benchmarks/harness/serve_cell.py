"""The serving cells (traffic kinds ``closed_loop`` and ``open_loop``): the
parent's side. It deploys the replica, warms every shape the mix can reach,
drives the load from this one process, stamps every token as it arrives at the
client, and reduces the stamps to the end-to-end metrics."""

from __future__ import annotations

import random
import threading
import time

from benchmarks import families
from benchmarks.harness import arith, common, traffic as tr
from benchmarks.harness.common import say


class Load:
    """The requests of one run and what the client saw of each."""

    def __init__(self, handle, mix: dict, seed: int, vocab: int):
        self.handle, self.mix, self.seed, self.vocab = handle, mix, seed, vocab
        self.cycle = tr.request_cycle(mix, seed)
        self.records = []  # one dict a request, appended when it is sent
        self.lock = threading.Lock()
        self.next_index = 0
        self.stop_sending = threading.Event()
        self.streams = []  # open loop: one thread a request

    def take_index(self) -> int:
        with self.lock:
            i = self.next_index
            self.next_index += 1
            return i

    def send(self, index: int, due: float) -> None:
        """One streamed request; every token stamped on arrival."""
        req = tr.request(self.mix, self.seed, index, self.vocab, self.cycle)
        rec = {"index": index, "due": due, "sent": time.time(), "prompt": req["prompt"],
               "want": req["max_new_tokens"], "tokens": [], "arrivals": [], "error": None}
        with self.lock:
            self.records.append(rec)
        kw = dict(self.mix.get("sampling", {}))
        try:
            stream = self.handle.options(stream=True).generate.remote(
                req["prompt"], max_new_tokens=req["max_new_tokens"], **kw)
            for tok in stream:
                rec["arrivals"].append(time.time())
                rec["tokens"].append(int(tok))
        except Exception as e:  # noqa: BLE001 - a shed or a failed stream is a counted failure
            rec["error"] = f"{type(e).__name__}: {e}"

    def closed_loop(self) -> list:
        def caller():
            while not self.stop_sending.is_set():
                self.send(self.take_index(), time.time())

        return [threading.Thread(target=caller, daemon=True) for _ in range(int(self.mix["callers"]))]

    def open_loop(self, start: float, horizon_s: float) -> list:
        """One sender thread that keeps the schedule and a thread a request."""
        times = tr.arrival_times(self.mix, self.seed, horizon_s)

        def sender():
            for i, t in enumerate(times):
                due = start + t
                delay = due - time.time()
                if delay > 0:
                    time.sleep(delay)
                th = threading.Thread(target=self.send, args=(i, due), daemon=True)
                self.streams.append(th)
                th.start()

        return [threading.Thread(target=sender, daemon=True)]


def failed(rec: dict) -> bool:
    return rec["error"] is not None or len(rec["tokens"]) != rec["want"]


def run(cell: dict, args) -> int:
    import ray_tpu
    from ray_tpu import serve

    from benchmarks.harness.replica import BenchLLMServer

    mix, config = cell["traffic"], cell["config"]
    model, engine = families.of(config).model_kwargs(config), dict(config["engine"])
    worst = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    if worst > engine["max_blocks_per_seq"] * engine["block_size"]:
        raise SystemExit(f"traffic asks for {worst} tokens a request, the engine holds fewer")

    num_tpus = common.start_cluster(cell["chips"], args.rehearse)
    say(f"cluster up {time.time() - args.t_start:.2f} s after the run's start")
    serve.run(
        serve.deployment(
            BenchLLMServer, name="llm",
            max_ongoing_requests=engine["max_batch"] + engine.get("max_waiting", 32),
            ray_actor_options={"num_tpus": num_tpus},
        ).bind(config, weight_seed=args.seed, deployment="llm"),
        name="bench", route_prefix="/bench",
    )
    h = serve.get_deployment_handle("llm", app_name="bench")
    say(f"replica up {time.time() - args.t_start:.2f} s after the run's start")

    # warm every prefill bucket the mix can reach and the greedy decode step,
    # whatever the seed, through the served path
    lo = tr.prefill_bucket(mix["prompt_len"]["lo"])
    hi = tr.prefill_bucket(mix["prompt_len"]["hi"])
    buckets = [b for b in (lo * 2**i for i in range(32)) if b <= hi]
    rng = random.Random(args.seed)
    for b in buckets:
        t = time.time()
        prompt = [rng.randrange(1, model["vocab_size"] - 1) for _ in range(b)]
        out = list(h.options(stream=True).generate.remote(prompt, max_new_tokens=3))
        if len(out) != 3:
            raise SystemExit(f"warm-up of bucket {b} gave {len(out)} tokens")
        say(f"warmed prefill bucket {b} and the decode step in {time.time() - t:.2f} s")

    # load: a ramp (set-up), then the window
    load = Load(h, mix, args.seed, model["vocab_size"])
    heart = common.Heartbeat()
    ramp = float(mix["ramp_seconds"])
    start = time.time() + 0.2
    t0, t1 = start + ramp, start + ramp + args.seconds
    if mix["kind"] == "closed_loop":
        threads = load.closed_loop()
    else:
        threads = load.open_loop(start, ramp + args.seconds)
    time.sleep(max(0.0, start - time.time()))
    for th in threads:
        th.start()

    time.sleep(max(0.0, t0 - time.time()))
    c0 = h.bench_counters.remote().result(timeout_s=60)
    window_s = args.seconds
    if args.trace:
        # counters and client stamps are read over the untraced part; the
        # trace takes the last few seconds of the window, not the whole of it
        trace_s = min(float(mix.get("trace_seconds", 4.0)), args.seconds / 2)
        window_s = args.seconds - trace_s
        t1 = t0 + window_s
    time.sleep(max(0.0, t1 - time.time()))
    c1 = h.bench_counters.remote().result(timeout_s=60)
    if args.trace:
        h.bench_start_trace.remote(common.trace_dir(cell, args)).result(timeout_s=120)
        time.sleep(trace_s)
        h.bench_stop_trace.remote().result(timeout_s=300)
    load.stop_sending.set()
    say(f"window over; load average {common.loadavg():.2f}; waiting for the streams in flight")
    deadline = time.time() + 180  # for all of them together
    for th in threads + load.streams:
        th.join(timeout=max(0.0, deadline - time.time()))
    if any(th.is_alive() for th in threads + load.streams):
        say("a stream did not end within 180 s of the window's end")

    records = sorted(load.records, key=lambda r: r["index"])
    arrivals = [r["arrivals"] for r in records]
    in_window = [r for r in records if t0 <= r["due"] < t1]
    bad = [r for r in records if failed(r)]
    for r in bad[:5]:
        say(f"failed request {r['index']}: {r['error'] or 'short stream'} "
            f"({len(r['tokens'])}/{r['want']} tokens)")
    attempted = len(in_window) + sum(1 for r in bad if r not in in_window)

    # -- end to end ------------------------------------------------------
    e2e = {"setup_s": t0 - args.t_start}
    n_tokens = arith.tokens_in_window(arrivals, t0, t1)
    e2e["serve_tokens_per_s"] = n_tokens / window_s
    gaps = arith.token_gaps(arrivals, t0, t1)

    # -- diagnostics, on earlier lines -------------------------------------
    steps = c1["decode_steps"] - c0["decode_steps"]
    first_tokens = sum(1 for ts in arrivals if ts and t0 <= ts[0] < t1)
    prefills = {}
    for r in records:
        if r["arrivals"] and t0 <= r["arrivals"][0] < t1:
            b = tr.prefill_bucket(len(r["prompt"]))
            prefills[b] = prefills.get(b, 0) + 1
    contexts = [len(r["prompt"]) + i for r in records for i, t in enumerate(r["arrivals"])
                if i and t0 <= t < t1]
    compile_in_window = {k: c1["compile"][k] - c0["compile"][k] for k in c1["compile"]}
    counters = {
        "decode_steps": steps,
        "decode_step_ms_sum": c1["decode_step_ms_sum"] - c0["decode_step_ms_sum"],
        "decode_tokens": c1["decode_tokens"] - c0["decode_tokens"],
        "prefill_tokens": c1["prefill_tokens"] - c0["prefill_tokens"],
        "shed": c1["shed"] - c0["shed"],
        "first_tokens": first_tokens,
        "max_batch": engine["max_batch"],
        "replica_window_s": c1["t"] - c0["t"],
    }
    say(f"tokens/s per 5 s slice: {[round(x, 1) for x in arith.slice_rates(arrivals, t0, t1, 5.0)]}")
    say(f"window {window_s} s: {n_tokens} tokens at the client, {len(in_window)} requests due, "
        f"{len(bad)} failed; engine counters {counters}")
    say(f"prefills by bucket {dict(sorted(prefills.items()))}; compilation inside the window "
        f"{compile_in_window}; longest gap between two tokens of a stream "
        f"{1e3 * max(gaps, default=0):.1f} ms; {len(gaps)} gaps, mean {1e3 * arith.mean(gaps or [0]):.3f} ms, "
        f"99th percentile {1e3 * arith.percentile(gaps or [0], 99):.3f} ms")
    say(f"host stalls in the window (seconds after its start, seconds away): replica "
        f"{[(round(t - t0, 2), round(d, 2)) for t, d in c1['host_stalls'] if t0 <= t < t1]}, "
        f"parent {heart.within(t0, t1)}")
    if mix["kind"] == "open_loop":
        late = [r["sent"] - r["due"] for r in in_window]
        say(f"generator late by {1e3 * max(late, default=0):.2f} ms at most, "
            f"{1e3 * arith.mean(late or [0]):.2f} ms in the mean")
        # a backlog that grows through the window shows as a later half slower than the earlier
        mid = (t0 + t1) / 2
        halves = [[r["arrivals"][0] - r["due"] for r in in_window if r["arrivals"] and lo <= r["due"] < hi]
                  for lo, hi in ((t0, mid), (mid, t1))]
        say("time to first token, ms: " + "; ".join(
            f"{name} half mean {1e3 * arith.mean(h or [0]):.1f} p90 {1e3 * arith.percentile(h or [0], 90):.1f} "
            f"max {1e3 * max(h, default=0):.1f} ({len(h)} requests)" for name, h in zip(("first", "second"), halves))
            + f"; waiting in the engine at the window's end {c1['kv']['waiting']}, running {c1['kv']['running']}")

    # -- correct: the comparison with the plain reference ------------------
    chk = mix["check"]
    done = [r for r in records if not failed(r)]
    rng = random.Random(args.seed)
    # side by side in the decode slots, so no more of them than slots
    picked = rng.sample(done, min(int(chk["requests"]), engine["max_batch"], len(done)))
    verdict = h.bench_check.remote(
        [(r["prompt"], r["tokens"]) for r in picked], int(chk["decode_steps"]), bool(args.control)
    ).result(timeout_s=600)
    limits = config["limits"]
    compared = {k: (verdict[k], limits[k]) for k in limits}
    correct = bool(picked) and all(v <= lim for v, lim in compared.values())
    args.verdict = (f"correct={correct}: " + "; ".join(f"{k} {v:.6g} (limit {lim})" for k, (v, lim) in compared.items())
                    + f"; over {verdict['positions']} positions of {len(picked)} requests; all readings {verdict}")
    say(args.verdict)

    trace = h.bench_reduce_trace.remote(not args.keep_trace).result(timeout_s=600) if args.trace else None
    stats = h.bench_counters.remote().result(timeout_s=60)
    device = stats["device"]
    serve.shutdown()
    ray_tpu.shutdown()
    common.check_device(device, cell["chips"], args.rehearse)

    if args.trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        ctx = {"cell": cell, "config": config, "model": model, "engine": engine, "counters": counters,
               "records": records, "window": (t0, t1), "mean_context": arith.mean(contexts or [0]), "trace": trace, "peaks": None if args.rehearse
               else common.peaks_for(device["kind"]), "e2e": e2e}
        metrics = common.read_layer_metrics(cell, ctx)
        breakdown = trace["breakdown"]
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]} for m in cell["end_to_end"]}
        breakdown = None
    args.result = (correct, attempted, len(bad), metrics, device, breakdown)
    return 0
