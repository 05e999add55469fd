"""Training step-plane tests: per-step stage attribution ("where did the
step go"), recompile detection, ingest-stall attribution, the goodput
downtime ledger, live mid-run publication, and regression guards for the
PR-2 timeline / PR-11 trace / PR-13 memory planes riding the same ring."""

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.util import state

_STAGES = (
    "data_wait_ms",
    "host_to_device_ms",
    "compile_ms",
    "compute_ms",
    "collective_wait_ms",
    "checkpoint_stall_ms",
    "report_ms",
    "other_ms",
)


def _fit(loop, name, tmp_path, workers=1, config=None, **kw):
    trainer = JaxTrainer(
        loop,
        train_loop_config=config or {},
        scaling_config=ScalingConfig(num_workers=workers),
        run_config=RunConfig(storage_path=str(tmp_path), name=name, **kw),
        datasets=kw.pop("datasets", None) if "datasets" in kw else None,
    )
    return trainer.fit()


def test_step_stage_sum_within_10pct_2rank(ray_start_regular, tmp_path):
    """The acceptance bar: per-rank stage decomposition sums to within 10%
    of the measured step wall on a 2-rank run, with head-side
    collective_wait + straggler attribution."""

    def loop(config):
        ctx = train.get_context()
        for i in range(4):
            # rank 1 computes longer: rank 0 must show collective_wait
            time.sleep(0.03 + 0.04 * ctx.get_world_rank())
            train.report({"loss": float(i)})

    res = _fit(loop, "obs_sum", tmp_path, workers=2)
    assert res.error is None
    d = state.train_run("obs_sum")
    assert d is not None and d["world"] == 2
    assert d["steps_seen"] == 4
    checked = 0
    for srec in d["steps"]:
        assert set(srec["ranks"]) == {"0", "1"}
        for rec in srec["ranks"].values():
            wall = rec["wall_ms"]
            total = sum(rec["stages"].get(k, 0.0) for k in _STAGES)
            assert wall > 0
            assert abs(total - wall) <= 0.10 * wall, (rec["stages"], wall)
            checked += 1
    assert checked == 8
    # rank 1 is the straggler (its pre-report timestamp is latest); rank 0
    # waited for it in the step's collectives
    last = d["steps"][-1]["ranks"]
    skew = d["skew"][d["steps"][-1]["step"]]
    assert skew["straggler_rank"] == 1
    assert last["0"]["stages"]["collective_wait_ms"] > 10.0
    assert last["1"]["stages"]["collective_wait_ms"] == 0.0
    # run digest row surfaces the same run
    runs = state.list_train_runs()
    assert any(r["run"] == "obs_sum" and r["steps"] == 4 for r in runs)
    # timeline renders a per-rank waterfall with the straggler marked
    text = ray_tpu.train_timeline("obs_sum").summary()
    assert "step waterfall" in text and "straggler" in text


def test_ingest_stall_attribution_throttled_dataset(ray_start_regular, tmp_path):
    """A throttled dataset's batch waits land in data_wait, attributed to
    the bottleneck streaming-executor operator; device_put time lands in
    host_to_device."""

    def loop(config):
        it = train.get_dataset_shard("train")
        assert it is not None
        n = 0
        for batch in it.iter_jax_batches(batch_size=8, drop_last=False):
            train.report({"rows": int(next(iter(batch.values())).shape[0])})
            n += 1
        assert n > 0

    def slow(block):
        time.sleep(0.04)
        return block

    ds = ray_tpu.data.range(32).map_batches(slow)
    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path), name="obs_ingest"),
        datasets={"train": ds},
    )
    res = trainer.fit()
    assert res.error is None
    d = state.train_run("obs_ingest")
    assert d is not None and d["steps_seen"] >= 3
    totals = d["totals"]
    assert totals["data_wait_ms"] > 30.0, totals
    # per-operator stall attribution from the backpressure stats
    assert d["ops"], d
    assert sum(d["ops"].values()) > 10.0
    # the throttled map stage (or its source feed) is the named bottleneck
    assert any("map" in op or op == "source" for op in d["ops"])
    # host->device transfer was measured on the iter_jax_batches path
    assert totals["host_to_device_ms"] >= 0.0
    h2d_steps = [
        rec["stages"]["host_to_device_ms"]
        for s in d["steps"]
        for rec in s["ranks"].values()
    ]
    assert any(v > 0 for v in h2d_steps)
    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text()
    assert "ray_tpu_train_ingest_stall_seconds_total" in text
    assert "ray_tpu_train_data_wait_ratio" in text


def test_dataset_shard_is_per_rank_disjoint(ray_start_regular, tmp_path):
    """get_dataset_shard gives each rank a disjoint lazy shard of the
    trainer-attached dataset (round-robin over source blocks, stages
    preserved) — not the full dataset duplicated per rank."""

    def add_one(block):
        return {"id": [int(v) + 1000 for v in block["id"]]}

    def loop2(config):
        ctx = train.get_context()
        it = train.get_dataset_shard("train")
        seen = []
        for batch in it.iter_batches(batch_size=64):
            seen.extend(int(v) for v in batch["id"])
        with open(
            os.path.join(str(tmp_path), f"rank{ctx.get_world_rank()}.txt"), "w"
        ) as fh:
            fh.write(",".join(map(str, sorted(seen))))
        train.report({"n": len(seen)})

    trainer = JaxTrainer(
        loop2,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path), name="obs_shard"),
        datasets={
            "train": ray_tpu.data.range(64, num_blocks=8).map_batches(add_one)
        },
    )
    assert trainer.fit().error is None
    seen_by_rank = {}
    for r in (0, 1):
        with open(os.path.join(str(tmp_path), f"rank{r}.txt")) as fh:
            seen_by_rank[r] = set(
                int(x) for x in fh.read().split(",") if x
            )
    assert seen_by_rank[0] and seen_by_rank[1]
    assert not (seen_by_rank[0] & seen_by_rank[1]), "ranks saw shared rows"
    # stages applied on the sharded path (map ran: values offset by 1000)
    assert seen_by_rank[0] | seen_by_rank[1] == set(range(1000, 1064))


def _jit_loop(vary):
    def loop(config):
        import jax
        import numpy as np

        from ray_tpu._private import sampler, stepplane

        # the flusher's 1s probe may not have fired yet in this fresh
        # worker: install the jax.monitoring listener deterministically
        sampler.install_jax_hooks()
        f = jax.jit(lambda x: (x * 2.0).sum())
        for i in range(5):
            n = 8 + (i if vary else 0)
            x = np.ones((n,), dtype=np.float32)
            stepplane.note_batch_signature(f"x:float32[{n}]")
            float(f(x))
            train.report({"i": float(i)})

    return loop


def test_recompile_detector_flags_shape_change(ray_start_regular, tmp_path):
    res = _fit(_jit_loop(vary=True), "obs_recomp", tmp_path)
    assert res.error is None
    d = state.train_run("obs_recomp")
    warm = int(
        getattr(ray_tpu.init(ignore_reinit_error=True).config,
                "train_recompile_warmup_steps", 2)
    )
    flagged = [
        rec
        for s in d["steps"]
        for rec in s["ranks"].values()
        if rec["recompiled"]
    ]
    assert flagged, d["steps"]
    # every flag is post-warmup and carries the changed shape signature
    for rec in flagged:
        assert rec["step"] > warm
        assert rec["sig"] and "float32" in rec["sig"]
    assert d["recompiles"] == len(flagged)
    events = state.list_cluster_events(
        filters=[("type", "=", "TRAIN_RECOMPILE")]
    )
    assert events and events[-1].get("signature")
    # compile time was attributed to the flagged steps' compile stage
    assert any(rec["stages"]["compile_ms"] > 0 for rec in flagged)


def test_recompile_detector_silent_on_static_shapes(ray_start_regular, tmp_path):
    res = _fit(_jit_loop(vary=False), "obs_static", tmp_path)
    assert res.error is None
    d = state.train_run("obs_static")
    assert d["recompiles"] == 0
    assert not any(
        rec["recompiled"] for s in d["steps"] for rec in s["ranks"].values()
    )
    assert not state.list_cluster_events(
        filters=[("type", "=", "TRAIN_RECOMPILE")]
    )


def test_checkpoint_stall_stage(ray_start_regular, tmp_path):
    def loop(config):
        import tempfile

        for i in range(3):
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "w.bin"), "wb") as fh:
                fh.write(os.urandom(256 * 1024))
            train.report(
                {"i": float(i)}, checkpoint=Checkpoint.from_directory(d)
            )

    res = _fit(loop, "obs_ckpt", tmp_path)
    assert res.error is None
    d = state.train_run("obs_ckpt")
    stalls = [
        rec["stages"]["checkpoint_stall_ms"]
        for s in d["steps"]
        for rec in s["ranks"].values()
    ]
    assert any(v > 0 for v in stalls), stalls
    assert d["totals"]["checkpoint_stall_ms"] > 0


def test_downtime_ledger_under_seeded_kill(ray_start_regular, tmp_path):
    """One seeded kill: the in-run recovery window lands in the downtime
    ledger as cause=recovery and goodput reports the attributed gap."""
    marker = str(tmp_path / "killed_once")

    def loop(config):
        ctx = train.get_context()
        for i in range(6):
            time.sleep(0.05)
            train.report({"i": float(i)})
            if (
                i == 2
                and ctx.get_world_rank() == 1
                and not os.path.exists(marker)
            ):
                open(marker, "w").close()
                os._exit(1)  # seeded preemption of rank 1

    res = _fit(
        loop,
        "obs_chaos",
        tmp_path,
        workers=2,
        failure_config=FailureConfig(max_failures=2, retry_backoff_s=0.1),
    )
    assert res.error is None
    ledger = res.goodput["downtime_ledger"]
    causes = {e["cause"] for e in ledger}
    assert causes & {"recovery", "gang_restart"}, ledger
    attributed = sum(e["seconds"] for e in ledger)
    assert attributed > 0
    assert res.goodput["downtime_s"] == pytest.approx(
        sum(res.goodput["downtime_by_cause"].values()), rel=0.01
    )
    # the scheduler-side run record carries the same ledger + final status
    d = state.train_run("obs_chaos")
    meta = d["meta"]
    assert meta["status"] == "finished"
    assert meta["downtime_ledger"]
    from ray_tpu.util.metrics import prometheus_text

    assert "ray_tpu_train_downtime_seconds" in prometheus_text()


def test_goodput_published_live_mid_run(tmp_path):
    """Satellite: ray_tpu_train_goodput + run meta appear DURING the run on
    the publish cadence, not only at fit() teardown."""
    os.environ["RAY_TPU_TRAIN_GOODPUT_PUBLISH_INTERVAL_S"] = "0.2"
    try:
        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)

        def loop(config):
            for i in range(30):
                time.sleep(0.1)
                train.report({"i": float(i)})

        done = []

        def run():
            done.append(_fit(loop, "obs_live", tmp_path))

        t = threading.Thread(target=run)
        t.start()
        try:
            seen_running = False
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and not seen_running:
                rows = [
                    r
                    for r in state.list_train_runs()
                    if r["run"] == "obs_live"
                ]
                if rows and rows[0]["status"] == "running" and rows[0][
                    "goodput"
                ] is not None:
                    seen_running = True
                time.sleep(0.2)
            assert seen_running, "run meta never published mid-run"
            from ray_tpu.util.metrics import prometheus_text

            assert "ray_tpu_train_goodput" in prometheus_text()
        finally:
            t.join(timeout=60)
        assert done and done[0].error is None
    finally:
        os.environ.pop("RAY_TPU_TRAIN_GOODPUT_PUBLISH_INTERVAL_S", None)
        ray_tpu.shutdown()


def test_jax_compile_spans_join_trace(ray_start_regular):
    """Satellite: jax:* duration spans carry the executing (task, trace)
    instead of landing as global orphans — ray_tpu.trace(id) shows them
    inside the request's span tree."""

    @ray_tpu.remote
    def jit_task():
        import jax
        import numpy as np

        from ray_tpu._private import sampler

        sampler.install_jax_hooks()
        f = jax.jit(lambda x: (x * 3.0).sum())
        out = float(f(np.ones((16,), dtype=np.float32)))
        from ray_tpu.util import tracing

        return out, tracing.current_trace_id()

    out, trace_id = ray_tpu.get(jit_task.remote(), timeout=120)
    assert out == 48.0
    assert trace_id
    t = ray_tpu.trace(trace_id)
    jax_spans = [
        s for s in t.spans.values() if (s.name or "").startswith("jax:")
    ]
    assert jax_spans, [s.name for s in t.spans.values()]
    # parented inside the tree, not floating as roots
    assert any(s.parent_id for s in jax_spans)


def test_prior_planes_regression_guard(ray_start_regular, tmp_path):
    """PR-2 timeline, PR-11 traces, PR-13 memory plane keep working with
    the step plane riding the same telemetry ring."""

    def loop(config):
        for i in range(2):
            time.sleep(0.01)
            train.report({"i": float(i)})

    res = _fit(loop, "obs_guard", tmp_path)
    assert res.error is None
    # PR-2: chrome trace renders with task phase spans
    events = ray_tpu.timeline()
    assert any(e.get("cat") == "TASK_PHASE" for e in events)
    # PR-11: traces recorded; step records carry a joinable trace id
    assert ray_tpu.recent_traces()
    d = state.train_run("obs_guard")
    tids = [
        rec.get("trace_id")
        for s in d["steps"]
        for rec in s["ranks"].values()
    ]
    assert any(tids)
    t = ray_tpu.trace([x for x in tids if x][0])
    assert t.span_count() >= 1
    # PR-13: memory plane summaries still served
    summary = state.summarize_objects(group_by="callsite")
    assert "total_objects" in summary
    # step-plane series all exported with the documented names
    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text()
    for series in (
        "ray_tpu_train_step_seconds",
        "ray_tpu_train_step_wall_seconds",
        "ray_tpu_train_steps_total",
    ):
        assert series in text, series


def test_cli_train_runs_and_steps(ray_start_regular, tmp_path, capsys):
    def loop(config):
        for i in range(3):
            time.sleep(0.01)
            train.report({"i": float(i)})

    assert _fit(loop, "obs_cli", tmp_path).error is None
    import argparse

    from ray_tpu.scripts.cli import cmd_train

    base = dict(num_cpus=None, num_tpus=None, json=False, rank=None, limit=20)
    cmd_train(argparse.Namespace(train_cmd="runs", run=None, **base))
    out = capsys.readouterr().out
    assert "obs_cli" in out
    cmd_train(argparse.Namespace(train_cmd="steps", run="obs_cli", **base))
    out = capsys.readouterr().out
    assert "step waterfall" in out and "rank 0" in out
    cmd_train(argparse.Namespace(train_cmd="stalls", run="obs_cli", **base))
    out = capsys.readouterr().out
    assert "where did the step go" in out
