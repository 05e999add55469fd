"""The clock inside the two hot loops: the engine's loop records, request
spans and counters, the trainer's report stage and step records, the files
they leave under ``<session_dir>/loops/``, the bound metric handles they are
folded through, and the named scopes of the device programs. Tiny sizes, CPU.
"""

import dataclasses
import glob
import json
import os
import sys
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import train  # noqa: E402
from ray_tpu._private import looplog, telemetry  # noqa: E402
from ray_tpu._private.profiling import traced_section  # noqa: E402
from ray_tpu.models import generation as G  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig, init_params  # noqa: E402
from ray_tpu.serve.exceptions import DeploymentOverloadedError  # noqa: E402
from ray_tpu.serve.llm import engine as engine_mod  # noqa: E402
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.util import metrics  # noqa: E402

CFG = TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=128, dtype=jnp.float32,
)
ECFG = EngineConfig(
    block_size=4, num_blocks=64, max_batch=3, max_blocks_per_seq=16, max_waiting=16,
    stream_timeout_s=60.0,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _series(name, deployment):
    """This process's series of one metric for one deployment label."""
    with metrics._lock:
        return {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in metrics._local.get(name, {}).items() if f'"{deployment}"' in k}


def _read_loops(session_dir, prefix):
    out = []
    for path in sorted(glob.glob(os.path.join(session_dir, "loops", prefix + "*.jsonl"))):
        with open(path) as f:
            out.extend(json.loads(line) for line in f)
    return out


# -- the engine's loop records and counters ----------------------------------


def test_loop_records_are_monotone_bounded_and_agree_with_the_series(params, monkeypatch, ray_start_regular):
    monkeypatch.setattr(engine_mod, "LOOP_RING", 8)
    eng = InferenceEngine(params, CFG, ECFG, deployment="loop-rec")
    try:
        streams = [eng.submit([3 + i, 5, 7, 11][: 2 + i % 3], max_new_tokens=6 + i) for i in range(5)]
        outs = [s.tokens() for s in streams]
        assert [len(o) for o in outs] == [6, 7, 8, 9, 10]
        assert all(s.ttft_s is not None and s.ttft_s > 0 for s in streams)
        deadline = time.time() + 10
        while eng._has_active() and time.time() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)  # the loop folds its last iteration after the last token is out
        stats = eng.loop_stats(records=10_000)
    finally:
        eng.shutdown()
    fields = stats["fields"]
    recs = [dict(zip(fields, r)) for r in stats["records"]]
    # the ring is bounded: loop, request and stream records share it
    assert recs and len(recs) + len(stats["requests"]) + stats["stream"]["streams"] == 8
    assert eng.decode_steps > 8  # and had more to hold than it keeps
    assert eng.loop_stats(records=2)["records"] == stats["records"][-2:]
    for r in recs:
        stamps = [r[k] for k in ("t_loop", "t_admit_end", "t_result", "t_retire_end",
                                 "t_dispatch", "t_dispatch_end", "t_emit_end") if r[k]]
        assert stamps == sorted(stamps), r  # phases in order on one clock
        assert abs(r["t_loop"] / 1e9 - time.time()) < 600  # which is the wall clock, in ns
    assert [r["t_loop"] for r in recs] == sorted(r["t_loop"] for r in recs)
    assert [r["step"] for r in recs] == sorted(r["step"] for r in recs)
    # the series count what was done: a step a dispatch, the tokens that came out
    step = next(iter(_series("ray_tpu_llm_decode_step_ms", "loop-rec").values()))
    assert step["count"] == eng.decode_steps == recs[-1]["step"] and step["sum"] > 0
    assert sum(step["buckets"]) == step["count"]
    tokens = _series("ray_tpu_llm_tokens_total", "loop-rec")
    assert sum(v for k, v in tokens.items() if '"decode"' in k) == sum(len(o) for o in outs)
    assert sum(v for k, v in tokens.items() if '"prefill"' in k) == sum([2, 3, 4, 2, 3])
    assert _series("ray_tpu_llm_shed_total", "loop-rec") == {}


def test_live_sequences_over_records_are_the_decode_tokens_less_first_tokens(params, ray_start_regular):
    eng = InferenceEngine(params, CFG, ECFG, deployment="loop-live")
    try:
        outs = [s.tokens() for s in [eng.submit([2, 3, 4 + i], max_new_tokens=4 + 2 * i) for i in range(4)]]
        time.sleep(0.1)
        stats = eng.loop_stats(records=10_000)
    finally:
        eng.shutdown()
    live, prefills = (stats["fields"].index(k) for k in ("live", "prefills"))
    first_tokens = sum(r[prefills] for r in stats["records"])
    assert first_tokens == 4 == len(stats["requests"])
    tokens = _series("ray_tpu_llm_tokens_total", "loop-live")
    decode_tokens = sum(v for k, v in tokens.items() if '"decode"' in k)
    assert sum(r[live] for r in stats["records"]) == decode_tokens - first_tokens
    assert decode_tokens == sum(len(o) for o in outs)
    assert eng.decode_steps == sum(1 for r in stats["records"] if r[live])
    # the phases, summed from the records when read
    ph = stats["phases"]
    assert all(ph[k]["sum_ns"] >= ph[k]["max_ns"] > 0
               for k in ("queue_wait", "prefill", "prefill_stall", "device_wait", "dispatch_gap", "emit"))
    assert ph["queue_wait"]["count"] == ph["prefill"]["count"] == 4
    assert ph["device_wait"]["count"] == eng.decode_steps and ph["emit"]["count"] == len(stats["records"])
    # what the steps' attention read: a sequence's blocks are those of its
    # positions so far (prompt 3, block 4: one block until the step that writes
    # position 4), summed over the dispatched slots
    kv_blocks = stats["fields"].index("kv_blocks")
    assert all((r[kv_blocks] > 0) == (r[live] > 0) for r in stats["records"])
    assert all(r[live] <= r[kv_blocks] <= r[live] * ECFG.max_blocks_per_seq for r in stats["records"])
    kv = stats["kv_blocks"]
    assert kv["count"] == eng.decode_steps and kv["sum"] == sum(r[kv_blocks] for r in stats["records"])
    want = sum(-(-(3 + 1 + t) // ECFG.block_size) for i in range(4) for t in range(4 + 2 * i - 1))
    assert kv["sum"] == want and kv["max"] == max(r[kv_blocks] for r in stats["records"])
    reason = stats["request_fields"].index("reason")
    assert [r[reason] for r in stats["requests"]] == ["length"] * 4


# the step record as it stood before ``kv_neighbours``: the aggregate adds no field to it
STEP_FIELDS = ("step", "t_loop", "t_admit_end", "t_result", "t_retire_end", "t_dispatch", "t_dispatch_end", "t_emit_end",
               "live", "prefills", "fused", "kv_blocks", "ahead", "overrun")


@pytest.mark.parametrize("dispatches,want", [
    ([[1, 1, 1, 1]], {"count": 4, "sum": 3}),  # a full batch: every sequence but the first slot's behind a live one
    ([[1, 0, 1, 1]], {"count": 3, "sum": 1}),  # a hole in the middle: the sequence behind it starts its own first chunk
    ([[0, 0, 1, 0]], {"count": 1, "sum": 0}),  # one sequence
    ([[1, 1, 1, 1], [1, 0, 1, 1], [0, 0, 1, 0], [0, 1, 1, 0]], {"count": 10, "sum": 5}),  # summed over dispatches
], ids=["a_full_batch", "a_hole_in_the_middle", "one_sequence", "summed_over_dispatches"])
def test_kv_neighbours_counts_the_live_sequences_behind_a_live_slot(params, dispatches, want):
    """``loop_stats()["kv_neighbours"]`` over made-up dispatches: the slots
    filled by hand, the loop's thread not started, the decode program stood in
    for. Telemetry or not; and the step record keeps its fields."""
    from ray_tpu.serve.llm.kv_cache import BlockTable

    assert looplog.LLM_STEP_FIELDS == STEP_FIELDS and looplog.LLM_STEP_RING_FIELDS == STEP_FIELDS + ("ring_rows",)
    eng = InferenceEngine(params, CFG, dataclasses.replace(ECFG, max_batch=4), deployment="loop-neighbours", start=False)
    try:
        seen = []
        eng._decode_greedy = lambda _params, tokens, _positions, _tables, pool, active: (seen.append(list(active)) or tokens, pool)
        assert eng.loop_stats()["kv_neighbours"] == {"count": 0, "sum": 0}
        for slots in dispatches:
            eng._slots = [
                engine_mod._Running(engine_mod._Request(temperature=0.0, max_new_tokens=100, need_blocks=0),
                                    BlockTable(eng._alloc, 3), i) if live else None
                for i, live in enumerate(slots)]
            step = eng._dispatch_step([])
            assert [i for i, _run in step.rows] == [i for i, live in enumerate(slots) if live]
            for _i, run in step.rows:
                run.table.release()
        eng._flight.clear()
        eng._slots = [None] * 4
        assert seen == [[bool(x) for x in slots] for slots in dispatches]
        stats = eng.loop_stats()
        assert stats["kv_neighbours"] == want and eng.decode_steps == len(dispatches)
        assert stats["fields"] == STEP_FIELDS
    finally:
        eng.shutdown()


def test_ahead_and_overrun_are_recorded_and_the_series_sums_to_the_runs_retire_to_retire_time(params, ray_start_regular):
    eng = InferenceEngine(params, CFG, ECFG, deployment="loop-ahead")
    try:
        streams = [eng.submit([2, 3, 4 + i], max_new_tokens=10 + 3 * i) for i in range(3)]
        assert [len(s.tokens()) for s in streams] == [10, 13, 16]
        deadline = time.time() + 10
        while eng._has_active() and time.time() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)
        stats = eng.loop_stats(records=10_000)
    finally:
        eng.shutdown()
    assert stats["fields"] == looplog.LLM_STEP_FIELDS and stats["fields"][-2:] == ("ahead", "overrun")
    assert all(len(r) == len(STEP_FIELDS) for r in stats["records"])  # ``kv_neighbours`` rides no step record
    recs = [dict(zip(stats["fields"], r)) for r in stats["records"]]
    dispatching = [r for r in recs if r["live"]]
    retiring = [r for r in recs if r["t_result"]]
    assert len(dispatching) == len(retiring) == eng.decode_steps
    # from idle the first step goes out alone; every later one behind a step in flight
    assert [r["ahead"] for r in dispatching] == [0] + [1] * (len(dispatching) - 1)
    assert stats["ahead"] == {"count": len(dispatching), "sum": len(dispatching) - 1}
    assert stats["overrun"] == {"count": len(retiring), "sum": 0}  # every request ended by length
    # a prefill holds the loop for its enqueue only; the first token is read behind it, in device order
    reqs = [dict(zip(stats["request_fields"], r)) for r in stats["requests"]]
    assert all(r["t_admit"] < r["t_first"] <= r["t_finish"] for r in reqs)
    # the series: each retired step observes the time since the retire before
    # it (the first: since the top of its own dispatch), so one unbroken run
    # sums to the time from its first dispatch to its last retire
    step = next(iter(_series("ray_tpu_llm_decode_step_ms", "loop-ahead").values()))
    assert step["count"] == eng.decode_steps
    run_ms = (retiring[-1]["t_retire_end"] - dispatching[0]["t_dispatch"]) / 1e6
    assert step["sum"] == pytest.approx(run_ms, rel=0.02, abs=0.5)


# -- request spans, under the caller's span -----------------------------------


def test_every_ended_request_leaves_spans_under_the_callers_span(params, ray_start_regular):
    """Finished, failed and shed: each leaves the spans of the phases it
    reached, with the caller's trace id and the caller's span as parent;
    ``ray_tpu.trace`` shows them; the head wrote the records to disk."""
    small = EngineConfig(block_size=4, num_blocks=8, max_batch=2, max_blocks_per_seq=8, max_waiting=4)
    eng = InferenceEngine(params, CFG, small, deployment="loop-span")
    real_prefill = eng._prefill

    def failing_prefill(p, toks, *rest):
        if int(toks[0, 0]) == 96:  # the request marked to fail
            raise RuntimeError("prefill blew up")
        return real_prefill(p, toks, *rest)

    eng._prefill = failing_prefill
    try:
        with traced_section("serve:replica:stand-in") as _:
            from ray_tpu.util import tracing

            ctx = tracing.get_current_context()
            ok = eng.submit([5, 6, 7], max_new_tokens=5)
            assert len(ok.tokens()) == 5
            bad = eng.submit([96, 6], max_new_tokens=3)
            with pytest.raises(RuntimeError, match="prefill blew up"):
                bad.tokens()
            with pytest.raises(DeploymentOverloadedError):
                eng.submit([1] * 20, max_new_tokens=12)  # 8 blocks of 7 usable
        time.sleep(0.1)
        stats = eng.loop_stats()
        reason = stats["request_fields"].index("reason")
        assert [r[reason] for r in stats["requests"]] == ["length", "error", "shed_blocks"]
        assert sum(_series("ray_tpu_llm_shed_total", "loop-span").values()) == 1
    finally:
        eng.shutdown()
    t = None
    for _ in range(20):
        t = ray_tpu.trace(ctx.trace_id)
        if sum(1 for s in t.spans.values() if (s.name or "").startswith("llm.")) >= 6:
            break
        time.sleep(0.2)
    llm = [s for s in t.spans.values() if (s.name or "").startswith("llm.")]
    assert all(s.parent_id == ctx.span_id and s.trace_id == ctx.trace_id for s in llm)
    by_request = {}
    for s in llm:
        by_request.setdefault(s.extra["request"], {})[s.name] = s
    done, failed, shed = by_request[ok.request_id], by_request[bad.request_id], by_request[-1]
    assert set(done) == {"llm.queue_wait", "llm.prefill", "llm.decode"}
    assert done["llm.decode"].extra["finish_reason"] == "length"
    assert done["llm.decode"].extra["tokens"] == 5 and done["llm.decode"].extra["steps"] == 4
    assert done["llm.prefill"].extra["bucket"] == 8 and done["llm.prefill"].extra["prompt_len"] == 3
    assert done["llm.queue_wait"].end <= done["llm.prefill"].start + 1e-6
    assert done["llm.prefill"].end <= done["llm.decode"].start + 1e-6
    # the stream's TTFT is taken from the same stamps as the spans
    assert ok.ttft_s == pytest.approx(done["llm.prefill"].end - done["llm.queue_wait"].start, abs=1e-5)
    assert set(failed) == {"llm.queue_wait", "llm.prefill"}
    assert failed["llm.prefill"].extra["finish_reason"] == "error"
    assert set(shed) == {"llm.queue_wait"} and shed["llm.queue_wait"].extra["finish_reason"] == "shed_blocks"
    # the replica stand-in's span is their parent in the tree
    parent = t.spans[ctx.span_id]
    assert {c.span_id for c in parent.children} >= {s.span_id for s in llm}

    session_dir = ray_start_regular.node.session_dir
    recs = _read_loops(session_dir, f"llm-loop-span-{os.getpid()}")
    reqs = {r["request"]: r for r in recs if r["kind"] == "llm_request"}
    assert reqs[ok.request_id]["reason"] == "length" and reqs[ok.request_id]["trace_id"] == ctx.trace_id
    assert reqs[bad.request_id]["reason"] == "error" and reqs[-1]["reason"] == "shed_blocks"
    assert 0 < reqs[ok.request_id]["t_submit"] <= reqs[ok.request_id]["t_admit"] <= reqs[ok.request_id]["t_first"]
    steps = [r for r in recs if r["kind"] == "llm_step"]
    assert steps and set(steps[0]) == {"kind", *looplog.LLM_STEP_FIELDS}
    # the slots' neighbours, cumulative, in records of their own: one sequence a step here, so nobody behind a live slot
    behind = [r for r in recs if r["kind"] == "llm_kv_neighbours"]
    assert behind and set(behind[0]) == {"kind", *looplog.LLM_NEIGHBOUR_FIELDS}
    assert behind[-1]["count"] == behind[-1]["step"] == steps[-1]["step"] and behind[-1]["sum"] == 0
    assert looplog.last_dir == os.path.join(session_dir, "loops")


def test_a_request_through_llm_deployment_shows_its_phases_under_the_replicas_span():
    from ray_tpu import serve
    from ray_tpu.serve.llm import TINY_MODEL, llm_deployment

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        engine_cfg = dict(block_size=4, num_blocks=128, max_batch=3, max_blocks_per_seq=16, max_waiting=16)
        serve.run(llm_deployment(TINY_MODEL, engine_cfg, deployment_name="llm"), name="loopapp", route_prefix=None)
        h = serve.get_app_handle("loopapp")
        assert len(list(h.options(stream=True).generate.remote([3, 1, 4, 1, 5], max_new_tokens=6))) == 6
        # the replica's own view of its loop, beside kv_stats
        stats = h.loop_stats.remote().result(timeout_s=60)
        assert stats["deployment"] == "llm" and stats["phases"]["device_wait"]["count"] >= 5
        assert [dict(zip(stats["request_fields"], r))["tokens"] for r in stats["requests"]] == [6]
        found = None
        deadline = time.time() + 30
        while found is None and time.time() < deadline:
            for digest in ray_tpu.recent_traces(limit=30):
                t = ray_tpu.trace(digest["trace_id"])
                names = {s.name for s in t.spans.values()}
                if {"llm.queue_wait", "llm.prefill", "llm.decode"} <= names:
                    found = t
                    break
            else:
                time.sleep(0.3)
        assert found is not None, "no trace of the request shows the engine's phases"
        replica = next(s for s in found.spans.values() if (s.name or "").startswith("serve:replica:llm.generate"))
        phases = [s for s in found.spans.values() if (s.name or "").startswith("llm.")]
        assert len(phases) == 3 and all(s.parent_id == replica.span_id for s in phases)
        assert "llm.decode" in found.summary()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_with_telemetry_off_nothing_is_recorded_or_written(params):
    rt = ray_tpu.init(num_cpus=1, _system_config={"telemetry_enabled": False}, ignore_reinit_error=True)
    try:
        from ray_tpu._private import stepplane

        buf = telemetry.get_buffer()
        before = (len(buf._spans), sum(len(v) for v in buf._loops.values()))
        eng = InferenceEngine(params, CFG, ECFG, deployment="loop-off")
        try:
            with traced_section("serve:replica:stand-in"):
                assert len(eng.submit([5, 6, 7], max_new_tokens=4).tokens()) == 4
            time.sleep(0.1)
            stats = eng.loop_stats()
        finally:
            eng.shutdown()
        # the engine served, and recorded nothing of its loop ...
        assert eng.decode_steps >= 3 and stats["records"] == [] and stats["requests"] == []
        assert all(p["count"] == 0 for p in stats["phases"].values())
        # ... and nothing left the engine: no span, no loop record, no step timer, no file
        assert (len(buf._spans), sum(len(v) for v in buf._loops.values())) == before
        assert stepplane.make_timer("off", 0, 1) is None
        assert not os.path.exists(os.path.join(rt.node.session_dir, "loops"))
    finally:
        ray_tpu.shutdown()


# -- bound metric handles ------------------------------------------------------


def test_bound_handles_and_the_unbound_api_give_the_same_text(ray_start_regular):
    def drive(prefix, bound: bool):
        c = metrics.Counter(f"{prefix}_total", "c", tag_keys=("who",))
        g = metrics.Gauge(f"{prefix}_level", "g", tag_keys=("who",))
        h = metrics.Histogram(f"{prefix}_ms", "h", boundaries=[1, 10, 100], tag_keys=("who",))
        tags = {"who": "x"}
        if bound:
            cb, gb, hb = c.bind(tags), g.bind(tags), h.bind(tags)
            for v in (0.5, 1, 7, 250):
                cb.inc()
                cb.inc(2.5)
                gb.set(v)
                hb.observe(v)
            hb.observe_many([3, 30])
        else:
            for v in (0.5, 1, 7, 250):
                c.inc(tags=tags)
                c.inc(2.5, tags=tags)
                g.set(v, tags=tags)
                h.observe(v, tags=tags)
            h.observe_many([3, 30], tags=tags)

    drive("lt_bound", True)
    drive("lt_plain", False)
    text = metrics.prometheus_text()
    bound = sorted(line.replace("lt_bound", "X") for line in text.splitlines() if "lt_bound" in line)
    plain = sorted(line.replace("lt_plain", "X") for line in text.splitlines() if "lt_plain" in line)
    assert bound == plain and len(bound) == 3 * 2 + 1 + 1 + 6
    assert 'X_total{who="x"} 14.0' in bound
    assert 'X_ms_bucket{who="x",le="1"} 2' in bound and 'X_ms_bucket{who="x",le="+Inf"} 6' in bound
    assert 'X_ms_sum{who="x"} 291.5' in bound


def test_a_bound_update_calls_neither_json_nor_telemetry(ray_start_regular):
    c = metrics.Counter("lt_hot_total", "c", tag_keys=("who",)).bind({"who": "x"})
    g = metrics.Gauge("lt_hot_level", "g", tag_keys=("who",)).bind({"who": "x"})
    h = metrics.Histogram("lt_hot_ms", "h", tag_keys=("who",)).bind({"who": "x"})
    h.observe(1.0)  # the entry exists: the steady state is what a loop pays
    called = []

    def profiler(frame, event, arg):
        if event == "call":
            called.append(frame.f_code.co_filename)
        elif event == "c_call":
            called.append(getattr(arg, "__module__", None) or "")

    sys.setprofile(profiler)
    try:
        for i in range(50):
            c.inc()
            g.set(float(i))
            h.observe(float(i))
    finally:
        sys.setprofile(None)
    assert called  # the updates were seen
    offenders = [f for f in called if "json" in f or "telemetry" in f]
    assert offenders == [], sorted(set(offenders))
    # one snapshot of each dirty metric per flush, however many records landed
    snap = telemetry._dirty_metrics()
    assert {"lt_hot_total", "lt_hot_level", "lt_hot_ms"} <= set(snap)
    assert snap["lt_hot_total"][2] == {'{"who": "x"}': 50.0}
    assert not {"lt_hot_total", "lt_hot_level", "lt_hot_ms"} & set(telemetry._dirty_metrics())


# -- the trainer's report stage and step records --------------------------------


def test_step_records_time_the_report_and_reach_the_loop_and_the_disk(ray_start_regular, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.util import state

    def loop(config=None):
        ctx = train.get_context()
        seen = []
        for i in range(5):
            time.sleep(0.02)
            last = ctx.get_last_step()
            seen.append(None if last is None else (last["step"], last["stages"]["report_ms"], last["wall_ms"]))
            train.report({"i": i, "seen": list(seen)})

    res = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path), name="loop_steps"),
    ).fit()
    assert res.error is None
    seen = res.metrics["seen"]
    # the loop is handed each closed step as it goes: none before the first report returns
    assert seen[0] is None and [s[0] for s in seen[1:]] == [1, 2, 3, 4]
    assert all(s[1] > 0 and s[2] >= 20 for s in seen[1:])
    d = state.train_run("loop_steps")
    for srec in d["steps"]:
        rec = srec["ranks"]["0"]
        st = rec["stages"]
        assert st["report_ms"] > 0
        assert sum(st.values()) == pytest.approx(rec["wall_ms"], rel=0.1)
        # the step's bounds on the profiler's clock
        assert rec["t2_ns"] - rec["t0_ns"] == pytest.approx(rec["wall_ms"] * 1e6, rel=0.05)
        assert abs(rec["t2_ns"] / 1e9 - rec["t2"]) < 1e-3
    ray_tpu.timeline()  # a cluster-wide flush: the session's last record rides telemetry
    lines = _read_loops(ray_start_regular.node.session_dir, "train-loop_steps-rank0")
    assert sorted(r["step"] for r in lines) == [1, 2, 3, 4, 5]
    assert all(r["kind"] == "train_step" and r["stages"]["report_ms"] > 0 for r in lines)


# -- named scopes in the device programs -----------------------------------------


def _scoped(text: str, scope: str) -> bool:
    """Whether some operation's location in the lowered module lies under
    ``scope`` (``block/mlp``; a transformed scope reads ``jvp(head)``)."""
    import re

    return re.search(r'loc\("(?:[^"]*[/(])?' + re.escape(scope) + r'[/)"]', text) is not None


def test_the_lowered_train_step_and_decode_step_carry_the_scopes(params):
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.spmd import build_lm_train_step

    mesh = create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    bundle = build_lm_train_step(CFG, mesh)
    state = jax.eval_shape(lambda: bundle.init_fn(jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    step = bundle.step_fn.lower(state, tok, tok).as_text(debug_info=True)
    for scope in ("block/attn", "block/mlp", "head", "loss", "optimizer"):
        assert _scoped(step, scope), scope
    # the backward pass keeps them: its recomputed block is still the block
    assert _scoped(step, "rematted_computation/block/mlp") or "transpose(jvp(block))" in step

    _prefill, _decode, greedy = G.make_paged_fns(CFG, block_size=4)
    pool = G.init_paged_pool(CFG, 16, 4)
    b = 3
    decode = greedy.lower(
        params, jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32), jnp.zeros((b, 8), jnp.int32),
        pool, jnp.ones((b,), bool),
    ).as_text(debug_info=True)
    for scope in ("block/paged_scatter", "block/paged_gather", "block/paged_attn", "block/mlp", "head"):
        assert _scoped(decode, scope), scope


# -- where the records land ---------------------------------------------------------


def test_sessions_land_under_the_process_temporary_directory(monkeypatch, tmp_path):
    """Two checkouts run with a ``TMPDIR`` each keep their sessions, and the
    loop records in them, apart; the flag still overrides."""
    import tempfile

    from ray_tpu._private.config import Config

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert Config().session_dir_root == os.path.join(str(tmp_path), "ray_tpu_sessions")
    assert Config.from_env().session_dir_root == os.path.join(str(tmp_path), "ray_tpu_sessions")
    monkeypatch.setenv("RAY_TPU_SESSION_DIR_ROOT", str(tmp_path / "elsewhere"))
    assert Config.from_env().session_dir_root == str(tmp_path / "elsewhere")
    log = looplog.LoopLog(os.path.join(Config.from_env().session_dir_root, "session_x"))
    monkeypatch.setattr(looplog, "last_dir", None)
    log.ingest({"llm-a-1": [("s", *range(len(looplog.LLM_STEP_FIELDS))), ("bogus",)]})
    log.close()
    assert looplog.last_dir == str(tmp_path / "elsewhere" / "session_x" / "loops")
    (line,) = open(os.path.join(looplog.last_dir, "llm-a-1.jsonl")).read().splitlines()
    assert json.loads(line) == {"kind": "llm_step", **dict(zip(looplog.LLM_STEP_FIELDS, range(len(looplog.LLM_STEP_FIELDS))))}


def _step_20ms(i, live, **kw):
    """An ``llm_step`` record as the head ingests it: iteration ``i`` at 20 ms an iteration, its result 15 ms in."""
    ms = 1_000_000
    rec = dict.fromkeys(looplog.LLM_STEP_FIELDS, 0)
    rec.update(step=i, t_loop=i * 20 * ms, t_result=i * 20 * ms + 15 * ms, live=live, **kw)
    return ("s", *(rec[k] for k in looplog.LLM_STEP_FIELDS))


def test_loop_summary_tool_reads_the_share_ahead_and_a_newcomers_wait(tmp_path):
    """``tools/loop_summary.py`` over records as the head writes them: the
    time the batch was full, how often the loop ran ahead, what a newcomer
    waited for its first token, the windows the expert layers walked a layer
    a step, the pairs their grouped calls visited a touched expert. A record older than ``ahead`` reads 0."""
    import subprocess

    ms = 1_000_000
    fields = looplog.LLM_STEP_FIELDS
    steps = [_step_20ms(1, 1), *(_step_20ms(i, 2, ahead=1) for i in range(2, 12)), _step_20ms(12, 1, ahead=1)]
    old = steps[5][: 1 + fields.index("ahead")]  # as a program before the two fields wrote it
    req = ("r", 7, 30 * ms, 60 * ms, 95 * ms, 200 * ms, 5, 8, 4, 3, "length", None)
    log = looplog.LoopLog(str(tmp_path))
    # cumulative counts at steps 3 and 9 of a model of 2 expert layers: 13 windows over 12 layer-steps, 42 pairs
    # visited for 40 experts touched
    moe = [("m", t * 20 * ms, t, *({"windows": w, "touched": touched, "pairs": pairs, "layers": 2}.get(k, 0)
                                   for k in looplog.LLM_MOE_FIELDS[2:]))
           for t, w, touched, pairs in ((3, 6, 10, 10), (9, 19, 50, 52))]
    # cumulative at steps 2 and 11: 18 live sequences dispatched between them, 9 of them behind a live slot
    behind = [("n", t * 20 * ms, t, count, held) for t, count, held in ((2, 3, 1), (11, 21, 10))]
    log.ingest({"llm-x-1": [*steps[:5], old, *steps[6:], req, *moe, *behind]})
    log.close()
    tool = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools", "loop_summary.py")
    out = subprocess.run([sys.executable, tool, str(tmp_path / "loops"), "--skip-s", "0"],
                         capture_output=True, text=True, check=True).stdout
    got = json.loads(out)
    assert got["slots"] == 2 and got["steps"] == 10 and got["overrun"] == 0
    assert got["ahead_share"] == pytest.approx(0.9)  # nine of the ten say so, the old record nothing
    assert got["result_to_result_ms"] == pytest.approx(20.0)
    assert got["first_token_ms"] == {"count": 1, "mean_ms": 35.0, "median_ms": 35.0, "p90_ms": 35.0, "max_ms": 35.0}
    assert got["queue_wait_ms"]["mean_ms"] == 30.0
    assert got["windows_per_layer_step"] == pytest.approx(13 / 12)
    assert got["pairs_per_touched"] == pytest.approx(42 / 40)
    assert got["kv_neighbour_share"] == pytest.approx(9 / 18) and "latent" not in got


@pytest.mark.parametrize("prompt, steps, share, chunks", [
    (5, 3, 3 * 256 / (6 + 7 + 8), 1.0),  # one prefix of 256 rows scored at every step
    (250, 8, (6 * 256 + 2 * 512) / sum(range(251, 259)), 1.0),  # the answer crosses into the second prefix
    (1020, 6, (4 * 1024 + 2 * 1280) / sum(range(1021, 1027)), (4 * 1 + 2 * 2) / 6),  # and into the second chunk
])
def test_loop_summary_tool_says_what_the_latent_kernel_scored(tmp_path, prompt, steps, share, chunks):
    """``--latent``: rows scored over rows live and chunks a sequence a call,
    by arithmetic over the finished requests' prompt and decode steps, at the
    kernel's own prefix and chunk."""
    import subprocess

    from ray_tpu.ops import paged_attention

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    try:
        import loop_summary
    finally:
        sys.path.pop(0)
    assert loop_summary.LATENT_PREFIX_ROWS == paged_attention._LATENT_PREFIX
    assert loop_summary.LATENT_CHUNK_ROWS * 640 * 2 == paged_attention._LATENT_CHUNK_BYTES  # a bfloat16 row stored 640 wide

    ms = 1_000_000
    req = ("r", 7, 30 * ms, 60 * ms, 95 * ms, 200 * ms, prompt, 8, steps + 1, steps, "length", None)
    late = ("r", 8, 30 * ms, 60 * ms, 95 * ms, 900 * ms, 9, 16, 2, 1, "length", None)  # finished after the batch was full
    log = looplog.LoopLog(str(tmp_path))
    log.ingest({"llm-x-1": [_step_20ms(1, 1), *(_step_20ms(i, 2) for i in range(2, 12)), _step_20ms(12, 1), req, late]})
    log.close()
    out = subprocess.run([sys.executable, os.path.join(root, "tools", "loop_summary.py"), str(tmp_path / "loops"),
                          "--skip-s", "0", "--latent"], capture_output=True, text=True, check=True).stdout
    got = json.loads(out)["latent"]
    assert got["requests"] == 1
    assert got["rows_scored_share"] == pytest.approx(share) and got["chunks_a_sequence"] == pytest.approx(chunks)


# -- how a loop came to run: the start's stamps and the compile records -----------


SERVER_ENGINE = dict(block_size=4, num_blocks=64, max_batch=3, max_blocks_per_seq=16, max_waiting=16)
STAGES = {"trace", "lower", "compile", "cache_load"}


def _server(name):
    from ray_tpu.serve.llm.deployment import TINY_MODEL, LLMServer

    return LLMServer(TINY_MODEL, SERVER_ENGINE, deployment=name)


def _compile_records(srv):
    return [dict(zip(looplog.COMPILE_FIELDS, r[1:])) for r in srv._engine._compiles.copy()]


def test_a_server_leaves_one_start_record_with_monotone_stamps(ray_start_regular):
    before = time.time_ns()
    srv = _server("start-rec")
    try:
        assert len(srv([3, 1, 4], 3)) == 3  # the loop's thread has run by now
        start = srv.loop_stats()["start"]
    finally:
        srv._engine.shutdown()
    stamps = [start[k] for k in looplog.LLM_START_FIELDS[:6]]
    assert looplog.LLM_START_FIELDS[:6] == ("t_init", "t_backend", "t_params", "t_placed", "t_pool", "t_ready")
    assert before <= stamps[0] and stamps == sorted(stamps) and stamps[-1] <= time.time_ns()
    assert start["placed"] == 0  # on the CPU nothing is re-laid
    assert start["pool_bytes"] == SERVER_ENGINE["num_blocks"] * srv._engine._bytes_per_block > 0
    ray_tpu.timeline()  # a cluster-wide flush
    recs = _read_loops(ray_start_regular.node.session_dir, f"llm-start-rec-{os.getpid()}")
    (on_disk,) = [r for r in recs if r["kind"] == "llm_start"]
    assert on_disk == {"kind": "llm_start", **{k: start[k] for k in looplog.LLM_START_FIELDS}}
    # the weights' jit ran before the engine existed, on the constructor's thread: kept, and written under its stem
    init = [r for r in recs if r["kind"] == "compile" and r["where"] == "init"]
    assert init and all(start["t_init"] <= r["t"] <= start["t_ready"] and r["step"] == 0 for r in init)
    assert any(r["stage"] == "compile" and r["t"] <= start["t_params"] for r in init)


def test_the_first_request_leaves_compile_records_that_name_its_programs(ray_start_regular):
    srv = _server("start-compile")
    try:
        assert len(srv([3, 1, 4], 3)) == 3
        recs = _compile_records(srv)
        start = srv.loop_stats()["start"]
    finally:
        srv._engine.shutdown()
    assert recs and all(set(r) == set(looplog.COMPILE_FIELDS) and r["stage"] in STAGES and r["seconds"] >= 0 for r in recs)
    loop = [r for r in recs if r["where"] == "loop"]
    for program in ("prefill", "decode_step_greedy"):
        # traced under its own name, lowered and compiled as jax's ``jit(name)``
        assert {r["stage"] for r in loop if r["program"] in (program, f"jit({program})")} == {"trace", "lower", "compile"}
    assert all(r["step"] == 0 for r in loop)  # before the first decode step went out
    assert {r["where"] for r in recs} == {"init", "loop"}
    # a jnp function traced inside a program is part of the program's seconds, not a record
    assert not any(r["program"] in ("_where", "_einsum", "multiply") for r in recs)
    by_stage = {stage: sum(r["seconds"] for r in recs if r["stage"] == stage) for stage in STAGES}
    assert {s: sum(by.values()) for s, by in start["compile_s"].items()} == pytest.approx(by_stage)
    # the operator's series counts the same seconds, by stage
    series = _series("ray_tpu_llm_compile_seconds_total", "start-compile")
    assert sum(series.values()) == pytest.approx(sum(by_stage.values()))
    assert any('"compile"' in k for k in series)
    assert "ray_tpu_llm_compile_seconds_total{" in metrics.prometheus_text()
    ray_tpu.timeline()
    on_disk = [r for r in _read_loops(ray_start_regular.node.session_dir, f"llm-start-compile-{os.getpid()}")
               if r["kind"] == "compile"]
    assert [{k: r[k] for k in looplog.COMPILE_FIELDS} for r in on_disk] == recs


def test_a_warmed_bucket_compiles_nothing_and_a_new_one_says_at_which_step(ray_start_regular):
    srv = _server("start-bucket")
    try:
        assert len(srv([3, 1, 4], 4)) == 4
        warmed = len(_compile_records(srv))
        assert len(srv([2, 7, 1], 4)) == 4  # the same bucket, the same decode program
        assert len(_compile_records(srv)) == warmed
        steps = srv._engine.decode_steps
        assert len(srv(list(range(1, 20)), 4)) == 4  # 19 tokens: the bucket of 32, not yet met
        late = _compile_records(srv)[warmed:]
    finally:
        srv._engine.shutdown()
    # what ``compiles_in_window`` counts: a program compiled while the replica serves
    assert {r["stage"] for r in late} >= {"trace", "lower", "compile"}
    assert all(r["step"] == steps > 0 and r["where"] == "loop" and "prefill" in r["program"] for r in late)


def test_a_trainers_compile_records_lie_in_the_step_that_counted_them(ray_start_regular, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config=None):
        import jax
        import jax.numpy as jnp

        for i in range(5):
            # a program a step: each is traced, lowered and compiled inside its step
            jax.jit(lambda x, i=i: (x * (i + 2.0)).sum())(jnp.ones(8)).block_until_ready()
            train.report({"i": i})

    res = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path), name="loop_compiles"),
    ).fit()
    assert res.error is None
    ray_tpu.timeline()
    lines = _read_loops(ray_start_regular.node.session_dir, "train-loop_compiles-rank0")
    steps = sorted((r for r in lines if r["kind"] == "train_step"), key=lambda r: r["step"])
    compiles = [r for r in lines if r["kind"] == "compile"]
    assert len(steps) == 5 and compiles and all(set(r) == {"kind", *looplog.COMPILE_FIELDS} for r in compiles)
    assert all(r["stage"] in STAGES and r["where"] == "loop" for r in compiles)
    # the listener is in from the second step at the latest (the timer probes at every report)
    assert {r["step"] for r in compiles} >= {1, 2, 3, 4}
    for rec in compiles:
        (step,) = [s for s in steps if s["t0_ns"] <= rec["t"] < s["t2_ns"]]
        assert step["step"] == rec["step"] + 1  # steps done when it landed: the step it landed in is the next to close
    for s in steps:
        mine = [r for r in compiles if s["t0_ns"] <= r["t"] < s["t2_ns"]]
        assert sum(r["seconds"] for r in mine) <= s["stages"]["compile_ms"] / 1e3 + 1e-6
        if s["step"] >= 2:
            assert {r["stage"] for r in mine if "<lambda>" in r["program"]} == {"trace", "lower", "compile"}


def test_with_telemetry_off_a_start_leaves_stamps_and_no_record():
    rt = ray_tpu.init(num_cpus=1, _system_config={"telemetry_enabled": False}, ignore_reinit_error=True)
    try:
        buf = telemetry.get_buffer()
        before = sum(len(v) for v in buf._loops.values())
        srv = _server("start-off")
        try:
            assert len(srv([5, 6, 7], 4)) == 4
            start = srv.loop_stats()["start"]
        finally:
            srv._engine.shutdown()
        assert srv._engine.decode_steps >= 3 and _compile_records(srv) == []
        assert all(by == {} for by in start["compile_s"].values())
        stamps = [start[k] for k in looplog.LLM_START_FIELDS[:6]]
        assert stamps[0] > 0 and stamps == sorted(stamps)  # plain stores either way
        assert sum(len(v) for v in buf._loops.values()) == before
        assert not os.path.exists(os.path.join(rt.node.session_dir, "loops"))
    finally:
        ray_tpu.shutdown()


def test_loop_summary_tool_prints_where_a_start_went(tmp_path):
    import subprocess

    s = 1_000_000_000
    log = looplog.LoopLog(str(tmp_path))
    start = ("b", 10 * s, 12 * s, 17 * s, 18 * s, 18 * s + s // 2, 18 * s + s // 2 + 1000, 3, 4096)
    compiles = [
        ("c", 13 * s, 0.5, "trace", "<lambda>", 0, "init"), ("c", 14 * s, 1.0, "lower", "jit(<lambda>)", 0, "init"),
        ("c", 16 * s, 0.25, "cache_load", None, 0, "init"), ("c", 16 * s, 2.0, "compile", "jit(<lambda>)", 0, "init"),
        ("c", 19 * s, 0.75, "trace", "prefill", 0, "loop"), ("c", 20 * s, 0.5, "lower", "jit(prefill)", 0, "loop"),
        ("c", 21 * s, 1.5, "compile", "jit(prefill)", 0, "loop"),
        ("c", 40 * s, 0.125, "compile", "jit(prefill)", 9, "loop"),  # after the batch was full: a bucket not warmed
    ]
    fields = looplog.LLM_STEP_FIELDS
    steps = []
    for i, live in ((1, 1), (2, 2), (3, 2)):
        rec = dict.fromkeys(fields, 0)
        rec.update(step=i, t_loop=(21 + i) * s, t_result=(21 + i) * s + 1000, live=live)
        steps.append(("s", *(rec[k] for k in fields)))
    log.ingest({"llm-x-1": [*compiles[:4], start, *compiles[4:7], *steps, compiles[7]]})
    log.close()
    tool = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools", "loop_summary.py")
    out = subprocess.run([sys.executable, tool, str(tmp_path / "loops"), "--skip-s", "0"],
                         capture_output=True, text=True, check=True).stdout
    got = json.loads(out)["start"]
    assert got["phases_s"] == {"init_to_backend": 2.0, "backend_to_params": 5.0, "params_to_placed": 1.0,
                               "placed_to_pool": 0.5, "pool_to_ready": 1e-6}
    assert got["placed"] == 3 and got["pool_bytes"] == 4096 and got["events"] == 8
    assert got["seconds_by_stage"] == {"trace": 1.25, "lower": 1.5, "cache_load": 0.25, "compile": 3.625}
    assert got["lowering_s"] == {"<lambda>": 1.5, "prefill": 1.25} and list(got["lowering_s"]) == ["<lambda>", "prefill"]
    assert got["compile_s"] == {"<lambda>": 2.0, "prefill": 1.625}
    assert got["compiles_after_full"] == ["jit(prefill)"]
    # a run of a program older than the two kinds: no such object
    old = looplog.LoopLog(str(tmp_path / "old"))
    old.ingest({"llm-x-1": steps})
    old.close()
    out = subprocess.run([sys.executable, tool, str(tmp_path / "old" / "loops"), "--skip-s", "0"],
                         capture_output=True, text=True, check=True).stdout
    assert json.loads(out)["start"] is None


def test_a_requests_compile_span_names_its_program(ray_start_regular):
    """``ray_tpu.trace`` shows *which* program a traced caller's compile was:
    the ``jax:*`` span under the caller's span carries jax's ``fun_name``."""
    from ray_tpu._private import sampler
    from ray_tpu.util import tracing

    assert sampler.install_jax_hooks()
    with traced_section("serve:replica:stand-in"):
        ctx = tracing.get_current_context()
        jax.jit(lambda x: x * 3.0 + 1, inline=False)(jnp.ones(5)).block_until_ready()
    named = []
    for _ in range(20):
        t = ray_tpu.trace(ctx.trace_id)
        named = [s for s in t.spans.values() if (s.name or "").startswith("jax:") and "program" in (s.extra or {})]
        if {s.name.rsplit(".", 1)[-1] for s in named} >= {"jaxpr_trace_duration", "backend_compile_duration"}:
            break
        time.sleep(0.2)
    by_event = {s.name.rsplit(".", 1)[-1]: s for s in named}
    assert by_event["jaxpr_trace_duration"].extra["program"] == "<lambda>"
    assert by_event["backend_compile_duration"].extra["program"] == "jit(<lambda>)"
    assert all(s.parent_id == ctx.span_id for s in named)
