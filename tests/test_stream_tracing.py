"""A token's way out of the replica, stamped where the work happens: the
engine's ``llm_stream`` records (held by the loop, waiting for its stream's
thread, sent), the caller's ``serve_stream`` records (in transit, the gaps in
the caller's hands), the controller's ``serve_probe`` records, what
``loop_stats()`` and the two tools make of them, and that nothing of it exists
with telemetry off. Tiny sizes, CPU.
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import serve  # noqa: E402
from ray_tpu._private import looplog, telemetry  # noqa: E402
from ray_tpu._private.worker import get_runtime  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig, init_params  # noqa: E402
from ray_tpu.serve import api as serve_api  # noqa: E402
from ray_tpu.serve.llm import TINY_MODEL, llm_deployment  # noqa: E402
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine, TokenStream  # noqa: E402

CFG = TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=128, dtype=jnp.float32,
)
ECFG = EngineConfig(
    block_size=4, num_blocks=64, max_batch=3, max_blocks_per_seq=16, max_waiting=16,
    stream_timeout_s=60.0,
)
TOOLS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")
MS = 1_000_000


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _stream_records(eng) -> list:
    return [dict(zip(looplog.LLM_STREAM_FIELDS, r[1:])) for r in eng._ring.copy() if r[0] == "t"]


def _read(session_dir, prefix, kind) -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(session_dir, "loops", prefix + "*.jsonl"))):
        with open(path) as f:
            out.extend(r for r in map(json.loads, f) if r["kind"] == kind)
    return out


# -- the engine's side: held, wake, send ---------------------------------------


def test_a_driven_engines_stream_record_counts_every_token_and_joins_its_request(params, ray_start_regular):
    eng = InferenceEngine(params, CFG, ECFG, deployment="st-rec")
    try:
        t0 = time.time_ns()
        streams = [eng.submit([3 + i, 5, 7], max_new_tokens=5 + i) for i in range(4)]
        outs = [s.tokens() for s in streams]
        t1 = time.time_ns()
        time.sleep(0.1)
        stats = eng.loop_stats()
        recs = _stream_records(eng)
    finally:
        eng.shutdown()
    assert len(recs) == 4
    requests = {d["request"]: d for d in (dict(zip(stats["request_fields"], r)) for r in stats["requests"])}
    for s, out, rec in zip(streams, outs, sorted(recs, key=lambda r: r["request"])):
        assert rec["request"] == s.request_id and rec["tokens"] == len(out) == requests[rec["request"]]["tokens"]
        assert rec["held_n"] == rec["wake_n"] == rec["send_n"] == rec["tokens"]
        # on one clock and in order: taken after the request's first token was on the host, back after taken
        assert t0 <= requests[rec["request"]]["t_first"] <= rec["t_first_taken"] <= rec["t_last_back"] <= t1
        for seg in ("held", "wake", "send"):
            assert 0 <= rec[seg + "_max"] <= rec[seg + "_sum"] <= t1 - t0, (seg, rec)
    # the same, summed when read
    st = stats["stream"]
    assert st["streams"] == 4 and st["held"]["count"] == st["wake"]["count"] == st["send"]["count"] == sum(map(len, outs))
    assert st["wake"]["sum_ns"] == sum(r["wake_sum"] for r in recs) and st["send"]["max_ns"] == max(r["send_max"] for r in recs)


def test_a_tokens_stamps_are_in_order_from_the_result_to_the_consumers_return():
    got = []
    s = TokenStream(7, 5.0, record=got.append, trace_id="abc")
    t_result = time.time_ns()
    for tok in (11, 12, 13):
        s._emit(tok, t_result)
    s._finish("length")
    items = list(s._q.queue)
    assert [i[1] for i in items[:3]] == [11, 12, 13] and items[3] == ("done", "length")
    assert all(t_result <= t_put for _k, _tok, _t, t_put in items[:3])  # the loop's thread: result, then put
    assert [i[3] for i in items[:3]] == sorted(i[3] for i in items[:3])
    assert s.tokens() == [11, 12, 13] and s.finish_reason == "length"
    (rec,) = got
    d = dict(zip(looplog.LLM_STREAM_FIELDS, rec[1:]))
    assert rec[0] == "t" and d["request"] == 7 and d["trace_id"] == "abc" and d["tokens"] == 3
    assert items[0][3] <= d["t_first_taken"] <= d["t_last_back"] <= time.time_ns()  # put, then taken, then back
    assert d["held_sum"] == sum(i[3] - t_result for i in items[:3]) and d["held_max"] == items[2][3] - t_result


def test_a_consumer_that_sleeps_shows_in_send_and_in_the_next_tokens_wake_not_in_held(params, ray_start_regular):
    eng = InferenceEngine(params, CFG, ECFG, deployment="st-slow")
    try:
        eng.submit([2, 3], max_new_tokens=3).tokens()  # compiled: the steps below take milliseconds
        stream = eng.submit([5, 6, 7], max_new_tokens=6)
        out = []
        for tok in stream:
            out.append(tok)
            if len(out) == 1:
                time.sleep(0.4)  # the consumer's own time with the first token
        time.sleep(0.05)
        rec = _stream_records(eng)[-1]
    finally:
        eng.shutdown()
    assert rec["tokens"] == len(out) == 6
    assert rec["send_max"] >= 0.4e9  # the first token was out with the consumer that long
    assert rec["wake_max"] >= 0.2e9  # the second was in the queue meanwhile: put by the loop, not yet taken
    assert rec["held_max"] < 0.2e9  # and the loop handed every token on without waiting for the consumer


def test_an_abandoned_stream_leaves_one_record(params, ray_start_regular):
    eng = InferenceEngine(params, CFG, ECFG, deployment="st-gone")
    try:
        it = iter(eng.submit([5, 6, 7], max_new_tokens=8))
        assert isinstance(next(it), int) and isinstance(next(it), int)
        it.close()  # the consumer walks away at the second token's yield
        recs = _stream_records(eng)
    finally:
        eng.shutdown()
    (rec,) = recs
    assert rec["tokens"] == 2 and rec["send_n"] == 2 and rec["t_last_back"] >= rec["t_first_taken"] > 0


def test_a_failed_stream_leaves_one_record(params, ray_start_regular):
    eng = InferenceEngine(params, CFG, ECFG, deployment="st-fail")
    real = eng._prefill

    def failing_prefill(p, toks, *rest):
        raise RuntimeError("prefill blew up")

    eng._prefill = failing_prefill
    try:
        bad = eng.submit([9, 6], max_new_tokens=3)
        with pytest.raises(RuntimeError, match="prefill blew up"):
            bad.tokens()
        eng._prefill = real
        assert len(eng.submit([9, 6], max_new_tokens=3).tokens()) == 3
        recs = _stream_records(eng)
    finally:
        eng.shutdown()
    assert [(r["request"], r["tokens"]) for r in recs] == [(bad.request_id, 0), (bad.request_id + 1, 3)]
    assert recs[0]["t_first_taken"] == recs[0]["t_last_back"] == recs[0]["held_n"] == recs[0]["send_n"] == 0


def test_a_stream_that_times_out_leaves_one_record():
    got = []
    s = TokenStream(3, 0.05, record=got.append)
    s._emit(5, time.time_ns())
    with pytest.raises(TimeoutError, match="stalled"):
        s.tokens()
    (rec,) = got
    d = dict(zip(looplog.LLM_STREAM_FIELDS, rec[1:]))
    assert d["tokens"] == d["send_n"] == 1 and d["t_last_back"] >= d["t_first_taken"] > 0


# -- through serve: the caller's side, and both sides' counts -----------------


@pytest.fixture
def serve_cluster():
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    if ray_tpu.is_initialized():  # a test that reads the session's files has shut it down itself
        serve.shutdown()
    ray_tpu.shutdown()


def test_128_concurrent_streams_leave_128_records_on_each_side_and_the_tokens_add_up(serve_cluster):
    engine_cfg = dict(block_size=4, num_blocks=512, max_batch=8, max_blocks_per_seq=8, max_waiting=128)
    serve.run(llm_deployment(TINY_MODEL, engine_cfg, deployment_name="llm"), name="st128", route_prefix=None)
    h = serve.get_app_handle("st128")
    assert len(list(h.options(stream=True).generate.remote([3, 1, 4], max_new_tokens=2))) == 2  # compiled
    outs, errors = [None] * 128, []

    def one(i):
        try:
            outs[i] = list(h.options(stream=True).generate.remote([3, 1 + i % 7, 4], max_new_tokens=3 + i % 4))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(128)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=240)
    assert not errors and [len(o) for o in outs] == [3 + i % 4 for i in range(128)]
    served = 2 + sum(len(o) for o in outs)
    stats = h.loop_stats.remote().result(timeout_s=60)
    assert stats["stream"]["streams"] == 129 and stats["stream"]["wake"]["count"] == served
    session = serve_cluster.node.session_dir
    deadline = time.time() + 30  # the replica's batches go out once a flush interval
    while len(_read(session, "llm-llm-", "llm_stream")) < 129 and time.time() < deadline:
        time.sleep(0.2)
    serve.shutdown()
    ray_tpu.shutdown()
    engine, callers = _read(session, "llm-llm-", "llm_stream"), _read(session, "serve-", "serve_stream")
    assert len(engine) == len(callers) == 129
    assert sum(r["tokens"] for r in engine) == sum(r["items"] for r in callers) == served
    assert sum(r["tokens"] for r in _read(session, "llm-llm-", "llm_request")) == served
    assert {r["request"] for r in engine} == {r["request"] for r in _read(session, "llm-llm-", "llm_request")}
    for r in callers:
        # every item came straight from the replica with its sender's stamp, on this host's one clock
        assert r["transit_n"] == r["items"] and 0 <= r["transit_max"] <= r["transit_sum"]
        assert r["deployment"] == "llm" and r["method"] == "generate" and r["attempts"] == 0 and r["task"] and r["replica"]
        assert 0 <= r["gap_max"] <= r["t_last_got"] - r["t_first_got"]
    # written by the caller's own process, the driver; the controller's probes beside them under its pid
    assert os.path.exists(os.path.join(session, "loops", f"serve-llm-{os.getpid()}.jsonl"))
    probes = _read(session, "serve-", "serve_probe")
    assert probes and all(p["t_answered"] >= p["t_sent"] > 0 and p["budget_s"] == 10.0 for p in probes)
    assert {p["replica"] for p in probes} == {r["replica"] for r in callers}


def test_the_longest_gap_in_the_callers_hands_is_at_least_the_pause_the_replica_makes(serve_cluster):
    @serve.deployment
    class Pauses:
        def items(self, n, pause_s):
            for i in range(n):
                if i == 2:
                    time.sleep(pause_s)
                yield i

    serve.run(Pauses.bind(), name="stgap", route_prefix=None)
    h = serve.get_app_handle("stgap")
    assert list(h.options(stream=True).items.remote(5, 0.3)) == [0, 1, 2, 3, 4]
    (rec,) = (dict(zip(looplog.SERVE_STREAM_FIELDS, r[1:]))
              for recs in telemetry.get_buffer()._loops.values() for r in recs if r[0] == "g")
    assert rec["items"] == 5 and rec["deployment"] == "Pauses" and rec["method"] == "items"
    assert 0.3e9 <= rec["gap_max"] <= rec["t_last_got"] - rec["t_first_got"]
    assert rec["transit_n"] == 5 and rec["transit_max"] < 0.3e9  # the pause was the replica's, not the connection's


@ray_tpu.remote
class _Source:
    def items(self, n):
        yield from range(n)


def test_a_direct_stream_items_stamp_rides_its_message_and_stays_with_the_item(ray_start_regular):
    src = _Source.remote()
    before = time.time_ns()
    gen = src.items.options(num_returns="streaming").remote(3)
    refs = list(gen)
    assert ray_tpu.get(refs, timeout=60) == [0, 1, 2]
    sent = [get_runtime().stream_item_sent_ns(r.id()) for r in refs]
    assert before <= sent[0] <= sent[1] <= sent[2] <= time.time_ns()
    oid = refs[0].id()
    del refs, gen
    assert get_runtime().stream_item_sent_ns(oid) == 0  # gone with the item: nothing is kept beyond it


# -- the controller's probe ------------------------------------------------------


@ray_tpu.remote
class _Probed:
    def __init__(self, answer_after_s=0.0):
        self.answer_after_s = answer_after_s

    def check_health(self):
        time.sleep(self.answer_after_s)
        return True

    def num_ongoing(self):
        return 0

    def latency_samples(self):
        return []

    def ttft_samples(self):
        return []


def _controller(monkeypatch, replicas, budget_s=None):
    """The controller's own class, in this process, over replicas that are
    already there: ``_reconcile_once`` as it runs, with nothing to persist and
    no replica to start."""
    ctrl = object.__new__(serve_api.ServeController._cls)
    ctrl._lock = threading.Lock()
    ctrl._stop = False
    ctrl.apps = {"app": {"probed": {"spec": {"num_replicas": len(replicas), "health_check_period_s": 5.0},
                                     "replicas": list(replicas), "init_args": (), "init_kwargs": {}}}}
    if budget_s is not None:
        ctrl.PROBE_BUDGET_S = budget_s
    monkeypatch.setattr(ctrl, "_persist", lambda: None, raising=False)
    monkeypatch.setattr(ctrl, "_start_replicas", lambda *a, **kw: [], raising=False)
    return ctrl


def _probe_records() -> list:
    return [dict(zip(looplog.SERVE_PROBE_FIELDS, r[1:]))
            for stem, recs in telemetry.get_buffer()._loops.items() if stem == f"serve-probed-{os.getpid()}"
            for r in recs if r[0] == "p"]


def test_a_probing_pass_leaves_one_probe_record_a_replica(monkeypatch, ray_start_regular):
    telemetry.flush()
    replicas = [_Probed.remote(), _Probed.remote()]
    ray_tpu.get([r.check_health.remote() for r in replicas], timeout=60)
    ctrl = _controller(monkeypatch, replicas)
    before = time.time_ns()
    ctrl._reconcile_once()
    recs = _probe_records()
    assert [r["replica"] for r in recs] == [r._actor_id.hex() for r in replicas]
    assert all(before <= r["t_sent"] <= r["t_answered"] <= time.time_ns() for r in recs)
    assert all(r["budget_s"] == 10.0 and r["deployment"] == "probed" for r in recs)
    ctrl._reconcile_once()  # the period has not passed: no probe, no record
    assert len(_probe_records()) == 2
    assert len(ctrl.apps["app"]["probed"]["replicas"]) == 2


def test_a_health_check_that_outlasts_the_budget_reads_unanswered(monkeypatch, ray_start_regular):
    telemetry.flush()
    slow = _Probed.remote(3.0)
    ray_tpu.get(slow.num_ongoing.remote(), timeout=60)
    ctrl = _controller(monkeypatch, [slow], budget_s=0.5)
    ctrl._reconcile_once()
    (rec,) = _probe_records()
    assert rec["t_sent"] > 0 and rec["t_answered"] == 0 and rec["budget_s"] == 0.5
    assert ctrl.apps["app"]["probed"]["replicas"] == []  # and the probe's verdict is what it always was


# -- telemetry off: no stamp, no record ---------------------------------------------


def test_with_telemetry_off_the_queue_the_handle_and_the_controller_stamp_and_record_nothing(params, monkeypatch):
    rt = ray_tpu.init(num_cpus=4, _system_config={"telemetry_enabled": False}, ignore_reinit_error=True)
    try:
        buf = telemetry.get_buffer()
        before = sum(len(v) for v in buf._loops.values())
        # the engine: the queue's items carry zeros where the stamps would ride, and the plain iterator runs
        eng = InferenceEngine(params, CFG, ECFG, deployment="st-off")
        try:
            stream = eng.submit([5, 6, 7], max_new_tokens=4)
            deadline = time.time() + 60
            while stream._q.qsize() < 5 and time.time() < deadline:
                time.sleep(0.01)
            items = list(stream._q.queue)
            assert [i[0] for i in items] == ["tok"] * 4 + ["done"] and all(i[3] == 0 for i in items[:4])
            assert stream._record is None and iter(stream).gi_code is TokenStream._iter_plain.__code__
            assert len(stream.tokens()) == 4 and eng.loop_stats()["stream"]["streams"] == 0
        finally:
            eng.shutdown()
        # the runtime's streaming loop: the message's field rides as 0
        gen = _Source.remote().items.options(num_returns="streaming").remote(2)
        refs = list(gen)
        assert ray_tpu.get(refs, timeout=60) == [0, 1]
        assert [get_runtime().stream_item_sent_ns(r.id()) for r in refs] == [0, 0]
        # serve's handle and the controller
        serve.run(llm_deployment(TINY_MODEL, dict(block_size=4, num_blocks=64, max_batch=2, max_blocks_per_seq=8),
                                 deployment_name="llm"), name="stoff", route_prefix=None)
        h = serve.get_app_handle("stoff")
        assert len(list(h.options(stream=True).generate.remote([3, 1, 4], max_new_tokens=4))) == 4
        replica = _Probed.remote()
        ray_tpu.get(replica.num_ongoing.remote(), timeout=60)
        ctrl = _controller(monkeypatch, [replica])
        monkeypatch.setattr(serve_api.time, "time_ns", lambda: pytest.fail("a probe was stamped"))
        ctrl._reconcile_once()
        monkeypatch.undo()
        assert len(ctrl.apps["app"]["probed"]["replicas"]) == 1
        assert sum(len(v) for v in buf._loops.values()) == before
        serve.shutdown()
        assert not os.path.exists(os.path.join(rt.node.session_dir, "loops"))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -- the schema, and the two tools ---------------------------------------------------


@pytest.mark.parametrize("tag,kind,fields", [
    ("t", "llm_stream", looplog.LLM_STREAM_FIELDS),
    ("g", "serve_stream", looplog.SERVE_STREAM_FIELDS),
    ("p", "serve_probe", looplog.SERVE_PROBE_FIELDS),
])
def test_the_three_kinds_round_trip_through_the_one_schema(tag, kind, fields):
    values = [f"v{i}" if f in ("trace_id", "task", "deployment", "method", "replica") else i * 3
              for i, f in enumerate(fields)]
    line = looplog.encode((tag, *values))
    assert json.loads(line) == {"kind": kind, **dict(zip(fields, values))}
    assert len(set(fields)) == len(fields) and looplog.encode((tag,)) == json.dumps({"kind": kind})
    if kind == "llm_stream":
        assert fields[5:] == tuple(s + p for s in ("held", "wake", "send") for p in ("_n", "_sum", "_max"))


def test_loop_summary_tool_prints_a_stream_section_over_the_full_batch_time(tmp_path):
    fields = looplog.LLM_STEP_FIELDS

    def step(i, live):
        rec = dict.fromkeys(fields, 0)
        rec.update(step=i, t_loop=i * 20 * MS, t_result=i * 20 * MS + 15 * MS, live=live, ahead=1)
        return ("s", *(rec[k] for k in fields))

    def stream(req, back, n, held, wake, send):
        return ("t", req, None, n, back - 50 * MS, back, n, held * n * MS, 2 * held * MS,
                n, wake * n * MS, 3 * wake * MS, n, send * n * MS, 2 * send * MS)

    def served(last, n, transit, gap):
        return ("g", "ab" * 24, "x", "generate", "cd" * 16, n, last - 50 * MS, last, n, transit * n * MS,
                2 * transit * MS, gap * MS, 0)

    def probe(sent, trip):
        return ("p", sent, sent + trip * MS if trip else 0, 10.0, "x", "cd" * 16)

    # the batch is full (2 slots) from step 2 to step 11: 40 ms to 220 ms
    steps = [step(1, 1), *(step(i, 2) for i in range(2, 12)), step(12, 1)]
    log = looplog.LoopLog(str(tmp_path))
    log.ingest({
        "llm-x-1": [*steps, stream(0, 100 * MS, 4, 1, 2, 3), stream(1, 200 * MS, 5, 3, 4, 5),
                    stream(2, 400 * MS, 5, 100, 100, 100)],  # the last ended after the batch thinned
        "serve-x-2": [served(101 * MS, 4, 1, 30), served(201 * MS, 5, 2, 45), served(401 * MS, 5, 50, 17_300)],
        "serve-x-3": [probe(10 * MS, 900), probe(60 * MS, 8), probe(160 * MS, 12), probe(210 * MS, 0)],
    })
    log.close()
    out = subprocess.run([sys.executable, os.path.join(TOOLS, "loop_summary.py"), str(tmp_path / "loops"), "--skip-s", "0"],
                         capture_output=True, text=True, check=True).stdout
    st = json.loads(out)["stream"]
    assert st["streams"] == st["caller_streams"] == 2
    assert st["held_ms"] == {"count": 2, "mean_ms": 2.0, "median_ms": 2.0, "p90_ms": 3.0, "max_ms": 3.0}
    assert st["wake_ms"]["mean_ms"] == 3.0 and st["send_ms"]["max_ms"] == 5.0
    assert st["transit_ms"]["mean_ms"] == 1.5 and st["gap_max_ms"] == 45.0
    assert st["probe_ms"] == {"count": 2, "mean_ms": 10.0, "median_ms": 10.0, "p90_ms": 12.0, "max_ms": 12.0, "missed": 1}
    # a program that writes no such record: the section says so
    bare = tmp_path / "bare"
    log = looplog.LoopLog(str(bare))
    log.ingest({"llm-x-1": steps})
    log.close()
    out = subprocess.run([sys.executable, os.path.join(TOOLS, "loop_summary.py"), str(bare / "loops"), "--skip-s", "0"],
                         capture_output=True, text=True, check=True).stdout
    assert json.loads(out)["stream"] is None


def test_a_streams_stamps_lie_on_the_clock_of_a_kept_profiler_trace(params, tmp_path, ray_start_regular):
    """The stream records' stamps are ``time.time_ns()``, the clock of a
    profiler trace's events: a stream taken while a trace runs has its first
    token inside the traced window, and the tool that holds a trace against
    the loop records counts it there."""
    eng = InferenceEngine(params, CFG, ECFG, deployment="st-trace")
    try:
        eng.submit([2, 3], max_new_tokens=3).tokens()  # before the trace: not in its window
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            assert len(eng.submit([5, 6, 7], max_new_tokens=6).tokens()) == 6
            time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
        recs = _stream_records(eng)
    finally:
        eng.shutdown()
    sys.path.insert(0, TOOLS)
    try:
        import loop_trace_check
    finally:
        sys.path.remove(TOOLS)
    from jax.profiler import ProfileData

    t_base, t_end = loop_trace_check.trace_window_ns(ProfileData.from_file(loop_trace_check.newest_xplane(str(tmp_path / "trace"))))
    before, inside = recs
    assert before["t_last_back"] < t_base <= inside["t_first_taken"] <= inside["t_last_back"] <= t_end
    # and the tool, over the records as the head writes them
    log = looplog.LoopLog(str(tmp_path))
    log.ingest({"llm-st-trace-1": [r for r in eng._ring.copy() if r[0] in ("s", "t")]})
    log.close()
    proc = subprocess.run([sys.executable, os.path.join(TOOLS, "loop_trace_check.py"), "--trace", str(tmp_path / "trace"),
                           "--loops", str(tmp_path / "loops")], capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode in (0, 2), proc.stderr[-2000:]  # 2: an annotation a millisecond off, on a busy host
    assert json.loads(proc.stdout)["streams_in_trace"] == 1
