"""Tests: the unified telemetry plane.

Batched task-event pipeline (``_private/telemetry.py``), runtime metrics
exporter (``util/metrics.prometheus_text`` + ``runtime_metrics`` rpc), and
the cross-process chrome-trace timeline (``ray_tpu.timeline``). Parity:
``python/ray/tests/test_task_events*.py``, ``test_metrics_agent.py``,
``test_tracing.py``.
"""

import json
import re
import time

import pytest

import ray_tpu


# -- chrome-trace timeline ---------------------------------------------------


def test_timeline_chrome_trace_schema(ray_start_regular, tmp_path):
    """timeline(filename=) writes a valid chrome://tracing JSON array whose
    spans cover the full task lifecycle with stable tids."""

    @ray_tpu.remote
    def work(x):
        return x + 1

    assert ray_tpu.get([work.remote(i) for i in range(3)], timeout=60) == [1, 2, 3]

    out = tmp_path / "trace.json"
    events = ray_tpu.timeline(filename=str(out))
    on_disk = json.loads(out.read_text())
    assert on_disk == events and isinstance(on_disk, list)

    for e in events:
        # chrome trace event schema: required keys, numeric timestamps
        assert {"ph", "pid", "tid", "ts", "name", "args"} <= set(e)
        assert isinstance(e["ts"], (int, float))
        assert "state" in e["args"]

    states = {e["args"]["state"] for e in events}
    assert {"SUBMITTED", "QUEUED", "DISPATCHED", "RUNNING", "FINISHED"} <= states

    # lifecycle phase spans are "X" complete events with durations
    phases = [e for e in events if e.get("cat") == "TASK_PHASE"]
    assert any(e["name"].endswith(":run") for e in phases)
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in phases)

    # stable tid registry: every event of one task shares one tid, and tids
    # are small sequential ints (the seed's hash(task_id) % 1000 collided
    # and changed across runs)
    by_task = {}
    for e in events:
        tid_key = e["args"].get("task_id")
        if tid_key:
            by_task.setdefault(tid_key, set()).add(e["tid"])
    assert by_task and all(len(tids) == 1 for tids in by_task.values())
    all_tids = {next(iter(t)) for t in by_task.values()}
    assert all_tids <= set(range(1, len(by_task) + 2))


def test_timeline_worker_events_cross_process(ray_start_regular):
    """Worker-side RUNNING/FINISHED events carry real worker pids, so the
    run phases of concurrent tasks land on >= 2 distinct processes."""
    import os

    @ray_tpu.remote
    def hold():
        time.sleep(0.2)
        return os.getpid()

    pids = set(ray_tpu.get([hold.remote() for _ in range(4)], timeout=60))
    events = ray_tpu.timeline()
    run_pids = {
        e["pid"]
        for e in events
        if e.get("cat") == "TASK_PHASE" and e["args"]["state"] == "FINISHED"
    }
    assert len(run_pids & pids) >= min(2, len(pids))


def test_trace_parent_links_nested_task_actor(ray_start_regular):
    """Trace context propagates driver -> task -> actor method; the
    timeline's spans reconstruct one parent-linked tree across processes."""
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    try:

        @ray_tpu.remote
        class Leaf:
            def ping(self):
                return tracing.get_current_context().to_dict()

        @ray_tpu.remote
        def mid(leaf):
            ctx = tracing.get_current_context()
            inner = ray_tpu.get(leaf.ping.remote(), timeout=60)
            return ctx.to_dict(), inner

        leaf = Leaf.remote()
        root = tracing.start_span()
        outer, inner = ray_tpu.get(mid.remote(leaf), timeout=60)
        assert outer["trace_id"] == root.trace_id == inner["trace_id"]
        assert outer["parent_id"] == root.span_id
        assert inner["parent_id"] == outer["span_id"]

        events = ray_tpu.timeline()
        spans = [e for e in events if e.get("cat") == "PROFILE"]
        by_span = {
            e["args"]["span_id"]: e for e in spans if e["args"].get("span_id")
        }
        # the actor-method span links to the mid-task span, which executed
        # in a different process: a cross-process parent edge
        child = by_span[inner["span_id"]]
        parent = by_span[child["args"]["parent_id"]]
        assert parent["args"]["span_id"] == outer["span_id"]
        assert parent["pid"] != child["pid"]
        # chrome flow events bind the edge visually
        flow_ids = {e.get("id") for e in events if e.get("ph") in ("s", "f")}
        assert inner["span_id"] in flow_ids
    finally:
        tracing.reset_tracing()  # back to config-driven (default-on) tracing
        tracing.deactivate()


# -- prometheus exposition ---------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9.+-eE]+$"
)


def test_prometheus_text_parses(ray_start_regular):
    """Counter/gauge/histogram lines follow the exposition format and the
    runtime-internal series are present (>= 10 of them)."""
    from ray_tpu.util.metrics import Counter, Gauge, Histogram, prometheus_text

    Counter("tp_requests_total", tag_keys=("route",)).inc(3.0, tags={"route": "/x"})
    Gauge("tp_depth").set(4.0)
    h = Histogram("tp_latency_ms", boundaries=[1, 10])
    h.observe(0.5)
    h.observe(20.0)

    text = prometheus_text()
    lines = text.strip().splitlines()
    types = {}
    for line in lines:
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            types[name] = kind
        elif not line.startswith("#"):
            assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"

    assert types["tp_requests_total"] == "counter"
    assert types["tp_depth"] == "gauge"
    assert types["tp_latency_ms"] == "histogram"
    assert 'tp_requests_total{route="/x"} 3.0' in text
    assert "tp_latency_ms_count 2" in text
    assert 'tp_latency_ms_bucket{le="1"} 1' in text
    assert 'tp_latency_ms_bucket{le="+Inf"} 2' in text

    runtime = {n for n in types if n.startswith("ray_tpu_")}
    assert len(runtime) >= 10, sorted(runtime)
    assert "ray_tpu_scheduler_queue_depth" in runtime
    assert "ray_tpu_telemetry_dropped_total" in runtime
    assert "ray_tpu_object_store_bytes_used" in runtime


def test_metrics_merge_across_processes(ray_start_regular):
    """Counter increments from several worker processes SUM in the
    exposition (the seed's per-record KV flush was last-writer-wins)."""
    from ray_tpu.util.metrics import prometheus_text

    @ray_tpu.remote
    class Recorder:
        def bump(self):
            import os

            from ray_tpu.util.metrics import Counter

            Counter("tp_merge_total").inc(5.0)
            return os.getpid()

    recorders = [Recorder.remote() for _ in range(2)]
    pids = set(ray_tpu.get([r.bump.remote() for r in recorders], timeout=60))
    text = prometheus_text()
    line = next(l for l in text.splitlines() if l.startswith("tp_merge_total"))
    assert float(line.split()[-1]) == 5.0 * len(pids)


# -- batched flush -----------------------------------------------------------


def test_batched_metric_flush_interval_50ms():
    """Under metrics_report_interval_ms=50, N records coalesce into a few
    interval batches — one KV write per interval per metric, not one
    blocking RPC per record — and nothing is silently lost."""
    import ray_tpu as rt

    rt.init(num_cpus=2, _system_config={"metrics_report_interval_ms": 50},
            ignore_reinit_error=True)
    try:
        from ray_tpu._private import telemetry
        from ray_tpu.util.metrics import Counter, prometheus_text

        # the buffer is the process's: what earlier tests of this process
        # dropped (a runtime gone under their last events) is not this
        # test's loss, so both counts are taken over the test's own records
        dropped_before = telemetry.dropped_total()
        flushes_before = telemetry.get_buffer().flushes
        c = Counter("tp_bulk_total")
        n = 400
        for _ in range(n):
            c.inc()
        text = prometheus_text()  # forces the final flush: read-your-writes
        assert f"tp_bulk_total {float(n)}" in text
        stats = rt.get_runtime().rpc("event_stats")
        batches = stats.get("cmd.telemetry_batch", {}).get("count", 0)
        assert 0 < batches < n / 4, batches
        # a record marks its metric dirty and sends nothing: batches leave
        # this process by the interval (and the read's flush), not by the record
        assert 0 < telemetry.get_buffer().flushes - flushes_before < n / 4
        assert telemetry.dropped_total() == dropped_before
    finally:
        rt.shutdown()


def test_telemetry_disabled_drops_pipeline():
    """telemetry_enabled=False turns the event pipeline off end to end:
    no task events, no metric forwarding (the overhead-budget escape hatch
    measured by bench_core's telemetry row)."""
    import ray_tpu as rt

    rt.init(num_cpus=1, _system_config={"telemetry_enabled": False},
            ignore_reinit_error=True)
    try:

        @rt.remote
        def f():
            return 1

        assert rt.get(f.remote(), timeout=60) == 1
        assert rt.timeline() == []
    finally:
        rt.shutdown()


def test_telemetry_buffer_drop_accounting():
    """Overflow beyond capacity is counted, never silent."""
    from ray_tpu._private.telemetry import TelemetryBuffer

    buf = TelemetryBuffer(capacity=10)
    for i in range(25):
        buf.record_event({"i": i})
    assert buf.dropped_total == 15
    batch = buf._drain()
    assert len(batch["events"]) == 10
    assert batch["dropped"] == 15


# -- state API operators + limit pushdown ------------------------------------


def test_state_api_comparison_operators(ray_start_regular):
    from ray_tpu.util import state

    @ray_tpu.remote
    def g():
        return 1

    ray_tpu.get([g.remote() for _ in range(3)], timeout=60)
    rows = state.list_tasks(filters=[("retries_left", ">=", 0)])
    assert len(rows) >= 3
    assert state.list_tasks(filters=[("retries_left", "<", 0)]) == []
    assert state.list_tasks(filters=[("retries_left", ">", -1), ("state", "=", "FINISHED")])
    # non-numeric fields never match ordering filters
    assert state.list_tasks(filters=[("name", "<", 5)]) == []
    with pytest.raises(ValueError):
        state.list_tasks(filters=[("name", "~", "g")])


def test_state_api_limit_pushdown(ray_start_regular):
    from ray_tpu.util import state

    @ray_tpu.remote
    def h():
        return 1

    ray_tpu.get([h.remote() for _ in range(6)], timeout=60)
    assert len(state.list_tasks(limit=2)) == 2
    # the server truncates at the limit: the capped fetch is what filters see
    drv = ray_tpu.get_runtime()
    assert len(drv.rpc("list_tasks", 3)) == 3
