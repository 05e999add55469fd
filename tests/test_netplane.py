"""Transfer-plane observability tests (fast tier-1).

Covers: per-transfer stage decomposition (dial → request →
first_byte_wait → wire → seal sums against wall time), the config-driven
``wait_covered`` / ``wait_serves_drained`` deadlines with the typed
``ObjectTransferStalledError``, pipelined-relay fail propagation when the
source dies mid-serve, leaked-buffer accounting, same-host shm vs socket
content parity, the scheduler's link ledger + relay-hop tagging + trace
join on a real socket-plane broadcast, the slow-link and stalled-transfer
watchdogs (seeded positive + calm-silence), SLOW_LINK /
OBJECT_TRANSFER_STALLED queryability through the state API and the
``ray_tpu events --type`` CLI, and the ``ray_tpu net`` CLI surfaces.
"""

import json
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.ids import ObjectID, NodeID
from ray_tpu._private.object_store import ObjectStoreClient
from ray_tpu._private.object_transfer import (
    ObjectServer,
    _InflightRead,
    fetch_from_same_host,
    fetch_into_local_store,
    fetch_object_bytes,
)
from ray_tpu.exceptions import ObjectTransferStalledError
from ray_tpu.util import state

KEY = b"test-key"


def _sch():
    from ray_tpu._private.worker import get_runtime

    return get_runtime().node.scheduler


@pytest.fixture
def two_cpu():
    rt = ray_tpu.init(num_cpus=2)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def served_store(tmp_path):
    shm_dir = str(tmp_path / "shm")
    store = ObjectStoreClient(shm_dir, str(tmp_path / "fb"), 1 << 28)
    store.shm_dir = shm_dir  # the peer-read root (tests only)
    server = ObjectServer(store, "127.0.0.1", KEY)
    yield store, server
    server.close()
    store.close()


# ---------------------------------------------------------------------------
# stage decomposition
# ---------------------------------------------------------------------------


def test_fetch_stage_decomposition(served_store, tmp_path):
    """A socket fetch decomposes into dial/request/first_byte_wait/wire/
    seal; bytes and chunks are recorded and the stage sum approximates the
    wall (acceptance: within 10%, measured here against the driver wall)."""
    store, server = served_store
    dest = ObjectStoreClient(str(tmp_path / "shm2"), str(tmp_path / "fb2"), 1 << 28)
    oid = ObjectID.from_random()
    payload = bytes(range(256)) * (64 * 1024)  # 16 MiB: several chunks
    store.put_bytes(oid, payload)
    stats = {}
    t0 = time.perf_counter()
    ok = fetch_into_local_store(
        dest, server.address, oid, KEY, stats=stats
    )
    wall_ms = (time.perf_counter() - t0) * 1e3
    assert ok and bytes(dest.get(oid, timeout=5)) == payload
    assert stats["path"] == "socket"
    assert stats["bytes"] == len(payload)
    assert stats["chunks"] >= 2
    for k in ("dial_ms", "request_ms", "first_byte_wait_ms", "wire_ms",
              "seal_ms"):
        assert k in stats, f"missing stage {k}: {stats}"
    ssum = sum(stats[k] for k in ("dial_ms", "request_ms",
                                  "first_byte_wait_ms", "wire_ms", "seal_ms"))
    assert ssum <= wall_ms * 1.10
    assert ssum >= wall_ms * 0.5  # the stages cover the bulk of the wall
    dest.close()


def test_shm_peer_vs_socket_parity(served_store, tmp_path):
    """Same-host short-circuit and the socket plane must deliver identical
    bytes; the shm copy records a shm_peer stage record."""
    store, server = served_store
    oid = ObjectID.from_random()
    payload = np.arange(512 * 1024, dtype=np.int64).tobytes()  # 4 MiB
    store.put_bytes(oid, payload)

    via_socket = bytes(fetch_object_bytes(server.address, oid, KEY))

    dest = ObjectStoreClient(str(tmp_path / "shm3"), str(tmp_path / "fb3"), 1 << 28)
    stats = {}
    assert fetch_from_same_host(
        dest, store.shm_dir, oid, stats=stats
    ), "same-host short-circuit missed a sealed .obj copy"
    via_shm = bytes(dest.get(oid, timeout=5))
    assert via_shm == via_socket == payload
    assert stats["path"] == "shm_peer"
    assert stats["bytes"] == len(payload)
    assert "wire_ms" in stats and "seal_ms" in stats
    dest.close()


# ---------------------------------------------------------------------------
# typed stall error + drain/leak accounting (satellites 1 + 2)
# ---------------------------------------------------------------------------


def test_wait_covered_timeout_raises_typed_error():
    """A coverage TIMEOUT raises ObjectTransferStalledError with progress
    provenance instead of the old bare False; an upstream FAILURE still
    returns False (the downstream re-sources)."""
    buf = bytearray(100)
    tracker = _InflightRead(memoryview(buf), 100)
    tracker.mark(0, 40)
    with pytest.raises(ObjectTransferStalledError) as ei:
        tracker.wait_covered(40, 80, timeout=0.1)
    err = ei.value
    assert err.covered_bytes == 40
    assert err.total_bytes == 100
    assert err.waited_s >= 0.1
    # failure semantics unchanged: returns False, never raises
    tracker.fail()
    assert tracker.wait_covered(40, 80, timeout=0.1) is False


def test_wait_serves_drained_deadline_is_config_driven():
    buf = bytearray(10)
    tracker = _InflightRead(memoryview(buf), 10)
    tracker.serve_begin()
    t0 = time.monotonic()
    assert tracker.wait_serves_drained(timeout=0.2) is False
    assert time.monotonic() - t0 < 5.0
    tracker.serve_end()
    assert tracker.wait_serves_drained(timeout=0.2) is True


def test_relay_fail_propagation_mid_serve(served_store):
    """Pipelined relay: a downstream peer streaming off an IN-FLIGHT
    receive must fail promptly — not hang — when the upstream source dies
    mid-transfer (tracker.fail cascades through wait_covered)."""
    store, server = served_store
    oid = ObjectID.from_random()
    buf = bytearray(32 * 1024 * 1024)
    tracker = server.register_inflight(oid, memoryview(buf), len(buf))
    tracker.mark(0, 9 * 1024 * 1024)  # one served chunk lands...

    results = []

    def downstream():
        try:
            results.append(fetch_object_bytes(server.address, oid, KEY))
        except Exception as e:  # noqa: BLE001
            results.append(e)

    t = threading.Thread(target=downstream, daemon=True)
    t.start()
    time.sleep(0.3)  # downstream is now blocked on chunk 2's coverage
    tracker.fail()  # ...then the upstream dies mid-transfer
    server.unregister_inflight(oid)
    t.join(timeout=15)
    assert not t.is_alive(), "downstream fetch hung on a dead upstream"
    assert len(results) == 1 and isinstance(results[0], Exception), results


def test_leaked_buffer_accounting(two_cpu):
    """A drain-timeout leak (stats rode the fetch completion message)
    lands on the leaked-buffer counters and emits a WARNING cluster
    event — recycled-arena leakage is visible, not silent."""
    sch = _sch()
    head = sch._node.head_node_id
    oid = ObjectID.from_random()
    sch._fetching[(oid, head)] = (head, True)
    sch._xfer_complete(
        oid, head, False,
        stats={"path": "socket", "bytes": 1 << 20, "wire_ms": 5.0,
               "leaked_bytes": 1 << 20, "error": "relay serves did not drain"},
    )
    assert sch._xfer_leaked[0] == 1
    assert sch._xfer_leaked[1] == 1 << 20
    evs = state.list_cluster_events(
        filters=[("type", "=", "TRANSFER_BUFFER_LEAKED")]
    )
    assert evs and evs[-1]["leaked_bytes"] == 1 << 20
    summary = state.summarize_transfers(group_by="path")
    assert summary["leaked_buffers"] == 1
    assert summary["leaked_bytes"] == 1 << 20


# ---------------------------------------------------------------------------
# ledger + relay hops + trace join on a real socket-plane broadcast
# ---------------------------------------------------------------------------


def test_socket_broadcast_ledger_and_trace_join():
    """The flagship end-to-end check: a socket-plane broadcast (shm
    short-circuit off) fills the link ledger with socket + relay rows
    (hop-tagged), per-transfer stage sums stay within 10% of the recorded
    wall, per-source fanout admission is honored (peak load <= cap), and
    the consuming task's trace shows a wire child span with link + GiB/s."""
    import ray_tpu.cluster_utils as cu

    cluster = cu.Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        for _ in range(3):
            cluster.add_node(num_cpus=1, resources={"reader": 1.0}, wait=False)
        cluster.wait_for_nodes(timeout=300)
        sch = _sch()
        sch.config.same_host_shm_transfer = False

        @ray_tpu.remote(num_cpus=0, resources={"reader": 1.0})
        def read(x):
            from ray_tpu.util import tracing

            ctx = tracing.get_current_context()
            return int(x[0]) + x.nbytes, ctx.trace_id if ctx else None

        # a live worker on every reader node first: the three reads below then
        # start together, so the third finds both source slots taken and relays
        # (readers that spawn one after another on a loaded machine do not)
        ray_tpu.get([read.remote(np.zeros(1, np.int64)) for _ in range(3)], timeout=600)
        blob = ray_tpu.put(np.full(2 * 1024 * 1024, 7, dtype=np.int64))
        out = ray_tpu.get(
            [read.remote(blob) for _ in range(3)], timeout=600
        )
        assert [o[0] for o in out] == [7 + 16 * 1024 * 1024] * 3

        deadline = time.time() + 30
        while time.time() < deadline:
            links = state.list_links()
            if sum(r["transfers"] for r in links) >= 3 and not sch._fetching:
                break
            time.sleep(0.2)
        paths = {r["path"] for r in links}
        assert "socket" in paths, links
        assert "relay" in paths, links  # fanout=2, 3 dests => >= 1 relay hop
        assert all(r["bytes"] >= 16 * 1024 * 1024 for r in links)
        assert max(r["max_hop"] for r in links) >= 1
        # fanout admission: no source ever served more than the cap
        assert sch._xfer_load_peak <= sch.config.object_transfer_fanout

        xfers = state.list_transfers()
        assert len(xfers) >= 3
        for r in xfers:
            assert r["ok"], r
            assert r["stages_ms"], r
            if r.get("total_ms"):
                ssum = sum(r["stages_ms"].values())
                assert ssum <= r["total_ms"] * 1.10, r
        # per-path + per-job groupings see the broadcast
        by_path = state.summarize_transfers(group_by="path")
        assert {r["group"] for r in by_path["rows"]} >= {"socket", "relay"}
        by_task = state.summarize_transfers(group_by="task")
        assert by_task["rows"] and by_task["rows"][0]["group"] == "<put>"

        # trace join: the task's trace carries a wire child span naming the
        # link it crossed with a measured rate
        trace_id = out[0][1]
        assert trace_id
        deadline = time.time() + 15
        wire = []
        while time.time() < deadline and not wire:
            t = ray_tpu.trace(trace_id)
            wire = [
                s for s in t.spans.values()
                if s.name.startswith("wire:") and s.extra.get("link")
            ]
            if not wire:
                time.sleep(0.3)
        assert wire, "no link-labeled wire span joined the trace"
        assert wire[0].extra.get("gib_per_s") is not None
        assert "->" in wire[0].extra["link"]
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# watchdogs: slow link + stalled transfer (seeded positive, calm silence)
# ---------------------------------------------------------------------------


def _feed_link(sch, src, dst, gibps, n=4, nbytes=8 * 1024 * 1024):
    """Synthesize n completed socket transfers at a given rate."""
    wire_ms = nbytes / 2**30 / gibps * 1e3
    for _ in range(n):
        oid = ObjectID.from_random()
        sch._fetching[(oid, dst)] = (src, True)
        sch._xfer_complete(
            oid, dst, True,
            stats={"path": "socket", "bytes": nbytes, "wire_ms": wire_ms,
                   "total_ms": wire_ms, "t0": time.time()},
        )


def test_slow_link_watchdog_flags_only_throttled_link(two_cpu):
    sch = _sch()
    head = sch._node.head_node_id
    nodes = [NodeID.from_random() for _ in range(4)]
    # three healthy links and one ~20x slower (the seeded throttled pair)
    for dst in nodes[:3]:
        _feed_link(sch, head, dst, gibps=2.0)
    _feed_link(sch, nodes[0], nodes[3], gibps=0.1)
    sch._net_watchdog_scan()
    evs = state.list_cluster_events(filters=[("type", "=", "SLOW_LINK")])
    assert len(evs) == 1, evs
    slow_label = sch._node_label(nodes[3])
    assert evs[0]["link"].endswith(slow_label)
    assert evs[0]["exemplar_object_ids"]
    assert sch._slow_link_events == 1
    slow_rows = [r for r in state.list_links() if r.get("slow")]
    assert len(slow_rows) == 1 and slow_rows[0]["dst"] == slow_label
    # re-scan within the dedup window: no event flood
    sch._net_watchdog_scan()
    assert sch._slow_link_events == 1


def test_slow_link_watchdog_silent_on_uniform_links(two_cpu):
    sch = _sch()
    head = sch._node.head_node_id
    for dst in (NodeID.from_random() for _ in range(4)):
        _feed_link(sch, head, dst, gibps=1.0)
    sch._net_watchdog_scan()
    assert sch._slow_link_events == 0
    assert not state.list_cluster_events(filters=[("type", "=", "SLOW_LINK")])


def test_stalled_transfer_watchdog(two_cpu):
    """An in-flight fetch whose received-byte watermark stops moving past
    transfer_stall_warn_s gets an OBJECT_TRANSFER_STALLED event with
    progress provenance; progress resets the clock."""
    from ray_tpu._private import netplane

    sch = _sch()
    head = sch._node.head_node_id
    src = NodeID.from_random()
    oid = ObjectID.from_random()
    key = (oid, head)
    sch._fetching[key] = (src, True)
    sch._fetch_meta[key] = {
        "t0": time.time(), "t0_mono": time.monotonic(), "hop": 0,
        "trace": ("t" * 32, "s" * 16), "seen_bytes": -1,
        "seen_t": time.monotonic(),
    }
    netplane.begin_inflight(oid.hex(), 1 << 26)
    netplane.note_progress(oid.hex(), 1 << 20)
    try:
        sch._net_watchdog_scan()  # observes the watermark: arms, no event
        assert sch._xfer_stalled_total == 0
        # no progress since, and the watermark is old enough now
        sch._fetch_meta[key]["seen_t"] = time.monotonic() - 100.0
        sch._net_watchdog_scan()
        assert sch._xfer_stalled_total == 1
        evs = state.list_cluster_events(
            filters=[("type", "=", "OBJECT_TRANSFER_STALLED")]
        )
        assert evs, "stall event missing"
        ev = evs[-1]
        assert ev["object_id"] == oid.hex()
        assert ev["bytes_received"] == 1 << 20
        assert ev["total_bytes"] == 1 << 26
        assert ev["trace_id"] == "t" * 32
        # progress resumes -> the clock re-arms (no second event)
        netplane.note_progress(oid.hex(), 2 << 20)
        sch._net_watchdog_scan()
        assert sch._xfer_stalled_total == 1
    finally:
        netplane.end_inflight(oid.hex())
        sch._fetching.pop(key, None)
        sch._fetch_meta.pop(key, None)


# ---------------------------------------------------------------------------
# event queryability + CLI surfaces (satellites 4 + 6)
# ---------------------------------------------------------------------------


def test_new_event_types_queryable_like_pr4_set(two_cpu, capsys):
    """SLOW_LINK / OBJECT_TRANSFER_STALLED are queryable through
    state.list_cluster_events filters and `ray_tpu events --type`, exactly
    like the PR-4 event set."""
    sch = _sch()
    sch.record_cluster_event(
        "SLOW_LINK", "link a->b EWMA under fleet median",
        severity="WARNING", link="a->b",
    )
    sch.record_cluster_event(
        "OBJECT_TRANSFER_STALLED", "transfer of deadbeef stalled",
        severity="WARNING", link="a->b", object_id="deadbeef",
    )
    for etype in ("SLOW_LINK", "OBJECT_TRANSFER_STALLED"):
        rows = state.list_cluster_events(filters=[("type", "=", etype)])
        assert rows and all(r["type"] == etype for r in rows)

    from ray_tpu.scripts.cli import main

    main(["events", "--type", "SLOW_LINK", "--json"])
    rows = json.loads(capsys.readouterr().out)
    assert rows and all(r["type"] == "SLOW_LINK" for r in rows)


def test_net_cli_surfaces(two_cpu, capsys):
    sch = _sch()
    _feed_link(sch, sch._node.head_node_id, NodeID.from_random(), gibps=1.0)
    from ray_tpu.scripts.cli import main

    main(["net", "links", "--json"])
    links = json.loads(capsys.readouterr().out)
    assert links and links[0]["path"] == "socket"
    main(["net", "transfers", "--json"])
    xfers = json.loads(capsys.readouterr().out)
    assert xfers and xfers[0]["stages_ms"]["wire_ms"] > 0
    main(["net", "top", "--group-by", "path", "--json"])
    top = json.loads(capsys.readouterr().out)
    assert top["rows"][0]["group"] == "socket"
    # human-readable renderings don't crash either
    main(["net", "links"])
    assert "SRC" in capsys.readouterr().out
    main(["net", "top"])
    assert "transfers:" in capsys.readouterr().out


def test_dashboard_net_endpoint(two_cpu):
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    sch = _sch()
    _feed_link(sch, sch._node.head_node_id, NodeID.from_random(), gibps=1.0)
    port = start_dashboard(port=0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/net", timeout=10
        ) as resp:
            body = json.loads(resp.read())
        assert body["links"], body
        assert body["transfers"], body
        assert any(r["group"] == "socket" for r in body["summary"]["rows"])
    finally:
        stop_dashboard()


def test_metrics_expose_transfer_series(two_cpu):
    sch = _sch()
    _feed_link(sch, sch._node.head_node_id, NodeID.from_random(), gibps=1.0)
    series = {s["name"]: s for s in sch._runtime_metric_series()}
    for name in (
        "ray_tpu_transfer_path_gib_per_s",
        "ray_tpu_transfers_inflight",
        "ray_tpu_transfer_stage_seconds_total",
        "ray_tpu_link_bytes_total",
        "ray_tpu_link_throughput_gib_per_s",
        "ray_tpu_transfer_relay_hops_total",
        "ray_tpu_transfer_leaked_buffers_total",
        "ray_tpu_transfer_leaked_bytes_total",
        "ray_tpu_transfer_stalled_total",
        "ray_tpu_transfer_retries_total",
        "ray_tpu_slow_link_events_total",
    ):
        assert name in series, name
    link_bytes = series["ray_tpu_link_bytes_total"]["data"]
    assert sum(link_bytes.values()) >= 4 * 8 * 1024 * 1024
