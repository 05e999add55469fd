"""Transfer-plane observability: per-transfer stage records + link health.

Answers "where did the *wire* go" the way the tracing plane (PR 11) answers
"where did the *time* go", the memory plane (PR 13) "where did the *bytes*
go", and the step plane (PR 14) "where did the *step* go". The cross-node
socket plane is the slowest path in the system (BENCH_SCALE broadcast:
0.33 GiB/s socket vs 28.8 GiB/s shm) and was, until this plane, one opaque
number per fetch. Parity: the reference's per-chunk PushManager /
ObjectBufferPool accounting (``push_manager.h:30``,
``object_buffer_pool.h:41``).

Capture follows the memory plane's ride-existing-messages rule — no new
RPCs on the transfer path:

* **fetch stage records** — ``fetch_via_src_info`` fills a stats dict
  (dial → request → first_byte_wait → wire (bytes, chunks) → seal) that
  rides the fetch's EXISTING completion message (``object_fetched`` /
  ``fetch_done``), where the scheduler — which already knows (src, dst,
  hop) from ``_fetching`` — folds it into the link ledger;
* **in-flight progress** — :func:`begin_inflight` /
  :func:`note_progress` keep a per-process registry of receiving
  transfers; node daemons attach a snapshot to their EXISTING 1 Hz
  heartbeat, the head reads its own registry directly, and the
  scheduler's watchdog turns "bytes stopped moving" into
  ``OBJECT_TRANSFER_STALLED`` events;
* **worker-side read records** — zero-copy peer-arena reads and
  spill-restores (no completion message exists for these) ride the
  telemetry batch ring (``TelemetryBuffer.record_transfer``), gated by a
  size floor so small-object gets stay unrecorded;
* **wire trace spans** — a worker blocked in arg-fetch records a
  ``wire:<path>`` PROFILE span under its task's active trace context, and
  passes that context with its ``ensure_local`` rpc so the scheduler can
  emit the transfer's wire span as a child of the task's ``arg_fetch``
  (the way PR 14 adopted ``jax:*`` spans into the trace tree).

Scheduler-side consumers: the bounded link ledger (``_net_links``), the
1 Hz slow-link / stalled-transfer watchdog, ``state.list_links`` /
``state.summarize_transfers``, the ``ray_tpu net`` CLI, and the dashboard
network tab (see ``Scheduler._net_watchdog_scan``).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

# transfer paths (ledger key vocabulary)
PATH_SOCKET = "socket"
PATH_SHM_PEER = "shm_peer"
PATH_SPILL = "spill"
PATH_RELAY = "relay"

# stage keys every record may carry (ms; presentation order)
STAGE_KEYS = ("dial_ms", "request_ms", "first_byte_wait_ms", "wire_ms",
              "seal_ms")

_DEFAULT_COVERAGE_TIMEOUT_S = 120.0
_DEFAULT_DRAIN_TIMEOUT_S = 60.0

# module-level override for processes with no connected runtime (node
# daemons): raylet calls configure(config) after its registration reply
_cfg_override: Optional[dict] = None

# (runtime identity, verdict) — memoized like memplane: this check sits on
# read hot paths
_enabled_cache: tuple = (None, False)


def configure(config) -> None:
    """Install the resolved cluster config in a runtime-less process (node
    daemons). Driver/worker processes resolve through the connected
    runtime instead."""
    global _cfg_override, _enabled_cache
    _cfg_override = {
        "enabled": bool(getattr(config, "transfer_plane_enabled", True))
        and bool(getattr(config, "telemetry_enabled", True)),
        "coverage_timeout_s": float(
            getattr(config, "transfer_coverage_timeout_s",
                    _DEFAULT_COVERAGE_TIMEOUT_S)
        ),
        "drain_timeout_s": float(
            getattr(config, "transfer_drain_timeout_s",
                    _DEFAULT_DRAIN_TIMEOUT_S)
        ),
        "min_record_bytes": int(
            getattr(config, "net_min_record_bytes", 256 * 1024)
        ),
    }
    _enabled_cache = (None, False)


def _runtime_cfg():
    from ray_tpu._private import telemetry

    rt = telemetry._runtime()
    return getattr(rt, "config", None) if rt is not None else None


def enabled() -> bool:
    """Transfer plane on? Daemons read the configure() override; connected
    processes the runtime config (memoized per runtime — read hot path)."""
    if _cfg_override is not None:
        return _cfg_override["enabled"]
    from ray_tpu._private import telemetry

    rt = telemetry._runtime()
    if rt is None:
        return False
    global _enabled_cache
    cached_rt, verdict = _enabled_cache
    if cached_rt is rt:
        return verdict
    cfg = getattr(rt, "config", None)
    verdict = bool(getattr(cfg, "telemetry_enabled", True)) and bool(
        getattr(cfg, "transfer_plane_enabled", True)
    )
    _enabled_cache = (rt, verdict)
    return verdict


def coverage_timeout_s() -> float:
    """``_InflightRead.wait_covered`` deadline (config-driven; was 120s
    hardcoded)."""
    if _cfg_override is not None:
        return _cfg_override["coverage_timeout_s"]
    cfg = _runtime_cfg()
    return float(
        getattr(cfg, "transfer_coverage_timeout_s",
                _DEFAULT_COVERAGE_TIMEOUT_S)
    )


def drain_timeout_s() -> float:
    """``_InflightRead.wait_serves_drained`` deadline (was 60s
    hardcoded)."""
    if _cfg_override is not None:
        return _cfg_override["drain_timeout_s"]
    cfg = _runtime_cfg()
    return float(
        getattr(cfg, "transfer_drain_timeout_s", _DEFAULT_DRAIN_TIMEOUT_S)
    )


def min_record_bytes() -> int:
    if _cfg_override is not None:
        return _cfg_override["min_record_bytes"]
    cfg = _runtime_cfg()
    return int(getattr(cfg, "net_min_record_bytes", 256 * 1024))


# --------------------------------------------------------------------------
# in-flight receive registry (stall-watchdog input)
# --------------------------------------------------------------------------

# oid hex -> {"bytes", "total", "t0", "last_progress"} (monotonic stamps are
# process-local: consumers compare BYTES across observations, never clocks)
_inflight: Dict[str, dict] = {}
_inflight_lock = threading.Lock()


def begin_inflight(oid_hex: str, total: int) -> None:
    with _inflight_lock:
        _inflight[oid_hex] = {
            "bytes": 0,
            "total": int(total),
            "t0": time.time(),
            "last_progress": time.monotonic(),
        }


def note_progress(oid_hex: str, nbytes: int) -> None:
    """Cumulative received-byte watermark for one in-flight receive. Called
    from the chunk recv loop — one dict update per chunk, no locks beyond
    the registry's (progress callbacks already serialize per stripe)."""
    ent = _inflight.get(oid_hex)
    if ent is not None:
        ent["bytes"] = max(ent["bytes"], int(nbytes))
        ent["last_progress"] = time.monotonic()


def end_inflight(oid_hex: str) -> None:
    with _inflight_lock:
        _inflight.pop(oid_hex, None)


def inflight_snapshot() -> Dict[str, dict]:
    """{oid hex: {"bytes", "total", "age_s"}} — rides node heartbeats; the
    head scheduler reads this registry directly for its own fetches."""
    now = time.time()
    with _inflight_lock:
        return {
            k: {
                "bytes": v["bytes"],
                "total": v["total"],
                "age_s": round(now - v["t0"], 3),
            }
            for k, v in _inflight.items()
        }


# --------------------------------------------------------------------------
# worker-side read records + wire trace spans
# --------------------------------------------------------------------------


def _mint_span_id() -> str:
    return os.urandom(8).hex()


# read records captured in a RUNTIME-LESS process (node daemons): the
# telemetry ring has nowhere to flush there, so these ride the daemon's
# next heartbeat instead (drained by raylet._heartbeat_loop). Bounded:
# overflow drops the oldest.
_PENDING_READS_MAX = 256
_pending_reads: list = []
_pending_lock = threading.Lock()


def drain_pending_reads() -> list:
    """Records accumulated with no connected runtime — attach to the next
    heartbeat (ride-existing-messages; empty in driver/worker processes)."""
    with _pending_lock:
        out, _pending_reads[:] = list(_pending_reads), []
        return out


def record_read(
    path: str,
    oid,
    nbytes: int,
    wire_s: float,
    src_shm_dir: str = "",
    t0: Optional[float] = None,
) -> None:
    """One zero-copy peer-arena read or spill-restore completed in this
    process: ship a compact ledger record through the telemetry ring — or,
    in a runtime-less daemon, the pending queue its heartbeat drains
    (these paths have no completion message to ride). Size-floored so
    small-object gets don't flood the batch pipeline."""
    if not enabled() or int(nbytes) < min_record_bytes():
        return
    try:
        from ray_tpu._private import telemetry
        from ray_tpu.util import tracing

        # compact positional record, decoded scheduler-side:
        # (path, oid_bin, bytes, wire_s, t0, src_shm_dir, trace_id)
        rec = (
            path,
            oid.binary() if hasattr(oid, "binary") else bytes(oid),
            int(nbytes),
            float(wire_s),
            float(t0 if t0 is not None else time.time() - wire_s),
            src_shm_dir or "",
            tracing.current_trace_id(),
        )
        if telemetry._runtime() is None:
            # daemon process: no pipe to flush a telemetry batch down —
            # queue for the heartbeat instead of spinning a flusher that
            # can only fail
            with _pending_lock:
                if len(_pending_reads) >= _PENDING_READS_MAX:
                    _pending_reads.pop(0)
                _pending_reads.append(rec)
            return
        buf = telemetry.get_buffer()
        buf.record_transfer(rec)
        buf.ensure_flusher()
    except Exception:
        pass  # observability must never fail the data path


def record_wire_span(
    path: str,
    nbytes: int,
    t0: float,
    duration_s: float,
    oid=None,
    link: str = "",
    with_rate: bool = True,
) -> None:
    """Record a ``wire:<path>`` PROFILE span under the CURRENT trace
    context (the task span whose arg_fetch blocked on this read), so
    ``ray_tpu.trace(id)`` shows which path a slow fetch crossed even when
    the transfer itself ran in another process."""
    if not enabled() or duration_s < 0.001:
        return
    try:
        from ray_tpu._private import telemetry
        from ray_tpu.util import tracing

        ctx = tracing.get_current_context()
        if ctx is None:
            return
        extra = {
            "trace_id": ctx.trace_id,
            "span_id": _mint_span_id(),
            "parent_id": ctx.span_id,
            "path": path,
            "bytes": int(nbytes),
        }
        if link:
            extra["link"] = link
        # with_rate=False: the span covers a BLOCKED-READ window (polls
        # included), not a wire — a rate derived from it would mislead;
        # the scheduler's transfer span carries the authoritative GiB/s
        if with_rate and duration_s > 0 and nbytes:
            extra["gib_per_s"] = round(nbytes / 2**30 / duration_s, 4)
        if oid is not None:
            extra["object_id"] = oid.hex() if hasattr(oid, "hex") else str(oid)
        telemetry.record_span(
            {
                "event": f"wire:{path}",
                "start": t0,
                "end": t0 + duration_s,
                "duration_ms": duration_s * 1e3,
                "pid": os.getpid(),
                "extra": extra,
            }
        )
    except Exception:
        pass


def requester_ctx():
    """The calling thread's (trace_id, span_id) for a transfer it is about
    to ask for, or None (untraced, or the plane off)."""
    if not enabled():
        return None
    from ray_tpu.util import tracing

    ctx = tracing.get_current_context()
    return (ctx.trace_id, ctx.span_id) if ctx is not None else None


def finish_blocked_read(
    path: str,
    nbytes: int,
    t_wall0: float,
    t_perf0: float,
    peer_dur: float,
    peer_dir: str,
    oid,
) -> None:
    """Shared tail of the driver/worker blocked-read window (worker.py and
    worker_process.py time the same state machine): emit the
    ``wire:<path>`` trace span — no rate: the window includes polls, and a
    zero-copy mapping moves no bytes; the scheduler's transfer span
    carries the authoritative GiB/s — and, for zero-copy peer reads (which
    have no completion message), the ledger byte record. No-op for a plain
    local-shm hit."""
    if path == "shm":
        return
    dur = time.perf_counter() - t_perf0
    record_wire_span(
        path, nbytes, t_wall0,
        peer_dur if path == "shm_peer" and peer_dur > 0 else dur,
        oid=oid, with_rate=False,
    )
    if path == "shm_peer":
        record_read(
            "shm_peer", oid, nbytes, peer_dur or dur,
            src_shm_dir=peer_dir, t0=t_wall0,
        )


def stage_sum_ms(stats: dict) -> float:
    """Sum of a record's stage decomposition (acceptance: within 10% of
    the transfer's wall time)."""
    return float(sum(stats.get(k) or 0.0 for k in STAGE_KEYS))
