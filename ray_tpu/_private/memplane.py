"""Memory-observability plane: allocation provenance + byte attribution.

Answers "where did the *bytes* go" the way the tracing plane (PR 11)
answers "where did the *time* go". Parity: ``ray memory``'s per-object
provenance grouped by creation callsite with ref-holder attribution
(``python/ray/_private/internal_api.py`` memory_summary / the
CoreWorker's ``ObjectRefInfo`` callsite capture).

Three process-side capture points feed the scheduler's bounded provenance
index through the PR-2 telemetry ring:

* **allocation provenance** — every store-backed ``put`` / task-return /
  stream-item records its creation callsite (``file.py:LINE`` digest,
  interned with bounded cardinality), size, kind, and active trace id;
  the owner task/job ids ride in the object id itself (an oid embeds its
  creating task id). Shipped batched (``telemetry.record_object_event``),
  never per-record RPCs.
* **spill/restore byte attribution** — the store clients call
  :func:`note_spill` / :func:`note_restore` with the victim oid; the
  owning job is decoded from the oid and the bytes land on the
  ``ray_tpu_spill_bytes_total{job=}`` / ``ray_tpu_restore_bytes_total``
  counters (batched through the same metrics pipeline).
* **device-memory telemetry** — :func:`maybe_record_device_metrics` is
  probed from the telemetry flusher cadence (the PR-11 jax-monitoring
  seam): once user code has imported jax, per-device
  ``ray_tpu_device_*`` gauges (live buffer count/bytes, bytes-in-use and
  HBM peak where the backend reports ``memory_stats``) are recorded.
  Never imports jax itself.

Scheduler-side consumers: the provenance index, the 1 Hz leak watchdog,
``state.summarize_objects`` server-side grouping, the ``ray_tpu memory``
CLI, and the OOM-kill forensics snapshot (see
``Scheduler._memory_watchdog_scan`` / ``memory_forensics_snapshot``).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, Optional

# bounded per-process callsite interning: beyond the cap every new site
# collapses into one bucket so a pathological codegen loop can't balloon
# the provenance index's label cardinality
_CALLSITE_CACHE_MAX = 1024
_ELIDED = "<elided>"

_callsite_cache: Dict[tuple, str] = {}
_callsite_lock = threading.Lock()

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# (runtime identity, verdict) — the flags can't change under a live
# runtime, and this check sits on the put hot path (bench-budgeted)
_enabled_cache: tuple = (None, False)


def enabled() -> bool:
    """Memory plane on? Requires the telemetry pipeline (records ride its
    batches); ``memory_plane_enabled`` gates the capture side. Memoized
    per connected runtime — this is the put hot path."""
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
        getattr(cfg, "memory_plane_enabled", True)
    )
    _enabled_cache = (rt, verdict)
    return verdict


def user_callsite(depth_limit: int = 12) -> str:
    """``file.py:LINE`` of the nearest stack frame OUTSIDE ray_tpu — the
    user line that created the object. Interned (bounded): repeated puts
    from one site share a single string."""
    try:
        frame = sys._getframe(1)
    except ValueError:
        return "<unknown>"
    depth = 0
    while frame is not None and depth < depth_limit:
        code = frame.f_code
        fn = code.co_filename
        if not fn.startswith(_PKG_DIR):
            key = (fn, frame.f_lineno)
            with _callsite_lock:
                cs = _callsite_cache.get(key)
                if cs is None:
                    if len(_callsite_cache) >= _CALLSITE_CACHE_MAX:
                        return _ELIDED
                    cs = f"{os.path.basename(fn)}:{frame.f_lineno}"
                    _callsite_cache[key] = cs
            return cs
        frame = frame.f_back
        depth += 1
    return "<internal>"


def capture_put() -> Optional[tuple]:
    """Hot-path provenance capture for ``put``: returns ``(callsite,
    trace_id, t)`` to ride the put's EXISTING registration message
    (``put_done`` / ``submit_put``) — zero extra messages, and the
    provenance can never race the commit it describes. None when the
    plane is off. Returns/stream items have no per-object message and use
    :func:`record_object` (telemetry batches) instead."""
    if not enabled():
        return None
    from ray_tpu.util import tracing

    return (user_callsite(), tracing.current_trace_id(), time.time())


def record_object(oid, size: int, kind: str, callsite: Optional[str] = None) -> None:
    """One store-backed object came to life: ship its provenance record
    (batched). ``kind`` is ``put`` / ``return`` / ``stream_item``. The
    creating task and job ids are embedded in the oid — the scheduler
    decodes them at ingest, keeping this record small. Hot path: one
    bounded stack walk + one ring-buffer append per store-backed put."""
    if not enabled():
        return
    from ray_tpu._private import telemetry
    from ray_tpu.util import tracing

    # compact positional record (oid_bin, size, kind, callsite, trace, t):
    # one tuple alloc on the put hot path, decoded scheduler-side
    buf = telemetry.get_buffer()
    buf.record_object_event(
        (
            oid.binary(),
            int(size),
            kind,
            callsite if callsite is not None else user_callsite(),
            tracing.current_trace_id(),
            time.time(),
        )
    )
    buf.ensure_flusher()


# --------------------------------------------------------------------------
# spill / restore byte attribution (per owning job)
# --------------------------------------------------------------------------

_byte_counters: Dict[str, object] = {}
_counter_lock = threading.Lock()


def _job_hex_of(oid) -> str:
    try:
        return oid.binary()[20:24].hex()
    except Exception:
        return "unknown"


def _spill_restore_counters():
    """Lazily construct the per-job spill/restore counters (metric names
    stay literal constructor args: the metrics-lint scanner keys on it)."""
    with _counter_lock:
        if "spill" not in _byte_counters:
            from ray_tpu.util.metrics import Counter

            _byte_counters["spill"] = Counter(
                "ray_tpu_spill_bytes_total",
                "bytes spilled out of the object-store arena, by owning job",
                tag_keys=("job",),
            )
            _byte_counters["restore"] = Counter(
                "ray_tpu_restore_bytes_total",
                "bytes restored from the spill path into the object store, "
                "by owning job",
                tag_keys=("job",),
            )
    return _byte_counters


def note_spill(oid, nbytes: int) -> None:
    """An object left the arena for the spill path; charge its owning job
    (the oid embeds the creating task's job id)."""
    if not enabled():
        return
    try:
        _spill_restore_counters()["spill"].inc(
            int(nbytes), tags={"job": _job_hex_of(oid)}
        )
    except Exception:
        pass  # observability must never fail the data path


def note_restore(oid, nbytes: int) -> None:
    """A spilled object was restored into the store; per-job accounting."""
    if not enabled():
        return
    try:
        _spill_restore_counters()["restore"].inc(
            int(nbytes), tags={"job": _job_hex_of(oid)}
        )
    except Exception:
        pass


# --------------------------------------------------------------------------
# device-memory telemetry (the PR-11 jax-monitoring seam)
# --------------------------------------------------------------------------

_DEVICE_PROBE_INTERVAL_S = 5.0
_last_device_probe = 0.0
_device_gauges: Dict[str, object] = {}


def _get_device_gauges() -> Dict[str, object]:
    """Lazily construct the ``ray_tpu_device_*`` gauges (literal names:
    the metrics-lint scanner keys on the constructor call)."""
    with _counter_lock:
        if "live_buffers" not in _device_gauges:
            from ray_tpu.util.metrics import Gauge

            _device_gauges["live_buffers"] = Gauge(
                "ray_tpu_device_live_buffers",
                "live jax arrays held by this process (jax.live_arrays)",
                tag_keys=("pid",),
            )
            _device_gauges["live_bytes"] = Gauge(
                "ray_tpu_device_live_bytes",
                "bytes held by live jax arrays in this process",
                tag_keys=("pid",),
            )
            _device_gauges["bytes_in_use"] = Gauge(
                "ray_tpu_device_bytes_in_use",
                "device allocator bytes in use (jax memory_stats)",
                tag_keys=("pid", "device"),
            )
            _device_gauges["peak_bytes_in_use"] = Gauge(
                "ray_tpu_device_peak_bytes_in_use",
                "device allocator high-water mark (HBM peak)",
                tag_keys=("pid", "device"),
            )
    return _device_gauges


_jax_backend_up = False


def note_jax_backend_up() -> None:
    """This process has started a JAX backend (it compiled, or a ray_tpu
    entry point checked its platform): the device sweep may now run. The
    sweep's own calls (``live_arrays``/``local_devices``) START a backend
    where there is none, and a process that merely imported jax would then
    open — and keep — the chip its neighbour was given."""
    global _jax_backend_up
    _jax_backend_up = True


def maybe_record_device_metrics() -> bool:
    """Record per-device JAX memory gauges when (and only when) this
    process is known to have a JAX backend up (``note_jax_backend_up``).
    Called from the telemetry flusher cadence; self-rate-limited; never
    imports jax or starts a backend itself. Returns True when a sweep was
    recorded."""
    global _last_device_probe
    if not _jax_backend_up or not enabled():
        return False
    now = time.monotonic()
    if now - _last_device_probe < _DEVICE_PROBE_INTERVAL_S:
        return False
    _last_device_probe = now
    try:
        return collect_device_metrics()
    except Exception:
        return False


def collect_device_metrics() -> bool:
    """One sweep of jax device stats into the ``ray_tpu_device_*`` gauges.
    Separate from the rate-limited probe so tests/read paths can force it."""
    import jax

    pid = str(os.getpid())
    gauges = _get_device_gauges()
    # host-side view: live committed arrays (buffer count + bytes). This is
    # what a leaked jnp array shows up in even on CPU-only builds where the
    # backend has no allocator stats.
    try:
        arrs = jax.live_arrays()
        n_bytes = 0
        for a in arrs:
            try:
                n_bytes += int(a.nbytes)
            except Exception:
                pass
        gauges["live_buffers"].set(len(arrs), tags={"pid": pid})
        gauges["live_bytes"].set(n_bytes, tags={"pid": pid})
    except Exception:
        pass
    # allocator-side view: per-device bytes_in_use / peak (TPU/GPU backends;
    # CPU returns None -> skipped)
    try:
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            tags = {
                "pid": pid,
                "device": f"{getattr(d, 'platform', '?')}:{getattr(d, 'id', '?')}",
            }
            if "bytes_in_use" in stats:
                gauges["bytes_in_use"].set(
                    int(stats["bytes_in_use"]), tags=tags
                )
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                gauges["peak_bytes_in_use"].set(int(peak), tags=tags)
    except Exception:
        pass
    # KV-cache view: every registered paged-pool provider (LLM engines in
    # this process) folds into the ray_tpu_kv_* gauges alongside the
    # allocator stats, so `ray_tpu memory` shows KV occupancy next to HBM
    try:
        for name, provider in list(_kv_providers.items()):
            try:
                record_kv_occupancy(provider())
            except Exception:
                pass
    except Exception:
        pass
    return True


# -- paged KV cache occupancy (LLM serving plane) ----------------------------
#
# The serve-plane inference engine reserves KV blocks at admission and
# sheds on exhaustion; these gauges make that live shed signal visible in
# the same device-gauge surface as HBM use. Providers are callables
# returning an engine's kv_stats() snapshot, swept by
# collect_device_metrics() and updated inline by the engine on every
# admission/finish edge.

_kv_gauges: Dict[str, object] = {}
_kv_providers: Dict[str, object] = {}


def register_kv_provider(deployment: str, provider) -> None:
    """Register a KV-stats source (an engine's ``kv_stats``) so periodic
    device sweeps refresh the ``ray_tpu_kv_*`` gauges even when the
    engine is idle."""
    _kv_providers[str(deployment)] = provider


def _get_kv_gauges() -> Dict[str, object]:
    with _counter_lock:
        if "blocks_total" not in _kv_gauges:
            from ray_tpu.util.metrics import Gauge

            _kv_gauges["blocks_total"] = Gauge(
                "ray_tpu_kv_blocks_total",
                "usable KV-cache blocks in the paged device pool per LLM "
                "deployment (excludes the reserved null block)",
                tag_keys=("deployment",),
            )
            _kv_gauges["blocks_free"] = Gauge(
                "ray_tpu_kv_blocks_free",
                "KV-cache blocks currently on the free list per LLM "
                "deployment — the admission-control shed signal",
                tag_keys=("deployment",),
            )
            _kv_gauges["occupancy"] = Gauge(
                "ray_tpu_kv_occupancy_ratio",
                "fraction of usable KV-cache blocks in use per LLM "
                "deployment (1.0 = pool exhausted, requests shed)",
                tag_keys=("deployment",),
            )
            _kv_gauges["bytes_total"] = Gauge(
                "ray_tpu_kv_pool_bytes",
                "device bytes reserved by the paged KV pool per LLM "
                "deployment (blocks x bytes-per-block, both k and v)",
                tag_keys=("deployment",),
            )
            _kv_gauges["state_rows_used"] = Gauge(
                "ray_tpu_kv_state_rows_used",
                "state rows held by live sequences per LLM deployment: a model "
                "kind whose layers keep a recurrent state has one a decode slot "
                "(0 for every other kind)",
                tag_keys=("deployment",),
            )
            _kv_gauges["ring_bytes"] = Gauge(
                "ray_tpu_kv_ring_bytes",
                "device bytes the state rows' rings of window-attention rows "
                "hold per LLM deployment, null row included (0 for a model "
                "kind without window layers)",
                tag_keys=("deployment",),
            )
    return _kv_gauges


def record_kv_occupancy(stats: Dict[str, object]) -> None:
    """Fold one engine ``kv_stats()`` snapshot into the KV gauges."""
    if not enabled():
        return
    try:
        gauges = _get_kv_gauges()
        tags = {"deployment": str(stats.get("deployment", "llm"))}
        total = int(stats.get("blocks_total", 0))
        free = int(stats.get("blocks_free", 0))
        gauges["blocks_total"].set(float(total), tags=tags)
        gauges["blocks_free"].set(float(free), tags=tags)
        gauges["occupancy"].set(
            0.0 if not total else 1.0 - free / total, tags=tags
        )
        bpb = int(stats.get("bytes_per_block", 0))
        if bpb:
            # pool bytes include the reserved null block
            gauges["bytes_total"].set(float((total + 1) * bpb), tags=tags)
        gauges["state_rows_used"].set(float(stats.get("state_rows_used", 0)), tags=tags)
        rows = int(stats.get("state_rows_total", 0))
        gauges["ring_bytes"].set(float((rows + 1 if rows else 0) * int(stats.get("ring_bytes", 0))), tags=tags)
    except Exception:
        pass
