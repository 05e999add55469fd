"""Application metrics: Counter / Gauge / Histogram.

Parity: ``python/ray/util/metrics.py`` + the metrics agent's Prometheus
exposition (``python/ray/_private/metrics_agent.py:483``). Records update a
process-local shadow and ride the telemetry plane
(``ray_tpu._private.telemetry``): the background flusher ships at most ONE
snapshot per metric per ``metrics_report_interval_ms`` — the seed did a
blocking KV RPC on *every* ``Counter.inc()`` and silently swallowed
failures. The scheduler merges per-process snapshots (counters/histograms
sum across processes, gauges last-writer-wins) into the GCS KV, and
:func:`prometheus_text` exposes them plus the runtime-internal series
(scheduler queue depth, handler event_stats, object-store usage, fastcopy
stage bandwidth, telemetry drop counters) in Prometheus text format.
"""

from __future__ import annotations

import bisect
import json
import threading
from typing import Dict, List, Optional, Tuple

from ray_tpu._private.worker import get_runtime

_NS = "metrics"
_lock = threading.Lock()
# local shadow, the process's record of every series: name -> {labels_json:
# value}. Updated in place under ``_lock``; a histogram's value is a dict
# (count, sum, buckets, boundaries). The telemetry flusher snapshots the
# metrics marked dirty once per interval (``_collect_dirty``)
_local: Dict[str, Dict[str, object]] = {}
# name -> (kind, description) of every metric constructed here, and the names
# updated since the flusher's last snapshot
_meta: Dict[str, Tuple[str, str]] = {}
_dirty: set = set()


def _collect_dirty() -> Dict[str, Tuple[str, str, dict]]:
    """The flusher's side: one snapshot of each metric updated since the
    last call (one KV write per interval per metric, however many records
    landed). Histogram entries are copied so the batch can be pickled while
    the series move on."""
    with _lock:
        names = list(_dirty)
        _dirty.clear()
        out = {}
        for name in names:
            kind, description = _meta.get(name, ("untyped", ""))
            out[name] = (kind, description, {
                k: (dict(v, buckets=list(v["buckets"])) if isinstance(v, dict) else v)
                for k, v in _local.get(name, {}).items()
            })
    return out


def _ensure_flusher() -> None:
    """Off the record path: where a metric or a series is made. The
    flusher ships what the record path only marks."""
    from ray_tpu._private import telemetry

    if telemetry.enabled():
        telemetry.get_buffer().ensure_flusher()


class _Bound:
    """One series of a metric (a fixed label set): the label key is built
    once, and an update is a dict store under the module lock plus a set
    add. Nothing here serialises, copies or calls into the telemetry plane,
    so a hot loop can afford one per step."""

    __slots__ = ("_name", "_key", "_series")

    def __init__(self, metric: "_Metric", key: str):
        self._name = metric._name
        self._key = key
        self._series = _local[metric._name]


class _BoundCounter(_Bound):
    __slots__ = ()

    def inc(self, value: float = 1.0) -> None:
        series, key = self._series, self._key
        with _lock:
            series[key] = series.get(key, 0.0) + value
            _dirty.add(self._name)


class _BoundGauge(_Bound):
    __slots__ = ()

    def set(self, value: float) -> None:
        with _lock:
            self._series[self._key] = value
            _dirty.add(self._name)


class _BoundHistogram(_Bound):
    __slots__ = ("_boundaries",)

    def __init__(self, metric: "Histogram", key: str):
        super().__init__(metric, key)
        self._boundaries = metric._boundaries

    def observe(self, value: float) -> None:
        self.observe_many((value,))

    def observe_many(self, values) -> None:
        if not values:
            return
        bounds = self._boundaries
        with _lock:
            entry = self._series.get(self._key)
            if entry is None:
                entry = self._series[self._key] = {
                    "count": 0,
                    "sum": 0.0,
                    "buckets": [0] * (len(bounds) + 1),
                    "boundaries": bounds,
                }
            buckets = entry["buckets"]
            for value in values:
                entry["count"] += 1
                entry["sum"] += value
                # first bound >= value; past the last one, the overflow bucket
                buckets[bisect.bisect_left(bounds, value)] += 1
            _dirty.add(self._name)


class _Metric:
    KIND = "untyped"
    _BOUND = _Bound

    def __init__(self, name: str, description: str = "", tag_keys: Tuple[str, ...] = ()):
        self._name = name
        self._description = description
        self._tag_keys = tuple(tag_keys)
        self._default_tags: Dict[str, str] = {}
        # label items -> bound series, so the un-bound API builds a label
        # key once per label set and not once per record
        self._bound: Dict[tuple, _Bound] = {}
        with _lock:
            _local.setdefault(name, {})
            _meta[name] = (self.KIND, description)
        _ensure_flusher()

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        self._bound = {}
        return self

    def _key(self, tags: Optional[Dict[str, str]]) -> str:
        merged = {**self._default_tags, **(tags or {})}
        return json.dumps(merged, sort_keys=True)

    def bind(self, tags: Optional[Dict[str, str]] = None):
        """The series for ``tags`` as a handle whose updates cost a dict
        store: build it once outside the loop, update it inside."""
        ident = tuple(sorted(tags.items())) if tags else ()
        bound = self._bound.get(ident)
        if bound is None:
            bound = self._bound[ident] = self._BOUND(self, self._key(tags))
            _ensure_flusher()
        return bound


class Counter(_Metric):
    KIND = "counter"
    _BOUND = _BoundCounter

    def inc(self, value: float = 1.0, tags: Optional[Dict[str, str]] = None):
        self.bind(tags).inc(value)


class Gauge(_Metric):
    KIND = "gauge"
    _BOUND = _BoundGauge

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        self.bind(tags).set(value)


# default histogram grid: sub-millisecond buckets resolve dispatch-path
# costs (direct-call send, lease grant, arg materialization live in the
# 10us-1ms band the old [0.1, 1, 10, 100, 1000] grid lumped into one
# bucket), still reaching 10s for slow requests. Units are whatever the
# metric observes — for *_ms series this spans 10us .. 10s.
DEFAULT_HISTOGRAM_BOUNDARIES: List[float] = [
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1, 2.5, 5, 10, 25, 50, 100, 250, 500,
    1000, 2500, 5000, 10000,
]

# per-metric boundary overrides (configure_histogram_boundaries), consulted
# at CONSTRUCTION time; env var RAY_TPU_HIST_BUCKETS_<NAME> (comma-separated
# floats, metric name uppercased with non-alnum -> _) wins over both
_boundary_overrides: Dict[str, List[float]] = {}


def configure_histogram_boundaries(name: str, boundaries: List[float]) -> None:
    """Set the bucket bounds for histograms named ``name`` created AFTER
    this call (per-metric bucket configurability). Bounds must ascend."""
    bounds = list(boundaries)
    if bounds != sorted(bounds) or not bounds:
        raise ValueError("histogram boundaries must be ascending and non-empty")
    with _lock:
        _boundary_overrides[name] = bounds


def _env_boundaries(name: str) -> Optional[List[float]]:
    import os
    import re

    key = "RAY_TPU_HIST_BUCKETS_" + re.sub(r"[^A-Za-z0-9]", "_", name).upper()
    raw = os.environ.get(key)
    if not raw:
        return None
    try:
        bounds = [float(p) for p in raw.split(",") if p.strip()]
        return bounds if bounds == sorted(bounds) and bounds else None
    except ValueError:
        return None


def resolve_boundaries(name: str, explicit: Optional[List[float]] = None) -> List[float]:
    """Boundary resolution order: env override > configure_histogram_
    boundaries > constructor argument > the default grid."""
    env = _env_boundaries(name)
    if env is not None:
        return env
    with _lock:
        override = _boundary_overrides.get(name)
    if override is not None:
        return list(override)
    if explicit:
        # preserved verbatim: int bounds render as le="1", not le="1.0"
        return list(explicit)
    return list(DEFAULT_HISTOGRAM_BOUNDARIES)


class Histogram(_Metric):
    KIND = "histogram"
    _BOUND = _BoundHistogram

    def __init__(self, name, description="", boundaries: Optional[List[float]] = None,
                 tag_keys: Tuple[str, ...] = ()):
        self._boundaries = resolve_boundaries(name, boundaries)
        super().__init__(name, description, tag_keys)

    def observe(self, value: float, tags: Optional[Dict[str, str]] = None):
        self.bind(tags).observe_many((value,))

    def observe_many(self, values, tags: Optional[Dict[str, str]] = None):
        """Fold a batch of observations in under one lock hold."""
        self.bind(tags).observe_many(values)


def _sync_cluster_telemetry(rt) -> None:
    """Read-your-writes for the batched pipeline: flush this process's
    buffer, then ask the scheduler to pull every worker's (bounded wait).
    Remote (socket-attached) drivers skip the cluster pull — their view may
    lag one flush interval."""
    from ray_tpu._private import telemetry

    telemetry.flush()
    scheduler = getattr(rt, "scheduler", None)
    if scheduler is not None:
        try:
            scheduler.request_telemetry_flush()
        except Exception:
            pass


def _format_series(lines: List[str], name: str, kind: str, description: str,
                   data: Dict[str, object]) -> None:
    lines.append(f"# HELP {name} {description}")
    lines.append(f"# TYPE {name} {kind if kind != 'untyped' else 'gauge'}")
    for labels_json, value in data.items():
        labels = json.loads(labels_json) if labels_json.startswith("{") else {}
        label_str = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
        label_part = "{" + label_str + "}" if label_str else ""
        if kind == "histogram" and isinstance(value, dict):
            lines.append(f"{name}_count{label_part} {value['count']}")
            lines.append(f"{name}_sum{label_part} {value['sum']}")
            bounds = value.get("boundaries") or []
            cumulative = 0
            for b, n in zip(bounds, value.get("buckets", ())):
                cumulative += n
                le = "{" + ",".join(filter(None, [label_str, f'le="{b}"'])) + "}"
                lines.append(f"{name}_bucket{le} {cumulative}")
            le_inf = "{" + ",".join(filter(None, [label_str, 'le="+Inf"'])) + "}"
            lines.append(f"{name}_bucket{le_inf} {value['count']}")
        else:
            lines.append(f"{name}{label_part} {value}")


def prometheus_text() -> str:
    """All recorded metrics — application (GCS KV aggregated) plus the
    scheduler's runtime-internal series — in Prometheus exposition format."""
    rt = get_runtime()
    _sync_cluster_telemetry(rt)
    if hasattr(rt, "scheduler_rpc"):
        keys = rt.scheduler_rpc("kv_keys", (_NS, b""))
        get = lambda k: rt.scheduler_rpc("kv_get", (_NS, k))  # noqa: E731
        runtime_series = rt.scheduler_rpc("runtime_metrics", ())
    else:
        keys = rt.rpc("kv_keys", _NS, b"")
        get = lambda k: rt.rpc("kv_get", _NS, k)  # noqa: E731
        runtime_series = rt.rpc("runtime_metrics")
    lines: List[str] = []
    for key in sorted(keys):
        raw = get(key)
        if raw is None:
            continue
        payload = json.loads(raw)
        _format_series(
            lines,
            key.decode(),
            payload["kind"],
            payload.get("description", ""),
            payload["data"],
        )
    for series in runtime_series or ():
        _format_series(
            lines,
            series["name"],
            series.get("kind", "gauge"),
            series.get("description", ""),
            series.get("data", {}),
        )
    return "\n".join(lines) + "\n"
