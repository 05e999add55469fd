"""What every cell kind shares: the checkout's paths and environment, the
cluster's start and stop (copied from ``chip_smoke.py``: the parent stays off
JAX, nothing it started outlives it), the files a cell is made of, and the
result line."""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def say(msg: str) -> None:
    print(msg, flush=True)


def export_environment() -> None:
    """Before ``ray_tpu.init()``, so that every worker inherits it."""
    env = os.environ
    # one compile cache at a fixed path inside the checkout (the path is part
    # of the key); where the machine names one, that one
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    # every program, however quick to compile, comes from the cache in the
    # second run: set-up is then the same from run to run
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    # a Pallas kernel's module carries its Python call stack into the cache
    # key: without this a moved checkout or a shifted line recompiles
    env.setdefault("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "0")
    # workers import ``benchmarks.*`` by name
    env["PYTHONPATH"] = ROOT + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")


# -- the files of a cell ---------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration and
    traffic files, found by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))

    def of_cell(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {
        "name": workload,
        "chips": cell["chips"],
        "config": config,
        "traffic": traffic,
        "end_to_end": of_cell(bench["end_to_end"]),
        "per_layer": of_cell(bench["per_layer"]),
    }


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"no peaks recorded for device_kind {device_kind!r}: add it to benchmarks/peaks.json "
            f"with its source (known: {sorted(table)})"
        )
    return table[device_kind]


def read_layer_metrics(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader,
    ``layer_metrics/<name>.py``. A reader that finds nothing returns None and
    the metric is left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("layer_metric", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(correct, attempted, failed, metrics, device, breakdown=None) -> None:
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)


def trace_dir(cell: dict, args) -> str:
    """Where a traced run writes its trace: inside the checkout, and under
    ``chiprun_out/`` only when it is to be kept."""
    base = os.path.join(ROOT, "chiprun_out", "trace") if args.keep_trace else os.path.join(ROOT, ".bench_trace")
    return os.path.join(base, cell["name"])


def loadavg() -> float:
    return os.getloadavg()[0]


class Heartbeat:
    """A thread that sleeps 50 ms at a time and notes each time it woke more
    than 250 ms late. A stall of the whole process (the GIL held, the process
    not scheduled, the machine frozen) shows here; a slow device does not.
    One in the parent and one in the process that holds the chip tell the
    three apart when a run dips."""

    def __init__(self):
        import threading

        self.stalls = []  # (when it fell asleep, how long it stayed away), seconds
        self._halt = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        last = time.time()
        while not self._halt.wait(0.05):
            now = time.time()
            if now - last > 0.25:
                self.stalls.append((last, now - last))
            last = now

    def stop(self) -> list:
        self._halt.set()
        self._thread.join()
        return self.stalls

    def within(self, t0: float, t1: float) -> list:
        """(seconds after t0, seconds away) of the stalls inside [t0, t1)."""
        return [(round(t - t0, 2), round(d, 2)) for t, d in self.stalls if t0 <= t < t1]


# -- processes (chip_smoke.py's, copied) -----------------------------------


def _descendants(root: int) -> dict:
    """pid -> parent pid of every process below ``root``, zombies included."""
    parent = {}
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                with open(f"/proc/{name}/stat") as f:
                    parent[int(name)] = int(f.read().rsplit(")", 1)[1].split()[1])
            except OSError:
                pass
    out, frontier = {}, [root]
    while frontier:
        p = frontier.pop()
        kids = [c for c, pp in parent.items() if pp == p]
        out.update((c, p) for c in kids)
        frontier.extend(kids)
    return out


def stop_processes() -> list:
    """Every way out of a run leads through here: nothing it started may
    outlive it. ``ray_tpu.shutdown()`` tells the workers to exit;
    multiprocessing's forkserver and resource tracker end only when they
    notice this process gone, which is after it has ended. So: the workers
    first (they are the forkserver's children and keep it alive), then the
    two helpers, each waited for. Returns the pids that did not go by
    themselves and were killed."""
    from multiprocessing import forkserver, resource_tracker

    me, killed = os.getpid(), []

    def end(live, grace_s: float) -> None:
        deadline = time.monotonic() + grace_s
        while live() and time.monotonic() < deadline:
            time.sleep(0.1)
        for pid in live():
            killed.append(pid)
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        deadline = time.monotonic() + 10.0
        while live() and time.monotonic() < deadline:
            time.sleep(0.1)

    def unreaped() -> list:
        for pid, ppid in _descendants(me).items():
            if ppid == me:
                try:
                    os.waitpid(pid, os.WNOHANG)
                except ChildProcessError:
                    pass
        return sorted(_descendants(me))

    try:
        if "ray_tpu" in sys.modules:
            import ray_tpu

            ray_tpu.shutdown()
    finally:
        end(lambda: sorted(p for p, pp in _descendants(me).items() if pp != me), 15.0)
        forkserver._forkserver._stop()
        resource_tracker._resource_tracker._stop()
        end(unreaped, 5.0)
    return killed


def start_cluster(need_chips: int, allow_cpu: bool) -> int:
    """``ray_tpu.init()``; the TPU count a worker may ask for. Without the
    chips the cell needs the run ends here, unless this is a rehearsal."""
    import ray_tpu

    if "jax" in sys.modules:
        raise RuntimeError("the parent must stay off jax: it would hold the chip")
    ray_tpu.init()
    chips = int(ray_tpu.cluster_resources().get("TPU", 0))
    if chips < need_chips:
        if not allow_cpu:
            raise SystemExit(f"benchmark: the cell needs {need_chips} TPU chip(s), this host shows {chips}")
        return 0
    return need_chips


def check_device(device: dict, chips: int, allow_cpu: bool) -> None:
    if allow_cpu:
        return
    if device["platform"] != "tpu" or device["count"] != chips:
        raise SystemExit(f"benchmark: ran on {device}, not on {chips} TPU chip(s)")
    peaks_for(device["kind"])
