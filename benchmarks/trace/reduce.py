"""The reduction from a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read. It lives with the benchmark so that every PR computes
the same number in the same way; ``tests/test_trace.py`` checks it on the
recorded trace kept beside this file.

What a TPU trace holds (``jax.profiler.ProfileData``): a plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per operation that
ran on the chip and whose line ``XLA Modules`` has one event per program run;
and the plane ``/host:CPU`` with a line per host thread, which holds the
Python tracer's events (``$file.py:line function``). All on one clock, in
nanoseconds. Off the chip (rehearsals) the operations are the host events
that carry an ``hlo_op`` stat.

    python -m benchmarks.trace.reduce <file.xplane.pb>     prints the reduction
    python -m benchmarks.trace.reduce --describe <file>    planes, lines, stats
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys

GAP_FLOOR_NS = 2_000  # idle stretches shorter than this are lumped as short_gaps
MAX_NAMED_GAPS = 3_000  # and so are all but the longest few thousand
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def union_ns(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps_ns(busy, t0, t1) -> list:
    """The complement of merged ``busy`` inside [t0, t1]."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return out


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def load_events(path: str) -> dict:
    """device -> {"ops": [(name, module, start, end)], "modules": [(name,
    start, end)]}, and host -> [(name, start, end, line)]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": [], "async": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] = [(op_name(e.name), None, e.start_ns, e.start_ns + e.duration_ns)
                                  for e in line.events]
                elif line.name == "Async XLA Ops":  # from -start to -done: collectives, copies in flight
                    dev["async"] = [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                                    for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    st = _stats(e) if not e.name.startswith("$") else {}
                    if "hlo_op" in st:  # an operation that ran on the CPU backend
                        dev = devices.setdefault("/host:CPU", {"ops": [], "modules": [], "async": []})
                        dev["ops"].append((e.name, "jit_" + str(st.get("hlo_module", "")).removeprefix("jit_"),
                                           e.start_ns, e.start_ns + e.duration_ns))
                    elif e.duration_ns > 0:
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns, line.name))
    for dev in devices.values():
        _name_modules(dev)
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """On the chip an operation's event carries its whole HLO line,
    ``%fusion.97 = s32[8]{0} fusion(...)``: the instruction's name is enough."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops) -> list:
    """(name, module, self_ns) for each operation: its duration less that of
    the operations nested inside it (a ``while`` spans its body's)."""
    out, stack = [], []  # stack of [end, index into out]
    for name, mod, s, e in sorted(ops, key=lambda o: (o[2], -o[3])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= min(e, stack[-1][0]) - s
        out.append([name, mod, e - s])
        stack.append([e, len(out) - 1])
    return out


def module_name(event_name: str) -> str:
    """``jit_decode_step_greedy(1234567)`` -> ``jit_decode_step_greedy``."""
    return event_name.split("(", 1)[0]


def _name_modules(dev: dict) -> None:
    """Give each operation the program it ran in, by containment in time."""
    mods = sorted(dev["modules"], key=lambda m: m[1])
    if not mods:
        return
    ops, j = [], 0
    for name, mod, s, e in sorted(dev["ops"], key=lambda o: o[2]):
        while j + 1 < len(mods) and mods[j][2] <= s:
            j += 1
        inside = mods[j][1] <= s < mods[j][2]
        ops.append((name, module_name(mods[j][0]) if inside else (mod or ""), s, e))
    dev["ops"] = ops


def program_files() -> set:
    """Base names of the Python files of this checkout's program and
    benchmark: the frames an idle stretch is worth naming by."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    names = set()
    for top in ("ray_tpu", "benchmarks"):
        for _dir, _subdirs, files in os.walk(os.path.join(root, top)):
            names.update(f for f in files if f.endswith(".py"))
    return names


class HostFrames:
    """What the host was doing at a moment: the narrowest frame of the
    program's own files that covers it (``$engine.py:601 _retire_step``), or,
    where none does, the narrowest host event of any kind."""

    def __init__(self, host, prefer=()):
        import numpy as np

        self.names = [h[0] for h in host]
        self.start = np.asarray([h[1] for h in host], dtype=np.int64)
        self.end = np.asarray([h[2] for h in host], dtype=np.int64)
        self.ours = np.asarray([n.startswith("$") and n[1:].split(":", 1)[0] in prefer for n in self.names], dtype=bool)

    def at(self, t: int) -> str:
        import numpy as np

        covering = (self.start <= t) & (t < self.end)
        for mask in (covering & self.ours, covering):
            idx = np.nonzero(mask)[0]
            if idx.size:
                i = idx[np.argmin(self.end[idx] - self.start[idx])]
                return self.names[i].lstrip("$").replace(" ", "_")
        return "no_host_event"


def reduce_events(ev: dict) -> dict:
    devices, host = ev["devices"], ev["host"]
    if not devices:
        raise ValueError("the trace holds no operation that ran on a device")
    # the traced window: from the first operation on a device to the end of
    # the last (the host's events run on while the trace is written out)
    t0 = min(o[2] for d in devices.values() for o in d["ops"])
    t1 = max(o[3] for d in devices.values() for o in d["ops"])
    busy_each, ops_s, modules, coll_s = [], {}, {}, 0.0
    first = sorted(devices)[0]
    for dname, d in devices.items():
        busy = union_ns((o[2], o[3]) for o in d["ops"])
        busy_each.append(sum(e - s for s, e in busy))
        if dname != first:
            continue  # per-operation numbers are read on the first chip
        for name, mod, self_ns in self_times(d["ops"]):
            key = f"{mod}/{name}" if mod else name
            ops_s[key] = ops_s.get(key, 0.0) + self_ns / 1e9
            if mod:  # off the chip there are no module events: the operations' own time stands in
                m = modules.setdefault(mod, {"count": 0, "total_s": 0.0})
                m["ops_s"] = m.get("ops_s", 0.0) + self_ns / 1e9
        for name, s, e in d["modules"]:
            m = modules.setdefault(module_name(name), {"count": 0, "total_s": 0.0})
            m["count"] += 1
            m["total_s"] += (e - s) / 1e9
        # time with a collective under way: as an operation of its own, or in
        # flight between its -start and its -done (hidden behind compute or not)
        coll = [(s, e) for name, _m, s, e in d["ops"] if any(c in name for c in COLLECTIVES)]
        coll += [(s, e) for name, s, e in d["async"] if any(c in name for c in COLLECTIVES)]
        coll_s = sum(e - s for s, e in union_ns(coll)) / 1e9
        idle, frames = {}, HostFrames(host, program_files())
        gaps = sorted(gaps_ns(busy, t0, t1), key=lambda g: g[0] - g[1])
        for i, (s, e) in enumerate(gaps):
            named = i < MAX_NAMED_GAPS and e - s >= GAP_FLOOR_NS
            key = frames.at((s + e) // 2) if named else "short_gaps"
            idle[key] = idle.get(key, 0.0) + (e - s) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_each) / len(busy_each) / 1e9,
        "devices": len(devices),
        "ops_s": ops_s,
        "modules": modules,
        "collective_s": coll_s,
        "breakdown": {"device_ops": top(ops_s), "idle_gaps": top(idle)},
    }


def newest_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def reduce_directory(directory: str, remove: bool = False) -> dict:
    """Reduce the newest trace under ``directory``; traces are large, so a
    run that was not asked to keep its own removes it."""
    try:
        return reduce_events(load_events(newest_xplane(directory)))
    finally:
        if remove:
            shutil.rmtree(directory, ignore_errors=True)


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:3]:
                print(f"     {e.name[:90]!r} start {e.start_ns} dur {e.duration_ns} stats {list(e.stats)[:8]}")


if __name__ == "__main__":
    if sys.argv[1] == "--describe":
        describe(sys.argv[2])
    else:
        out = reduce_events(load_events(sys.argv[1]))
        out.pop("ops_s")
        print(json.dumps(out, indent=1))
