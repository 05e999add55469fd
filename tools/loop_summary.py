#!/usr/bin/env python3
"""What the engine's loop records of one run say of the time the batch was full:

    python tools/loop_summary.py <session_dir>/loops [--skip-s 4] [--latent]

One JSON object: over the iterations from the first to the last with every
decode slot dispatched (less ``--skip-s`` seconds of ramp at the start), the
share of dispatched steps that went out with a step still in flight
(``ahead``), the rows dropped for an EOS seen a step late (``overrun``), the
mean time between two results; and over the requests admitted in that time,
``t_first - t_admit`` (a newcomer's wait for its first token, behind whatever
was in flight) and ``t_admit - t_submit``; and, of a model with expert
layers, the windows of held rows its grouped matmuls walked a layer a decode
step between that time's first and last ``llm_moe`` record
(``windows_per_layer_step``: 1 = no call spilled past its first window; None
without two records that carry the count), the (row tile, expert) pairs those
calls visited over the held experts that got a row (``pairs_per_touched``: 1 =
every touched expert's weights streamed once a call), and ``kv_neighbour_share``: of the
live sequences dispatched between that time's first and last
``llm_kv_neighbours`` record, the share whose preceding slot was live too, so
whose first chunk the paged kernels started during the predecessor's last
(None without two such records). Records older than a field read 0
there. ``stream`` (None of a program that writes no such record) follows a
token out of the replica over the same time: of the engine's streams that ended
in it (``llm_stream``) the mean, median, 90th percentile and maximum of a
stream's own mean of ``held`` (result on the host to the queue), ``wake``
(queue to the stream's thread) and ``send`` (the thread until it is back); of
the callers' (``serve_stream``) the same of ``transit`` (the sender's send to
the caller's hands) and the longest gap between two items of one stream; and
the controller's health probes sent in it (``serve_probe``): their round trips,
and how many were never answered. And ``start``, how the loop came to run (None of a program that leaves
no such record): the phases of the engine's ``llm_start`` record in seconds
(``init_to_backend`` .. ``pool_to_ready``), what it placed and its pool's
bytes; from the ``compile`` records (the engine's or the trainer's) the
seconds by stage, ``lowering_s`` (tracing and lowering) and ``compile_s`` (the
backend's, loads from the compile cache inside it) by program, heaviest first;
and ``compiles_after_full``, the programs compiled after the first iteration
with every slot dispatched: in a replica whose shapes were warmed, none.
``--latent``, of a kind whose decode step reads a latent cache
(``ops/paged_attention.py:paged_latent_attention``; LongCat, Kimi-K2): what the
kernel scored over what was live, from the requests that finished in that time
alone (a request of prompt ``p`` and ``n`` decode steps met the kernel at every
length from ``p + 1`` to ``p + n``, and a length is scored in whole prefixes of
``LATENT_PREFIX_ROWS`` under one chain a chunk of ``LATENT_CHUNK_ROWS``):
``rows_scored_share``, the mean over requests of rows scored over rows live
(1.0 = no dead row scored; a chunk of 512 rows scored whole, as before PR 63,
reads 1.3-1.4 at answers of hundreds of tokens) and ``chunks_a_sequence``, the
mean chunks a sequence a call.
Reads with the standard library alone; newest session under the temporary
directory where no directory is given.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

from loop_trace_check import load_records  # the script's own directory: tools/


def newest_loops_dir() -> str:
    found = glob.glob(os.path.join(tempfile.gettempdir(), "ray_tpu_sessions", "*", "loops"))
    if not found:
        raise SystemExit("no <session_dir>/loops under the temporary directory")
    return max(found, key=os.path.getmtime)


def spread_ms(values_ns: list) -> dict:
    if not values_ns:
        return {"count": 0}
    ms = sorted(v / 1e6 for v in values_ns)
    return {"count": len(ms), "mean_ms": statistics.fmean(ms), "median_ms": statistics.median(ms),
            "p90_ms": ms[min(len(ms) - 1, int(0.9 * len(ms)))], "max_ms": ms[-1]}


def moe_ratio(moe_recs: list, t0: int, t1: int, count: str, over: str = ""):
    """What the ``llm_moe`` records between ``t0`` and ``t1`` added to ``count``
    over what they added to ``over`` (none named: the expert layers that ran,
    decode steps x ``layers``); None without two records that carry both."""
    recs = [r for r in moe_recs if t0 <= r["t"] <= t1 and count in r and (not over or over in r)]
    if len(recs) < 2:
        return None
    first, last = recs[0], recs[-1]
    under = last[over] - first[over] if over else (last["step"] - first["step"]) * last["layers"]
    return (last[count] - first[count]) / under if under > 0 else None


def kv_neighbour_share(recs: list, t0: int, t1: int):
    recs = [r for r in recs if t0 <= r["t"] <= t1]
    if len(recs) < 2:
        return None
    count = recs[-1]["count"] - recs[0]["count"]
    return (recs[-1]["sum"] - recs[0]["sum"]) / count if count > 0 else None


# the latent kernel's constants in rows (``ops/paged_attention.py``: ``_LATENT_PREFIX``, and ``_LATENT_CHUNK_BYTES`` over a
# stored row's 1,280 B); ``tests/test_loop_tracing.py`` holds them to the kernel's
LATENT_PREFIX_ROWS, LATENT_CHUNK_ROWS = 256, 1024


def latent_summary(requests: list, prefix: int = LATENT_PREFIX_ROWS, chunk: int = LATENT_CHUNK_ROWS):
    shares, chunks = [], []
    for r in requests:
        lengths = range(r["prompt_len"] + 1, r["prompt_len"] + r["steps"] + 1)
        if lengths:
            shares.append(sum(-(-n // prefix) * prefix for n in lengths) / sum(lengths))
            chunks.append(sum(-(-n // chunk) for n in lengths) / len(lengths))
    if not shares:
        return None
    return {"requests": len(shares), "rows_scored_share": statistics.fmean(shares), "chunks_a_sequence": statistics.fmean(chunks)}


def stream_summary(recs: dict, t0: int, t1: int):
    engine = [r for r in recs["llm_stream"] if t0 <= r["t_last_back"] <= t1]
    callers = [r for r in recs["serve_stream"] if t0 <= r["t_last_got"] <= t1]
    probes = [r for r in recs["serve_probe"] if t0 <= r["t_sent"] <= t1]
    if not (engine or callers or probes):
        return None

    def stream_means(streams: list, segment: str) -> dict:
        return spread_ms([r[segment + "_sum"] / r[segment + "_n"] for r in streams if r[segment + "_n"]])

    return {
        "streams": len(engine), **{seg + "_ms": stream_means(engine, seg) for seg in ("held", "wake", "send")},
        "caller_streams": len(callers), "transit_ms": stream_means(callers, "transit"),
        "gap_max_ms": max(r["gap_max"] for r in callers) / 1e6 if callers else None,
        "probe_ms": {**spread_ms([r["t_answered"] - r["t_sent"] for r in probes if r["t_answered"]]),
                     "missed": sum(1 for r in probes if not r["t_answered"])},
    }


START_STAMPS = ("t_init", "t_backend", "t_params", "t_placed", "t_pool", "t_ready")


def start_summary(recs: dict):
    starts, compiles = recs["llm_start"], recs["compile"]
    if not starts and not compiles:
        return None
    out = {}
    if starts:
        st = starts[0]
        out["phases_s"] = {f"{a[2:]}_to_{b[2:]}": (st[b] - st[a]) / 1e9 for a, b in zip(START_STAMPS, START_STAMPS[1:])}
        out.update(placed=st["placed"], pool_bytes=st["pool_bytes"])
    by_stage, lowering, compiling = {}, {}, {}
    for r in compiles:
        by_stage[r["stage"]] = by_stage.get(r["stage"], 0.0) + r["seconds"]
        # jax names a program ``f`` while it traces it and ``jit(f)`` from then on
        name = (r["program"] or "").removeprefix("jit(").removesuffix(")")
        into = lowering if r["stage"] in ("trace", "lower") else compiling if r["stage"] == "compile" else None
        if into is not None:
            into[name] = into.get(name, 0.0) + r["seconds"]
    out.update(events=len(compiles), seconds_by_stage=by_stage,
               lowering_s=dict(sorted(lowering.items(), key=lambda kv: -kv[1])),
               compile_s=dict(sorted(compiling.items(), key=lambda kv: -kv[1])))
    live = [r for r in recs["llm_step"] if r["live"]]
    if live:
        full = max(r["live"] for r in live)
        t_full = min(r["t_loop"] for r in live if r["live"] == full)
        out["compiles_after_full"] = [r["program"] for r in compiles if r["stage"] == "compile" and r["t"] > t_full]
    return out


def summarise(recs: dict, skip_s: float, latent: bool = False) -> dict:
    steps = [r for r in recs["llm_step"] if r["live"]]
    if not steps:
        return {"steps": 0}
    full = max(r["live"] for r in steps)
    at_full = [r["t_loop"] for r in steps if r["live"] == full]
    t0, t1 = min(at_full) + int(skip_s * 1e9), max(at_full)
    window = [r for r in steps if t0 <= r["t_loop"] <= t1]
    results = sorted(r["t_result"] for r in recs["llm_step"] if r["t_result"] and t0 <= r["t_loop"] <= t1)
    reqs = [r for r in recs["llm_request"] if r["t_first"] and t0 <= r["t_admit"] <= t1]
    return {
        "slots": full, "seconds": (t1 - t0) / 1e9, "steps": len(window),
        "ahead_share": sum(r.get("ahead", 0) for r in window) / max(len(window), 1),
        "overrun": sum(r.get("overrun", 0) for r in recs["llm_step"] if t0 <= r["t_loop"] <= t1),
        "mean_live": statistics.fmean(r["live"] for r in window) if window else 0.0,
        "result_to_result_ms": (results[-1] - results[0]) / 1e6 / (len(results) - 1) if len(results) > 1 else None,
        "first_token_ms": spread_ms([r["t_first"] - r["t_admit"] for r in reqs]),
        "queue_wait_ms": spread_ms([r["t_admit"] - r["t_submit"] for r in reqs]),
        "windows_per_layer_step": moe_ratio(recs["llm_moe"], t0, t1, "windows"),
        "pairs_per_touched": moe_ratio(recs["llm_moe"], t0, t1, "pairs", "touched"),
        "kv_neighbour_share": kv_neighbour_share(recs["llm_kv_neighbours"], t0, t1),
        "stream": stream_summary(recs, t0, t1),
        **({"latent": latent_summary([r for r in recs["llm_request"] if t0 <= r["t_finish"] <= t1])} if latent else {}),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("loops", nargs="?", help="<session_dir>/loops (default: the newest session's)")
    ap.add_argument("--skip-s", type=float, default=4.0)
    ap.add_argument("--latent", action="store_true", help="the kind's decode step runs paged_latent_attention")
    args = ap.parse_args()
    where = args.loops or newest_loops_dir()
    recs = load_records(where)
    print(json.dumps({"loops": where, **summarise(recs, args.skip_s, args.latent), "start": start_summary(recs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
