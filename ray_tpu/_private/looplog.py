"""Loop records on disk: what the two hot loops measured of themselves, kept
where it outlives the cluster.

The serving engine keeps one record per iteration of its loop and one per
finished request; the trainer's step plane closes one record per step. Three
kinds follow a token out of the replica (``llm_stream``, ``serve_stream``) and
the controller's health probe (``serve_probe``); they are set out beside their
fields below. Two kinds say how a loop came to run: ``llm_start``, one an engine, holds the
stamps of its start from the constructor's first line to the loop's thread;
``compile``, one a ``jax.monitoring`` duration event of a program's tracing,
lowering, backend compilation or load from the compile cache, names the
program and the step it landed in, in the engine's file or the trainer's
(``sampler.install_jax_hooks`` is the one listener that makes them). They
ride the telemetry batches (``TelemetryBuffer.record_loop`` for the engine
and for both loops' ``compile`` records, the step records' own two channels
for the trainer), and the head appends them as they land to

    <session_dir>/loops/llm-<deployment>-<pid>.jsonl
    <session_dir>/loops/serve-<deployment>-<pid>.jsonl
    <session_dir>/loops/train-<run>-rank<r>.jsonl

next to ``<session_dir>/logs/``: one JSON object a line, each naming its
``kind`` and its fields, so a reader needs nothing from this package. Stamps
are ``time.time_ns()``, the clock of a profiler trace's host and device
events. ``telemetry_enabled`` off means no batches, so no files.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Iterable, Optional

# one engine loop iteration (serve/llm/engine.py ``_loop``); 0 = the phase did
# not happen in this iteration
LLM_STEP_FIELDS = (
    "step",  # decode steps dispatched so far, this one included
    "t_loop",  # top of the iteration, after any idle wait
    "t_admit_end",  # after the last prefill of the iteration
    "t_result",  # the oldest in-flight step's result is on the host
    "t_retire_end",
    "t_dispatch",
    "t_dispatch_end",  # around the call of the decode program
    "t_emit_end",  # tokens handed to the streams, series folded: end of the iteration
    "live",  # sequences in the dispatched step
    "prefills",  # prefills done in the iteration
    "fused",  # 1 = the greedy (on-device argmax) program ran
    "kv_blocks",  # live KV blocks of the dispatched sequences: what the step's attention reads
    "ahead",  # decode steps still in flight when this iteration dispatched its own: 1 = the loop ran ahead
    "overrun",  # rows of the step(s) retired in this iteration whose sequence had already ended (an EOS seen a step late)
)
# ... of a model kind that keeps a ring of window rows a sequence (``paged_ring``): one field more, which the
# records of every other kind do not carry
LLM_STEP_RING_FIELDS = LLM_STEP_FIELDS + (
    "ring_rows",  # live rows of the dispatched sequences' rings, the sum of min(length, window): what a window layer reads
)
# one finished, failed or shed request
LLM_REQUEST_FIELDS = (
    "request",
    "t_submit",
    "t_admit",  # popped from the waiting queue (0: shed, or failed before)
    "t_first",  # first token on the host (0: none)
    "t_finish",
    "prompt_len",
    "bucket",
    "tokens",
    "steps",
    "reason",  # length | stop | error | shed_blocks | shed_waiting | shutdown
    "trace_id",
)
# one read of the expert layers' routing counts (a model that has such a layer
# sums them on the device; the engine reads them once a flush interval). The
# counts (``models.moe.COUNTS``, in that order) are cumulative since the engine
# started, summed over layers and decode steps: differences between two records
# are what the steps between them did
LLM_MOE_FIELDS = (
    "t",  # when the counts reached the host
    "step",  # decode steps dispatched when the counts were copied: they cover at least these
    "held",  # (token, choice) rows sent to experts this replica holds
    "zero",  # ... to zero-compute (identity) experts
    "absent",  # ... to experts of another chip's share
    "touched",  # held experts with at least one row, a layer a step
    "peak",  # rows of the held expert that got the most, a layer a step
    "windows",  # windows of held rows the grouped matmuls walked, a layer a step (1 a layer-step: none spilled)
    "pairs",  # (row tile, expert) pairs a layer's grouped calls visited (over ``touched``: 1 = every touched expert streamed once a call)
    "layers",  # expert layers a decode step runs
)
# one read of how the dispatched sequences lay in their slots, once a flush interval: cumulative since the engine
# started. The paged kernels start a sequence's first chunk during its predecessor's last where both slots are live
# (``ops/paged_attention.py``): ``sum`` over ``count`` is the share of sequence-steps whose first copy was hidden so
LLM_NEIGHBOUR_FIELDS = (
    "t",
    "step",  # decode steps dispatched so far: the counts cover exactly these
    "count",  # live sequences dispatched, summed over steps
    "sum",  # ... whose preceding slot was live too
)
# one engine's start, made when its loop's thread starts: where the time from
# the constructor's first line to a replica that serves went
LLM_START_FIELDS = (
    "t_init",  # top of LLMServer.__init__ (of InferenceEngine.__init__ where there is no server)
    "t_backend",  # the platform is chosen and the first device is in hand
    "t_params",  # the weights are on the device (waited for, not just dispatched)
    "t_placed",  # paged.place_params done: the stacked tensors the kind names lie as it wants them
    "t_pool",  # the pool is made and committed
    "t_ready",  # the loop's thread runs
    "placed",  # tensors re-laid
    "pool_bytes",  # blocks and state rows
)
# one jax.monitoring duration event of a program on its way to the device, in
# the file of the loop its process holds. A trace nested in another program's
# (a jitted function called while an outer one is traced) is part of the outer
# one's seconds and leaves no record of its own
COMPILE_FIELDS = (
    "t",  # the event's end
    "seconds",
    "stage",  # trace | lower | compile | cache_load; ``compile`` holds its ``cache_load``
    "program",  # jax's fun_name: ``prefill`` traced, ``jit(prefill)`` lowered and compiled
    "step",  # decode steps dispatched, or the trainer's steps done, when it landed
    "where",  # init: the constructor's thread before the loop runs | loop: the loop's thread | other
)
# A token's way out of the replica, in four segments, each stamped by the thread that does the work:
#   held     the step's result on the host (``llm_step.t_result``; a first token's ``llm_request.t_first``) -> the
#            ``put`` into its stream's queue: the loop dispatching the next step before it delivers, the other rows
#   wake     the ``put`` -> ``get`` returns on the stream's own thread: GIL turns, the thread still sending the last token
#   send     ``get`` returns -> the iterator is entered again after its ``yield``: the generators above it, the
#            runtime's ``serialize_to_bytes`` and ``conn.send`` (or the head's ``generator_item``)
#   transit  just before the runtime's streaming loop sends the item -> ``ray_tpu.get(ref)`` has returned in the
#            caller's ``DeploymentResponseGenerator``: the connection, the caller's wake-up, the fetch
# ``send`` and ``transit`` overlap by the ``conn.send`` call itself; nothing is subtracted. Each segment is folded
# into count, sum and maximum (ns) where it ends; no list a token.
_SEGMENT = ("_n", "_sum", "_max")
# one ``TokenStream`` whose iterator ended (finished, failed, timed out or closed by its consumer), written by the
# stream's own thread into the engine's file
LLM_STREAM_FIELDS = (
    "request",  # the engine's id: joins ``llm_request``
    "trace_id",
    "tokens",  # taken from the queue by the consumer
    "t_first_taken",  # ``get`` returned with the first token (0: none)
    "t_last_back",  # the iterator was last entered again after a ``yield`` (0: never)
    *(seg + part for seg in ("held", "wake", "send") for part in _SEGMENT),
)
# one ``DeploymentResponseGenerator`` that ended, written by the caller's process (a worker's or the driver's own)
# into ``serve-<deployment>-<pid>.jsonl``
SERVE_STREAM_FIELDS = (
    "task",  # the stream's task id (of its last attempt), hex
    "deployment",
    "method",
    "replica",  # the actor id of the replica that served the last attempt, hex
    "items",  # in the caller's hands
    "t_first_got",
    "t_last_got",  # ``ray_tpu.get`` returned with the first and the last item (0: none)
    *("transit" + part for part in _SEGMENT),  # over the items that arrived with the sender's stamp
    "gap_max",  # the longest time between two items in the caller's hands, ns
    "attempts",  # re-dispatches after a replica's death
)
# one health probe of one replica, written by the controller (its pid) into ``serve-<deployment>-<pid>.jsonl``
SERVE_PROBE_FIELDS = (
    "t_sent",  # before ``check_health.remote()``
    "t_answered",  # its result is in the controller's hands (0: the budget ran out or the call failed)
    "budget_s",  # what the pass allowed its probes together
    "deployment",
    "replica",
)
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_KINDS = {"s": ("llm_step", LLM_STEP_RING_FIELDS), "r": ("llm_request", LLM_REQUEST_FIELDS),
          "m": ("llm_moe", LLM_MOE_FIELDS), "n": ("llm_kv_neighbours", LLM_NEIGHBOUR_FIELDS),
          "b": ("llm_start", LLM_START_FIELDS),
          "c": ("compile", COMPILE_FIELDS),
          "t": ("llm_stream", LLM_STREAM_FIELDS), "g": ("serve_stream", SERVE_STREAM_FIELDS),
          "p": ("serve_probe", SERVE_PROBE_FIELDS)}

MAX_FILE_BYTES = 32 << 20  # a file past this moves to <name>.1 (one kept)
_MAX_OPEN = 64

# where this process's head last wrote loop records; stays after shutdown so
# that whoever ran the cluster can find what it left
last_dir: Optional[str] = None


def encode(rec) -> Optional[str]:
    """An engine record tuple (tag, field values...) as a JSON line."""
    try:
        kind, fields = _KINDS[rec[0]]
        return json.dumps({"kind": kind, **dict(zip(fields, rec[1:]))})  # a step of a kind without rings: a field fewer
    except (KeyError, IndexError, TypeError, ValueError):
        return None  # telemetry batches are untrusted


class LoopLog:
    """The head's writer: bounded append files under one directory."""

    def __init__(self, session_dir: str):
        self._dir = os.path.join(session_dir, "loops")
        self._files: Dict[str, object] = {}

    def append(self, stem: str, lines: Iterable[str]) -> None:
        """Append lines to ``<stem>.jsonl`` and flush: a batch lands whole."""
        global last_dir
        text = "".join(line + "\n" for line in lines if line)
        if not text:
            return
        name = re.sub(r"[^A-Za-z0-9_.-]", "_", str(stem))[:160] + ".jsonl"
        fh = self._files.get(name)
        try:
            if fh is None:
                os.makedirs(self._dir, exist_ok=True)
                last_dir = self._dir
                if len(self._files) >= _MAX_OPEN:
                    self._files.pop(next(iter(self._files))).close()
                fh = self._files[name] = open(os.path.join(self._dir, name), "a")
            if fh.tell() + len(text) > MAX_FILE_BYTES:
                fh.close()
                path = os.path.join(self._dir, name)
                os.replace(path, path + ".1")
                fh = self._files[name] = open(path, "a")
            fh.write(text)
            fh.flush()
        except OSError:
            pass  # a full disk must not take the head's loop down

    def ingest(self, loops: Dict[str, list]) -> None:
        """One telemetry batch's engine records: stem -> [record tuple]."""
        for stem, recs in loops.items():
            self.append(stem, (encode(r) for r in recs))

    def append_train_step(self, rec: dict) -> None:
        """One decoded step record (``stepplane.decode_record``)."""
        self.append(f"train-{rec.get('run')}-rank{rec.get('rank')}",
                    (json.dumps({"kind": "train_step", **rec}),))

    def close(self) -> None:
        for fh in self._files.values():
            try:
                fh.close()
            except OSError:
                pass
        self._files.clear()
