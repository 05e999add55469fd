"""Continuous-batching inference engine: the in-replica serving loop.

One background thread runs the schedule vLLM popularised — prefill new
requests as decode-batch slots free up, then advance every running
sequence one token per step:

* **prefill/decode split** — each admitted request is prefilled alone at
  a power-of-two padded length (one compile per bucket), emitting its
  first token (the stream's TTFT); decode then runs at a fixed
  ``max_batch`` with inactive slots masked to the null block, so there is
  exactly ONE compiled decode step regardless of which sequences occupy
  the slots.
* **in-flight batching** — new requests join the running batch at step
  boundaries; nobody waits for a "batch" to form or drain.
* **immediate reclamation** — a sequence frees its slot and its KV blocks
  when its last step is dispatched (the host counts the rows in flight), or
  when an EOS is read; not when its batch cohort ends.
* **one step ahead** — while every live request is greedy the newest token
  of each slot stays on the device: step k+1 is enqueued before step k is
  read, a newcomer's prefill is enqueued and its first token read later, in
  device order. A sampled request puts the loop back to one step in flight
  (``_loop`` says what is in flight when).
* **KV-aware admission** — ``submit`` reserves a request's worst-case
  block need (prompt + max_new_tokens) up front; when the reservation
  cannot fit, it sheds with the serve plane's typed
  :class:`DeploymentOverloadedError` (-> HTTP 503 + Retry-After at the
  proxy) instead of queueing into a guaranteed stall. Admitted sequences
  can therefore never deadlock on allocation.

The fixed decode shape also buys schedule-invariance: a sequence's
tokens depend only on its own prompt and (seed, step) PRNG stream, never
on which neighbours share the batch — continuous batching is tokenwise
identical to isolated decode (tested).

What the loop measures of itself (schema in ``_private/looplog.py``). Every
stamp is ``time.time_ns()``, the clock a profiler trace's events are on. One
fixed-size tuple per iteration: top of the iteration, end of its prefills,
the oldest in-flight step's result on the host, end of retire, around the
dispatch, end of the iteration; then ``ahead`` (steps still in flight at the
dispatch) and ``overrun`` (rows read for sequences that had ended). Phases
follow by subtraction: ``t_admit_end - t_loop`` is the host's time to enqueue
the prefills (run ahead, nobody waits for them there; a sampled request's is
waited for), ``t_result - t_admit_end`` how long the thread waited for the
device, ``t_dispatch_end - t_result`` the host's turn, which the device waits
for only with one step in flight. What a stream waits for a token is the
series ``ray_tpu_llm_decode_step_ms``, retire to retire. A request that ends leaves a record too and, when its
caller was traced, three spans under the caller's span (``llm.queue_wait``,
``llm.prefill``, ``llm.decode``). The records ride the telemetry batches to
``<session_dir>/loops/`` and the newest stay in a bounded ring in the process
(``loop_stats``, which does its sums when read). Before the dispatch the loop
only reads the clock; records, spans and the ``ray_tpu_llm_*`` series (bound
handles) are made after it, while the device is busy. Each phase is also a
``TraceAnnotation`` (``llm.admit``, ``llm.prefill``, ``llm.retire``,
``llm.dispatch``, ``llm.emit``) on this thread's line of any profiler trace.
With ``telemetry_enabled`` off no record or span is made and the ring is empty.

How the loop came to run leaves two more kinds, neither made by the loop. The
constructors stamp a start's phases (the server's first line, the backend up,
the weights on the device, the tensors placed, the pool committed, the loop's
thread running) and the loop's thread, before its first iteration, makes one
``llm_start`` record of them and the start's one log line. Every program that
is traced, lowered, compiled or loaded from the compile cache in this process
leaves a ``compile`` record with its name, its seconds, the decode steps
dispatched so far and whether it came from the constructor (``init``), the
loop's thread (``loop``: a prefill bucket's or a decode program's first call)
or elsewhere; it comes from ``sampler``'s ``jax.monitoring`` listener, on the
thread that compiled, and feeds ``ray_tpu_llm_compile_seconds_total``. A step
that compiles nothing runs no line of this. ``loop_stats()["start"]`` has the
stamps and the records' seconds by stage and program.

A model with an expert layer (``models/longcat.py``, ``models/kimi.py``) sums what its decode
steps routed in a leaf of the pool, on the device. Once a flush interval the
loop, after its dispatch, enqueues a copy of that leaf behind the step in
flight and reads it an iteration later, when that step has been retired: no
step waits for it. Each read feeds ``ray_tpu_llm_moe_rows_total`` /
``ray_tpu_llm_moe_experts_touched_total`` / ``ray_tpu_llm_moe_peak_rows_total`` /
``ray_tpu_llm_moe_windows_total`` / ``ray_tpu_llm_moe_pairs_total`` and, with telemetry on, one loop
record of kind ``llm_moe`` (cumulative counts; ``looplog.LLM_MOE_FIELDS``).

How the dispatched sequences lay in their slots (a live sequence behind a live
one: the paged kernels hide its first copy) is two integers summed at each
dispatch: ``loop_stats()["kv_neighbours"]``, and with telemetry on one
``llm_kv_neighbours`` record a flush interval (``looplog.LLM_NEIGHBOUR_FIELDS``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import logging
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu._private import memplane, sampler, telemetry
from ray_tpu._private.looplog import (
    COMPILE_FIELDS, COMPILE_STAGES, LLM_MOE_FIELDS, LLM_REQUEST_FIELDS, LLM_START_FIELDS, LLM_STEP_FIELDS, LLM_STEP_RING_FIELDS,
    LLM_STREAM_FIELDS,
)
from ray_tpu._private.profiling import annotate
from ray_tpu.serve.exceptions import DeploymentOverloadedError
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

__all__ = ["EngineConfig", "InferenceEngine", "TokenStream"]

# engine telemetry (lazy singletons like the replica's): per-deployment
# occupancy of the two continuous-batching queues plus token/shed counters
_metrics: dict = {}


def _engine_metrics() -> dict:
    if not _metrics:
        from ray_tpu.util.metrics import Counter, Gauge, Histogram

        _metrics["running"] = Gauge(
            "ray_tpu_llm_running_seqs",
            "sequences currently holding a decode-batch slot (in-flight "
            "batching occupancy) per LLM deployment",
            tag_keys=("deployment",),
        )
        _metrics["waiting"] = Gauge(
            "ray_tpu_llm_waiting_requests",
            "admitted requests waiting for a decode slot per LLM "
            "deployment (admission-bounded; beyond it requests shed)",
            tag_keys=("deployment",),
        )
        _metrics["tokens"] = Counter(
            "ray_tpu_llm_tokens_total",
            "tokens processed by the engine per deployment and phase "
            "(prefill = prompt tokens cached, decode = tokens generated)",
            tag_keys=("deployment", "phase"),
        )
        _metrics["shed"] = Counter(
            "ray_tpu_llm_shed_total",
            "requests shed by KV-aware admission (free-block reservation "
            "or waiting-queue bound exceeded) per LLM deployment",
            tag_keys=("deployment",),
        )
        _metrics["step"] = Histogram(
            "ray_tpu_llm_decode_step_ms",
            "host wall time from the end of the previous decode step's retire "
            "(or the top of this step's dispatch, where that came later: one "
            "step in flight) to the end of this step's retire, on the "
            "monotonic clock: what a stream waits for its next token; the "
            "loop records split it (loop_stats)",
            tag_keys=("deployment",),
        )
        _metrics["moe_rows"] = Counter(
            "ray_tpu_llm_moe_rows_total",
            "(token, choice) rows the decode steps' expert layers routed, by "
            "destination: held = an expert this replica holds, zero = a "
            "zero-compute (identity) expert, absent = an expert of another "
            "chip's share (adds nothing here); summed over layers",
            tag_keys=("deployment", "dest"),
        )
        _metrics["moe_touched"] = Counter(
            "ray_tpu_llm_moe_experts_touched_total",
            "held experts that got at least one row, summed over layers and "
            "decode steps: what a step reads of its expert weights",
            tag_keys=("deployment",),
        )
        _metrics["moe_peak"] = Counter(
            "ray_tpu_llm_moe_peak_rows_total",
            "rows of the held expert that got the most, summed over layers "
            "and decode steps: over held rows / experts touched it says how "
            "uneven the router's choice leaves the load (1 = even)",
            tag_keys=("deployment",),
        )
        _metrics["moe_windows"] = Counter(
            "ray_tpu_llm_moe_windows_total",
            "windows of held rows the expert layers' grouped matmuls walked, "
            "summed over layers and decode steps: over decode steps x expert "
            "layers, 1 = every call's held rows fit its first window",
            tag_keys=("deployment",),
        )
        _metrics["moe_pairs"] = Counter(
            "ray_tpu_llm_moe_pairs_total",
            "(row tile, expert) pairs the expert layers' grouped matmuls "
            "visited, summed over layers and decode steps: over experts "
            "touched, 1 = every touched expert's weights streamed once a call",
            tag_keys=("deployment",),
        )
        _metrics["compile"] = Counter(
            "ray_tpu_llm_compile_seconds_total",
            "seconds a replica's process spent bringing programs to the "
            "device, by stage: trace, lower, compile (the backend's, which "
            "holds cache_load: an executable read from the compile cache); "
            "a start's are expected, a rise in a replica that serves is a "
            "program compiled in some request's way",
            tag_keys=("deployment", "stage"),
        )
    return _metrics


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Sizing knobs for one engine instance (one replica).

    ``num_blocks`` includes the reserved null block; usable KV capacity is
    ``(num_blocks - 1) * block_size`` tokens. ``max_waiting`` bounds the
    waiting queue BEYOND currently-free decode slots (``max_waiting=0``
    still admits straight into an idle slot) — with capacity reserved at
    admission, it is a latency bound, not a safety valve.
    """

    block_size: int = 16
    num_blocks: int = 256
    max_batch: int = 4
    max_blocks_per_seq: int = 32
    max_waiting: int = 32
    retry_after_s: float = 1.0
    stream_timeout_s: float = 120.0


@functools.lru_cache(maxsize=None)
def _take_first_token_program():
    """The one device program of the engine's own (``jit_take_first_token``):
    a prefill's first token, taken where the logits are. ``newest (B,) int32``
    with entry ``slot`` replaced by ``argmax(logits[0])``, the first maximum
    as numpy's argmax on the host picks; nothing donated, since ``newest`` is
    also a step's result that the host has still to read."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def take_first_token(newest, logits, slot):
        return newest.at[slot].set(jnp.argmax(logits[0], axis=-1).astype(newest.dtype))

    return take_first_token


# loop and request records kept in the process for loop_stats(); at 20 steps
# a second this is the last three minutes
LOOP_RING = 4096
PREFILL_BUCKET_MIN = 8  # the smallest prompt bucket; each one on is twice the last
IDLE_POLL_S = 0.05  # how long an idle loop waits before it looks again


class _Request:
    __slots__ = (
        "id",
        "prompt",
        "max_new_tokens",
        "temperature",
        "top_k",
        "seed",
        "eos_token",
        "need_blocks",
        "out",
        "ctx",  # the submitting thread's trace context (the replica's span)
        "t_submit",  # time_ns stamps; 0 = not reached
        "t_admit",
        "t_first",
        "bucket",
    )

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _Running:
    """One admitted sequence: request + block table + decode state. It holds
    a decode slot (``slot``) until its last step has been dispatched or it has
    ended; the steps in flight keep it until their rows are read."""

    __slots__ = ("req", "table", "slot", "last_token", "generated", "dispatched", "reason")

    def __init__(self, req: _Request, table: BlockTable, slot: int):
        self.req = req
        self.table = table
        self.slot: Optional[int] = slot  # None once detached: blocks released, the slot free
        self.last_token = 0  # the newest token the host has read
        self.generated = 0  # tokens the host has read
        self.dispatched = 1  # tokens read or in flight: the prefill's, then a row a step
        self.reason: Optional[str] = None  # why it ended; None while it runs


class _Step:
    """One decode step in flight: its rows ``(slot, sequence)``, its result
    still on the device, whether the greedy program ran, ``perf_counter_ns``
    at the top of its dispatch, the live KV blocks its attention reads and, of
    a kind with rings, their live rows."""

    __slots__ = ("rows", "out", "fused", "t0", "kv_blocks", "ring_rows")

    def __init__(self, rows, out, fused, t0, kv_blocks, ring_rows):
        self.rows, self.out, self.fused, self.t0, self.kv_blocks, self.ring_rows = rows, out, fused, t0, kv_blocks, ring_rows


class _First:
    """A newcomer's first token in flight: entry ``slot`` of ``vec``, the
    device's token vector as its prefill left it."""

    __slots__ = ("run", "slot", "vec")

    def __init__(self, run, slot, vec):
        self.run, self.slot, self.vec = run, slot, vec


class TokenStream:
    """Per-request consumer handle: iterate tokens as the engine emits
    them. Terminates cleanly at end-of-sequence; engine-side failures
    re-raise here (typed, never a silent hang — a stalled engine trips
    ``stream_timeout_s``).

    ``record``: where the engine has telemetry on, its ``_record``. The
    stream then follows each token out of the loop's hands (``looplog``'s
    segments ``held``, ``wake`` and ``send``): the loop's thread stamps the
    ``put`` into the queue's item beside the token's ``t_result``, the
    consumer's thread stamps ``get``'s return and its own return from the
    ``yield``, folds the three differences into count, sum and maximum, and
    writes one ``llm_stream`` record when its iterator ends, however it
    ends. Without one no stamp is made and the items carry zeros."""

    def __init__(self, request_id: int, timeout_s: float, submitted_ns: Optional[int] = None,
                 record=None, trace_id: Optional[str] = None):
        self.request_id = request_id
        self._timeout_s = timeout_s
        self._q: "queue.Queue" = queue.Queue()
        self._submitted_ns = time.time_ns() if submitted_ns is None else submitted_ns
        self._record = record
        self._trace_id = trace_id
        self.ttft_s: Optional[float] = None
        self.finish_reason: Optional[str] = None

    # engine side -------------------------------------------------------
    def _emit(self, token: int, t_ns: int = 0) -> None:
        """``t_ns``: when the token reached the host, where the engine has
        stamped it (a step's ``t_result``; the first token's ``t_first``, on
        which the request's ``llm.prefill`` span ends too)."""
        if self.ttft_s is None:
            # wall-clock stamps (the spans'): a clock set back must not go negative
            self.ttft_s = max(0, (t_ns or time.time_ns()) - self._submitted_ns) / 1e9
        self._q.put(("tok", token, t_ns, time.time_ns() if self._record is not None else 0))

    def _finish(self, reason: str) -> None:
        self._q.put(("done", reason))

    def _fail(self, error: BaseException) -> None:
        self._q.put(("err", error))

    # consumer side -----------------------------------------------------
    def _next(self) -> tuple:
        try:
            return self._q.get(timeout=self._timeout_s)
        except queue.Empty:
            raise TimeoutError(
                f"token stream {self.request_id} stalled for "
                f"{self._timeout_s:g}s"
            ) from None

    def __iter__(self):
        if self._record is not None:
            return self._iter_stamped()
        return self._iter_plain()

    def _iter_plain(self):
        while True:
            item = self._next()
            if item[0] == "tok":
                yield item[1]
            elif item[0] == "done":
                self.finish_reason = item[1]
                return
            else:
                raise item[1]

    def _iter_stamped(self):
        """``_iter_plain`` with the consumer's two stamps a token and the
        stream's record at its end. A consumer that closes the iterator at a
        ``yield`` ends that token's ``send`` there."""
        now = time.time_ns
        tokens = t_first_taken = t_back = out_since = 0
        held_n = held_sum = held_max = wake_sum = wake_max = send_n = send_sum = send_max = 0
        try:
            while True:
                item = self._next()
                if item[0] == "tok":
                    t_taken = now()
                    _kind, tok, t_result, t_put = item
                    tokens += 1
                    if tokens == 1:
                        t_first_taken = t_taken
                    if t_result:  # every token of an engine has one; a bare ``_emit(tok)`` does not
                        held_n += 1
                        held_sum += t_put - t_result
                        held_max = max(held_max, t_put - t_result)
                    wake_sum += t_taken - t_put
                    wake_max = max(wake_max, t_taken - t_put)
                    out_since = t_taken
                    yield tok
                    t_back, out_since = now(), 0
                    send_n += 1
                    send_sum += t_back - t_taken
                    send_max = max(send_max, t_back - t_taken)
                elif item[0] == "done":
                    self.finish_reason = item[1]
                    return
                else:
                    raise item[1]
        finally:
            if out_since:  # closed at the ``yield``: the token was the consumer's until now
                t_back = now()
                send_n += 1
                send_sum += t_back - out_since
                send_max = max(send_max, t_back - out_since)
            self._record((
                "t", self.request_id, self._trace_id, tokens, t_first_taken, t_back,
                held_n, held_sum, held_max, tokens, wake_sum, wake_max, send_n, send_sum, send_max,
            ))

    def tokens(self) -> List[int]:
        """Drain the stream to completion and return every token."""
        return list(self)


class InferenceEngine:
    """Continuous-batching engine over a paged KV pool (one per replica)."""

    def __init__(
        self,
        params,
        model_cfg,
        engine_cfg: Optional[EngineConfig] = None,
        *,
        deployment: str = "llm",
        start: bool = True,
        started: Optional[tuple] = None,
    ):
        """``started``: ``(t_init, t_backend)`` of the server that built this
        engine, in ``time.time_ns()``; without one the start is the engine's own."""
        now = time.time_ns
        t_init = now()
        import jax

        from ray_tpu.models import generation as G, moe, paged, paged_model

        ecfg = engine_cfg or EngineConfig()
        if ecfg.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.model_cfg = model_cfg
        self.cfg = ecfg
        self.deployment = deployment
        self._G = G
        # -- what the start leaves behind (module docstring): the listener is
        # in before the first program is traced, and every compile event of
        # this process from ``t_init`` on is this engine's, the kept ones of
        # the weights' jit among them
        sampler.install_jax_hooks()
        # resolved once: a replica builds its engine after it has connected
        self._tel = telemetry.get_buffer() if telemetry.enabled() else None
        self._stem = f"llm-{deployment}-{os.getpid()}"
        self._thread: Optional[threading.Thread] = None
        self._init_thread = threading.get_ident()
        self.decode_steps = 0  # dispatched so far: a step's number
        self._kv_neighbours = [0, 0]  # live sequences dispatched; those whose preceding slot was live too
        self._compiles: "collections.deque[tuple]" = collections.deque(maxlen=LOOP_RING)
        self._m_compile = {
            stage: _engine_metrics()["compile"].bind({"deployment": deployment, "stage": stage})
            for stage in COMPILE_STAGES.values()
        }
        t_init, t_backend = started or (t_init, now())
        self._start = dict.fromkeys(LLM_START_FIELDS, 0)
        self._start.update(t_init=t_init, t_backend=t_backend)
        sampler.set_compile_sink(self, since_ns=t_init)
        # the weights are on the device, not just dispatched there: the stamp is theirs
        jax.block_until_ready(params)
        self._start["t_params"] = now()
        # the pool is the model's to shape: its module makes it, says what a
        # block of it holds, and gives the layer the three programs run over it
        model = paged_model(model_cfg)
        # a kind whose layers keep a state a sequence (a recurrent layer's) says
        # what a row of it holds; it gets a row a decode slot, handed out with
        # the blocks and found by the programs in the table's last column
        self._state_bytes = int(model.paged_state_bytes(model_cfg)) if hasattr(model, "paged_state_bytes") else 0
        # ... and a kind whose window layers keep a ring a sequence in that row says how many rows a ring has
        # and what a row's rings hold: the loop counts the live rows a step's window attention reads
        self._rings = dict(model.paged_ring(model_cfg)) if hasattr(model, "paged_ring") else {"rows": 0, "bytes": 0}
        state_rows = ecfg.max_batch if self._state_bytes else 0
        pool_rows = state_rows + 1 if state_rows else 0  # with the null row, as the pool holds them
        self._prefill, self._decode, self._decode_greedy = paged.make_paged_fns(
            model.paged_layer, model_cfg, block_size=ecfg.block_size, state_rows=bool(state_rows)
        )
        # the weights as the kind wants them to lie on the device, before the
        # pool exists: ``params`` is the engine's from here (placed originals
        # are deleted), and every reader of ``self.params`` gets the placed tree
        self.params, self.placed = paged.place_params(model, model_cfg, params)
        self._start.update(t_placed=now(), placed=len(self.placed))
        self._pool = model.init_paged_pool(
            model_cfg, ecfg.num_blocks, ecfg.block_size, **({"state_rows": pool_rows} if pool_rows else {})
        )
        if any(x.committed for x in jax.tree.leaves(self.params)):
            # one committed argument (a placed or a sharded weight) commits a
            # program's results, the pool among them: it starts as it will
            # come back, or the first program called is lowered again when it
            # next meets the pool, inside some request's time to first token
            self._pool = jax.tree.map(lambda x: jax.device_put(x, x.sharding), self._pool)
        jax.block_until_ready(self._pool)
        self._start["t_pool"] = now()
        self._device = next(iter(jax.tree.leaves(self._pool)[0].devices()))
        self._alloc = BlockAllocator(ecfg.num_blocks, ecfg.block_size, state_rows=state_rows)
        self._slots: List[Optional[_Running]] = [None] * ecfg.max_batch
        self._waiting: "list[tuple[_Request, TokenStream]]" = []
        self._streams: Dict[int, TokenStream] = {}
        self._committed_blocks = 0
        self._ids = itertools.count()
        self._cv = threading.Condition()
        self._stop = False
        self.max_context = min(
            (ecfg.max_blocks_per_seq - bool(state_rows)) * ecfg.block_size, model_cfg.max_seq_len
        )
        self._bytes_per_block = int(model.paged_block_bytes(model_cfg, ecfg.block_size))
        self._pool_rows = pool_rows
        self._start["pool_bytes"] = ecfg.num_blocks * self._bytes_per_block + pool_rows * self._state_bytes
        # what the loop has enqueued on the device and not yet read, in device
        # order: decode steps and newcomers' first tokens (the loop's alone)
        self._flight: "collections.deque" = collections.deque()
        # the newest token of every slot, on the device: the greedy step's
        # output with newcomers' first tokens written over their slots; the
        # next step's ``tokens`` while anything is in flight
        self._newest = None
        self._take_first_token = _take_first_token_program()
        self._steps_retired = 0
        self._retired_at = 0  # perf_counter_ns at the end of the newest retire
        # a model with an expert layer sums its routing counts on the device,
        # in a leaf of the pool; the loop copies them out once a flush interval
        self._routing_counts = getattr(model, "routing_counts", None)
        self._moe_layers = getattr(model_cfg, "n_expert_layers", 0)  # the kind's: what a step's counts sum over
        self._moe_copy = None  # (the copy, still on the device; the step it was taken behind)
        self._moe_seen = [0] * len(moe.COUNTS)  # the counts last read, modulo 2**32
        self._moe_total = [0] * len(moe.COUNTS)  # ``moe.COUNTS`` since the engine started
        # -- what the loop measures of itself (module docstring) ----------
        self._ring: "collections.deque[tuple]" = collections.deque(maxlen=LOOP_RING)
        self._gauge_period_ns = int(telemetry.flush_interval_s() * 1e9)
        self._gauges_at = 0
        m, tags = _engine_metrics(), {"deployment": deployment}
        self._m_running = m["running"].bind(tags)
        self._m_waiting = m["waiting"].bind(tags)
        self._m_shed = m["shed"].bind(tags)
        self._m_step = m["step"].bind(tags)
        self._m_prefill_tokens = m["tokens"].bind({**tags, "phase": "prefill"})
        self._m_decode_tokens = m["tokens"].bind({**tags, "phase": "decode"})
        if self._routing_counts is not None:  # a model without an expert layer has no such series
            self._m_moe = [m["moe_rows"].bind({**tags, "dest": d}) for d in ("held", "zero", "absent")]
            self._m_moe += [m[name].bind(tags) for name in ("moe_touched", "moe_peak", "moe_windows", "moe_pairs")]
        # periodic device sweeps refresh the ray_tpu_kv_* gauges of an idle engine
        memplane.register_kv_provider(deployment, self._occupancy)
        if start:
            self.start()

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="llm-engine", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        """The loop's thread: the start's last stamp, its record (telemetry
        on) and its one log line, then the loop."""
        st = self._start
        st["t_ready"] = time.time_ns()
        if self._tel is not None:
            self._tel.record_loop(self._stem, ("b", *(st[k] for k in LLM_START_FIELDS)))
        spent = {stage: sum(by.values()) for stage, by in self._compile_seconds().items()}

        def s(first: str, last: str) -> float:
            return (st[last] - st[first]) / 1e9

        logger.info(
            "%s: ready %.2f s after its start: backend %.2f s, weights %.2f, placed %s in %.2f, the pool's %.3f GB "
            "(%d blocks of %d B, %d state rows of %d B, %d B of it rings) in %.2f; of it %.2f s tracing and lowering and %.2f s "
            "compiling (%.2f loading from the compile cache)",
            self.deployment, s("t_init", "t_ready"), s("t_init", "t_backend"), s("t_backend", "t_params"),
            self.placed, s("t_params", "t_placed"), st["pool_bytes"] / 1e9, self.cfg.num_blocks,
            self._bytes_per_block, self._pool_rows, self._state_bytes, self._rings["bytes"], s("t_placed", "t_pool"),
            spent["trace"] + spent["lower"], spent["compile"], spent["cache_load"],
        )
        self._loop()

    def note_compile(self, t_ns: int, seconds: float, stage: str, program: Optional[str], ident: int) -> None:
        """One compile event of this process (``sampler.set_compile_sink``),
        on the thread that compiled: the operator's series and, telemetry on,
        a ``compile`` record (``looplog.COMPILE_FIELDS``). A lock and an
        append each; never from the loop unless the loop compiled."""
        self._m_compile[stage].inc(seconds)
        if self._tel is None:
            return
        thread = self._thread
        if thread is None:
            where = "init" if ident == self._init_thread else "other"
        else:
            where = "loop" if ident == thread.ident else "other"
        rec = ("c", t_ns, seconds, stage, program, self.decode_steps, where)
        self._compiles.append(rec)
        self._tel.record_loop(self._stem, rec)

    def _compile_seconds(self) -> Dict[str, Dict[str, float]]:
        """The held ``compile`` records' seconds, stage -> program -> sum."""
        out: Dict[str, Dict[str, float]] = {stage: {} for stage in COMPILE_STAGES.values()}
        for rec in self._compiles.copy():
            d = dict(zip(COMPILE_FIELDS, rec[1:]))
            by = out[d["stage"]]
            by[d["program"]] = by.get(d["program"], 0.0) + d["seconds"]
        return out

    def shutdown(self, timeout_s: float = 10.0) -> None:
        """Stop the loop and fail any unfinished streams (typed)."""
        sampler.clear_compile_sink(self)
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
        err = RuntimeError("inference engine shut down")
        ended: List[tuple] = []
        now = time.time_ns()
        with self._cv:
            for req, stream in self._waiting:
                self._committed_blocks -= req.need_blocks
                stream._fail(err)
                ended.append((req, "shutdown", now, 1 if req.t_first else 0))
            self._waiting.clear()
            # the loop's last iteration read what was in flight; a loop that
            # did not get there in time leaves it here
            unread = [f.run for f in self._flight if type(f) is _First]
            unread += [run for f in self._flight if type(f) is _Step for _i, run in f.rows]
            self._flight.clear()
            for run in [*self._slots, *unread]:
                if run is not None and run.reason is None:
                    run.reason = "shutdown"
                    self._detach(run)
                    run.req.out._fail(err)
                    ended.append((run.req, "shutdown", now, run.generated))
        for item in ended:
            self._close_request(*item)
        self._m_running.set(0.0)
        self._m_waiting.set(0.0)
        self._refresh_kv_gauges()

    # -- admission ------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        *,
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        eos_token: Optional[int] = None,
    ) -> TokenStream:
        """Admit a request (KV-reservation admission control) and return
        its :class:`TokenStream`. Sheds with ``DeploymentOverloadedError``
        when the worst-case block need cannot be reserved or the waiting
        queue is at its bound — fast, typed, never queued into a stall."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = len(prompt) + max_new_tokens
        if total > self.max_context:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds engine context {self.max_context} "
                f"(max_blocks_per_seq x block_size, capped by max_seq_len)"
            )
        need = self._alloc.blocks_for_tokens(total)
        usable = self._alloc.num_usable
        ctx = tracing.get_current_context()  # the replica's span, if traced
        with self._cv:
            if self._stop:
                raise RuntimeError("inference engine is shut down")
            free_slots = sum(1 for s in self._slots if s is None)
            load = self._committed_blocks + need
            cause = None
            if len(self._waiting) >= self.cfg.max_waiting + free_slots:
                cause = "waiting"
            elif load > usable:
                cause = "blocks"
            if cause is None:
                req = _Request(
                    id=next(self._ids),
                    prompt=prompt,
                    max_new_tokens=int(max_new_tokens),
                    temperature=float(temperature),
                    top_k=int(top_k),
                    seed=int(seed),
                    eos_token=eos_token,
                    need_blocks=need,
                    out=None,
                    ctx=ctx,
                    t_submit=time.time_ns(),
                    t_admit=0,
                    t_first=0,
                    bucket=0,
                )
                stream = TokenStream(
                    req.id, self.cfg.stream_timeout_s, req.t_submit,
                    record=None if self._tel is None else self._record,
                    trace_id=ctx.trace_id if ctx is not None else None,
                )
                req.out = stream
                self._committed_blocks += need
                self._waiting.append((req, stream))
                self._streams[req.id] = stream
                waiting = len(self._waiting)
                self._cv.notify_all()
        if cause is not None:
            self._m_shed.inc()
            if self._tel is not None:
                now = time.time_ns()
                shed = _Request(id=-1, prompt=prompt, ctx=ctx, t_submit=now, t_admit=0, t_first=0, bucket=0)
                self._close_request(shed, "shed_" + cause, now, 0)
            raise DeploymentOverloadedError(
                deployment=self.deployment,
                retry_after_s=self.cfg.retry_after_s,
                load=load,
                capacity=usable,
            )
        self._m_waiting.set(float(waiting))
        return stream

    # -- stats ----------------------------------------------------------

    def kv_stats(self) -> Dict[str, Any]:
        """KV/batching occupancy plus where the pool lives, as JAX reports
        it — a caller can tell a replica on the chip from one that is not."""
        dev = self._device
        return {
            **self._occupancy(),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(dev.client.devices()),
            "device_peak_bytes": (dev.memory_stats() or {}).get("peak_bytes_in_use"),
        }

    def _occupancy(self) -> Dict[str, Any]:
        """Host-side KV/batching occupancy snapshot (also the memplane
        gauge source via the registered provider)."""
        usable = self._alloc.num_usable
        free = self._alloc.num_free
        with self._cv:
            running = sum(1 for s in self._slots if s is not None)
            waiting = len(self._waiting)
            committed = self._committed_blocks
        return {
            "deployment": self.deployment,
            "block_size": self.cfg.block_size,
            "blocks_total": usable,
            "blocks_free": free,
            "blocks_committed": committed,
            "occupancy": 0.0 if not usable else 1.0 - free / usable,
            "running": running,
            "waiting": waiting,
            "bytes_per_block": self._bytes_per_block,
            **self._state_rows(),
        }

    def _state_rows(self) -> Dict[str, int]:
        """The state rows of a kind that keeps a state a sequence (0 of 0 for
        every other kind), what one row holds over all layers, and how much of
        that is rings of window rows (0 for a kind without them)."""
        rows = self._alloc.state_rows
        return {"state_rows_total": rows, "state_rows_used": rows - self._alloc.state_rows_free,
                "state_bytes": self._state_bytes, "ring_bytes": self._rings["bytes"]}

    def _refresh_kv_gauges(self) -> None:
        """The ``ray_tpu_kv_*`` gauges from this engine's occupancy: the
        loop calls it at most once a telemetry flush interval (a gauge is
        read no oftener) and when it drains, shutdown once. The one metric
        call of the engine that goes through another plane's code, which
        keeps its own guard (``record_kv_occupancy`` never raises)."""
        memplane.record_kv_occupancy(self._occupancy())

    def loop_stats(self, records: int = 64) -> Dict[str, Any]:
        """The newest loop records (``records``) and request records
        (``requests``) this process holds, as tuples in the order of
        ``fields`` / ``request_fields`` with stamps in ``time.time_ns()``,
        and ``phases``: count, sum and maximum in ns of each phase over all
        the ring holds, computed here on the reader's thread. ``queue_wait``
        and ``prefill`` are per request (submit -> popped -> first token),
        ``prefill_stall`` per iteration that prefilled, ``device_wait`` and
        ``dispatch_gap`` per retired step, ``emit`` per iteration.
        ``kv_blocks`` is what the dispatched steps' attention read: count of
        steps, sum and maximum of their live KV blocks (over a step's ``live``
        x ``max_blocks_per_seq`` it is the share of the tables that is live).
        ``ahead``: count of dispatched steps and how many of them went out
        with a step still in flight (sum over count is how often the loop ran
        ahead); ``overrun``: count of retired steps and the rows of them that
        were dropped because their sequence had already ended.
        ``kv_neighbours``: count of live sequences dispatched since the engine
        started and how many of them had a live sequence in the slot before
        theirs: the paged kernels start such a sequence's first chunk during
        its predecessor's last (sum over count is how often; telemetry or not).
        ``stream``: a token's way out of the loop's hands, over the ``llm_stream``
        records the ring holds (one a stream whose iterator ended): how many
        streams, and count, sum and maximum in ns of ``held`` (result on the
        host -> ``put``), ``wake`` (``put`` -> the stream's thread has it) and
        ``send`` (-> the thread is back for the next), summed here when read.
        ``prefill`` now ends when the first token is read, which is after the
        steps that were in flight before the prefill, and ``prefill_stall`` is
        the host's time to enqueue the iteration's prefills.
        ``start``: the stamps of the engine's start (``looplog.LLM_START_FIELDS``;
        ``t_ready`` 0 until the loop's thread runs) and ``compile_s``, the
        seconds of the ``compile`` records held, stage -> program -> sum.
        Empty with ``telemetry_enabled`` off, but for ``start``'s stamps and
        ``placed``: the stacked
        tensors the engine re-laid on the device at start, name ->
        ``major_to_minor`` (``paged.place_params``; empty where it placed none),
        and ``state_rows_total|used``, ``state_bytes`` and ``ring_bytes`` (a
        kind that keeps a state a sequence; zeros for every other). A kind with
        rings has ``looplog.LLM_STEP_RING_FIELDS`` as its ``fields``: its step
        records end in ``ring_rows``."""
        ring = self._ring.copy()  # atomic against the loop's appends
        steps = [r[1:] for r in ring if r[0] == "s"]
        reqs = [r[1:] for r in ring if r[0] == "r"]
        routed = [r[1:] for r in ring if r[0] == "m"]
        streams = [dict(zip(LLM_STREAM_FIELDS, r[1:])) for r in ring if r[0] == "t"]
        spans: Dict[str, List[int]] = {
            k: [] for k in ("queue_wait", "prefill", "prefill_stall", "device_wait", "dispatch_gap", "emit")
        }
        kv: List[int] = []
        ahead: List[int] = []
        overrun: List[int] = []
        for r in steps:
            d = dict(zip(LLM_STEP_RING_FIELDS, r))  # a kind without rings: a field fewer
            if d["live"]:
                kv.append(d["kv_blocks"])
                ahead.append(d["ahead"])
            if d["t_result"]:
                overrun.append(d["overrun"])
            if d["prefills"]:
                spans["prefill_stall"].append(d["t_admit_end"] - d["t_loop"])
            if d["t_result"]:
                spans["device_wait"].append(d["t_result"] - d["t_admit_end"])
                if d["t_dispatch_end"]:
                    spans["dispatch_gap"].append(d["t_dispatch_end"] - d["t_result"])
            spans["emit"].append(d["t_emit_end"] - max(d["t_dispatch_end"], d["t_retire_end"], d["t_admit_end"]))
        for r in reqs:
            d = dict(zip(LLM_REQUEST_FIELDS, r))
            spans["queue_wait"].append((d["t_admit"] or d["t_finish"]) - d["t_submit"])
            if d["t_first"]:
                spans["prefill"].append(d["t_first"] - d["t_admit"])
        n = max(int(records), 0)
        return {
            "deployment": self.deployment,
            "fields": LLM_STEP_RING_FIELDS if self._rings["rows"] else LLM_STEP_FIELDS,
            "records": steps[-n:] if n else [],
            "request_fields": LLM_REQUEST_FIELDS,
            "requests": reqs[-n:] if n else [],
            "phases": {k: {"count": len(v), "sum_ns": sum(v), "max_ns": max(v, default=0)}
                       for k, v in spans.items()},
            "kv_blocks": {"count": len(kv), "sum": sum(kv), "max": max(kv, default=0)},
            "ahead": {"count": len(ahead), "sum": sum(ahead)},
            "overrun": {"count": len(overrun), "sum": sum(overrun)},
            "kv_neighbours": dict(zip(("count", "sum"), self._kv_neighbours)),
            "stream": {
                "streams": len(streams),
                **{seg: {"count": sum(d[seg + "_n"] for d in streams), "sum_ns": sum(d[seg + "_sum"] for d in streams),
                         "max_ns": max((d[seg + "_max"] for d in streams), default=0)}
                   for seg in ("held", "wake", "send")},
            },
            # the newest read of the expert layers' routing counts (cumulative)
            "moe": dict(zip(LLM_MOE_FIELDS, routed[-1])) if routed else None,
            # the stacked tensors re-laid on the device at start, name -> major_to_minor
            "placed": dict(self.placed),
            "start": {**self._start, "compile_s": self._compile_seconds()},
            **self._state_rows(),  # as ``kv_stats()`` has them now
        }

    def _record(self, rec: tuple) -> None:
        """One loop, request or stream record (telemetry on): into the ring,
        and on its way to ``<session_dir>/loops/``. An append and a locked
        append, from the engine thread after its dispatch, a shedding caller,
        or a stream's own thread when its iterator ends."""
        self._ring.append(rec)
        self._tel.record_loop(self._stem, rec)

    def _close_request(self, req: _Request, reason: str, t_finish: int, tokens: int) -> None:
        """A request has ended (finished, failed, shed, shut down): its
        record, and under a traced caller the spans of the phases it
        reached, as children of the caller's span. The engine thread calls
        this after its next dispatch. Nothing with telemetry off."""
        if self._tel is None:
            return
        t_admit = req.t_admit or t_finish
        ctx = req.ctx
        steps = max(0, tokens - 1)
        self._record((
            "r", req.id, req.t_submit, req.t_admit, req.t_first, t_finish,
            len(req.prompt), req.bucket, tokens, steps, reason,
            ctx.trace_id if ctx is not None else None,
        ))
        if ctx is None:
            return
        spans = [("llm.queue_wait", req.t_submit, t_admit, {})]
        if req.t_admit:
            spans.append(("llm.prefill", req.t_admit, req.t_first or t_finish,
                          {"bucket": req.bucket, "prompt_len": len(req.prompt)}))
        if req.t_first:
            spans.append(("llm.decode", req.t_first, t_finish, {"steps": steps, "tokens": tokens}))
        spans[-1][3]["finish_reason"] = reason
        for name, start, end, extra in spans:
            extra.update(
                deployment=self.deployment, request=req.id, trace_id=ctx.trace_id,
                span_id=tracing._new_id(8), parent_id=ctx.span_id,
            )
            telemetry.record_span({
                "event": name, "start": start / 1e9, "end": end / 1e9,
                "duration_ms": (end - start) / 1e6, "pid": os.getpid(),
                "task_id": None, "extra": extra,
            })

    # -- the loop -------------------------------------------------------

    def _has_active(self) -> bool:
        """A sequence holds a slot, or work the loop enqueued is still unread."""
        return bool(self._flight) or self._any_slot()

    def _any_slot(self) -> bool:
        return any(s is not None for s in self._slots)

    def _may_run_ahead(self, admits: List[tuple]) -> bool:
        """Every live request decodes greedily, this iteration's newcomers
        included, and so did the steps in flight: the next token of every slot
        is, or will be, on the device without the host's doing."""
        return not (
            any(req.temperature > 0 for _i, req in admits)
            or any(run is not None and run.req.temperature > 0 for run in self._slots)
            or any(type(f) is _Step and not f.fused for f in self._flight)
        )

    def _loop(self) -> None:
        """The scheduler. An iteration admits, retires, dispatches, emits.

        **What is in flight when.** Everything the loop enqueues on the device
        goes into ``_flight`` in device order and is read in that order: a
        decode step (its rows' next tokens) or a newcomer's first token. While
        every live request is greedy the loop runs one step ahead: with steps
        k-1 and k in flight it reads k-1, then dispatches k+1, whose ``tokens``
        argument is the on-device vector of each slot's newest token (step
        k's output, with a newcomer's first token written over its slot by
        ``take_first_token``); positions, tables and ``active`` come from the
        host, which knows them without the token values. So the device always
        has a step queued behind the one it runs, and the host's turn costs it
        nothing. A prefill is enqueued and not waited for: its first token is
        read, and handed to its stream at once, just before the result of the
        first step dispatched after it. Tokens of steps, queue wake-ups,
        series, gauges, request spans and the loop's own record come after the
        dispatch; before it the loop only reads the clock.

        **What the host knows one step late.** A sequence's last step is known
        when it is dispatched (the host counts rows in flight), so it gives
        up its slot and its blocks there, and a newcomer's prefill, enqueued
        behind that step, may take both. An EOS is seen when its step is read:
        the sequence has one more row in flight, which is discarded
        (``overrun``), inside its admission reservation.

        **Why a sampled batch is not run ahead.** ``temperature > 0`` samples
        on the host, from a row of logits: the next step's tokens exist only
        once the last step has been read. From the iteration such a request is
        admitted until it has ended the loop reads all that is in flight,
        then dispatches from the host's tokens: one step in flight, as ever.
        The loop chooses by what it sees in its slots, not by a setting."""
        now, flight = time.time_ns, self._flight
        while True:
            admits: List[tuple] = []
            with self._cv:
                while not (self._stop or self._waiting or flight or self._any_slot()):
                    self._cv.wait(IDLE_POLL_S)
                stopping = self._stop  # one last iteration reads what is in flight
                if stopping and not flight:
                    return
                t_loop = now()
                for i, slot in enumerate(self._slots):
                    if slot is None and self._waiting and not stopping:
                        req, _stream = self._waiting.pop(0)
                        req.t_admit = t_loop
                        admits.append((i, req))
            # requests that end in this iteration: (request, reason, when,
            # tokens, the error of one that failed), told and closed after the
            # dispatch and after the tokens read before they ended are out;
            # and those whose first token reached the host in it
            ended: List[tuple] = []
            firsts: List[_Request] = []
            ahead = not stopping and self._may_run_ahead(admits)
            t_admit_end = t_loop
            if admits:
                with annotate("llm.admit", requests=len(admits)):
                    for slot_idx, req in admits:
                        self._do_prefill(slot_idx, req, ahead, ended, firsts)
                t_admit_end = now()
            flying = sum(type(f) is _Step for f in flight)
            if ahead:
                # the older of two; or the last one, when no slot is left to dispatch
                steps = 1 if flying > (1 if self._any_slot() else 0) else 0
            else:
                steps = flying
            emissions: List[tuple] = []
            step_ms: List[float] = []
            t_result = t_retire_end = overrun = 0
            if flight and (steps or not flying):
                with annotate("llm.retire", step=self._steps_retired + 1):
                    t_result, overrun = self._retire(steps, ended, firsts, emissions, step_ms)
                t_retire_end = now()
            t_dispatch = t_dispatch_end = live = fused = kv_blocks = ring_rows = n_ahead = 0
            if self._any_slot() and not stopping:
                t_dispatch = now()
                n_ahead = flying - steps
                with annotate("llm.dispatch", step=self.decode_steps + 1):
                    step = self._dispatch_step(ended)
                t_dispatch_end = now()
                if step is not None:
                    live, fused, kv_blocks, ring_rows = len(step.rows), int(step.fused), step.kv_blocks, step.ring_rows
            # ---- the device is busy (or there is nothing for it to do) ----
            with annotate("llm.emit"):
                for stream, tok, t_tok in emissions:
                    stream._emit(tok, t_tok)
                for req, reason, _when, _tokens, error in ended:
                    if error is None:
                        req.out._finish(reason)  # after its final token
                    else:
                        req.out._fail(error)
                for ms in step_ms:
                    self._m_step.observe(ms)
                if emissions or firsts:
                    # each first token counts under ``decode`` too, as it always has
                    self._m_decode_tokens.inc(len(emissions) + len(firsts))
                if firsts:
                    self._m_prefill_tokens.inc(sum(len(r.prompt) for r in firsts))
                if admits or ended:
                    self._m_running.set(float(sum(1 for s in self._slots if s is not None)))
                    self._m_waiting.set(float(len(self._waiting)))
                for item in ended:
                    self._close_request(*item[:4])
                drained = not flight and not self._waiting
                if self._moe_copy is not None and (drained or self._moe_copy[1] <= self._steps_retired):
                    self._fold_routing_counts()
                if drained or t_loop - self._gauges_at >= self._gauge_period_ns:
                    self._gauges_at = t_loop
                    self._refresh_kv_gauges()
                    if self._tel is not None:  # to the loop's file alone: ``loop_stats`` reads the integers themselves
                        self._tel.record_loop(self._stem, ("n", now(), self.decode_steps, *self._kv_neighbours))
                    if self._routing_counts is not None and self._moe_copy is None:
                        self._moe_copy = (self._routing_counts(self._pool), self.decode_steps)
                        if drained:
                            self._fold_routing_counts()
            if self._tel is not None:
                self._record((
                    "s", self.decode_steps, t_loop, t_admit_end, t_result, t_retire_end,
                    t_dispatch, t_dispatch_end, now(), live, len(admits), fused, kv_blocks,
                    n_ahead, overrun, *((ring_rows,) if self._rings["rows"] else ()),
                ))
            if stopping:
                return

    def _fold_routing_counts(self) -> None:
        """Read the copy of the pool's routing counts taken at a gauge tick:
        by now the step it was enqueued behind has been retired (or nothing is
        in flight), so the read waits for no device work. The device sums
        modulo 2**32; the differences between reads add up here."""
        import numpy as np

        (copy, step), self._moe_copy = self._moe_copy, None
        now = [int(v) for v in np.asarray(copy)]
        seen, self._moe_seen = self._moe_seen, now
        for i, (a, b) in enumerate(zip(seen, now)):
            delta = (b - a) % (1 << 32)
            self._moe_total[i] += delta
            if delta:
                self._m_moe[i].inc(delta)
        if self._tel is not None:
            self._record(("m", time.time_ns(), step, *self._moe_total, self._moe_layers))

    # -- phases ---------------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = PREFILL_BUCKET_MIN
        while b < n:
            b *= 2
        return b

    def _sample(self, logits_row, req: _Request, step: int) -> int:
        """One token from one sequence's logits; the PRNG stream is keyed
        by (seed, step) only, so sampling is batch-composition invariant."""
        import numpy as np

        if req.temperature > 0:
            tok = self._G.sample_token(
                logits_row,
                temperature=req.temperature,
                top_k=req.top_k,
                key=self._G.sequence_key(req.seed, step),
            )
            return int(np.asarray(tok))
        return int(np.asarray(logits_row).argmax())

    def _detach(self, run: _Running) -> None:
        """Give up a sequence's slot, KV blocks and admission reservation,
        once: when its last step has been dispatched or it has ended, whichever
        comes first. What the device has still to do with the blocks was
        enqueued before anything their next owner will enqueue."""
        if run.slot is None:
            return
        run.table.release()  # blocks return to the pool immediately
        with self._cv:
            self._committed_blocks -= run.req.need_blocks
            self._slots[run.slot] = None
            self._cv.notify_all()
        run.slot = None

    def _fail_run(self, run: _Running, error: BaseException, ended: List[tuple]) -> None:
        """A step or a prefill of this sequence failed: its stream fails, once,
        however many of its rows are in flight (the loop tells it after the
        tokens that were read before)."""
        if run.reason is not None:
            return
        run.reason = "error"
        self._detach(run)
        self._streams.pop(run.req.id, None)
        ended.append((run.req, "error", time.time_ns(), run.generated, error))

    def _take(self, run: _Running, tok: int, ended: List[tuple]) -> None:
        """One more token of a sequence is on the host. Where it was the last
        the sequence ends: the slot is free if it was still held."""
        run.generated += 1
        run.last_token = tok
        if run.req.eos_token is not None and tok == run.req.eos_token:
            run.reason = "stop"
        elif run.generated >= run.req.max_new_tokens:
            run.reason = "length"
        else:
            return
        self._detach(run)
        self._streams.pop(run.req.id, None)
        ended.append((run.req, run.reason, time.time_ns(), run.generated, None))

    def _first_token(self, run: _Running, tok: int, ended: List[tuple], firsts: List[_Request]) -> None:
        """A request's first token is on the host: to its stream at once."""
        req = run.req
        req.t_first = time.time_ns()
        firsts.append(req)
        req.out._emit(tok, req.t_first)  # TTFT: submit -> first token
        self._take(run, tok, ended)

    def _host_tokens(self):
        """The newest token of every slot as the host has read it: the whole
        truth whenever nothing is in flight."""
        import jax.numpy as jnp
        import numpy as np

        return jnp.asarray(np.asarray([0 if run is None else run.last_token for run in self._slots], np.int32))

    def _do_prefill(
        self, slot_idx: int, req: _Request, ahead: bool, ended: List[tuple], firsts: List[_Request]
    ) -> None:
        """Enqueue a newcomer's prefill and give it the slot. Running ahead,
        its first token is taken on the device (argmax of the logits' row,
        the first maximum as numpy's) into the slot's entry of the token
        vector, and read later, in device order; else here, as the request
        samples."""
        import numpy as np
        import jax.numpy as jnp

        req.bucket = bucket = self._bucket(len(req.prompt))
        table = None
        try:
            with annotate("llm.prefill", bucket=bucket, prompt_len=len(req.prompt)):
                table = BlockTable(self._alloc)
                table.reserve(len(req.prompt))  # reserved at admission: cannot fail
                table.length = len(req.prompt)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, : len(req.prompt)] = req.prompt
                bt = np.asarray(
                    [table.as_list(self.cfg.max_blocks_per_seq)], np.int32
                )
                logits, self._pool = self._prefill(
                    self.params,
                    jnp.asarray(toks),
                    jnp.asarray(bt),
                    self._pool,
                    jnp.int32(len(req.prompt)),
                )
                if ahead:
                    base = self._newest if self._flight else self._host_tokens()
                    self._newest = self._take_first_token(base, logits, np.int32(slot_idx))
                else:
                    first = self._sample(logits[0], req, step=0)
        except BaseException as e:  # noqa: BLE001 — typed failure to the stream
            if table is not None:
                table.release()
            with self._cv:
                self._committed_blocks -= req.need_blocks
                self._streams.pop(req.id, None)
            ended.append((req, "error", time.time_ns(), 0, e))
            return
        run = _Running(req, table, slot_idx)
        self._slots[slot_idx] = run
        if not ahead:
            self._first_token(run, first, ended, firsts)
            return
        self._flight.append(_First(run, slot_idx, self._newest))
        if req.max_new_tokens <= 1:
            self._detach(run)  # no step to come: the slot is the next newcomer's

    def _dispatch_step(self, ended: List[tuple]) -> Optional["_Step"]:
        """Enqueue one decode step on the device and return without
        waiting for it. A batch where every sequence decodes greedily uses
        the fused-argmax step (B ints cross back to the host, not B x vocab
        logits); its tokens come from the device's vector while anything is
        in flight, from the host's copy otherwise. A sequence whose last row
        this is gives up its slot here."""
        import numpy as np
        import jax.numpy as jnp

        t0 = time.perf_counter_ns()
        b = self.cfg.max_batch
        mb = self.cfg.max_blocks_per_seq
        positions = np.zeros((b,), np.int32)
        tables = np.zeros((b, mb), np.int32)
        active = np.zeros((b,), bool)
        rows: List[tuple] = []
        kv_blocks = ring_rows = 0
        window = self._rings["rows"]
        fused = True
        for i, run in enumerate(self._slots):
            if run is None:
                continue
            # the input token lands at position `length`; growing the table
            # here can allocate a block — guaranteed by the admission
            # reservation to succeed
            pos = run.table.length
            run.table.append_token()
            positions[i] = pos
            tables[i] = run.table.as_list(mb)
            active[i] = True
            rows.append((i, run))
            kv_blocks += len(run.table.blocks)
            ring_rows += min(pos + 1, window)
            if run.req.temperature > 0:
                fused = False
        fn = self._decode_greedy if fused else self._decode
        try:
            out, self._pool = fn(
                self.params,
                self._newest if self._flight else self._host_tokens(),
                jnp.asarray(positions),
                jnp.asarray(tables),
                self._pool,
                jnp.asarray(active),
            )
        except BaseException as e:  # noqa: BLE001
            for _i, run in rows:
                self._fail_run(run, e, ended)
            return None
        self._newest = out if fused else None
        self.decode_steps += 1
        self._kv_neighbours[0] += len(rows)
        self._kv_neighbours[1] += int((active[1:] & active[:-1]).sum())
        step = _Step(rows, out, fused, t0, kv_blocks, ring_rows)
        self._flight.append(step)
        for _i, run in rows:
            run.dispatched += 1
            if run.dispatched >= run.req.max_new_tokens:
                self._detach(run)  # its last row is in flight
        return step

    def _retire(
        self, steps: int, ended: List[tuple], firsts: List[_Request], emissions: List[tuple],
        step_ms: List[float],
    ) -> tuple:
        """Read, in device order, the oldest ``steps`` decode steps in flight
        with the first tokens enqueued before them, and the first tokens left
        when no step is; each read blocks until the device has got there.
        Step tokens go to ``emissions`` and sequences that ended to
        ``ended``, for the loop to deliver AFTER its dispatch. Returns
        ``(t_result, overrun)``: when the last step's result was on the host
        (0: none was read, or it failed) and how many rows belonged to
        sequences that had already ended."""
        import numpy as np

        flight = self._flight
        t_result = overrun = 0
        while flight:
            head = flight[0]
            if type(head) is _Step:
                if not steps:
                    break
                steps -= 1
            elif not steps and any(type(f) is _Step for f in flight):
                break  # read with the step behind it
            flight.popleft()
            if type(head) is _First:
                if head.run.reason is None:
                    try:
                        tok = int(np.asarray(head.vec)[head.slot])
                    except BaseException as e:  # noqa: BLE001
                        self._fail_run(head.run, e, ended)
                    else:
                        self._first_token(head.run, tok, ended, firsts)
                continue
            self._steps_retired += 1
            try:
                np_out = np.asarray(head.out)  # blocks until the device step lands
            except BaseException as e:  # noqa: BLE001
                for _i, run in head.rows:
                    self._fail_run(run, e, ended)
                continue
            t_result = time.time_ns()
            for i, run in head.rows:
                if run.reason is not None:
                    overrun += 1  # it ended at an earlier read: the row is dropped
                    continue
                tok = int(np_out[i]) if head.fused else self._sample(np_out[i], run.req, step=run.generated)
                emissions.append((run.req.out, tok, t_result))
                self._take(run, tok, ended)
            # the step's series value, on the monotonic clock: what a stream
            # waited for this token, from the last retire's end (or the top of
            # this step's dispatch, where that came later) to this one's
            end = time.perf_counter_ns()
            step_ms.append((end - max(self._retired_at, head.t0)) / 1e6)
            self._retired_at = end
        return t_result, overrun
