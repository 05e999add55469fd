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
* **immediate reclamation** — a finished sequence frees its KV blocks at
  the step boundary it finishes on, not when its batch cohort ends.
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
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu.serve.exceptions import DeploymentOverloadedError
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable

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
            "wall time of one continuous-batching decode step (all active "
            "slots advance one token) per LLM deployment",
            tag_keys=("deployment",),
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
    prefill_bucket_min: int = 8
    idle_poll_s: float = 0.05
    stream_timeout_s: float = 120.0


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
        "submitted_at",
    )

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _Running:
    """One occupied decode slot: request + block table + decode state."""

    __slots__ = ("req", "table", "last_token", "generated")

    def __init__(self, req: _Request, table: BlockTable, first_token: int):
        self.req = req
        self.table = table
        self.last_token = first_token
        self.generated = 1


class TokenStream:
    """Per-request consumer handle: iterate tokens as the engine emits
    them. Terminates cleanly at end-of-sequence; engine-side failures
    re-raise here (typed, never a silent hang — a stalled engine trips
    ``stream_timeout_s``)."""

    def __init__(self, request_id: int, timeout_s: float):
        self.request_id = request_id
        self._timeout_s = timeout_s
        self._q: "queue.Queue" = queue.Queue()
        self._submitted_at = time.perf_counter()
        self.ttft_s: Optional[float] = None
        self.finish_reason: Optional[str] = None

    # engine side -------------------------------------------------------
    def _emit(self, token: int) -> None:
        if self.ttft_s is None:
            self.ttft_s = time.perf_counter() - self._submitted_at
        self._q.put(("tok", token))

    def _finish(self, reason: str) -> None:
        self._q.put(("done", reason))

    def _fail(self, error: BaseException) -> None:
        self._q.put(("err", error))

    # consumer side -----------------------------------------------------
    def __iter__(self):
        while True:
            try:
                kind, payload = self._q.get(timeout=self._timeout_s)
            except queue.Empty:
                raise TimeoutError(
                    f"token stream {self.request_id} stalled for "
                    f"{self._timeout_s:g}s"
                ) from None
            if kind == "tok":
                yield payload
            elif kind == "done":
                self.finish_reason = payload
                return
            else:
                raise payload

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
    ):
        from ray_tpu.models import generation as G

        ecfg = engine_cfg or EngineConfig()
        if ecfg.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.params = params
        self.model_cfg = model_cfg
        self.cfg = ecfg
        self.deployment = deployment
        self._G = G
        self._prefill, self._decode, self._decode_greedy = G.make_paged_fns(
            model_cfg, block_size=ecfg.block_size
        )
        self._pool = G.init_paged_pool(model_cfg, ecfg.num_blocks, ecfg.block_size)
        self._device = next(iter(self._pool["k"].devices()))
        self._alloc = BlockAllocator(ecfg.num_blocks, ecfg.block_size)
        self._slots: List[Optional[_Running]] = [None] * ecfg.max_batch
        self._waiting: "list[tuple[_Request, TokenStream]]" = []
        self._streams: Dict[int, TokenStream] = {}
        self._committed_blocks = 0
        self._ids = itertools.count()
        self._cv = threading.Condition()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.max_context = min(
            ecfg.max_blocks_per_seq * ecfg.block_size, model_cfg.max_seq_len
        )
        self._register_kv_provider()
        if start:
            self.start()

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="llm-engine", daemon=True
            )
            self._thread.start()

    def shutdown(self, timeout_s: float = 10.0) -> None:
        """Stop the loop and fail any unfinished streams (typed)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
        err = RuntimeError("inference engine shut down")
        with self._cv:
            for req, stream in self._waiting:
                self._committed_blocks -= req.need_blocks
                stream._fail(err)
            self._waiting.clear()
            for i, run in enumerate(self._slots):
                if run is not None:
                    run.table.release()
                    self._committed_blocks -= run.req.need_blocks
                    run.req.out._fail(err)
                    self._slots[i] = None
        self._update_gauges()

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
        with self._cv:
            if self._stop:
                raise RuntimeError("inference engine is shut down")
            free_slots = sum(1 for s in self._slots if s is None)
            overloaded = (
                len(self._waiting) >= self.cfg.max_waiting + free_slots
                or self._committed_blocks + need > usable
            )
            if overloaded:
                try:
                    _engine_metrics()["shed"].inc(
                        tags={"deployment": self.deployment}
                    )
                except Exception:
                    pass
                raise DeploymentOverloadedError(
                    deployment=self.deployment,
                    retry_after_s=self.cfg.retry_after_s,
                    load=self._committed_blocks + need,
                    capacity=usable,
                )
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
                submitted_at=time.perf_counter(),
            )
            stream = TokenStream(req.id, self.cfg.stream_timeout_s)
            req.out = stream
            self._committed_blocks += need
            self._waiting.append((req, stream))
            self._streams[req.id] = stream
            self._cv.notify_all()
        self._update_gauges()
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
        bytes_per_block = 0
        try:
            k = self._pool["k"]
            bytes_per_block = int(
                k.dtype.itemsize * 2 * k.shape[0] * self.cfg.block_size
                * k.shape[2] * k.shape[3]
            )
        except Exception:
            pass
        return {
            "deployment": self.deployment,
            "block_size": self.cfg.block_size,
            "blocks_total": usable,
            "blocks_free": free,
            "blocks_committed": committed,
            "occupancy": 0.0 if not usable else 1.0 - free / usable,
            "running": running,
            "waiting": waiting,
            "bytes_per_block": bytes_per_block,
        }

    def _register_kv_provider(self) -> None:
        try:
            from ray_tpu._private import memplane

            memplane.register_kv_provider(self.deployment, self._occupancy)
        except Exception:
            pass

    def _update_gauges(self) -> None:
        try:
            stats = self._occupancy()
            m = _engine_metrics()
            tags = {"deployment": self.deployment}
            m["running"].set(float(stats["running"]), tags=tags)
            m["waiting"].set(float(stats["waiting"]), tags=tags)
            from ray_tpu._private import memplane

            memplane.record_kv_occupancy(stats)
        except Exception:
            pass

    # -- the loop -------------------------------------------------------

    def _has_active(self) -> bool:
        return any(s is not None for s in self._slots)

    def _loop(self) -> None:
        """One-step-pipelined scheduler: step k+1 is dispatched to the
        device BEFORE step k's tokens are emitted to consumers, so queue
        wakeups, gauge updates, and next-iteration admissions overlap
        device compute instead of extending the step critical path."""
        inflight = None
        while True:
            admits: List[tuple] = []
            with self._cv:
                while (
                    not self._stop
                    and not self._waiting
                    and not self._has_active()
                    and inflight is None
                ):
                    self._cv.wait(self.cfg.idle_poll_s)
                if self._stop:
                    return
                for i, slot in enumerate(self._slots):
                    if slot is None and self._waiting:
                        admits.append((i, *self._waiting.pop(0)))
            for slot_idx, req, stream in admits:
                self._do_prefill(slot_idx, req, stream)
            emissions: List[tuple] = []
            finishes: List[tuple] = []
            if inflight is not None:
                emissions, finishes = self._retire_step(inflight)
                inflight = None
            # finished slots detach (blocks freed) before the next
            # dispatch; their streams see the 'done' marker after their
            # final token below
            for slot_idx, _run, _reason in finishes:
                self._detach_slot(slot_idx)
            if self._has_active():
                inflight = self._dispatch_step()
            for stream, tok in emissions:
                stream._emit(tok)
            for _slot_idx, run, reason in finishes:
                run.req.out._finish(reason)
            if admits or emissions or finishes:
                self._update_gauges()

    # -- phases ---------------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = max(int(self.cfg.prefill_bucket_min), 1)
        while b < n:
            b *= 2
        return b

    def _sample(self, logits_row, req: _Request, step: int) -> int:
        """One token from one sequence's logits; the PRNG stream is keyed
        by (seed, step) only, so sampling is batch-composition invariant."""
        import numpy as np

        if req.temperature and req.temperature > 0:
            tok = self._G.sample_token(
                logits_row,
                temperature=req.temperature,
                top_k=req.top_k,
                key=self._G.sequence_key(req.seed, step),
            )
            return int(np.asarray(tok))
        return int(np.asarray(logits_row).argmax())

    def _detach_slot(self, slot_idx: int) -> None:
        """Free a finished slot's KV blocks + admission reservation (the
        stream's 'done' marker is the caller's job, ordered after the
        final token emission)."""
        run = self._slots[slot_idx]
        run.table.release()  # blocks return to the pool immediately
        with self._cv:
            self._committed_blocks -= run.req.need_blocks
            self._slots[slot_idx] = None
            self._streams.pop(run.req.id, None)
            self._cv.notify_all()

    def _finish(self, slot_idx: int, reason: str) -> None:
        run = self._slots[slot_idx]
        self._detach_slot(slot_idx)
        run.req.out._finish(reason)

    def _fail_slot(self, slot_idx: int, error: BaseException) -> None:
        run = self._slots[slot_idx]
        run.table.release()
        with self._cv:
            self._committed_blocks -= run.req.need_blocks
            self._slots[slot_idx] = None
            self._streams.pop(run.req.id, None)
        run.req.out._fail(error)

    def _do_prefill(self, slot_idx: int, req: _Request, stream: TokenStream) -> None:
        import numpy as np
        import jax.numpy as jnp

        try:
            table = BlockTable(self._alloc)
            table.reserve(len(req.prompt))  # reserved at admission: cannot fail
            table.length = len(req.prompt)
            bucket = self._bucket(len(req.prompt))
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
            first = self._sample(logits[0], req, step=0)
        except BaseException as e:  # noqa: BLE001 — typed failure to the stream
            try:
                table.release()
            except Exception:
                pass
            with self._cv:
                self._committed_blocks -= req.need_blocks
                self._streams.pop(req.id, None)
            stream._fail(e)
            return
        try:
            _engine_metrics()["tokens"].inc(
                len(req.prompt),
                tags={"deployment": self.deployment, "phase": "prefill"},
            )
            _engine_metrics()["tokens"].inc(
                tags={"deployment": self.deployment, "phase": "decode"}
            )
        except Exception:
            pass
        run = _Running(req, table, first)
        self._slots[slot_idx] = run
        stream._emit(first)  # TTFT: admission -> first token
        if self._is_done(run, first):
            self._finish(slot_idx, self._done_reason(run, first))

    def _is_done(self, run: _Running, token: int) -> bool:
        return (
            run.generated >= run.req.max_new_tokens
            or (run.req.eos_token is not None and token == run.req.eos_token)
        )

    def _done_reason(self, run: _Running, token: int) -> str:
        if run.req.eos_token is not None and token == run.req.eos_token:
            return "stop"
        return "length"

    def _dispatch_step(self):
        """Enqueue one decode step on the device and return without
        waiting for it. A batch where every sequence decodes greedily
        uses the fused-argmax step (B ints cross back to the host, not
        B x vocab logits)."""
        import numpy as np
        import jax.numpy as jnp

        t0 = time.perf_counter()
        b = self.cfg.max_batch
        mb = self.cfg.max_blocks_per_seq
        tokens = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        tables = np.zeros((b, mb), np.int32)
        active = np.zeros((b,), bool)
        live: List[int] = []
        fused = True
        for i, run in enumerate(self._slots):
            if run is None:
                continue
            # the input token lands at position `length`; growing the table
            # here can allocate a block — guaranteed by the admission
            # reservation to succeed
            pos = run.table.length
            run.table.append_token()
            tokens[i] = run.last_token
            positions[i] = pos
            tables[i] = run.table.as_list(mb)
            active[i] = True
            live.append(i)
            if run.req.temperature and run.req.temperature > 0:
                fused = False
        fn = self._decode_greedy if fused else self._decode
        try:
            out, self._pool = fn(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(positions),
                jnp.asarray(tables),
                self._pool,
                jnp.asarray(active),
            )
        except BaseException as e:  # noqa: BLE001
            for i in list(live):
                self._fail_slot(i, e)
            return None
        return (live, out, fused, t0)

    def _retire_step(self, inflight) -> tuple:
        """Block on the in-flight step's result and fold it into the run
        states. Returns ``(emissions, finishes)`` for the loop to deliver
        AFTER it dispatches the next step."""
        import numpy as np

        live, out, fused, t0 = inflight
        try:
            np_out = np.asarray(out)  # blocks until the device step lands
        except BaseException as e:  # noqa: BLE001
            for i in list(live):
                if self._slots[i] is not None:
                    self._fail_slot(i, e)
            return [], []
        emissions: List[tuple] = []
        finishes: List[tuple] = []
        for i in live:
            run = self._slots[i]
            if fused:
                tok = int(np_out[i])
            else:
                tok = self._sample(np_out[i], run.req, step=run.generated)
            run.generated += 1
            run.last_token = tok
            emissions.append((run.req.out, tok))
            if self._is_done(run, tok):
                finishes.append((i, run, self._done_reason(run, tok)))
        try:
            tags = {"deployment": self.deployment}
            m = _engine_metrics()
            m["tokens"].inc(len(emissions), tags={**tags, "phase": "decode"})
            m["step"].observe((time.perf_counter() - t0) * 1e3, tags=tags)
        except Exception:
            pass
        return emissions, finishes
