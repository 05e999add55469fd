"""DataIterator: the per-trainer-worker consumption handle.

Parity: ``python/ray/data/iterator.py`` (``DataIterator.iter_batches``,
``to_tf``/``to_torch`` analogues) — plus ``iter_jax_batches`` which
``device_put``s each batch with an optional sharding, the TPU feed path
(SURVEY.md §7 step 5: blocks -> iter_batches -> device_put sharded).

This is also the training step plane's ingest seam: when a step timer is
active (``_private/stepplane``), time spent blocked in ``next()`` lands in
the step's ``data_wait`` stage — attributed to the bottleneck streaming-
executor operator via the pipeline's live backpressure stats — the
``device_put`` in ``iter_jax_batches`` in ``host_to_device``, and every
batch's abstract-shape signature feeds the recompilation detector.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterator, Optional

import numpy as np

# ingest stalls shorter than this are loop noise, not backpressure — they
# accrue to data_wait but skip the per-operator attribution walk
_ATTRIBUTE_STALL_S = 0.002


class DataIterator:
    def __init__(self, dataset):
        self._ds = dataset

    def _bottleneck_operator(self) -> str:
        """The streaming-executor stage the consumer is most plausibly
        waiting on RIGHT NOW: the stage with the deepest in-flight window
        (its backpressure queue is where the pipeline's slack went). Falls
        back to "source" when the dataset has no live execution stats
        (materialized datasets, plain block lists)."""
        stats = getattr(self._ds, "_exec_stats", None) or ()
        best, depth = None, 0
        for st in stats:
            try:
                inflight = st.inflight
            except Exception:
                continue
            if inflight > depth:
                best, depth = st.name, inflight
        return best or "source"

    def iter_batches(self, *, batch_size: int = 256, drop_last: bool = False):
        from ray_tpu._private import stepplane
        from ray_tpu._private.profiling import annotate

        it = iter(
            self._ds.iter_batches(batch_size=batch_size, drop_last=drop_last)
        )
        while True:
            timer = stepplane.current()  # re-read: a step may start mid-iter
            t0 = time.perf_counter()
            try:
                with annotate("train.data_wait"):
                    batch = next(it)
            except StopIteration:
                return
            if timer is not None:
                wait = time.perf_counter() - t0
                timer.note_data_wait(
                    wait,
                    self._bottleneck_operator()
                    if wait >= _ATTRIBUTE_STALL_S
                    else None,
                )
                timer.note_batch_signature(stepplane.batch_signature(batch))
            yield batch

    def iter_rows(self):
        return self._ds.iter_rows()

    def count(self) -> int:
        return self._ds.count()

    def materialize(self):
        return self._ds.materialize()

    def iter_jax_batches(
        self,
        *,
        batch_size: int = 256,
        drop_last: bool = True,
        sharding: Optional[Any] = None,
        dtypes: Optional[Dict[str, Any]] = None,
    ) -> Iterator[Dict[str, Any]]:
        """Batches as (optionally sharded) jax Arrays on device."""
        import jax

        from ray_tpu._private import stepplane
        from ray_tpu._private.profiling import annotate

        for batch in self.iter_batches(batch_size=batch_size, drop_last=drop_last):
            t0 = time.perf_counter()
            out = {}
            with annotate("train.host_to_device"):
                for k, v in batch.items():
                    arr = np.asarray(v)
                    if dtypes and k in dtypes:
                        arr = arr.astype(dtypes[k])
                    out[k] = jax.device_put(arr, sharding) if sharding is not None else jax.device_put(arr)
            timer = stepplane.current()
            if timer is not None:
                timer.note_host_to_device(time.perf_counter() - t0)
            yield out

    def iter_tf_batches(
        self,
        *,
        batch_size: int = 256,
        drop_last: bool = False,
        dtypes: Optional[Dict[str, Any]] = None,
    ) -> Iterator[Dict[str, Any]]:
        """Batches as tf tensors (parity: ``iter_tf_batches``)."""
        import tensorflow as tf

        for batch in self.iter_batches(batch_size=batch_size, drop_last=drop_last):
            out = {}
            for k, v in batch.items():
                t = tf.convert_to_tensor(np.asarray(v))
                if dtypes and k in dtypes:
                    t = tf.cast(t, dtypes[k])
                out[k] = t
            yield out

    def iter_torch_batches(
        self,
        *,
        batch_size: int = 256,
        drop_last: bool = False,
        dtypes: Optional[Dict[str, Any]] = None,
        device: Optional[str] = None,
    ) -> Iterator[Dict[str, Any]]:
        """Batches as torch tensors (parity: ``iter_torch_batches``)."""
        import torch

        for batch in self.iter_batches(batch_size=batch_size, drop_last=drop_last):
            out = {}
            for k, v in batch.items():
                t = torch.as_tensor(np.asarray(v))
                if dtypes and k in dtypes:
                    t = t.to(dtypes[k])
                if device:
                    t = t.to(device)
                out[k] = t
            yield out
