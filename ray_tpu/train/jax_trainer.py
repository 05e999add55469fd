"""JaxTrainer: the DataParallelTrainer equivalent for TPU.

Parity: ``DataParallelTrainer`` (``python/ray/train/data_parallel_trainer.py:25``)
+ ``TorchTrainer`` fit path (SURVEY.md §3.4). Differences by design:

* within one host no process-group rendezvous is needed — the train loop
  builds a mesh and jits; collectives are in-program over ICI. For a mesh
  *spanning* hosts, ``ScalingConfig(use_jax_distributed=True)`` makes each
  worker join a ``jax.distributed`` coordination service (rendezvous over
  the cluster KV) before the user loop runs — the TPU-native replacement
  for ``_setup_torch_process_group`` (``torch/config.py:65``);
* ``ScalingConfig(topology=...)`` turns into a slice-aware placement group;
* checkpoints are orbax pytrees behind the same dir-of-files ``Checkpoint``.
"""

from __future__ import annotations

import os
import time
import uuid
from typing import Any, Callable, Dict, Optional

from ray_tpu.train._backend_executor import BackendExecutor
from ray_tpu.train._checkpoint import Checkpoint
from ray_tpu.train._config import RunConfig, ScalingConfig
from ray_tpu.train._result import Result


def _retry_backoff(attempt: int, fail_cfg) -> float:
    """Delay before gang-restart ``attempt`` (1-based): exponential from
    ``retry_backoff_s`` capped at ``retry_backoff_max_s``, with +/-
    ``retry_backoff_jitter`` fraction of randomization so crash-looping
    gangs desynchronize instead of hammering the scheduler in lockstep."""
    import random

    base = max(0.0, fail_cfg.retry_backoff_s)
    delay = base * (2 ** max(0, attempt - 1))
    jitter = min(1.0, max(0.0, fail_cfg.retry_backoff_jitter))
    if jitter:
        delay *= 1.0 + random.uniform(-jitter, jitter)
    # the cap is applied LAST: retry_backoff_max_s is a hard bound an
    # operator can rely on, jitter included
    return max(0.0, min(fail_cfg.retry_backoff_max_s, delay))


def _setup_jax_distributed(rendezvous_key: str) -> bool:
    """Join the jax.distributed coordination service (backend ``on_start``).

    Rank 0 publishes ``ip:port`` through the cluster KV; every worker calls
    ``jax.distributed.initialize`` against it. Afterwards ``jax.devices()``
    is the global device set across the worker group.
    """
    from ray_tpu._private.worker import get_runtime
    from ray_tpu.parallel import distributed as dist
    from ray_tpu.train._session import get_context
    from ray_tpu.train.torch_trainer import _node_ip

    ctx = get_context()
    rank, world = ctx.get_world_rank(), ctx.get_world_size()
    if world <= 1:
        return False
    rt = get_runtime()
    coord = dist.rendezvous_via_kv(
        rt, rendezvous_key, rank, world, node_ip=_node_ip()
    )
    dist.initialize(coord, num_processes=world, process_id=rank)
    return True


def _teardown_jax_distributed(rendezvous_key: str) -> None:
    from ray_tpu._private.worker import get_runtime
    from ray_tpu.parallel import distributed as dist
    from ray_tpu.train._session import get_context

    try:
        # best-effort: rank 0 finishing first tears down the coordination
        # service, so a slower rank's shutdown may raise — that must never
        # overwrite a successful training result
        dist.shutdown()
    except Exception:
        pass
    try:
        if get_context().get_world_rank() == 0:
            dist.release_rendezvous(get_runtime(), rendezvous_key)
    except Exception:
        pass


class JaxTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self.train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_from_checkpoint = resume_from_checkpoint
        self.train_loop = self._wrap(
            train_loop_per_worker, self.scaling_config.use_jax_distributed
        )

    @staticmethod
    def _wrap(user_fn: Callable, distributed: bool) -> Callable:
        """The loop as a worker runs it: (join jax.distributed,) check that
        JAX gives the worker the device its resources name, run, leave."""
        base_key = f"jaxdist_{uuid.uuid4().hex[:12]}"

        def wrapped(config=None):
            import inspect

            from ray_tpu.train.jax_utils import ensure_platform

            # fit() injects a per-attempt suffix so a retry never rendezvous
            # against the dead coordinator a failed attempt left in the KV
            if isinstance(config, dict):
                key = f"{base_key}_{config.pop('__jaxdist_attempt__', 0)}"
            else:
                key = base_key
            # join BEFORE the check: it starts the backend, and
            # jax.distributed.initialize refuses once one exists
            joined = distributed and _setup_jax_distributed(key)
            try:
                ensure_platform()
                if config is not None and len(inspect.signature(user_fn).parameters):
                    return user_fn(config)
                return user_fn()
            finally:
                if joined:
                    _teardown_jax_distributed(key)

        return wrapped

    def fit(self) -> Result:
        from ray_tpu.train import checkpointing

        name = self.run_config.name or f"JaxTrainer_{time.strftime('%Y%m%d_%H%M%S')}"
        # external storage: train into a local staging dir, mirror each
        # checkpoint out through the commit protocol (parity: the
        # reference's storage_path sync to FS/S3)
        trial_dir, storage_uri = checkpointing.resolve_staging(
            self.run_config.resolved_storage_path(), name, kind="trial"
        )
        os.makedirs(trial_dir, exist_ok=True)

        ckpt_cfg = self.run_config.checkpoint_config
        manager = checkpointing.CheckpointManager(
            trial_dir,
            storage_uri=storage_uri,
            world_size=self.scaling_config.num_workers,
            keep=ckpt_cfg.num_to_keep,
            run_name=name,
            score_attribute=ckpt_cfg.checkpoint_score_attribute,
            score_order=ckpt_cfg.checkpoint_score_order,
        )
        executor = BackendExecutor(self.scaling_config, self.run_config, trial_dir)
        last: Dict[str, Any] = {}

        def on_report(rank, iteration, metrics, ckpt_path):
            if rank == 0:
                last.clear()
                last.update(metrics)
                last["training_iteration"] = iteration
            # shard barrier: once all world ranks have landed a shard for
            # this step — or every rank has reported it and at least one
            # brought a shard (rank-0-only checkpointing) — the manager
            # commits (manifest + COMMIT) in its background uploader;
            # train.report never waits on it
            manager.note_report(
                rank,
                iteration,
                ckpt_path or None,
                metrics=metrics if rank == 0 else None,
            )

        fail_cfg = self.run_config.failure_config
        max_failures = fail_cfg.max_failures
        attempt = 0
        error: Optional[Exception] = None
        train_fn = self.train_loop
        config = self.train_loop_config
        if self.datasets:
            config = dict(config or {})
            config["__datasets__"] = self.datasets

        def resume_fn():
            # every (re-)dispatch resumes from the latest COMMITTED step —
            # never from a partial, uncommitted upload
            return manager.latest_checkpoint() or self.resume_from_checkpoint

        def prepare_resume():
            # MUST fully drain before ranks rewrite the same step dirs a
            # still-running commit may be hashing, and a dead attempt's
            # half-complete barrier must not bleed into the resumed one.
            # The wait is bounded: a wedged mirror must surface as a
            # CheckpointDrainError (failing the attempt/run), not hang
            # recovery forever — proceeding without the drain could tear a
            # committed-looking dir, so failing is the only safe exit.
            drain_timeout = self.run_config.checkpoint_config.drain_timeout_s
            if not manager.wait(timeout=drain_timeout):
                raise checkpointing.CheckpointDrainError(
                    manager.pending_steps(), drain_timeout
                )
            manager.reset_barrier()

        try:
            while True:
                try:
                    executor.start()
                    # auto-resume via resume_fn; the FIRST attempt honors an
                    # explicit resume_from_checkpoint even when the (reused)
                    # trial dir holds older commits.
                    if attempt == 0 and self.resume_from_checkpoint is not None:
                        latest = self.resume_from_checkpoint
                    else:
                        latest = resume_fn()
                    run_config = config
                    if self.scaling_config.use_jax_distributed:
                        # per-attempt rendezvous key suffix (see _wrap)
                        run_config = dict(config or {})
                        run_config["__jaxdist_attempt__"] = attempt
                    executor.run(
                        train_fn,
                        run_config,
                        latest_ckpt=latest,
                        report_callback=on_report,
                        resume_fn=resume_fn,
                        prepare_resume=prepare_resume,
                        on_resize=manager.resize,
                        attempt_tag=attempt,
                        run_name=name,
                    )
                    error = None
                    break
                except Exception as e:  # noqa: BLE001
                    error = e
                    attempt += 1
                    # downtime ledger: the whole teardown -> backoff ->
                    # restart window is attributed (closed by the restarted
                    # attempt's first dispatch)
                    executor.open_downtime(
                        "gang_restart",
                        detail=f"attempt {attempt}: {type(e).__name__}",
                    )
                    executor.shutdown()
                    try:
                        prepare_resume()
                    except checkpointing.CheckpointDrainError as de:
                        # the plane is wedged: retrying would hit the same
                        # wall — surface the drain failure and stop, with
                        # the attempt's real error preserved as the cause
                        de.__cause__ = error
                        error = de
                        break
                    # an elastic shrink may have left the barrier at M <
                    # num_workers; the fresh gang is full-size again, and a
                    # short barrier would commit torn (M-of-N-shard) steps
                    manager.resize(self.scaling_config.num_workers)
                    if max_failures != -1 and attempt > max_failures:
                        break
                    try:
                        from ray_tpu.train._backend_executor import _get_metrics

                        _get_metrics()["restarts"].inc(tags={"kind": "gang"})
                    except Exception:
                        pass
                    time.sleep(_retry_backoff(attempt, fail_cfg))
                finally:
                    executor.shutdown()
        finally:
            # drain the upload queue before returning: fit()'s contract is
            # that every fully-reported checkpoint is committed (or failed
            # loudly) by the time the Result exists — and a drain that
            # TIMES OUT must never return looking fully committed
            drain_timeout = self.run_config.checkpoint_config.drain_timeout_s
            drain_t0 = time.monotonic()
            drained = manager.wait(timeout=drain_timeout)
            drain_s = time.monotonic() - drain_t0
            if drain_s > 0.05:
                # blocking on uncommitted uploads at teardown is downtime
                # the goodput ledger must attribute (PR-5 commit spans show
                # the same window from the storage side)
                executor.add_downtime(
                    "checkpoint_drain", drain_s, detail="fit() teardown drain"
                )
            if not drained:
                from ray_tpu.train._backend_executor import _record_event

                undrained = manager.pending_steps()
                _record_event(
                    "CHECKPOINT_FAILED",
                    f"run {name}: checkpoint drain timed out after "
                    f"{drain_timeout:.0f}s with steps {undrained} still "
                    f"uncommitted",
                    severity="ERROR",
                    run=name,
                    undrained_steps=undrained,
                )
                drain_err = checkpointing.CheckpointDrainError(
                    undrained, drain_timeout
                )
                if error is None:
                    error = drain_err
                else:
                    # the run already failed; ride along as context
                    error.checkpoint_drain_error = drain_err
            manager.shutdown(wait=False)

        best = manager.latest_checkpoint()
        # a terminally-failed attempt can leave its gang_restart/recovery
        # window open (the break skips the dispatch that would close it):
        # close it now so downtime_s == sum(ledger) in the final stats
        executor._close_downtime()
        goodput = executor.goodput_stats()
        goodput["downtime_ledger"] = executor.downtime_ledger()
        # final publication: the run's terminal status + complete ledger
        # land in the scheduler's StepIndex (state.train_run / dashboard)
        executor._push_run_meta(
            name, status="failed" if error is not None else "finished"
        )
        executor._publish_goodput(name)
        return Result(
            metrics=dict(last),
            checkpoint=best,
            path=trial_dir,
            error=error,
            goodput=goodput,
        )
