"""Multi-process SPMD learner group.

Parity: ``rllib/core/learner/learner_group.py:154-174`` — N learner workers
updating one policy. TPU-first redesign: instead of N torch-DDP processes
exchanging NCCL allreduces, each learner worker (an actor, typically one per
host/slice) joins a ``jax.distributed`` coordination service; the update is
then ONE jitted SPMD program whose mesh spans every worker's devices — XLA
places the gradient reductions on ICI (gloo on the virtual-CPU test path).

Driver protocol per step: split the host batch into per-process shards along
the env axis and invoke ``update`` on every worker concurrently; the workers
gang-execute the program. Rank 0 returns metrics and (refreshed) host params
for the env runners.

Fault tolerance (parity: learner-group restart in
``train/_internal/backend_executor.py``): a worker death surfaces as a failed
``update`` round; :meth:`restart` tears the group down, re-rendezvous under a
fresh attempt-suffixed key, and restores the last known params.
"""

from __future__ import annotations

import uuid
from typing import Any, Dict, List, Optional

import numpy as np

import ray_tpu
from ray_tpu import exceptions as exc


@ray_tpu.remote
class SPMDLearnerWorker:
    """One learner process; rank 0 is the metrics/params endpoint."""

    def __init__(self, rank: int, world: int, rdzv_key: str, builder_config: dict):
        from ray_tpu._private.worker import get_runtime
        from ray_tpu.parallel import distributed as dist
        from ray_tpu.train.jax_utils import ensure_platform

        self.rank, self.world = rank, world
        if world > 1:
            rt = get_runtime()
            coord = dist.rendezvous_via_kv(rt, rdzv_key, rank, world)
            dist.initialize(coord, num_processes=world, process_id=rank)
        ensure_platform()  # after the join: it starts the backend
        self._build(builder_config)

    def _build(self, bc: dict) -> None:
        import jax
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ray_tpu.rl.impala import (
            impala_batch_shardings,
            resolve_update_builder,
        )
        from ray_tpu.rl.models import init_mlp_policy

        self._jax = jax
        devices = jax.devices()  # GLOBAL devices across all learner processes
        self._mesh = Mesh(np.array(devices), ("data",))
        replicated, batch_shardings = impala_batch_shardings(self._mesh)
        self._replicated = replicated
        self._batch_shardings = batch_shardings
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(bc["grad_clip"]), optax.adam(bc["lr"])
        )
        host_params = init_mlp_policy(
            jax.random.PRNGKey(bc["seed"]),
            bc["obs_dim"],
            bc["num_actions"],
            bc["hidden"],
        )
        if "init_params" in bc and bc["init_params"] is not None:
            host_params = bc["init_params"]
        self.params = self._replicate(host_params)
        host_opt = bc.get("init_opt_state")
        if host_opt is None:
            host_opt = self.optimizer.init(host_params)
        self.opt_state = self._replicate(host_opt)
        self._update = jax.jit(
            resolve_update_builder(bc.get("update_builder", "impala"))(
                bc["cfg_vals"], self.optimizer
            ),
            in_shardings=(replicated, replicated, batch_shardings),
            out_shardings=(replicated, replicated, replicated),
        )

    def _replicate(self, pytree):
        """Host pytree -> fully-replicated global arrays (every process
        supplies the identical full value)."""
        jax = self._jax

        def rep(x):
            return jax.make_array_from_process_local_data(
                self._replicated, np.asarray(x)
            )

        return jax.tree.map(rep, pytree)

    def _globalize_batch(self, local_batch: Dict[str, np.ndarray]):
        """Per-process shard -> global sharded arrays (env axis split across
        all learner processes)."""
        jax = self._jax
        out = {}
        for k, v in local_batch.items():
            out[k] = jax.make_array_from_process_local_data(
                self._batch_shardings[k], v
            )
        return out

    def update(self, local_batch: Dict[str, np.ndarray]):
        """One gang-executed SPMD step; all ranks must call concurrently."""
        batch = self._globalize_batch(local_batch)
        self.params, self.opt_state, metrics = self._update(
            self.params, self.opt_state, batch
        )
        if self.rank != 0:
            return None
        host = {
            k: float(np.asarray(v.addressable_data(0)))
            for k, v in metrics.items()
        }
        return host, self.host_params()

    def host_params(self):
        jax = self._jax
        return jax.tree.map(
            lambda x: np.asarray(x.addressable_data(0)), self.params
        )

    def host_opt_state(self):
        jax = self._jax
        return jax.tree.map(
            lambda x: np.asarray(x.addressable_data(0)), self.opt_state
        )

    def set_params(self, host_params) -> None:
        self.params = self._replicate(host_params)

    def set_opt_state(self, host_opt_state) -> None:
        self.opt_state = self._replicate(host_opt_state)

    def ping(self) -> bool:
        return True

    def num_local_devices(self) -> int:
        return self._jax.local_device_count()

    def total_devices(self) -> int:
        return len(self._jax.devices())

    def shutdown(self) -> None:
        from ray_tpu.parallel import distributed as dist

        try:
            dist.shutdown()
        except Exception:
            pass


class SPMDLearnerGroup:
    """Driver-side handle to N gang-scheduled learner worker actors."""

    def __init__(
        self,
        num_workers: int,
        builder_config: dict,
        runtime_env: Optional[dict] = None,
        num_cpus_per_worker: float = 1.0,
        init_timeout_s: float = 300.0,
        update_timeout_s: float = 300.0,
    ):
        self.num_workers = num_workers
        self._builder_config = dict(builder_config)
        self._runtime_env = runtime_env
        self._num_cpus = num_cpus_per_worker
        self._init_timeout = init_timeout_s
        self._update_timeout = update_timeout_s
        self._attempt = 0
        self._params_cache = None
        self._opt_cache = None
        self.workers: List[Any] = []
        self.total_devices = 0
        self._start()

    def _start(self) -> None:
        key = f"rl_learners_{uuid.uuid4().hex[:8]}_a{self._attempt}"
        opts: Dict[str, Any] = {"num_cpus": self._num_cpus}
        if self._runtime_env:
            opts["runtime_env"] = self._runtime_env
        bc = dict(self._builder_config)
        bc["init_params"] = self._params_cache
        bc["init_opt_state"] = self._opt_cache
        self.workers = [
            SPMDLearnerWorker.options(**opts).remote(
                rank, self.num_workers, key, bc
            )
            for rank in range(self.num_workers)
        ]
        # barrier: every worker joined the coordination service and compiled
        counts = ray_tpu.get(
            [w.total_devices.remote() for w in self.workers],
            timeout=self._init_timeout,
        )
        assert len(set(counts)) == 1, f"device-count disagreement: {counts}"
        self.total_devices = counts[0]
        if self._params_cache is None:
            self._params_cache = ray_tpu.get(
                self.workers[0].host_params.remote(), timeout=self._init_timeout
            )

    def split(self, batch: Dict[str, np.ndarray]) -> List[Dict[str, np.ndarray]]:
        """Split the padded host batch into per-process contiguous shards
        along the env axis (matching the mesh's device order)."""
        world = self.num_workers
        shards: List[Dict[str, np.ndarray]] = [dict() for _ in range(world)]
        for k, v in batch.items():
            env_axis = 0 if k in ("last_values", "mask") else 1
            n = v.shape[env_axis]
            assert n % world == 0, f"{k}: env axis {n} not divisible by {world}"
            step = n // world
            for i in range(world):
                sl = [slice(None)] * v.ndim
                sl[env_axis] = slice(i * step, (i + 1) * step)
                shards[i][k] = v[tuple(sl)]
        return shards

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        """One SPMD step across the group; restarts the group on worker
        DEATH and retries once (the pre-batch params were restored, so
        re-feeding is not a double apply). A bare timeout first probes
        liveness: a slow-but-healthy gang gets one extended wait instead of
        a kill — killing it could discard an already-applied update and
        re-apply the batch."""
        shards = self.split(batch)
        refs = [w.update.remote(s) for w, s in zip(self.workers, shards)]
        try:
            out = ray_tpu.get(refs, timeout=self._update_timeout)
        except exc.GetTimeoutError:
            if self._all_alive():
                # healthy but slow (compile storm, loaded box): the update
                # may be mid-flight — wait it out rather than double-apply.
                # A second timeout means the gang is wedged, not slow:
                # restart and re-feed (documented at-least-once; optimizer
                # state is salvaged by restart()).
                try:
                    out = ray_tpu.get(refs, timeout=self._update_timeout)
                except exc.GetTimeoutError:
                    self.restart()
                    out = ray_tpu.get(
                        [w.update.remote(s) for w, s in zip(self.workers, shards)],
                        timeout=self._update_timeout,
                    )
            else:
                self.restart()
                out = ray_tpu.get(
                    [w.update.remote(s) for w, s in zip(self.workers, shards)],
                    timeout=self._update_timeout,
                )
        except (exc.ActorDiedError, exc.WorkerCrashedError, exc.TaskError):
            self.restart()
            out = ray_tpu.get(
                [w.update.remote(s) for w, s in zip(self.workers, shards)],
                timeout=self._update_timeout,
            )
        metrics, host_params = out[0]
        self._params_cache = host_params
        return metrics

    def _all_alive(self) -> bool:
        try:
            ray_tpu.get(
                [w.ping.remote() for w in self.workers], timeout=10.0
            )
            return True
        except Exception:
            return False

    def cached_params(self):
        return self._params_cache

    def set_params(self, host_params) -> None:
        self._params_cache = host_params
        ray_tpu.get(
            [w.set_params.remote(host_params) for w in self.workers],
            timeout=self._update_timeout,
        )

    def restart(self) -> None:
        """Kill every worker and rebuild the gang under a fresh rendezvous
        key, restoring the last known params (parity: backend_executor's
        worker-group restart). Optimizer state is salvaged from any
        surviving worker first, so a partial gang death doesn't silently
        reset Adam moments."""
        for w in self.workers:
            try:
                self._opt_cache = ray_tpu.get(
                    w.host_opt_state.remote(), timeout=10.0
                )
                break
            except Exception:
                continue
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self._attempt += 1
        self._start()

    def stop(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
