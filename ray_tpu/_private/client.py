"""Remote driver: connect to a running cluster over its head socket.

Design parity: ``ray.init(address=...)`` attaching a driver to an existing
cluster (``python/ray/_private/worker.py:1225``, the ``address="auto"`` path).
The remote driver reuses the worker wire protocol (submit/pull/rpc over one
socket) — it is a worker that never executes tasks — so the head needs no
driver-specific plumbing beyond the handshake (``head.py``). For same-machine
drivers the head's shm store is mapped directly; objects on other nodes are
pulled into it by the scheduler on demand.
"""

from __future__ import annotations

import os
import pickle
import threading
import time
from multiprocessing.connection import Client
from typing import Optional

from ray_tpu._private.ids import JobID, ObjectID, TaskID, WorkerID
from ray_tpu._private.worker import apply_dropped
from ray_tpu._private.worker_process import WorkerRuntime


class RemoteDriverRuntime(WorkerRuntime):
    """Driver attached to a remote head. API-compatible with DriverRuntime."""

    def __init__(self, address, auth_key: str):
        if isinstance(address, str):
            host, port = address.rsplit(":", 1)
            address = (host, int(port))
        key = auth_key.encode() if isinstance(auth_key, str) else auth_key
        conn = Client(tuple(address), authkey=key)
        from ray_tpu._private.object_transfer import set_nodelay

        set_nodelay(conn)
        conn.send(("register_driver", os.getpid()))
        kind, info = conn.recv()
        assert kind == "driver_registered", kind
        config = pickle.loads(info["config_blob"])

        # same-machine drivers map the head's shm directly (zero-copy);
        # cross-machine drivers (marker not visible) fall back to a private
        # local cache store with puts uploaded over the control socket and
        # gets pulled from the head's object server (Ray-Client parity,
        # python/ray/util/client/ARCHITECTURE.md).
        marker = os.path.join(info["shm_dir"], ".cluster_session")
        session = info.get("session_name", "")
        try:
            with open(marker) as fh:
                found = fh.read().strip()
        except OSError:
            found = None
        self._cross_machine = (
            found != session or bool(os.environ.get("RAY_TPU_FORCE_REMOTE_CLIENT"))
        )
        self._head_object_addr = info.get("object_addr")
        self._auth_key = key

        from ray_tpu._private.native_store import create_store_client

        self._private_store_dir = None
        if self._cross_machine:
            import tempfile

            base = tempfile.mkdtemp(prefix="ray_tpu_client_")
            self._private_store_dir = base
            store = create_store_client(
                os.path.join(base, "shm"),
                os.path.join(base, "spill"),
                config.object_store_memory,
            )
        else:
            # same-machine attach shares the head's arena: the spill config
            # must match the other clients of that arena
            from ray_tpu._private import external_storage as _xstorage

            store = create_store_client(
                info["shm_dir"],
                info["fallback_dir"],
                config.object_store_memory,
                spill_uri=(
                    config.spill_directory
                    if _xstorage.has_scheme(config.spill_directory)
                    else ""
                ),
            )
        super().__init__(conn, WorkerID(info["worker_id"]), store, config)
        # unique put-id namespace per driver (workers get theirs per-task);
        # a driver launched on behalf of a submitted job binds to that
        # job's arbitration record via the environment (job plane)
        self.job_id = JobID.from_int(int.from_bytes(os.urandom(3), "little"))
        env_job = os.environ.get("RAY_TPU_JOB_ID")
        if env_job:
            try:
                self.job_id = JobID.from_hex(env_job)
            except ValueError:
                pass
        self.current_task_id = TaskID.for_driver(self.job_id)
        self.closed = False
        self._reader = threading.Thread(
            target=self.reader_loop, name="client-reader", daemon=True
        )
        self._reader.start()

    def job_scope(
        self,
        *,
        name: str = "",
        priority: int = 0,
        weight: float = 1.0,
        quota=None,
        meta=None,
    ):
        """Remote-driver half of ``ray_tpu.job_scope`` (same contract as
        ``DriverRuntime.job_scope``): register a tenant over the head
        socket, then bind this driver's submissions/puts to it for the
        duration of the ``with`` block."""
        import contextlib

        from ray_tpu import exceptions as exc

        info = self.rpc(
            "submit_job", name, int(priority), float(weight), quota, meta
        )
        if info["admission"] == "REJECTED":
            raise exc.JobAdmissionError(
                f"job {name or info['job']} rejected by admission control"
            )
        job = JobID.from_hex(info["job"])

        @contextlib.contextmanager
        def _scope():
            prev_job, prev_task = self.job_id, self.current_task_id
            self.job_id = job
            self.current_task_id = TaskID.for_driver(job)
            try:
                yield info
            finally:
                self.job_id, self.current_task_id = prev_job, prev_task

        return _scope()

    # -- cross-machine object plane ---------------------------------------

    def put(self, value):
        if not self._cross_machine:
            return super().put(value)
        apply_dropped()
        oid = ObjectID.for_put(
            self.current_task_id or TaskID.nil(), self._put_counter.next()
        )
        blob = self.serde.serialize_to_bytes(value)
        # upload over the control socket; the head stores + commits it
        self._send(("put_object", oid, blob))
        self.store.put_bytes(oid, blob)  # local cache for re-reads
        return oid

    def _entry_value(self, oid, entry, timeout):
        if (
            self._cross_machine
            and entry[0] == "stored"
            and not self.store.contains(oid)
        ):
            # pull: ensure a head copy exists (transfer/reconstruction),
            # then fetch it from the head's object server into the cache
            from ray_tpu._private.object_transfer import fetch_object_bytes

            import logging

            logger = logging.getLogger(__name__)
            warned = False
            deadline = time.monotonic() + (timeout if timeout is not None else 60.0)
            while not self.store.contains(oid):
                try:
                    self.rpc("ensure_local", oid)
                    blob = fetch_object_bytes(
                        self._head_object_addr, oid, self._auth_key
                    )
                    if blob is not None:
                        self.store.put_bytes(oid, blob)
                        break
                except Exception as e:  # noqa: BLE001
                    if not warned:
                        warned = True
                        logger.warning(
                            "fetch of %s from head object server %r failing "
                            "(%r); retrying until the timeout",
                            oid.hex()[:8],
                            self._head_object_addr,
                            e,
                        )
                if time.monotonic() >= deadline:
                    # the fetch budget is spent; don't let the base class
                    # poll the private cache for the same timeout again
                    return super()._entry_value(oid, entry, 0.05)
                time.sleep(0.5)
        return super()._entry_value(oid, entry, timeout)

    def shutdown(self):
        """Disconnect from the cluster (the cluster keeps running)."""
        apply_dropped()  # nothing is left counted at exit
        self.closed = True
        if self._direct is not None:
            self._direct.shutdown()
        try:
            self.conn.close()
        except OSError:
            pass
        try:
            self.store.close()
        except Exception:
            pass
        if self._private_store_dir:
            import shutil

            shutil.rmtree(self._private_store_dir, ignore_errors=True)


def connect(address, auth_key: Optional[str] = None) -> RemoteDriverRuntime:
    auth_key = auth_key or os.environ.get("RAY_TPU_AUTH", "")
    return RemoteDriverRuntime(address, auth_key)
