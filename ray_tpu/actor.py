"""Actor classes and handles.

Design parity: ``python/ray/actor.py`` — ``ActorClass`` (``:566``),
``ActorClass._remote`` (``:854``), ``ActorHandle`` + ``ActorMethod``; named
actors via the GCS name registry (``gcs_actor_manager.h:278``); handles pickle
into tasks and reconstruct on the borrower side.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import cloudpickle

from ray_tpu import exceptions as exc
from ray_tpu._private.ids import ActorID, ObjectID, TaskID
from ray_tpu._private.runtime_env import upload_runtime_env as _upload_runtime_env
from ray_tpu.util.tracing import for_submission as _trace_for_submission
from ray_tpu._private.task_spec import SchedulingStrategy, TaskSpec, TaskType
from ray_tpu._private.worker import ObjectRef, ObjectRefGenerator, get_runtime, note_dropped, pack_args
from ray_tpu.remote_function import resolve_resources, resolve_strategy

_DEFAULT_ACTOR_OPTIONS = dict(
    num_cpus=1.0,
    num_tpus=0.0,
    resources=None,
    max_restarts=0,
    max_task_retries=0,
    max_concurrency=1,
    name=None,
    namespace=None,
    lifetime=None,  # None | "detached"
    scheduling_strategy=None,
    runtime_env=None,
    memory=None,
)


class ActorMethod:
    def __init__(self, handle: "ActorHandle", method_name: str, num_returns: int = 1):
        self._handle = handle
        self._method_name = method_name
        self._num_returns = num_returns

    def __call__(self, *a, **kw):
        raise TypeError(
            f"Actor method '{self._method_name}' cannot be called directly; "
            f"use '.remote()'."
        )

    def options(self, num_returns: int = 1, **_ignored):
        return ActorMethod(self._handle, self._method_name, num_returns)

    def remote(self, *args, **kwargs):
        return self._handle._submit_method(
            self._method_name, args, kwargs, self._num_returns
        )

    def bind(self, *args, **kwargs):
        from ray_tpu.dag import ClassMethodNode

        return ClassMethodNode(self._handle, self._method_name, args, kwargs)


class ActorHandle:
    def __init__(self, actor_id: ActorID, method_meta: Dict[str, int], owned: bool = False):
        self._actor_id = actor_id
        self._method_meta = method_meta
        self._owned = owned
        if owned:
            try:
                get_runtime().actor_handle_count(actor_id, 0)  # registration no-op
            except Exception:
                pass

    @property
    def _id(self) -> ActorID:
        return self._actor_id

    def __getattr__(self, name: str):
        meta = object.__getattribute__(self, "_method_meta")
        if name in meta:
            return ActorMethod(self, name, meta[name])
        raise AttributeError(name)

    # method-name pickles are identical across calls: cache them (hot path —
    # one cloudpickle.dumps per actor call showed up in the core microbench)
    _method_blob_cache: dict = {}

    def _submit_method(self, method_name: str, args, kwargs, num_returns: int):
        rt = get_runtime()
        streaming = num_returns == "streaming"
        packed_args, packed_kwargs = pack_args(rt, args, kwargs)
        blob = self._method_blob_cache.get(method_name)
        if blob is None:
            blob = cloudpickle.dumps(method_name)
            self._method_blob_cache[method_name] = blob
        spec = TaskSpec(
            task_id=rt.new_task_id(),
            task_type=TaskType.ACTOR_TASK,
            function=blob,
            args=packed_args,
            kwargs=packed_kwargs,
            num_returns=1 if streaming else num_returns,
            resources={},
            name=f"{method_name}",
            actor_id=self._actor_id,
            is_streaming=streaming,
            trace_ctx=_trace_for_submission(),
        )
        rt.submit(spec)
        if streaming:
            return ObjectRefGenerator(
                spec.task_id, ObjectRef(ObjectID.for_return(spec.task_id, 0), _owned=True)
            )
        refs = [ObjectRef(oid, _owned=True) for oid in spec.return_ids()]
        return refs[0] if num_returns == 1 else refs

    def __reduce__(self):
        return (ActorHandle, (self._actor_id, self._method_meta))

    def __repr__(self):
        return f"ActorHandle({self._actor_id.hex()[:12]})"

    def __del__(self):
        if getattr(self, "_owned", False):
            note_dropped("handle", self._actor_id)


class ActorClass:
    def __init__(self, cls, options: Optional[Dict[str, Any]] = None):
        self._cls = cls
        self._name = cls.__name__
        self._options = dict(_DEFAULT_ACTOR_OPTIONS)
        self._options.update(options or {})
        # keys the user set explicitly: these become lifetime resources
        self._explicit = set((options or {}).keys())
        self._pickled: Optional[bytes] = None
        functools.update_wrapper(self, cls, updated=[])

    def __call__(self, *a, **kw):
        raise TypeError(
            f"Actor class '{self._name}' cannot be instantiated directly; "
            f"use '{self._name}.remote()'."
        )

    def options(self, **updates) -> "ActorClass":
        new = ActorClass(self._cls, {**self._options, **updates})
        new._explicit = self._explicit | set(updates.keys())
        new._pickled = self._pickled
        return new

    def _method_meta(self) -> Dict[str, int]:
        meta = {}
        for name in dir(self._cls):
            if name.startswith("__") and name not in ("__call__",):
                continue
            m = getattr(self._cls, name, None)
            if callable(m):
                meta[name] = getattr(m, "__ray_num_returns__", 1)
        meta["__ray_terminate__"] = 1
        return meta

    def remote(self, *args, **kwargs) -> ActorHandle:
        rt = get_runtime()
        opts = self._options
        if self._pickled is None:
            self._pickled = cloudpickle.dumps(self._cls)
        name = opts.get("name")
        namespace = opts.get("namespace") or "default"
        actor_id = ActorID.of(rt.new_task_id().job_id())
        if name:
            if not rt.rpc("claim_actor_name", namespace, name, actor_id):
                raise ValueError(f"actor name '{name}' already taken")
        packed_args, packed_kwargs = pack_args(rt, args, kwargs)
        spec = TaskSpec(
            task_id=rt.new_task_id(),
            task_type=TaskType.ACTOR_CREATION,
            function=self._pickled,
            args=packed_args,
            kwargs=packed_kwargs,
            num_returns=1,
            resources=resolve_resources(opts),
            lifetime_resources=resolve_resources(
                {k: v for k, v in opts.items() if k in self._explicit}
            ),
            name=f"{self._name}.__init__",
            actor_id=actor_id,
            max_restarts=int(opts.get("max_restarts") or 0),
            max_concurrency=int(opts.get("max_concurrency") or 1),
            max_task_retries=int(opts.get("max_task_retries") or 0),
            detached=opts.get("lifetime") == "detached",
            actor_name=name,
            namespace=namespace,
            scheduling_strategy=resolve_strategy(opts),
            runtime_env=_upload_runtime_env(rt, opts.get("runtime_env")),
            trace_ctx=_trace_for_submission(),
        )
        rt.submit(spec)
        return ActorHandle(actor_id, self._method_meta(), owned=True)

    def bind(self, *args, **kwargs):
        from ray_tpu.dag import ClassNode

        return ClassNode(self, args, kwargs)


def get_actor(name: str, namespace: str = "default") -> ActorHandle:
    rt = get_runtime()
    actor_id = rt.rpc("get_actor_by_name", namespace, name)
    if actor_id is None:
        raise ValueError(f"no actor named '{name}' in namespace '{namespace}'")
    # method metadata is not stored server-side; return a dynamic handle
    return _DynamicActorHandle(actor_id)


class _DynamicActorHandle(ActorHandle):
    """Handle from get_actor: resolves any attribute as a method."""

    def __init__(self, actor_id: ActorID):
        super().__init__(actor_id, {}, owned=False)

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return ActorMethod(self, name, 1)

    def __reduce__(self):
        # the base reduce would rebuild a plain ActorHandle whose EMPTY
        # method table can't resolve any method — a dynamic handle must
        # stay dynamic across pickling (serve ships re-adopted replica
        # handles through the controller this way)
        return (_DynamicActorHandle, (self._actor_id,))


def kill(actor_or_ref, no_restart: bool = True) -> None:
    """Parity: ``ray.kill`` / ``ray.cancel``."""
    rt = get_runtime()
    if isinstance(actor_or_ref, ActorHandle):
        rt.kill_actor(actor_or_ref._actor_id, no_restart)
    else:
        raise TypeError("kill() expects an actor handle; use cancel() for tasks")
