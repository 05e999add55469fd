"""Lazy DAG API + compiled execution.

Parity: ``python/ray/dag`` — ``.bind()`` builds ``FunctionNode`` /
``ClassNode`` / ``ClassMethodNode`` / ``InputNode`` graphs (``dag_node.py``),
``.execute()`` walks them; ``experimental_compile`` returns a ``CompiledDAG``
(``compiled_dag_node.py:391``).

TPU-native compiled path: where the reference lowers compiled DAGs to mutable
plasma channels + NCCL p2p, stages that are pure jax functions fuse into ONE
jitted XLA program (``compile_jax_pipeline``) so inter-stage edges become
in-program values on-device — the aDAG analogue described in SURVEY.md §2.3.
Non-fusable (stateful-actor) stages run as pre-planned actor calls with the
object store carrying edges.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu._private.worker import note_dropped


class DAGNode:
    def execute(self, *input_args, **input_kwargs):
        return _execute(self, input_args, input_kwargs, {})

    def experimental_compile(self, buffer_size_bytes: int = 4 * 1024 * 1024):
        """Compile for repeated execution (parity:
        ``compiled_dag_node.py:391``). Actor-method graphs — linear chains,
        branches, diamonds, multi-output — lower to resident stage loops
        connected by channels: mutable shared-memory channels between
        same-node stages (``shared_memory_channel.py:88`` analogue), and
        authenticated one-slot socket channels for cross-node edges (the
        reference's cross-node mutable-object forwarding). Graphs that are
        not pure actor-method DAGs keep the pre-planned actor-call path."""
        chain = _linear_actor_chain(self)
        if chain is not None:
            return ChannelCompiledDAG(chain, buffer_size_bytes)
        plan = _general_actor_graph(self)
        if plan is not None:
            return GeneralCompiledDAG(plan, buffer_size_bytes)
        return CompiledDAG(self)


class InputNode(DAGNode):
    """Placeholder for the value supplied at ``execute()`` time."""

    def __init__(self, index: int = 0):
        self.index = index

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class InputAttributeNode(DAGNode):
    def __init__(self, parent: InputNode, key):
        self.parent = parent
        self.key = key


class FunctionNode(DAGNode):
    def __init__(self, remote_fn, args, kwargs):
        self.fn = remote_fn
        self.args = args
        self.kwargs = kwargs


class ClassNode(DAGNode):
    """A bound actor constructor; instantiated once per executing DAG."""

    def __init__(self, actor_cls, args, kwargs):
        self.actor_cls = actor_cls
        self.args = args
        self.kwargs = kwargs

    def bind_method(self, name):
        raise AttributeError(name)

    def __getattr__(self, name):
        if name.startswith("_") or name in ("actor_cls", "args", "kwargs"):
            raise AttributeError(name)

        class _M:
            def __init__(_s, node, method):
                _s.node = node
                _s.method = method

            def bind(_s, *args, **kwargs):
                return BoundClassMethodNode(_s.node, _s.method, args, kwargs)

        return _M(self, name)


class BoundClassMethodNode(DAGNode):
    def __init__(self, class_node: ClassNode, method: str, args, kwargs):
        self.class_node = class_node
        self.method = method
        self.args = args
        self.kwargs = kwargs


class ClassMethodNode(DAGNode):
    """Method bind on an existing actor handle."""

    def __init__(self, handle, method: str, args, kwargs):
        self.handle = handle
        self.method = method
        self.args = args
        self.kwargs = kwargs


class MultiOutputNode(DAGNode):
    """Marks several DAG leaves as the outputs of one execution (parity:
    ``ray.dag.MultiOutputNode``); ``execute()``/compiled results are lists."""

    def __init__(self, outputs):
        self.outputs = list(outputs)


def _execute(node, input_args, input_kwargs, memo: Dict[int, Any]):
    """Post-order walk; returns an ObjectRef (or plain value for inputs)."""
    if id(node) in memo:
        return memo[id(node)]

    def rec(v):
        if isinstance(v, DAGNode):
            return _execute(v, input_args, input_kwargs, memo)
        return v

    if isinstance(node, InputNode):
        result = input_args[node.index] if input_args else None
    elif isinstance(node, InputAttributeNode):
        base = rec(node.parent)
        if isinstance(base, ray_tpu.ObjectRef):
            base = ray_tpu.get(base)
        result = base[node.key]
    elif isinstance(node, FunctionNode):
        args = [rec(a) for a in node.args]
        kwargs = {k: rec(v) for k, v in node.kwargs.items()}
        result = node.fn.remote(*args, **kwargs)
    elif isinstance(node, ClassNode):
        args = [rec(a) for a in node.args]
        kwargs = {k: rec(v) for k, v in node.kwargs.items()}
        result = node.actor_cls.remote(*args, **kwargs)
    elif isinstance(node, BoundClassMethodNode):
        handle = rec(node.class_node)
        args = [rec(a) for a in node.args]
        kwargs = {k: rec(v) for k, v in node.kwargs.items()}
        result = getattr(handle, node.method).remote(*args, **kwargs)
    elif isinstance(node, ClassMethodNode):
        args = [rec(a) for a in node.args]
        kwargs = {k: rec(v) for k, v in node.kwargs.items()}
        result = getattr(node.handle, node.method).remote(*args, **kwargs)
    elif isinstance(node, MultiOutputNode):
        result = [rec(o) for o in node.outputs]
    else:
        raise TypeError(f"unknown DAG node {type(node)}")
    memo[id(node)] = result
    return result


class CompiledDAG:
    """Pre-planned execution: actors in the graph are instantiated once and
    reused across ``execute()`` calls (the reference's compiled DAGs likewise
    pin actors + channels; here edges ride the object store)."""

    def __init__(self, output_node: DAGNode):
        self.output = output_node
        self._actor_cache: Dict[int, Any] = {}
        self._instantiate_actors(output_node)

    def _instantiate_actors(self, node):
        if isinstance(node, ClassNode) and id(node) not in self._actor_cache:
            args = [a for a in node.args if not isinstance(a, DAGNode)]
            kwargs = {k: v for k, v in node.kwargs.items() if not isinstance(v, DAGNode)}
            self._actor_cache[id(node)] = node.actor_cls.remote(*args, **kwargs)
        for child in _children(node):
            self._instantiate_actors(child)

    def execute(self, *input_args, **input_kwargs):
        memo = {nid: handle for nid, handle in self._actor_cache.items()}
        return _execute(self.output, input_args, input_kwargs, memo)

    def teardown(self):
        for handle in self._actor_cache.values():
            try:
                ray_tpu.kill(handle)
            except Exception:
                pass


def _linear_actor_chain(output: DAGNode):
    """Detect InputNode -> m1(actor1) -> m2(actor2) -> ... chains.

    Returns [(class_node, method_name), ...] outermost-last, or None."""
    stages = []
    node = output
    while isinstance(node, BoundClassMethodNode):
        dag_args = [a for a in node.args if isinstance(a, DAGNode)]
        if len(node.args) != 1 or len(dag_args) != 1 or node.kwargs:
            return None
        stages.append((node.class_node, node.method))
        node = node.args[0]
    if not isinstance(node, InputNode) or not stages:
        return None
    # a ClassNode appearing in several stages must share ONE instance
    # (interpreted-execute semantics); the channel lowering spawns one
    # resident actor per stage, so bail to the actor-call path instead
    if len({id(cn) for cn, _ in stages}) != len(stages):
        return None
    return list(reversed(stages))


@ray_tpu.remote
class _PipelineStage:
    """Resident compiled-DAG stage: constructs the user class once, then
    loops channel-read -> method -> channel-write until the input closes."""

    def __init__(self, cls_blob: bytes, args, kwargs):
        import cloudpickle

        cls = cloudpickle.loads(cls_blob)
        self._inst = cls(*args, **kwargs)

    def run_loop(self, in_path, out_path, method, capacity):
        from ray_tpu.experimental.channel import Channel, ChannelClosedError

        in_ch = Channel(in_path, capacity)
        out_ch = Channel(out_path, capacity)
        fn = getattr(self._inst, method)
        while True:
            try:
                x = in_ch.read(timeout=None)
            except ChannelClosedError:
                out_ch.close()
                return
            if isinstance(x, _DagError):
                payload = x  # upstream failure: forward it downstream
            else:
                try:
                    payload = fn(x)
                except Exception as e:  # noqa: BLE001
                    import traceback

                    payload = _DagError(f"{e!r}\n{traceback.format_exc()}")
            try:
                # block until the reader consumes — a slow consumer must
                # backpressure the pipeline, not kill the resident loop
                out_ch.write(payload, timeout=None)
            except ChannelClosedError:
                return


class _DagError:
    """Stage failure riding the channel to the caller (parity: compiled DAGs
    propagate exceptions through the channel)."""

    def __init__(self, message: str):
        self.message = message


class _SeqBufferedResults:
    """FIFO result protocol shared by the channel-compiled DAGs: results
    arrive on the output channel(s) in execution order; out-of-order
    consumption buffers other executions' values per sequence number.
    Subclasses implement ``_read_one(timeout)``."""

    def _init_seq_state(self):
        self._closed = False
        self._next_seq = 0
        self._next_read = 0
        self._buffered: Dict[int, Any] = {}

    def __del__(self):
        # a dropped DAG must not leak resident stage actors (their loops
        # never finish on their own, so out-of-scope reaping can't fire);
        # killing them takes the runtime's locks, so not from here
        if not getattr(self, "_closed", True):  # built to the end, and open
            note_dropped("call", self.teardown)

    def _result_for(self, seq: int, timeout: float):
        if seq in self._buffered:
            return self._buffered.pop(seq)
        import time as _time

        deadline = _time.monotonic() + timeout
        while self._next_read <= seq:
            remaining = max(0.0, deadline - _time.monotonic())
            value = self._read_one(remaining)
            got = self._next_read
            self._next_read += 1
            if got == seq:
                return value
            self._buffered[got] = value
        return self._buffered.pop(seq)


class CompiledDAGRef:
    """Result handle of one compiled execution (parity: ``CompiledDAGRef``).

    Results are delivered in execution order on one channel; the owning DAG
    buffers out-of-order consumption so each ref gets ITS execution's value."""

    def __init__(self, dag: "ChannelCompiledDAG", seq: int, timeout: float):
        self._dag = dag
        self._seq = seq
        self._timeout = timeout

    def get(self, timeout: Optional[float] = None):
        value = self._dag._result_for(
            self._seq, self._timeout if timeout is None else timeout
        )
        err = None
        if isinstance(value, _DagError):
            err = value
        elif isinstance(value, list):
            err = next((v for v in value if isinstance(v, _DagError)), None)
        if err is not None:
            raise RuntimeError(f"compiled DAG stage failed: {err.message}")
        return value


class ChannelCompiledDAG(_SeqBufferedResults):
    """Linear actor pipeline lowered onto mutable shm channels."""

    def __init__(self, stages, capacity: int):
        import os
        import uuid

        import cloudpickle

        from ray_tpu._private.worker import get_driver
        from ray_tpu.experimental.channel import Channel

        drv = get_driver()
        base = (
            os.path.join(drv.node.shm_dir, "channels")
            if drv is not None and hasattr(drv, "node")
            else "/tmp/ray_tpu_channels"
        )
        tag = uuid.uuid4().hex[:8]
        n = len(stages)
        self._paths = [os.path.join(base, f"{tag}_{i}") for i in range(n + 1)]
        self._channels = [Channel(p, capacity, create=True) for p in self._paths]
        self._actors = []
        self._loops = []
        for i, (class_node, method) in enumerate(stages):
            args = [a for a in class_node.args if not isinstance(a, DAGNode)]
            kwargs = {
                k: v for k, v in class_node.kwargs.items() if not isinstance(v, DAGNode)
            }
            blob = cloudpickle.dumps(class_node.actor_cls._cls)
            actor = _PipelineStage.remote(blob, args, kwargs)
            self._actors.append(actor)
            self._loops.append(
                actor.run_loop.remote(
                    self._paths[i], self._paths[i + 1], method, capacity
                )
            )
        self._init_seq_state()

    def execute(self, value, timeout: float = 60.0) -> CompiledDAGRef:
        if self._closed:
            raise RuntimeError("compiled DAG is torn down")
        self._channels[0].write(value)
        ref = CompiledDAGRef(self, self._next_seq, timeout)
        self._next_seq += 1
        return ref

    def _read_one(self, timeout: float):
        return self._channels[-1].read(timeout=timeout)

    def teardown(self):
        if self._closed:
            return
        self._closed = True
        for ch in self._channels:
            ch.close()
        for a in self._actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
        for ch in self._channels:
            ch.release()
        import os

        for p in self._paths:
            try:
                os.unlink(p)
            except OSError:
                pass


def _general_actor_graph(output: DAGNode):
    """Validate + plan an arbitrary actor-method DAG for channel lowering.

    Supported nodes: BoundClassMethodNode (constant kwargs; args may mix
    constants with DAG edges), InputNode / InputAttributeNode sources, and a
    MultiOutputNode root. Returns a plan dict or None (caller falls back to
    the pre-planned actor-call path). Parity: the reference compiles exactly
    these graphs in ``compiled_dag_node.py:391``.
    """
    roots = output.outputs if isinstance(output, MultiOutputNode) else [output]
    if not roots or not all(isinstance(r, BoundClassMethodNode) for r in roots):
        return None

    method_nodes: List[BoundClassMethodNode] = []  # topo (producers first)
    seen: Dict[int, bool] = {}

    def visit(node) -> bool:
        if isinstance(node, (InputNode, InputAttributeNode)):
            if isinstance(node, InputAttributeNode):
                node = node.parent
            # channel executions carry ONE input value; a multi-positional
            # InputNode(index>0) would silently get the wrong argument here,
            # so those graphs keep the interpreted path
            if not isinstance(node, InputNode) or node.index != 0:
                return False
            return True
        if not isinstance(node, BoundClassMethodNode):
            return False
        if id(node) in seen:
            return seen[id(node)]
        seen[id(node)] = True  # provisional (cycles are impossible in DAGs)
        if any(isinstance(v, DAGNode) for v in node.kwargs.values()):
            seen[id(node)] = False
            return False
        if not all(
            visit(a) for a in node.args if isinstance(a, DAGNode)
        ):
            seen[id(node)] = False
            return False
        # every stage needs at least one channel input: an all-constant
        # method would loop eagerly, decoupled from execute() pacing
        if not any(isinstance(a, DAGNode) for a in node.args):
            seen[id(node)] = False
            return False
        # class construction args must be constants (one instance per
        # class_node, built once at compile time)
        cn = node.class_node
        if any(isinstance(a, DAGNode) for a in cn.args) or any(
            isinstance(v, DAGNode) for v in cn.kwargs.values()
        ):
            seen[id(node)] = False
            return False
        method_nodes.append(node)
        return True

    if not all(visit(r) for r in roots):
        return None
    if not method_nodes:
        return None
    return {"roots": roots, "method_nodes": method_nodes}


class _EdgeHole:
    """Compile-time marker for a channel-fed argument position. A dedicated
    class (not an in-band tuple) so user constants can never collide."""

    def __init__(self, index: int):
        self.index = index


@ray_tpu.remote
class _GeneralStage:
    """Resident stage hosting ONE user-class instance and one channel loop
    per bound method node (threads via max_concurrency)."""

    def __init__(self, cls_blob: bytes, args, kwargs):
        import cloudpickle
        import threading

        cls = cloudpickle.loads(cls_blob)
        self._inst = cls(*args, **kwargs)
        self._writers: Dict[str, Any] = {}
        # several method loops share one instance; user method bodies run
        # one at a time, like any other actor (interpreted semantics)
        self._inst_lock = threading.Lock()

    def node_shm(self):
        from ray_tpu.experimental.channel import node_shm_dir

        return node_shm_dir()

    def prepare(self, out_edges, capacity: int):
        """Create writer endpoints for this stage's output edges.
        ``out_edges`` = [(edge_id, kind)]; returns {edge_id: reader_spec}."""
        from ray_tpu._private.worker import get_runtime
        from ray_tpu.experimental.channel import create_writer, node_shm_dir

        cfg = get_runtime().config
        key = (cfg.cluster_auth_key or "local").encode()
        specs = {}
        for edge_id, kind in out_edges:
            w, spec = create_writer(
                kind, edge_id, key, capacity,
                shm_dir=node_shm_dir(), host=cfg.cluster_host,
            )
            self._writers[edge_id] = w
            specs[edge_id] = spec
        return specs

    def run_method_loop(
        self,
        method: str,
        arg_template: List,  # constants, with _EdgeHole(i) holes
        kwargs: Dict,
        in_specs: List,      # reader specs, one per hole, in hole order
        out_edge_ids: List[str],
        capacity: int,
    ):
        from ray_tpu._private.worker import get_runtime
        from ray_tpu.experimental.channel import (
            ChannelClosedError,
            open_reader,
        )

        cfg = get_runtime().config
        key = (cfg.cluster_auth_key or "local").encode()
        readers = [open_reader(s, key, capacity) for s in in_specs]
        writers = [self._writers[eid] for eid in out_edge_ids]
        fn = getattr(self._inst, method)
        while True:
            try:
                vals = [r.read(timeout=None) for r in readers]
            except ChannelClosedError:
                for w in writers:
                    w.close()
                return
            err = next((v for v in vals if isinstance(v, _DagError)), None)
            if err is not None:
                payload = err  # upstream failure: forward it downstream
            else:
                args = [
                    vals[a.index] if isinstance(a, _EdgeHole) else a
                    for a in arg_template
                ]
                try:
                    with self._inst_lock:
                        payload = fn(*args, **kwargs)
                except Exception as e:  # noqa: BLE001
                    import traceback

                    payload = _DagError(f"{e!r}\n{traceback.format_exc()}")
            try:
                for w in writers:
                    w.write(payload, timeout=None)
            except ChannelClosedError:
                return


class GeneralCompiledDAG(_SeqBufferedResults):
    """Arbitrary actor-method DAG lowered onto channels: shm between
    same-node stages, authenticated sockets across nodes. One resident
    actor per ClassNode; one loop thread per bound method."""

    def __init__(self, plan: Dict, capacity: int):
        import uuid

        import cloudpickle

        from ray_tpu._private.worker import get_runtime
        from ray_tpu.experimental.channel import (
            create_writer,
            node_shm_dir,
            open_reader,
        )

        cfg = get_runtime().config
        self._auth = (cfg.cluster_auth_key or "local").encode()
        self._capacity = capacity
        roots = plan["roots"]
        method_nodes = plan["method_nodes"]
        tag = uuid.uuid4().hex[:8]

        # one resident actor per ClassNode (methods on one class_node share
        # the instance; each method loop needs its own thread)
        loops_per_class: Dict[int, int] = {}
        for m in method_nodes:
            loops_per_class[id(m.class_node)] = (
                loops_per_class.get(id(m.class_node), 0) + 1
            )
        self._actors: Dict[int, Any] = {}
        for m in method_nodes:
            cid = id(m.class_node)
            if cid not in self._actors:
                cn = m.class_node
                user_opts = {
                    k: cn.actor_cls._options[k]
                    for k in cn.actor_cls._explicit
                    if k in ("num_cpus", "num_tpus", "resources",
                             "scheduling_strategy")
                }
                self._actors[cid] = _GeneralStage.options(
                    max_concurrency=loops_per_class[cid] + 1, **user_opts
                ).remote(
                    cloudpickle.dumps(cn.actor_cls._cls), cn.args, cn.kwargs
                )

        # locate every endpoint (same shm dir == same node == shm channel)
        shm_of = {
            cid: shm
            for cid, shm in zip(
                self._actors,
                ray_tpu.get(
                    [a.node_shm.remote() for a in self._actors.values()],
                    timeout=120,
                ),
            )
        }
        driver_shm = node_shm_dir()

        def loc(end) -> Optional[str]:
            return driver_shm if end == "driver" else shm_of[end]

        # edges: producer -> (consumer, arg position). Input edges carry an
        # optional attribute key resolved driver-side at write time.
        edges: List[Dict] = []
        in_holes: Dict[int, List] = {id(m): [] for m in method_nodes}
        for m in method_nodes:
            for a in m.args:
                if isinstance(a, InputNode):
                    src, edge_key = "driver", None
                elif isinstance(a, InputAttributeNode):
                    src, edge_key = "driver", a.key
                elif isinstance(a, BoundClassMethodNode):
                    src, edge_key = id(a.class_node), None
                else:
                    continue
                eid = f"{tag}_{len(edges)}"
                edge = {
                    "id": eid,
                    "src": src,
                    "src_node": a if src != "driver" else None,
                    "dst": id(m.class_node),
                    "key": edge_key,
                }
                edges.append(edge)
                in_holes[id(m)].append(edge)
        root_edges: List[Dict] = []
        for r in roots:
            eid = f"{tag}_{len(edges) + len(root_edges)}r"
            root_edges.append(
                {"id": eid, "src": id(r.class_node), "src_node": r,
                 "dst": "driver", "key": None}
            )

        def kind_of(edge) -> str:
            a, b = loc(edge["src"]), loc(edge["dst"])
            return "shm" if a is not None and a == b else "sock"

        # writer creation: group stage-produced edges by producing method
        # node (its loop owns the writer ends)
        produced: Dict[int, List[Dict]] = {}
        for e in edges + root_edges:
            if e["src"] == "driver":
                continue
            produced.setdefault(id(e["src_node"]), []).append(e)
        specs: Dict[str, Any] = {}
        for m in method_nodes:
            mine = produced.get(id(m), [])
            if mine:
                got = ray_tpu.get(
                    self._actors[id(m.class_node)].prepare.remote(
                        [(e["id"], kind_of(e)) for e in mine], capacity
                    ),
                    timeout=120,
                )
                specs.update(got)
        # driver-produced input edges
        self._input_writers: List = []
        for e in edges:
            if e["src"] != "driver":
                continue
            w, spec = create_writer(
                kind_of(e), e["id"], self._auth, capacity,
                shm_dir=driver_shm, host=cfg.cluster_host,
            )
            self._input_writers.append((w, e["key"]))
            specs[e["id"]] = spec

        # start one loop per method node
        self._loops = []
        for m in method_nodes:
            holes = in_holes[id(m)]
            template: List = []
            hole_i = 0
            for a in m.args:
                if isinstance(
                    a, (InputNode, InputAttributeNode, BoundClassMethodNode)
                ):
                    template.append(_EdgeHole(hole_i))
                    hole_i += 1
                else:
                    template.append(a)
            self._loops.append(
                self._actors[id(m.class_node)].run_method_loop.remote(
                    m.method,
                    template,
                    dict(m.kwargs),
                    [specs[e["id"]] for e in holes],
                    [e["id"] for e in produced.get(id(m), [])],
                    capacity,
                )
            )
        # driver-side readers for the root edges — opened LAZILY on the
        # first result read: a socket reader's auth handshake only completes
        # when the writing stage accepts (at its first write, i.e. after an
        # execute()), so opening here would deadlock compile for any
        # cross-node output stage
        self._out_specs = [specs[e["id"]] for e in root_edges]
        self._out_readers: Optional[List] = None
        self._multi = len(self._out_specs) > 1
        # every shm edge path, for unlink at teardown (stage-created shm
        # files live in this node's shm dir only when the stage is local,
        # so unlink is best-effort per path)
        self._shm_paths = [
            spec[1] for spec in specs.values() if spec[0] == "shm"
        ]
        self._broken = False
        self._init_seq_state()

    def execute(self, value, timeout: float = 60.0) -> CompiledDAGRef:
        if self._closed:
            raise RuntimeError("compiled DAG is torn down")
        if self._broken:
            raise RuntimeError(
                "compiled DAG is in an inconsistent state after a partial "
                "write/read timeout; teardown() and recompile"
            )
        for i, (w, key) in enumerate(self._input_writers):
            try:
                w.write(value if key is None else value[key], timeout=timeout)
            except Exception:
                if i > 0:
                    # some inputs carry this execution and some don't: the
                    # stages are now out of step — refuse further use
                    self._broken = True
                raise
        ref = CompiledDAGRef(self, self._next_seq, timeout)
        self._next_seq += 1
        return ref

    def _read_one(self, timeout: float):
        import time as _time

        if self._out_readers is None:
            from ray_tpu.experimental.channel import open_reader

            self._out_readers = [
                open_reader(s, self._auth, self._capacity)
                for s in self._out_specs
            ]
        deadline = _time.monotonic() + timeout
        vals = []
        for i, r in enumerate(self._out_readers):
            try:
                vals.append(
                    r.read(timeout=max(0.0, deadline - _time.monotonic()))
                )
            except Exception:
                if i > 0:
                    # earlier outputs of this execution were consumed; the
                    # channels are desynchronized — refuse further use
                    self._broken = True
                raise
        return vals if self._multi else vals[0]

    def _result_for(self, seq: int, timeout: float):
        if self._broken:
            raise RuntimeError(
                "compiled DAG is in an inconsistent state after a partial "
                "write/read timeout; teardown() and recompile"
            )
        return super()._result_for(seq, timeout)

    def teardown(self):
        if self._closed:
            return
        self._closed = True
        for w, _ in self._input_writers:
            try:
                w.close()
            except Exception:
                pass
        for a in self._actors.values():
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
        for r in self._out_readers or []:
            try:
                r.close()
            except Exception:
                pass
        import os as _os

        for p in self._shm_paths:
            try:
                _os.unlink(p)
            except OSError:
                pass


def _children(node) -> List[DAGNode]:
    out = []
    for attr in ("args", "kwargs", "class_node", "parent", "outputs"):
        v = getattr(node, attr, None)
        if isinstance(v, DAGNode):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            out.extend(x for x in v if isinstance(x, DAGNode))
        elif isinstance(v, dict):
            out.extend(x for x in v.values() if isinstance(x, DAGNode))
    return out


def compile_jax_pipeline(stages, donate: bool = False):
    """Fuse a chain of pure-jax stage functions into one jitted program.

    The TPU-native compiled-DAG fast path: stage boundaries become in-program
    values (XLA schedules/overlaps them; on a sharded mesh the edges lower to
    ICI transfers), instead of host round-trips through the object store.
    """
    import jax

    def fused(x):
        for stage in stages:
            x = stage(x)
        return x

    return jax.jit(fused, donate_argnums=(0,) if donate else ())
