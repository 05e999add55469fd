"""Regression tests for review findings on the core runtime."""

import time

import pytest

import ray_tpu


def test_get_duplicate_refs(ray_start_regular):
    @ray_tpu.remote
    def f():
        time.sleep(0.2)
        return 7

    r = f.remote()
    assert ray_tpu.get([r, r, r], timeout=60) == [7, 7, 7]


def test_exception_value_roundtrip(ray_start_regular):
    err = ValueError("stored, not raised")
    ref = ray_tpu.put(err)
    out = ray_tpu.get(ref)
    assert isinstance(out, ValueError)
    assert str(out) == "stored, not raised"


def test_task_returning_exception_object(ray_start_regular):
    @ray_tpu.remote
    def collect():
        return [KeyError("a"), 42]

    errs = ray_tpu.get(collect.remote(), timeout=60)
    assert isinstance(errs[0], KeyError)
    assert errs[1] == 42


def test_arg_pinned_after_driver_ref_dropped(ray_start_regular):
    import numpy as np

    @ray_tpu.remote
    def total(x, delay):
        time.sleep(delay)
        return float(x.sum())

    big = np.ones(300_000, dtype=np.float64)  # large enough to live in shm
    ref = ray_tpu.put(big)
    result = total.remote(ref, 0.5)
    del ref  # must not free the object out from under the running task
    assert ray_tpu.get(result, timeout=60) == 300_000.0


def test_failed_actor_init_releases_resources(ray_start_regular):
    @ray_tpu.remote(num_cpus=1)
    class Bad:
        def __init__(self):
            raise RuntimeError("nope")

        def ping(self):
            return 1

    handles = [Bad.remote() for _ in range(4)]  # would exhaust all 4 CPUs if leaked
    for h in handles:
        with pytest.raises(Exception):
            ray_tpu.get(h.ping.remote(), timeout=60)

    @ray_tpu.remote
    def still_works():
        return "yes"

    assert ray_tpu.get(still_works.remote(), timeout=60) == "yes"


def test_pending_pg_created_after_node_added(ray_start_cluster):
    from ray_tpu.util.placement_group import placement_group

    cluster = ray_start_cluster
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD")
    assert not pg.wait(0.3)  # only one node: infeasible
    cluster.add_node(num_cpus=2)
    assert pg.wait(10)  # retried once the node joined


def test_actor_method_num_returns(ray_start_regular):
    @ray_tpu.remote
    class Splitter:
        @ray_tpu.method(num_returns=2)
        def pair(self):
            return "a", "b"

    s = Splitter.remote()
    r1, r2 = s.pair.remote()
    assert ray_tpu.get([r1, r2], timeout=60) == ["a", "b"]


def test_retry_exceptions_true(ray_start_regular, tmp_path):
    marker = tmp_path / "attempts"

    @ray_tpu.remote(max_retries=3, retry_exceptions=True)
    def flaky():
        n = int(marker.read_text()) if marker.exists() else 0
        marker.write_text(str(n + 1))
        if n < 2:
            raise RuntimeError(f"attempt {n}")
        return n

    assert ray_tpu.get(flaky.remote(), timeout=60) == 2


def test_retry_exceptions_list_no_match(ray_start_regular, tmp_path):
    marker = tmp_path / "attempts"

    @ray_tpu.remote(max_retries=3, retry_exceptions=[KeyError])
    def flaky():
        n = int(marker.read_text()) if marker.exists() else 0
        marker.write_text(str(n + 1))
        raise ValueError("not retryable")

    with pytest.raises(ValueError):
        ray_tpu.get(flaky.remote(), timeout=60)
    assert marker.read_text() == "1"  # no retries on a non-matching type


def test_retry_exceptions_list_match(ray_start_regular, tmp_path):
    marker = tmp_path / "attempts"

    @ray_tpu.remote(max_retries=3, retry_exceptions=[ValueError])
    def flaky():
        n = int(marker.read_text()) if marker.exists() else 0
        marker.write_text(str(n + 1))
        if n == 0:
            raise ValueError("retry me")
        return "ok"

    assert ray_tpu.get(flaky.remote(), timeout=60) == "ok"


def test_detached_actor_survives_handle_drop(ray_start_regular):
    import gc

    @ray_tpu.remote(lifetime="detached", name="det1")
    class Holder:
        def __init__(self):
            self.v = 41

        def bump(self):
            self.v += 1
            return self.v

    h = Holder.remote()
    assert ray_tpu.get(h.bump.remote(), timeout=60) == 42
    aid = h._actor_id
    del h
    gc.collect()
    time.sleep(0.3)
    h2 = ray_tpu.get_actor("det1")
    assert ray_tpu.get(h2.bump.remote(), timeout=60) == 43
    ray_tpu.kill(h2)


def test_actor_max_task_retries_on_restart(ray_start_regular, tmp_path):
    marker = tmp_path / "attempts"

    @ray_tpu.remote(max_restarts=1, max_task_retries=1)
    class Crashy:
        def work(self):
            import os

            n = int(marker.read_text()) if marker.exists() else 0
            marker.write_text(str(n + 1))
            if n == 0:
                os._exit(1)  # kill the actor worker mid-call
            return n

    c = Crashy.remote()
    assert ray_tpu.get(c.work.remote(), timeout=60) == 1


def test_custom_serializer_scoped_and_deregisterable(ray_start_regular):
    import cloudpickle

    from ray_tpu._private.serialization import get_context

    class Odd:
        def __init__(self, x):
            self.x = x

    ctx = get_context()
    ctx.register_serializer(
        Odd, serializer=lambda o: o.x * 10, deserializer=lambda p: Odd(p)
    )
    try:
        blob = ctx.serialize_to_bytes(Odd(3))
        out = ctx.deserialize_from(memoryview(blob))
        assert isinstance(out, Odd) and out.x == 30
        # the registration must not leak into plain cloudpickle
        plain = cloudpickle.loads(cloudpickle.dumps(Odd(5)))
        assert plain.x == 5
    finally:
        ctx.deregister_serializer(Odd)
    blob = ctx.serialize_to_bytes(Odd(7))
    out = ctx.deserialize_from(memoryview(blob))
    assert out.x == 7  # default path after deregistration


def test_log_to_driver(ray_start_regular, capfd):
    @ray_tpu.remote
    def chatty():
        print("marker-from-worker-xyz")
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=60) == 1
    deadline = time.monotonic() + 10
    seen = ""
    while time.monotonic() < deadline:
        out, err = capfd.readouterr()
        seen += out + err
        if "marker-from-worker-xyz" in seen:
            break
        time.sleep(0.1)
    assert "marker-from-worker-xyz" in seen


def test_idle_worker_reaping(tmp_path):
    """Idle workers beyond the keep-warm floor exit after the timeout
    (parity: WorkerPool idle killing)."""
    import ray_tpu as rt
    from ray_tpu.util import state as state_api

    rt.init(
        num_cpus=4,
        ignore_reinit_error=True,
        _system_config={"worker_idle_timeout_s": 1.0},
    )
    try:
        @rt.remote
        def burst(i):
            time.sleep(0.1)
            return i

        rt.get([burst.remote(i) for i in range(8)], timeout=60)
        # several workers spawned; after the timeout only the floor remains
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            idle = [
                w for w in state_api.list_workers()
                if w["state"] == "idle" and not w["actor_id"]
            ]
            if len(idle) <= 2:
                break
            time.sleep(0.3)
        assert len(idle) <= 2, idle

        @rt.remote
        def again():
            return "ok"

        assert rt.get(again.remote(), timeout=60) == "ok"  # pool respawns fine
    finally:
        rt.shutdown()


def test_borrowed_ref_keeps_object_alive(ray_start_regular):
    """Parity: borrower tracking (reference_count.h:61) — an actor holding a
    deserialized ObjectRef keeps the object alive after the driver drops its
    own handle."""
    import gc
    import time

    import numpy as np

    import ray_tpu

    @ray_tpu.remote
    class Holder:
        def __init__(self, refs):
            self.refs = refs

        def read(self):
            return float(ray_tpu.get(self.refs[0], timeout=30).sum())

    arr = np.arange(50_000, dtype=np.float64)  # large enough to live in shm
    expect = float(arr.sum())
    ref = ray_tpu.put(arr)
    h = Holder.remote([ref])
    assert ray_tpu.get(h.read.remote(), timeout=60) == expect

    del ref, arr
    gc.collect()
    time.sleep(1.0)  # let the driver's remove_ref drain through the loop
    # the borrow held by the actor must keep the bytes fetchable
    assert ray_tpu.get(h.read.remote(), timeout=60) == expect


def test_object_freed_after_all_borrowers_drop(ray_start_regular):
    import gc
    import time

    import numpy as np

    import ray_tpu
    from ray_tpu.util import state

    @ray_tpu.remote
    class Holder:
        def __init__(self, refs):
            self.refs = refs

        def drop(self):
            self.refs = []
            import gc as _gc

            _gc.collect()
            return True

    ref = ray_tpu.put(np.arange(50_000, dtype=np.float64))
    oid_hex = ref.hex()
    h = Holder.remote([ref])
    ray_tpu.get(h.drop.remote(), timeout=60)
    del ref
    gc.collect()
    # every holder is gone and the transit pin was acked at deserialization,
    # so the free lands promptly (no TTL to wait out)
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        if all(o["object_id"] != oid_hex for o in state.list_objects()):
            break
        time.sleep(0.25)
    assert all(o["object_id"] != oid_hex for o in state.list_objects())


def test_borrowed_ref_survives_transit_pin_expiry(ray_start_regular):
    """A driver-held ref deserialized from a task result must outlive the
    sender's transit pin: the borrow flushes with the get, not lazily."""
    import time

    import numpy as np

    import ray_tpu
    from ray_tpu._private.worker import get_driver

    @ray_tpu.remote
    def producer():
        return ray_tpu.put(np.full(30_000, 7.0))

    inner = ray_tpu.get(producer.remote(), timeout=60)
    # idle longer than the old 10 s TTL cliff: with acknowledged handoff the
    # borrow was registered at deserialization, so no clock can free it
    assert get_driver().config.transit_pin_backstop_s > 60
    time.sleep(12.0)
    assert float(ray_tpu.get(inner, timeout=30).sum()) == 7.0 * 30_000


def test_ref_parked_in_blob_past_old_ttl(ray_start_regular):
    """Adversarial handoff: a serialized ref blob parked for longer than the
    old 10 s TTL cliff, with the sender's handle long gone, must still
    deserialize to a live object (acknowledged handoff has no clock)."""
    import gc
    import time

    import cloudpickle
    import numpy as np

    import ray_tpu

    ref = ray_tpu.put(np.full(20_000, 3.0))
    blob = cloudpickle.dumps(ref)  # takes the token transit pin
    del ref
    gc.collect()
    time.sleep(12.0)  # park past the old cliff; nothing else holds the object
    ref2 = cloudpickle.loads(blob)  # borrow + ack
    assert float(ray_tpu.get(ref2, timeout=30).sum()) == 3.0 * 20_000


def test_borrower_death_releases_refs(ray_start_regular):
    """A borrower whose worker dies mid-borrow must not leak its borrow: the
    scheduler releases dead holders' refs, so the object frees once every
    live handle is gone (the reference owner notices borrower death)."""
    import gc
    import time

    import numpy as np

    import ray_tpu
    from ray_tpu._private.worker import get_driver

    @ray_tpu.remote
    class Borrower:
        def __init__(self):
            self.held = None

        def hold(self, box):
            self.held = box["ref"]  # registers this worker as a borrower
            return True

    ref = ray_tpu.put(np.arange(30_000, dtype=np.float64))
    oid = ref.id()
    b = Borrower.remote()
    assert ray_tpu.get(b.hold.remote({"ref": ref}), timeout=60)
    ray_tpu.kill(b)  # borrower dies holding the borrow
    del b
    del ref
    gc.collect()
    sched = get_driver().node.scheduler
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sched._ref_counts.get(oid, 0) <= 0:
            break
        time.sleep(0.2)
    assert sched._ref_counts.get(oid, 0) <= 0, (
        f"borrow leaked: count={sched._ref_counts.get(oid)}"
    )


def test_nested_borrow_chain(ray_start_regular):
    """A ref nested inside containers through two task hops (each re-pickling
    it) survives each handoff and resolves at the end."""
    import numpy as np

    import ray_tpu

    @ray_tpu.remote
    def wrap(box):
        import time

        time.sleep(0.5)
        return {"inner": box["ref"], "hop": box.get("hop", 0) + 1}

    ref = ray_tpu.put(np.full(10_000, 5.0))
    hop1 = ray_tpu.get(wrap.remote({"ref": ref}), timeout=60)
    del ref
    import gc

    gc.collect()
    hop2 = ray_tpu.get(wrap.remote({"ref": hop1["inner"], "hop": hop1["hop"]}), timeout=60)
    del hop1
    gc.collect()
    assert hop2["hop"] == 2
    assert float(ray_tpu.get(hop2["inner"], timeout=30).sum()) == 5.0 * 10_000


def test_generator_refs_borrowed_cross_actor(ray_start_regular):
    """Streaming-generator return refs handed to another actor resolve there
    (generator refs flow through the same borrower protocol)."""
    import numpy as np

    import ray_tpu

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(3):
            yield np.full(5_000, float(i))

    @ray_tpu.remote
    class Consumer:
        def consume(self, box):
            import time

            time.sleep(0.3)
            # the nested ref is a genuine borrow (top-level args would be
            # auto-resolved before the method runs)
            return float(ray_tpu.get(box["r"], timeout=30).sum())

    c = Consumer.remote()
    totals = []
    for item_ref in gen.remote():
        totals.append(c.consume.remote({"r": item_ref}))
        del item_ref
    import gc

    gc.collect()
    assert ray_tpu.get(totals, timeout=120) == [0.0, 5_000.0, 10_000.0]


class _Cycle:
    """Holds what only the cyclic collector will free."""

    def __init__(self, held):
        self.held, self.me = held, self


@pytest.mark.parametrize("dropped", ["ref", "stream", "handle", "response"])
def test_a_finalizer_under_the_collector_takes_no_lock(ray_start_regular, dropped):
    """The rule at the top of ``_private/worker.py``: the collector may run a
    finalizer on a thread that holds the very lock the finalizer's decrement
    needs, so the finalizer only queues it, and the runtime's next entry point
    applies it. Before the rule the first case never came back: ``ObjectRef.__del__``
    went through ``remove_refs`` to ``MemoryStore.evict`` under the store's lock."""
    import gc
    import threading

    from ray_tpu import serve
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker import get_driver

    @ray_tpu.remote
    class Source:
        def value(self):
            return 7

        def slow(self):
            time.sleep(3)
            return 7

        def items(self, n):
            yield from range(n)

    direct = get_driver()._direct
    store = get_driver().scheduler.memory_store
    if dropped == "ref":  # a committed direct-call result
        held = Source.remote().value.remote()
        assert ray_tpu.get(held, timeout=60) == 7
        oid, lock = held.id(), store._lock

        def applied():
            return not store.contains(oid) and oid not in direct._owned

    elif dropped == "stream":  # an abandoned stream: four items nobody took
        held = Source.remote().items.options(num_returns="streaming").remote(4)
        ray_tpu.get(held._count_ref, timeout=60)
        items = [ObjectID.for_return(held._task_id, i) for i in range(1, 5)]
        assert all(store.contains(o) for o in items)
        lock = store._lock

        def applied():
            return not any(store.contains(o) for o in items)

    elif dropped == "handle":  # an actor handle with a call in flight
        held = Source.remote()
        in_flight = held.slow.remote()
        channel, lock = direct._actors[held._actor_id.binary()], direct._lock

        def applied():  # the decrement waits for the call, as it did
            return channel.pending_release == 1

    else:  # a response nobody asked for its result
        held = serve.run(serve.deployment(lambda: 7).bind(), name="dropped")
        held = held.remote()
        handle = held._call[0]
        lock = handle._lock

        def applied():
            return sum(handle._outstanding.values()) == 0

    try:
        assert not applied()
        box = [_Cycle(held)]
        del held

        def drop_under_the_lock():
            with lock:
                box.clear()
                gc.collect()

        thread = threading.Thread(target=drop_under_the_lock, daemon=True)
        thread.start()
        thread.join(10)
        assert not thread.is_alive(), "a finalizer waits for a lock its own thread holds"
        ray_tpu.put(0)  # the runtime's next entry point
        assert applied()
        if dropped == "handle":
            assert ray_tpu.get(in_flight, timeout=60) == 7
    finally:
        if dropped == "response":
            serve.shutdown()


def test_replica_death_and_failover_with_the_collector_at_every_allocation(ray_start_regular):
    """``tests/test_serve.py::test_replica_death_reconciled`` with the collector
    made to run where load used to make it: that test once sat out a whole
    tier-1 run in ``ObjectRef.__del__`` under ``MemoryStore.wait_for``."""
    import gc
    import threading

    from ray_tpu import serve

    @serve.deployment(num_replicas=1)
    class Fragile:
        def __call__(self):
            return "alive"

        def die(self):
            import os

            os._exit(1)

    def death_and_failover():
        handle = serve.run(Fragile.bind(), name="fragile")
        assert handle.remote().result(timeout_s=30) == "alive"
        try:
            handle.die.remote().result(timeout_s=30)
        except Exception:
            pass
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                assert serve.get_app_handle("fragile").remote().result(timeout_s=30) == "alive"
                done.append(True)
                return
            except Exception:
                time.sleep(0.5)

    done = []
    before = gc.get_threshold()
    gc.set_threshold(1)
    try:
        thread = threading.Thread(target=death_and_failover, daemon=True)
        thread.start()
        thread.join(60)
        alive = thread.is_alive()
    finally:
        gc.set_threshold(*before)
    try:
        assert not alive, "still going after 60 s"
        assert done, "the replica was not restarted"
    finally:
        serve.shutdown()
