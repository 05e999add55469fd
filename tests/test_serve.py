"""Serve tests. Parity: ``python/ray/serve/tests`` patterns (SURVEY.md §4)."""

import json
import os
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster():
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    serve.shutdown()
    ray_tpu.shutdown()


def test_function_deployment(serve_cluster):
    @serve.deployment
    def echo(payload=None):
        return {"echo": payload}

    handle = serve.run(echo.bind(), name="echo_app")
    assert handle.remote({"x": 1}).result(timeout_s=60) == {"echo": {"x": 1}}


def test_class_deployment_and_methods(serve_cluster):
    @serve.deployment
    class Counter:
        def __init__(self, start):
            self.v = start

        def __call__(self, k=1):
            self.v += k
            return self.v

        def value(self):
            return self.v

    handle = serve.run(Counter.bind(10), name="counter_app")
    assert handle.remote(5).result(timeout_s=60) == 15
    assert handle.value.remote().result(timeout_s=60) == 15


def test_multiple_replicas_spread_load(serve_cluster):
    import os

    @serve.deployment(num_replicas=2)
    class WhoAmI:
        def __call__(self):
            return os.getpid()

    handle = serve.run(WhoAmI.bind(), name="pids")
    pids = {handle.remote().result(timeout_s=60) for _ in range(20)}
    assert len(pids) == 2


def test_model_composition(serve_cluster):
    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Model:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            y = self.pre.remote(x).result(timeout_s=60)
            return y + 1

    handle = serve.run(Model.bind(Preprocess.bind()), name="composed")
    assert handle.remote(10).result(timeout_s=60) == 21


def test_replica_death_reconciled(serve_cluster):
    @serve.deployment(num_replicas=1)
    class Fragile:
        def __call__(self):
            return "alive"

        def die(self):
            import os

            os._exit(1)

    handle = serve.run(Fragile.bind(), name="fragile")
    assert handle.remote().result(timeout_s=60) == "alive"
    try:
        handle.die.remote().result(timeout_s=30)
    except Exception:
        pass
    # reconciler restarts the replica within a few seconds
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            fresh = serve.get_app_handle("fragile")
            assert fresh.remote().result(timeout_s=30) == "alive"
            break
        except Exception:
            time.sleep(0.5)
    else:
        pytest.fail("replica was not restarted")


def test_http_proxy(serve_cluster):
    @serve.deployment
    def double(payload=None):
        return {"doubled": payload["x"] * 2}

    serve.run(double.bind(), name="http_app", route_prefix="/double")
    req = urllib.request.Request(
        "http://127.0.0.1:8700/double",
        data=json.dumps({"x": 21}).encode(),
        headers={"Content-Type": "application/json"},
    )
    body = json.loads(urllib.request.urlopen(req, timeout=60).read())
    assert body["result"]["doubled"] == 42
    # 404 on unknown route
    try:
        urllib.request.urlopen("http://127.0.0.1:8700/nope", timeout=30)
        pytest.fail("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_batching(serve_cluster):
    @serve.deployment(max_ongoing_requests=8)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def __call__(self, items):
            self.batch_sizes.append(len(items))
            return [i * 10 for i in items]

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind(), name="batched")
    responses = [handle.remote(i) for i in range(8)]
    out = sorted(r.result(timeout_s=60) for r in responses)
    assert out == [0, 10, 20, 30, 40, 50, 60, 70]
    sizes = handle.sizes.remote().result(timeout_s=60)
    assert max(sizes) > 1  # batching actually coalesced requests


def test_status_and_delete(serve_cluster):
    @serve.deployment(num_replicas=2)
    def f(p=None):
        return 1

    serve.run(f.bind(), name="stat_app")
    st = serve.status()
    assert st["stat_app"]["f"]["num_replicas"] == 2
    serve.delete("stat_app")
    with pytest.raises(ValueError):
        serve.get_app_handle("stat_app")


def test_streaming_response(serve_cluster):
    @serve.deployment
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                yield i * 3

    handle = serve.run(Streamer.bind(), name="stream_app")
    out = list(handle.options(stream=True).remote(4))
    assert out == [0, 3, 6, 9]


@pytest.mark.parametrize("gate,threads", [(1, 8), (8, 36), (15, 64), (40, 64), (60, 64), (64, 68), (80, 84), (160, 164)])
def test_a_replicas_thread_pool_is_past_its_request_gate_at_every_size(gate, threads):
    """Four times the gate up to 64 threads (every size it had until a gate of
    60), and past that the gate and a few more: a stream holds its thread
    while it lasts, and a health probe needs one."""
    from ray_tpu.serve.api import replica_threads

    assert replica_threads(gate) == threads > gate


def test_a_replica_holds_more_streams_than_64_and_still_answers_another_call(serve_cluster):
    """65 streams held open under a gate of 66, past the 64 threads a replica's
    pool stopped at: each has entered (its first item came) and the gate's
    last request is still answered; then all of them end."""
    @serve.deployment(max_ongoing_requests=66)
    class Holder:
        def __init__(self):
            import threading

            self.released, self.entered, self.lock = threading.Event(), 0, threading.Lock()

        def hold(self):
            with self.lock:
                self.entered += 1
            yield "in"
            self.released.wait(120)
            yield "out"

        def release(self):
            self.released.set()
            return self.entered

    handle = serve.run(Holder.bind(), name="holder")
    streams = [iter(handle.options(stream=True).hold.remote()) for _ in range(65)]
    try:
        assert [next(s) for s in streams] == ["in"] * 65
        assert handle.release.remote().result(timeout_s=60) == 65
        assert [list(s) for s in streams] == [["out"]] * 65
    finally:
        serve.delete("holder")


def test_multiplexed_models(serve_cluster):
    @serve.deployment(num_replicas=2)
    class MultiModel:
        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            return f"model:{model_id}"

        def __call__(self, x):
            mid = serve.get_multiplexed_model_id()
            model = self.get_model(mid)
            return f"{model}+{x}"

    handle = serve.run(MultiModel.bind(), name="mux_app")
    r1 = handle.options(multiplexed_model_id="a").remote(1).result(timeout_s=60)
    r2 = handle.options(multiplexed_model_id="b").remote(2).result(timeout_s=60)
    assert r1 == "model:a+1"
    assert r2 == "model:b+2"


def test_autoscaling_up_and_down(serve_cluster):
    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_ongoing_requests": 1,
        },
        max_ongoing_requests=1,
    )
    class Slow:
        def __call__(self):
            time.sleep(1.2)
            return "ok"

    handle = serve.run(Slow.bind(), name="auto_app")

    def replica_count():
        st = serve.status()
        return st["auto_app"]["Slow"]["num_replicas"]

    assert replica_count() == 1
    # sustained burst: keep >= 6 requests in flight so the controller's
    # metric poll sees depth > target and scales out
    responses = [handle.remote() for _ in range(12)]
    deadline = time.monotonic() + 40
    grew = False
    while time.monotonic() < deadline:
        if replica_count() >= 2:
            grew = True
            break
        responses = [r for r in responses if True]  # keep refs alive
        time.sleep(0.5)
    for r in responses:
        r.result(timeout_s=120)
    assert grew, "deployment never scaled out"
    # idle: scales back down to min
    deadline = time.monotonic() + 60
    shrank = False
    while time.monotonic() < deadline:
        if replica_count() == 1:
            shrank = True
            break
        time.sleep(0.5)
    assert shrank, "deployment never scaled back in"


def test_lm_generation_deployment(serve_cluster):
    """KV-cache generation behind a Serve deployment (examples/serve_lm.py)."""
    import os
    import sys

    examples_dir = os.path.join(os.path.dirname(__file__), "..", "examples")
    sys.path.insert(0, examples_dir)
    try:
        from serve_lm import LMServer
    finally:
        sys.path.pop(0)

    handle = serve.run(LMServer.bind(), name="lm_gen")
    out = handle.generate.remote([1, 2, 3, 4], max_new_tokens=4).result(timeout_s=120)
    assert len(out["tokens"]) == 4
    assert all(isinstance(t, int) for t in out["tokens"])


def _repo_root_on_path():
    import os
    import sys

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if root not in sys.path:
        sys.path.insert(0, root)


def test_build_and_deploy_config(serve_cluster, tmp_path):
    """serve.build -> yaml -> deploy_config_file round trip with overrides."""
    _repo_root_on_path()
    from examples.serve_config_app import app

    config = serve.build(
        app, name="cfgapp", import_path="examples.serve_config_app:app"
    )
    names = [d["name"] for d in config["applications"][0]["deployments"]]
    assert set(names) == {"Doubler", "Ingress"}
    # override replica count through the config
    for d in config["applications"][0]["deployments"]:
        if d["name"] == "Doubler":
            d["num_replicas"] = 2
    path = str(tmp_path / "serve.yaml")
    serve.dump_config(config, path)

    handles = serve.deploy_config_file(path)
    assert serve.status()["cfgapp"]["Doubler"]["num_replicas"] == 2
    assert handles["cfgapp"].remote(20).result(timeout_s=60) == 41
    serve.delete("cfgapp")


def test_serve_cli_status_and_build(serve_cluster, tmp_path, capsys):
    _repo_root_on_path()
    from examples.serve_config_app import app as _app  # noqa: F401
    from ray_tpu.scripts.cli import main

    out = str(tmp_path / "out.yaml")
    main(["serve", "build", "examples.serve_config_app:app",
          "--name", "cliapp", "-o", out])
    import yaml

    config = yaml.safe_load(open(out))
    assert config["applications"][0]["import_path"] == "examples.serve_config_app:app"

    main(["serve", "run", out])
    main(["serve", "status"])
    captured = capsys.readouterr().out
    assert "cliapp" in captured
    from ray_tpu.serve import get_app_handle

    assert get_app_handle("cliapp").remote(1).result(timeout_s=60) == 3
    serve.delete("cliapp")


def test_grpc_ingress(serve_cluster):
    """Parity: the gRPC proxy ingress (proxy.py gRPCProxy)."""

    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"echo": payload, "n": len(payload)}

    serve.run(Echo.bind(), name="grpcapp")
    port = serve.start_grpc_proxy()
    out = serve.grpc_predict(f"127.0.0.1:{port}", "hello", application="grpcapp")
    assert out == {"echo": "hello", "n": 5}

    # errors surface as exceptions, not hung calls
    @serve.deployment
    class Boom:
        def __call__(self, payload):
            raise ValueError("nope")

    serve.run(Boom.bind(), name="grpcboom")
    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="nope"):
        serve.grpc_predict(f"127.0.0.1:{port}", "x", application="grpcboom")
    # unauthenticated raw pickle must be rejected before unpickling
    # (pickle.loads executes code; parity with the HMAC auth on every other
    # socket in the framework)
    import pickle

    import grpc

    from ray_tpu.serve._grpc_proxy import SERVICE_METHOD

    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        fn = channel.unary_unary(SERVICE_METHOD)
        with pytest.raises(grpc.RpcError) as excinfo:
            fn(pickle.dumps("unauthenticated"), timeout=30)
        assert excinfo.value.code() == grpc.StatusCode.UNAUTHENTICATED
    finally:
        channel.close()
    serve.delete("grpcapp")
    serve.delete("grpcboom")


def test_user_config_reconfigure(serve_cluster):
    """user_config: delivered at startup, and a redeploy changing ONLY
    user_config reconfigures live replicas without restarting them."""
    import os

    @serve.deployment(user_config={"threshold": 1})
    class Configurable:
        def __init__(self):
            self.threshold = None
            self.pid = os.getpid()

        def reconfigure(self, config):
            self.threshold = config["threshold"]

        def __call__(self):
            return {"threshold": self.threshold, "pid": self.pid}

    handle = serve.run(Configurable.bind(), name="ucfg")
    first = handle.remote().result(timeout_s=60)
    assert first["threshold"] == 1

    # redeploy with ONLY user_config changed -> same replica pid, new config
    handle2 = serve.run(
        Configurable.options(user_config={"threshold": 7}).bind(), name="ucfg"
    )
    second = handle2.remote().result(timeout_s=60)
    assert second["threshold"] == 7
    assert second["pid"] == first["pid"], "replica was restarted (heavyweight)"

    # changing num_replicas too -> full restart (new pid allowed)
    handle3 = serve.run(
        Configurable.options(user_config={"threshold": 9}, num_replicas=1,
                             max_ongoing_requests=4).bind(),
        name="ucfg",
    )
    third = handle3.remote().result(timeout_s=60)
    assert third["threshold"] == 9
    serve.delete("ucfg")


def test_rest_deploy_endpoint(serve_cluster, tmp_path):
    """PUT /api/serve/applications deploys a declarative config (parity: the
    reference's serve REST API)."""
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    _repo_root_on_path()
    port = start_dashboard(port=0)
    try:
        config = {
            "applications": [
                {
                    "name": "restapp",
                    "import_path": "examples.serve_config_app:app",
                    "deployments": [{"name": "Doubler", "num_replicas": 1}],
                }
            ]
        }
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/serve/applications",
            data=json.dumps(config).encode(),
            method="PUT",
            headers={"Content-Type": "application/json"},
        )
        body = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert body["deployed"] == ["restapp"]
        from ray_tpu.serve import get_app_handle

        assert get_app_handle("restapp").remote(3).result(timeout_s=60) == 7
        # GET /api/serve reflects it
        st = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port}/api/serve", timeout=30).read()
        )
        assert "restapp" in st
        serve.delete("restapp")
    finally:
        stop_dashboard()


def test_per_node_proxies():
    """One HTTP ingress per alive node (parity: ProxyState's proxy-per-node),
    each serving the registered routes via its own handles."""
    import json as _json
    import urllib.request

    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        cluster.add_node(num_cpus=1)
        cluster.wait_for_nodes()

        @serve.deployment
        class Echo:
            def __call__(self, x):
                return {"echo": x}

        serve.run(Echo.bind(), name="pnp", route_prefix="/pnp")
        proxies = serve.start_node_proxies()
        assert len(proxies) >= 2  # head + daemon node
        for nid, (host, port) in proxies.items():
            req = urllib.request.Request(
                f"http://{host}:{port}/pnp",
                data=_json.dumps(5).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=60) as resp:
                body = _json.loads(resp.read())
            assert body["result"] == {"echo": 5}, (nid, body)
        serve.delete("pnp")
    finally:
        cluster.shutdown()


def test_probed_queue_depths_reach_handles(serve_cluster):
    """The controller's reconcile loop probes replica queue depths and
    handles fold them into pow-2 scoring (pow_2_scheduler.py:49 parity)."""
    import time as _time

    @serve.deployment(num_replicas=2)
    class Slowish:
        def __call__(self, x):
            return x

    serve.run(Slowish.bind(), name="probed")
    handle = serve.get_app_handle("probed")
    assert handle.remote(1).result(timeout_s=60) == 1
    # wait past a reconcile pass, then force a refresh and check depths came
    deadline = _time.monotonic() + 30
    while _time.monotonic() < deadline:
        handle._last_refresh = 0.0
        handle.remote(2).result(timeout_s=60)
        if handle._probed_depths:
            break
        _time.sleep(0.5)
    assert handle._probed_depths, "controller depths never reached the handle"
    serve.delete("probed")


# ---- ASGI-grade ingress (parity: serve.ingress + uvicorn data plane) ----


def _http_roundtrip(host, port, method, path, body=b"", headers=None, n=1):
    """Raw HTTP/1.1 client exercising keep-alive: n requests on ONE socket.
    Returns list of (status, headers_dict, body_bytes)."""
    import socket

    out = []
    s = socket.create_connection((host, port), timeout=30)
    try:
        for _ in range(n):
            hdrs = {"Host": host, "Content-Length": str(len(body))}
            hdrs.update(headers or {})
            req = f"{method} {path} HTTP/1.1\r\n" + "".join(
                f"{k}: {v}\r\n" for k, v in hdrs.items()
            ) + "\r\n"
            s.sendall(req.encode() + body)
            f = s.makefile("rb")
            status = int(f.readline().split()[1])
            resp_headers = {}
            while True:
                line = f.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.decode().partition(":")
                resp_headers[k.strip().lower()] = v.strip()
            if resp_headers.get("transfer-encoding") == "chunked":
                chunks = []
                while True:
                    size = int(f.readline().strip(), 16)
                    if size == 0:
                        f.readline()
                        break
                    chunks.append(f.read(size))
                    f.readline()
                payload = b"".join(chunks)
            else:
                payload = f.read(int(resp_headers.get("content-length", 0)))
            out.append((status, resp_headers, payload))
    finally:
        s.close()
    return out


def test_http_raw_bytes_body(serve_cluster):
    """Non-JSON request/response: raw bytes pass through untouched."""
    import ray_tpu.serve as serve
    from ray_tpu.serve._proxy import ensure_proxy
    from ray_tpu.serve.api import _get_or_create_controller

    @serve.deployment
    def echo_upper(data):
        assert isinstance(data, bytes)
        return data.upper()  # bytes in, bytes out

    serve.run(echo_upper.bind(), name="rawapp", route_prefix="/raw")
    proxy = ensure_proxy(_get_or_create_controller(), "rawapp", "/raw")
    host, port = ray_tpu.get(proxy.address.remote(), timeout=60)
    [(status, hdrs, body)] = _http_roundtrip(
        host, port, "POST", "/raw", b"\x00binary\xffdata",
        headers={"Content-Type": "application/octet-stream"},
    )
    assert status == 200
    assert hdrs["content-type"] == "application/octet-stream"
    assert body == b"\x00BINARY\xffDATA"
    serve.delete("rawapp")


def test_http_asgi_app_and_streaming(serve_cluster):
    """An ASGI app mounted with serve.ingress: routed responses, raw bodies,
    and a chunked streaming endpoint delivering incrementally."""
    import ray_tpu.serve as serve
    from ray_tpu.serve._proxy import ensure_proxy
    from ray_tpu.serve.api import _get_or_create_controller

    async def app(scope, receive, send):
        assert scope["type"] == "http"
        path = scope["path"]
        if path.endswith("/stream"):
            await send({"type": "http.response.start", "status": 200,
                        "headers": [(b"content-type", b"text/plain")]})
            for i in range(5):
                await send({"type": "http.response.body",
                            "body": f"chunk-{i};".encode(), "more_body": True})
            await send({"type": "http.response.body", "body": b"done",
                        "more_body": False})
            return
        msg = await receive()
        body = msg.get("body", b"")
        await send({"type": "http.response.start", "status": 201,
                    "headers": [(b"content-type", b"application/x-custom"),
                                (b"x-echo-len", str(len(body)).encode())]})
        await send({"type": "http.response.body",
                    "body": b"asgi:" + body[::-1], "more_body": False})

    @serve.deployment
    @serve.ingress(app)
    class AsgiD:
        pass

    serve.run(AsgiD.bind(), name="asgiapp", route_prefix="/asgi")
    proxy = ensure_proxy(_get_or_create_controller(), "asgiapp", "/asgi")
    host, port = ray_tpu.get(proxy.address.remote(), timeout=60)

    [(status, hdrs, body)] = _http_roundtrip(
        host, port, "POST", "/asgi/echo", b"hello",
        headers={"Content-Type": "application/octet-stream"},
    )
    assert status == 201
    assert hdrs["content-type"] == "application/x-custom"
    assert hdrs["x-echo-len"] == "5"
    assert body == b"asgi:olleh"

    [(status, hdrs, body)] = _http_roundtrip(host, port, "GET", "/asgi/stream")
    assert status == 200
    assert hdrs.get("transfer-encoding") == "chunked"
    assert body == b"chunk-0;chunk-1;chunk-2;chunk-3;chunk-4;done"
    serve.delete("asgiapp")


def test_http_keep_alive_reuse(serve_cluster):
    """Several requests on one client socket (persistent connections)."""
    import ray_tpu.serve as serve
    from ray_tpu.serve._proxy import ensure_proxy
    from ray_tpu.serve.api import _get_or_create_controller

    @serve.deployment
    def count(payload=None):
        return {"n": (payload or {}).get("n", 0) * 2}

    serve.run(count.bind(), name="kaapp", route_prefix="/ka")
    proxy = ensure_proxy(_get_or_create_controller(), "kaapp", "/ka")
    host, port = ray_tpu.get(proxy.address.remote(), timeout=60)
    results = []
    import json as _json

    for i in range(4):
        results.append(
            _http_roundtrip(
                host, port, "POST", "/ka",
                _json.dumps({"n": i}).encode(),
                headers={"Content-Type": "application/json"},
            )[0]
        )
    # all four rode persistent connections and returned doubled values
    assert [
        _json.loads(b)["result"]["n"] for (_, _, b) in results
    ] == [0, 2, 4, 6]
    # and 4 requests over a SINGLE socket work end-to-end
    multi = _http_roundtrip(
        host, port, "POST", "/ka", _json.dumps({"n": 5}).encode(),
        headers={"Content-Type": "application/json"}, n=4,
    )
    assert all(_json.loads(b)["result"]["n"] == 10 for (_, _, b) in multi)
    serve.delete("kaapp")


# ---- websockets (parity: ASGI websocket scopes through the proxy) ----


def test_websocket_echo_roundtrip(serve_cluster):
    """Full RFC 6455 session: upgrade, subprotocol negotiation, text and
    binary echo, ping/pong, app-initiated close with code+reason."""
    import ray_tpu.serve as serve
    from ray_tpu.serve._proxy import ensure_proxy
    from ray_tpu.serve._ws import WSClient
    from ray_tpu.serve.api import _get_or_create_controller

    async def app(scope, receive, send):
        assert scope["type"] == "websocket"
        msg = await receive()
        assert msg["type"] == "websocket.connect"
        sub = scope["subprotocols"][0] if scope["subprotocols"] else None
        await send({"type": "websocket.accept", "subprotocol": sub})
        while True:
            msg = await receive()
            if msg["type"] == "websocket.disconnect":
                return
            if msg.get("text") is not None:
                if msg["text"] == "quit":
                    await send({"type": "websocket.close", "code": 4001,
                                "reason": "bye"})
                    return
                await send({"type": "websocket.send",
                            "text": msg["text"].upper()})
            else:
                await send({"type": "websocket.send",
                            "bytes": msg["bytes"][::-1]})

    @serve.deployment
    @serve.ingress(app)
    class WsD:
        pass

    serve.run(WsD.bind(), name="wsapp", route_prefix="/ws")
    proxy = ensure_proxy(_get_or_create_controller(), "wsapp", "/ws")
    host, port = ray_tpu.get(proxy.address.remote(), timeout=60)

    c = WSClient(host, port, "/ws/chat", subprotocols=("chat", "alt"))
    try:
        assert c.subprotocol == "chat"
        c.send_text("hello")
        assert c.recv() == "HELLO"
        c.send_bytes(b"\x01\x02\x03")
        assert c.recv() == b"\x03\x02\x01"
        c.ping(b"p")
        assert c.recv() == ("pong", b"p")
        c.send_text("quit")
        assert c.recv() == ("close", 4001, "bye")
    finally:
        c.close()
    serve.delete("wsapp")


def test_websocket_reject_and_client_disconnect(serve_cluster):
    """App close before accept surfaces as HTTP 403; an accepted session
    whose client vanishes delivers websocket.disconnect to the app."""
    import ray_tpu.serve as serve
    from ray_tpu.serve._proxy import ensure_proxy
    from ray_tpu.serve._ws import WSClient
    from ray_tpu.serve.api import _get_or_create_controller

    async def app(scope, receive, send):
        await receive()  # websocket.connect
        if scope["path"].endswith("/reject"):
            await send({"type": "websocket.close", "code": 1008})
            return
        await send({"type": "websocket.accept"})
        while True:
            msg = await receive()
            if msg["type"] == "websocket.disconnect":
                # visible side channel: write a marker the test can poll
                with open(scope["extensions"]["marker_path"], "w") as f:
                    f.write(str(msg.get("code")))
                return
            await send({"type": "websocket.send", "text": "ok"})

    import tempfile

    marker = tempfile.NamedTemporaryFile(delete=False)
    marker.close()
    marker_path = marker.name

    async def wrapped(scope, receive, send):
        ext = dict(scope.get("extensions") or {})
        ext["marker_path"] = marker_path
        scope = dict(scope)
        scope["extensions"] = ext
        await app(scope, receive, send)

    @serve.deployment
    @serve.ingress(wrapped)
    class WsR:
        pass

    serve.run(WsR.bind(), name="wsrapp", route_prefix="/wsr")
    proxy = ensure_proxy(_get_or_create_controller(), "wsrapp", "/wsr")
    host, port = ray_tpu.get(proxy.address.remote(), timeout=60)

    try:
        WSClient(host, port, "/wsr/reject")
        assert False, "upgrade should have been refused"
    except ConnectionError as e:
        assert "403" in str(e)

    c = WSClient(host, port, "/wsr/chat")
    c.send_text("x")
    assert c.recv() == "ok"
    c._sock.close()  # vanish without a close frame
    deadline = time.time() + 30
    code = ""
    while time.time() < deadline:
        with open(marker_path) as f:
            code = f.read().strip()
        if code:
            break
        time.sleep(0.2)
    assert code == "1006", f"app never saw the disconnect (marker={code!r})"
    os.unlink(marker_path)
    serve.delete("wsrapp")


def test_websocket_fragmented_message_with_interleaved_ping(serve_cluster):
    """RFC 6455 §5.4: control frames may be injected inside a fragmented
    message; the relay must buffer the partial message across them."""
    import ray_tpu.serve as serve
    from ray_tpu.serve import _ws as ws
    from ray_tpu.serve._proxy import ensure_proxy
    from ray_tpu.serve.api import _get_or_create_controller

    async def app(scope, receive, send):
        await receive()
        await send({"type": "websocket.accept"})
        while True:
            m = await receive()
            if m["type"] == "websocket.disconnect":
                return
            await send({"type": "websocket.send", "text": m["text"].upper()})

    @serve.deployment
    @serve.ingress(app)
    class WsF:
        pass

    serve.run(WsF.bind(), name="wsfrag", route_prefix="/wsfrag")
    proxy = ensure_proxy(_get_or_create_controller(), "wsfrag", "/wsfrag")
    host, port = ray_tpu.get(proxy.address.remote(), timeout=60)
    c = ws.WSClient(host, port, "/wsfrag")
    try:
        c._sock.sendall(ws.encode_frame(ws.OP_TEXT, b"hel", fin=False, mask=True))
        c._sock.sendall(ws.encode_frame(ws.OP_PING, b"p", mask=True))
        c._sock.sendall(ws.encode_frame(ws.OP_CONT, b"lo", fin=True, mask=True))
        msgs = [c.recv(), c.recv()]
        assert ("pong", b"p") in msgs and "HELLO" in msgs, msgs
    finally:
        c.close()
    serve.delete("wsfrag")


def test_websocket_replica_death_closes_session(serve_cluster):
    """Killing the replica mid-session must surface as an abnormal close
    (1011 close frame, or a dropped connection) to the client, not a hang."""
    from ray_tpu.serve._proxy import ensure_proxy
    from ray_tpu.serve._ws import WSClient
    from ray_tpu.serve.api import _get_or_create_controller, get_app_handle

    async def app(scope, receive, send):
        await receive()
        await send({"type": "websocket.accept"})
        while True:
            m = await receive()
            if m["type"] == "websocket.disconnect":
                return
            await send({"type": "websocket.send", "text": "pong"})

    @serve.deployment
    @serve.ingress(app)
    class WsK:
        pass

    serve.run(WsK.bind(), name="wskill", route_prefix="/wskill")
    proxy = ensure_proxy(_get_or_create_controller(), "wskill", "/wskill")
    host, port = ray_tpu.get(proxy.address.remote(), timeout=60)
    c = WSClient(host, port, "/wskill")
    try:
        c.send_text("hi")
        assert c.recv() == "pong"
        # kill every replica out from under the session
        handle = get_app_handle("wskill")
        replicas = list(handle._replicas)
        assert replicas, "no replicas to kill"
        for r in replicas:
            ray_tpu.kill(r)
        try:
            got = c.recv()
        except ConnectionError:
            got = ("close", 1006, "connection dropped")  # also abnormal
        assert isinstance(got, tuple) and got[0] == "close", got
        assert got[1] in (1006, 1011), got
    finally:
        c.close()
        serve.delete("wskill")
