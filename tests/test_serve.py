"""Serve-equivalent tests: deploy/route/update/recover/batch/HTTP.

Reference analog: serve/tests/test_deploy.py, test_handle.py,
test_batching.py, test_proxy.py.
"""

import json
import os
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


def test_deploy_and_route_across_replicas(rt):
    @serve.deployment(num_replicas=2)
    class Echo:
        def __init__(self, prefix):
            import os

            self.prefix = prefix
            self.pid = os.getpid()

        def __call__(self, x):
            return {"out": f"{self.prefix}{x}", "pid": self.pid}

    handle = serve.run(Echo.bind("hi:"))
    results = [handle.remote(i).result() for i in range(20)]
    assert [r["out"] for r in results] == [f"hi:{i}" for i in range(20)]
    # Power-of-two routing spreads load over both replica processes.
    assert len({r["pid"] for r in results}) == 2

    st = serve.status()
    assert st["Echo"]["running_replicas"] == 2


def test_replicas_construct_in_parallel_and_run_waits_for_them(rt):
    """serve.run waits on the constructors (a model replica compiles for
    minutes), not on a constant inside the controller, and replicas of one
    deployment construct side by side: each is inside its constructor while
    the other is, whatever the box's load makes of the seconds."""
    @serve.deployment(num_replicas=2)
    class Slow:
        def __init__(self):
            self.began = time.time()
            time.sleep(1.5)
            self.ended = time.time()

        def __call__(self):
            return os.getpid(), self.began, self.ended

    t0 = time.time()
    handle = serve.run(Slow.bind())
    returned = time.time()
    assert serve.status()["Slow"]["running_replicas"] == 2
    spans, deadline = {}, time.time() + 30
    while len(spans) < 2 and time.time() < deadline:
        pid, began, ended = handle.remote().result()
        spans[pid] = (began, ended)
    (began_a, ended_a), (began_b, ended_b) = spans.values()
    assert t0 < began_a < ended_b and t0 < began_b < ended_a  # side by side
    assert max(ended_a, ended_b) <= returned  # run waited for both


def test_failing_constructor_fails_run_with_its_error(rt):
    @serve.deployment
    class Broken:
        def __init__(self):
            raise ValueError("no chip for this replica")

        def __call__(self):
            return 1

    t0 = time.time()
    with pytest.raises(RuntimeError, match="no chip for this replica"):
        serve.run(Broken.bind(), timeout=60)
    assert time.time() - t0 < 30  # the error, not the timeout
    serve.delete("Broken")  # or the controller keeps retrying it


def test_rolling_update_changes_code(rt):
    @serve.deployment(num_replicas=1)
    def v1(x):
        return f"v1:{x}"

    handle = serve.run(v1.bind(), name="app")
    assert handle.remote(1).result() == "v1:1"

    @serve.deployment(num_replicas=1)
    def v2(x):
        return f"v2:{x}"

    handle = serve.run(v2.bind(), name="app")
    deadline = time.time() + 30
    while time.time() < deadline:
        if handle.remote(1).result() == "v2:1":
            break
        time.sleep(0.2)
    assert handle.remote(2).result() == "v2:2"


def test_replica_death_recovers(rt):
    @serve.deployment(num_replicas=2)
    class Svc:
        def __call__(self):
            import os

            return os.getpid()

    handle = serve.run(Svc.bind())
    pids = {handle.remote().result() for _ in range(10)}
    assert len(pids) == 2
    # Kill one replica process; the controller replaces it.
    import os
    import signal

    os.kill(next(iter(pids)), signal.SIGKILL)
    deadline = time.time() + 60
    while time.time() < deadline:
        if serve.status()["Svc"]["running_replicas"] == 2:
            try:
                new_pids = {handle.remote().result() for _ in range(10)}
                if len(new_pids) == 2:
                    break
            except Exception:
                pass
        time.sleep(0.3)
    else:
        pytest.fail("replica not replaced after death")


def test_batching(rt):
    @serve.deployment(num_replicas=1)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1)
        async def __call__(self, items):
            self.batch_sizes.append(len(items))
            return [i * 2 for i in items]

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind())
    responses = [handle.remote(i) for i in range(8)]
    assert [r.result() for r in responses] == [i * 2 for i in range(8)]
    sizes = handle.options("sizes").remote().result()
    assert max(sizes) > 1  # concurrent requests actually batched


def test_http_ingress(rt):
    @serve.deployment(num_replicas=1)
    def adder(a, b):
        return {"sum": a + b}

    serve.run(adder.bind(), name="adder")
    port = serve.start_http()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/adder",
            data=json.dumps({"a": 2, "b": 40}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert json.loads(resp.read()) == {"sum": 42}
    finally:
        serve.stop_http()


def test_autoscaling_scales_up(rt):
    @serve.deployment(autoscaling_config={
        "min_replicas": 1, "max_replicas": 3, "target_ongoing_requests": 1,
    })
    class Slow:
        def __call__(self):
            time.sleep(0.4)
            return "done"

    handle = serve.run(Slow.bind())
    assert serve.status()["Slow"]["running_replicas"] == 1
    # Sustained concurrent load drives queue pressure over target.
    deadline = time.time() + 45
    scaled = False
    inflight = []
    while time.time() < deadline and not scaled:
        inflight = [h for h in inflight if True][-8:]
        inflight.extend(handle.remote() for _ in range(4))
        time.sleep(0.2)
        if serve.status()["Slow"]["running_replicas"] >= 2:
            scaled = True
    assert scaled, "autoscaler did not add replicas under load"


def test_model_composition(rt):
    """Deployments calling other deployments: nested binds become their own
    deployments and the downstream receives a live DeploymentHandle
    (reference: serve deployment graphs / handle passing)."""

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Pipeline:
        def __init__(self, doubler):
            self.doubler = doubler

        def __call__(self, x):
            return self.doubler.remote(x).result() + 1

    handle = serve.run(Pipeline.bind(Doubler.bind()))
    assert handle.remote(10).result() == 21
    # Both nodes are live deployments with their own status entries.
    st = serve.status()
    assert "Pipeline" in st and "Doubler" in st


def test_multiplexing(rt):
    """Per-replica LRU of models keyed by the request's model id
    (reference: serve/multiplex.py + handle.options(multiplexed_model_id))."""

    @serve.deployment(num_replicas=2)
    class Host:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            self.loads.append(model_id)
            return f"model:{model_id}"

        def __call__(self):
            mid = serve.get_multiplexed_model_id()
            model = self.get_model(mid)
            return {"model": model, "loads": list(self.loads)}

    handle = serve.run(Host.bind())
    r1 = handle.options(multiplexed_model_id="a").remote().result()
    assert r1["model"] == "model:a"
    # Same model id -> same replica, warm cache: loads don't grow.
    r2 = handle.options(multiplexed_model_id="a").remote().result()
    assert r2["loads"].count("a") == 1
    # A different id loads separately (possibly on the other replica).
    r3 = handle.options(multiplexed_model_id="b").remote().result()
    assert r3["model"] == "model:b"


def test_multiplex_lru_eviction(rt):
    @serve.deployment(num_replicas=1)
    class Host:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            self.loads.append(model_id)
            return model_id

        def __call__(self):
            mid = serve.get_multiplexed_model_id()
            self.get_model(mid)
            return list(self.loads)

    handle = serve.run(Host.bind())
    for mid in ("a", "b", "c", "a"):  # c evicts a (LRU size 2) -> a reloads
        loads = handle.options(multiplexed_model_id=mid).remote().result()
    assert loads == ["a", "b", "c", "a"]


def test_grpc_ingress(rt):
    """Generic-method gRPC ingress (reference: serve/_private/proxy.py:545
    gRPCProxy): JSON-bytes request routed to a deployment handle."""
    import grpc

    from ray_tpu.serve.grpc_ingress import CALL_METHOD

    @serve.deployment(num_replicas=2)
    class Adder:
        def __call__(self, a, b=0):
            return {"sum": a + b}

        def neg(self, a):
            return -a

    serve.run(Adder.bind())
    port = serve.start_grpc()
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        stub = channel.unary_unary(CALL_METHOD)
        reply = json.loads(stub(json.dumps({
            "deployment": "Adder", "args": [40], "kwargs": {"b": 2},
        }).encode()))
        assert reply["result"] == {"sum": 42}

        reply = json.loads(stub(json.dumps({
            "deployment": "Adder", "method": "neg", "args": [7],
        }).encode()))
        assert reply["result"] == -7

        with pytest.raises(grpc.RpcError) as ei:
            stub(json.dumps({"deployment": "Nope", "args": []}).encode())
        assert ei.value.code() in (grpc.StatusCode.NOT_FOUND,
                                   grpc.StatusCode.INTERNAL)
        channel.close()
    finally:
        serve.stop_grpc()


def test_streaming_handle_and_http_sse(rt):
    """Generator deployments stream through the handle
    (options(stream=True)) and the HTTP ingress (SSE): tokens arrive one
    frame each, in order, with bounded consumer-side buffering
    (reference: proxy.py:537-598 streaming HTTP responses)."""

    @serve.deployment(num_replicas=1)
    class Tokens:
        def __call__(self, n=5, prefix="tok"):
            for i in range(n):
                yield f"{prefix}{i}"

    handle = serve.run(Tokens.bind())

    # Handle-level streaming: a DeploymentResponseGenerator of items.
    items = list(handle.options(stream=True).remote(4, prefix="h"))
    assert items == ["h0", "h1", "h2", "h3"]

    # HTTP SSE: Accept: text/event-stream gets one data: frame per token.
    port = serve.start_http()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/Tokens",
            data=json.dumps({"n": 3, "prefix": "t"}).encode(),
            headers={"Content-Type": "application/json",
                     "Accept": "text/event-stream"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["Content-Type"] == "text/event-stream"
            frames = []
            done = False
            for raw in resp:
                line = raw.decode().strip()
                if line.startswith("data:") and not done:
                    frames.append(json.loads(line[5:].strip()))
                if line.startswith("event: done"):
                    done = True
            assert done
            assert frames[:3] == ["t0", "t1", "t2"]
        # Unary POST on the same deployment still works (one-item stream
        # semantics don't leak into the non-streaming path: the generator
        # is returned whole, so clients must opt in via Accept).
    finally:
        serve.stop_http()


def test_streaming_grpc_ingress(rt):
    """unary_stream gRPC: one JSON frame per yielded token, then a done
    frame (reference: the gRPC proxy's streaming responses — the main
    reason a model server wants gRPC)."""
    import grpc

    from ray_tpu.serve.grpc_ingress import CALL_STREAM_METHOD

    @serve.deployment(num_replicas=1)
    class Gen:
        def tokens(self, n):
            for i in range(n):
                yield {"t": i}

    serve.run(Gen.bind())
    port = serve.start_grpc()
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        stub = channel.unary_stream(CALL_STREAM_METHOD)
        frames = [json.loads(b) for b in stub(json.dumps({
            "deployment": "Gen", "method": "tokens", "args": [5],
        }).encode())]
        assert frames[-1] == {"done": True}
        assert [f["item"]["t"] for f in frames[:-1]] == [0, 1, 2, 3, 4]

        # Unknown deployment aborts the stream with NOT_FOUND.
        with pytest.raises(grpc.RpcError) as ei:
            list(stub(json.dumps({"deployment": "Nope"}).encode()))
        assert ei.value.code() in (grpc.StatusCode.NOT_FOUND,
                                   grpc.StatusCode.INTERNAL)
        channel.close()
    finally:
        serve.stop_grpc()


def test_llm_token_streaming_deployment(rt):
    """The full LLM-serving story: a deployment holds Llama weights + a
    greedy decode loop and STREAMS tokens as they decode — handle-level
    and SSE (reference: Ray Serve's LLM APIs stream autoregressive
    tokens; here decode-step latency hides behind the serve streaming
    path)."""

    import cloudpickle
    import greedy_ref
    from greedy_ref import greedy_tokens

    @serve.deployment(num_replicas=1)
    class TinyLlama:
        def __init__(self):
            import jax
            import jax.numpy as jnp

            from ray_tpu.models import LlamaConfig, llama_init

            self.cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
            self.params = llama_init(self.cfg, jax.random.PRNGKey(0))

        def __call__(self, prompt_tokens, max_new_tokens=4):
            import queue as _q
            import threading

            out_q: "_q.Queue" = _q.Queue()

            def run():
                greedy_tokens(self.cfg, self.params, list(prompt_tokens),
                              max_new_tokens, stream=out_q.put)
                out_q.put(None)

            threading.Thread(target=run, daemon=True).start()
            while True:
                tok = out_q.get(timeout=120)
                if tok is None:
                    return
                yield tok

    # The replica's process has no tests/ on its path: the helper travels
    # with the deployment.
    cloudpickle.register_pickle_by_value(greedy_ref)
    try:
        handle = serve.run(TinyLlama.bind())
    finally:
        cloudpickle.unregister_pickle_by_value(greedy_ref)
    toks = list(handle.options(stream=True).remote([1, 2, 3], 5))
    assert len(toks) == 5 and all(isinstance(t, int) for t in toks)

    # Determinism across calls (greedy decode, same weights).
    toks2 = list(handle.options(stream=True).remote([1, 2, 3], 5))
    assert toks2 == toks

    port = serve.start_http()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/TinyLlama",
            data=json.dumps({"prompt_tokens": [1, 2, 3],
                             "max_new_tokens": 3}).encode(),
            headers={"Accept": "text/event-stream"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            frames = [json.loads(ln[5:].strip())
                      for ln in resp.read().decode().splitlines()
                      if ln.startswith("data:") and ln[5:].strip() != "null"]
        assert frames == toks[:3]
    finally:
        serve.stop_http()
