"""rtlint: framework-aware static analysis (ray_tpu/devtools/).

Reference analog: the protections Ray gets from protobuf schemas + C++
sanitizer CI, rebuilt as AST rules for a pure-Python control plane.  Each
rule gets a synthetic positive + negative; the self-check gate at the
bottom runs the whole suite over the real package and fails on any
unallowlisted finding — that test IS the CI gate every PR inherits.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from ray_tpu.devtools import rules_api, rules_async, rules_concurrency, \
    rules_config, rules_deadline, rules_jax, rules_metrics, \
    rules_resources, rules_rpc, rules_threads
from ray_tpu.devtools.rtlint import (Project, all_rules, default_allowlist,
                                     default_package_root, load_allowlist,
                                     run_lint)

REPO_ROOT = Path(__file__).resolve().parent.parent


def make_pkg(tmp_path: Path, files: dict) -> Path:
    root = tmp_path / "pkg"
    for rel, source in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(source))
    return root


def findings(root: Path, rule) -> list:
    return rule(Project(root))


# -- RT001: blocking calls in async defs --------------------------------------


class TestRT001:
    def test_flags_blocking_calls(self, tmp_path):
        root = make_pkg(tmp_path, {"core/head.py": """
            import shutil
            import subprocess
            import time


            async def h_x(conn, body):
                time.sleep(1)
                subprocess.run(["ls"])
                shutil.rmtree("/tmp/x")
                with open("/tmp/f") as f:
                    data = f.read()
                return data
        """})
        got = findings(root, rules_async.check_rt001)
        assert len(got) == 5
        assert all(f.rule == "RT001" for f in got)
        assert any("time.sleep" in f.message for f in got)
        assert any("subprocess.run" in f.message for f in got)
        assert any("open()" in f.message for f in got)

    def test_flags_sync_rpc_and_socket_methods(self, tmp_path):
        root = make_pkg(tmp_path, {"core/head.py": """
            async def h_x(self, conn, body):
                reply = self.rpc.call("ping", {})
                n = sock.recv_into(buf)
                return reply, n
        """})
        msgs = [f.message for f in findings(root, rules_async.check_rt001)]
        assert len(msgs) == 2
        assert any("synchronous RPC" in m for m in msgs)
        assert any(".recv_into()" in m for m in msgs)

    def test_clean_async_and_sync_not_flagged(self, tmp_path):
        root = make_pkg(tmp_path, {"core/head.py": """
            import asyncio
            import time


            def sync_helper():
                time.sleep(1)  # sync context: fine


            async def h_x(conn, body):
                await asyncio.sleep(1)           # async form: fine
                data = await reader.read(100)    # awaited read: fine

                def off_loop():
                    time.sleep(1)  # runs in an executor: fine

                await asyncio.get_running_loop().run_in_executor(
                    None, off_loop)
                return data
        """})
        assert findings(root, rules_async.check_rt001) == []


# -- RT002: lock held across await --------------------------------------------


class TestRT002:
    def test_flags_await_under_lock(self, tmp_path):
        root = make_pkg(tmp_path, {"core/head.py": """
            async def h_x(self, conn, body):
                with self._zygote_mutex:
                    await self.conn.push("x", {})
        """})
        got = findings(root, rules_async.check_rt002)
        assert len(got) == 1
        assert got[0].rule == "RT002"
        assert "_zygote_mutex" in got[0].message

    def test_lock_released_before_await_ok(self, tmp_path):
        root = make_pkg(tmp_path, {"core/head.py": """
            async def h_x(self, conn, body):
                with self._lock:
                    val = self.state
                await self.conn.push("x", {"v": val})
                with self._lock:  # no await inside: fine
                    self.state = None
        """})
        assert findings(root, rules_async.check_rt002) == []


# -- RT003: RPC drift ----------------------------------------------------------


_RPC_BASE = {
    "core/schema.py": """
        REQUIRED = {
            "kv_put": (("key", str),),
            "pull_object": (("object_id", bytes),),
        }
    """,
    "core/node_main.py": """
        async def h_pull_object(conn, body):
            return {}
    """,
}


class TestRT003:
    def test_clean_surface(self, tmp_path):
        root = make_pkg(tmp_path, {
            **_RPC_BASE,
            "core/client.py": """
                IDEMPOTENT_METHODS = frozenset({"kv_get"})


                class Client:
                    def f(self):
                        self.rpc.call("kv_put", {"key": "a"})
                        self.rpc.call("kv_get", {"key": "a"})
                        self.rpc.call_async("pull_object", {})
            """,
            "core/head.py": """
                async def h_kv_put(self, conn, body):
                    return {}


                async def h_kv_get(self, conn, body):
                    return {}
            """,
        })
        assert findings(root, rules_rpc.check_rt003) == []

    def test_all_four_drift_legs(self, tmp_path):
        root = make_pkg(tmp_path, {
            **_RPC_BASE,
            "core/client.py": """
                IDEMPOTENT_METHODS = frozenset()


                class Client:
                    def f(self):
                        self.rpc.call("missing_handler", {})
                        self.rpc.call("no_schema_row", {})
                        self.rpc.call_async("pull_object", {})
            """,
            "core/head.py": """
                async def h_no_schema_row(self, conn, body):
                    return {}


                async def h_kv_put(self, conn, body):
                    return {}


                async def h_orphan(self, conn, body):
                    return {}
            """,
            "core/schema.py": """
                REQUIRED = {
                    "kv_put": (("key", str),),
                    "pull_object": (("object_id", bytes),),
                    "row_without_handler": (("x", str),),
                }
            """,
        })
        msgs = "\n".join(
            f.message for f in findings(root, rules_rpc.check_rt003))
        assert "no h_missing_handler handler" in msgs
        assert "'no_schema_row' has no schema.REQUIRED row" in msgs
        assert "'row_without_handler' has no h_row_without_handler" in msgs
        assert "h_orphan has no call site" in msgs
        # pull_object is called (call_async) and has a schema row: clean.
        assert "h_pull_object has no call site" not in msgs
        assert "'pull_object' has no schema.REQUIRED row" not in msgs


# -- RT004: remote-function footguns ------------------------------------------


class TestRT004:
    def test_nested_get_and_closure_capture(self, tmp_path):
        root = make_pkg(tmp_path, {"data/pipeline.py": """
            import ray_tpu


            @ray_tpu.remote
            def stage(refs):
                return ray_tpu.get(refs)


            def build(big_array):
                @ray_tpu.remote
                def worker():
                    return big_array.sum()
                return worker
        """})
        got = findings(root, rules_api.check_rt004)
        msgs = "\n".join(f.message for f in got)
        assert "ray_tpu.get() inside remote 'stage'" in msgs
        assert "captures enclosing-scope variable(s) ['big_array']" in msgs

    def test_clean_remote_fn(self, tmp_path):
        root = make_pkg(tmp_path, {"data/pipeline.py": """
            import ray_tpu

            SCALE = 2  # module-level: shipped once with the function


            @ray_tpu.remote
            def stage(parts):  # refs resolve automatically as args
                return [p * SCALE for p in parts]
        """})
        assert findings(root, rules_api.check_rt004) == []


# -- RT005: undaemonized threads ----------------------------------------------


class TestRT005:
    def test_flags_leaky_thread(self, tmp_path):
        root = make_pkg(tmp_path, {"util/bg.py": """
            import threading


            def start():
                threading.Thread(target=print).start()
        """})
        got = findings(root, rules_threads.check_rt005)
        assert len(got) == 1 and got[0].rule == "RT005"

    def test_daemon_and_join_paths_ok(self, tmp_path):
        root = make_pkg(tmp_path, {"util/bg.py": """
            import threading


            class Runner:
                def start(self):
                    self._t = threading.Thread(target=print, daemon=True)
                    self._t.start()
                    # aliased join path (the checkpoint-writer pattern)
                    self._pending = threading.Thread(target=print)
                    self._pending.start()

                def wait(self):
                    t = self._pending
                    t.join()
        """})
        assert findings(root, rules_threads.check_rt005) == []


# -- RT006: metric-name drift --------------------------------------------------


_METRICS_MOD = """
    BUILTIN_METRICS = {
        "ray_tpu_good_total": "counter",
        "ray_tpu_stale_rows": "gauge",
    }
"""


class TestRT006:
    def test_drift_cases(self, tmp_path):
        root = make_pkg(tmp_path, {
            "util/metrics.py": _METRICS_MOD,
            "serve/app.py": """
                from ray_tpu.util.metrics import get_counter, get_gauge

                get_counter("ray_tpu_good_total", "ok")
                get_counter("ray_tpu_unregistered_total", "missing row")
                get_gauge("ray_tpu_good_total", "kind clash")
            """,
        })
        msgs = "\n".join(
            f.message for f in findings(root, rules_metrics.check_rt006))
        assert "'ray_tpu_unregistered_total' is not in" in msgs
        assert "one name must stick to one kind" in msgs
        assert "'ray_tpu_stale_rows' is emitted nowhere" in msgs

    def test_clean(self, tmp_path):
        root = make_pkg(tmp_path, {
            "util/metrics.py": """
                BUILTIN_METRICS = {"ray_tpu_good_total": "counter"}
            """,
            "serve/app.py": """
                from ray_tpu.util.metrics import get_counter

                get_counter("ray_tpu_good_total", "ok")
            """,
        })
        assert findings(root, rules_metrics.check_rt006) == []


# -- RT007: thread-role inference + guarded-by races ---------------------------


class TestRT007:
    def test_cross_role_unguarded_write_flagged(self, tmp_path):
        # A field written by a dedicated thread AND by public (main-role)
        # entry points with no lock anywhere: the canonical data race.
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._jobs = []
                    threading.Thread(target=self._drain, daemon=True,
                                     name="drainer").start()

                def submit(self, job):
                    self._jobs.append(job)

                def _drain(self):
                    self._jobs = []
        """})
        got = findings(root, rules_concurrency.check_rt007)
        assert len(got) == 1 and got[0].rule == "RT007"
        assert "Engine._jobs" in got[0].message
        roles = got[0].meta["roles"]
        assert "thread:drainer" in roles and "main" in roles

    def test_guarded_accesses_clean(self, tmp_path):
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._jobs = []
                    threading.Thread(target=self._drain, daemon=True).start()

                def submit(self, job):
                    with self._lock:
                        self._jobs.append(job)

                def _drain(self):
                    with self._lock:
                        self._jobs = []
        """})
        assert findings(root, rules_concurrency.check_rt007) == []

    def test_interprocedural_lock_held_on_entry(self, tmp_path):
        # The write lives in a "Lock held." helper whose every call site
        # holds the lock: entry-set inference must prove it guarded.
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._jobs = []
                    threading.Thread(target=self._drain, daemon=True).start()

                def submit(self, job):
                    with self._lock:
                        self._admit(job)

                def _admit(self, job):
                    self._jobs.append(job)

                def _drain(self):
                    with self._lock:
                        self._admit(None)
        """})
        assert findings(root, rules_concurrency.check_rt007) == []

    def test_init_only_writes_are_confined(self, tmp_path):
        # Written once in __init__, read from another role afterwards:
        # immutable publication, not a race.
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._cfg = {"x": 1}
                    threading.Thread(target=self._run, daemon=True).start()

                def _run(self):
                    return self._cfg
        """})
        assert findings(root, rules_concurrency.check_rt007) == []

    def test_declared_guard_map_verified(self, tmp_path):
        # _RT_GUARDED_BY is a promise the runtime sentinel enforces; a
        # write that breaks it statically must fail the lint, and a map
        # row naming a non-lock attribute is itself a finding.
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                _RT_GUARDED_BY = {"_jobs": "_lock", "_oops": "_nolock"}

                def __init__(self):
                    self._lock = threading.Lock()
                    self._jobs = []

                def submit(self, job):
                    self._jobs = [job]
        """})
        msgs = "\n".join(
            f.message for f in findings(root, rules_concurrency.check_rt007))
        assert "declared guarded by '_lock'" in msgs
        assert "does not hold it" in msgs
        assert "'_nolock'" in msgs and "not a lock attribute" in msgs

    def test_unguarded_vetting_and_stale_vetting(self, tmp_path):
        # _RT_UNGUARDED suppresses a vetted handoff; an entry vetting a
        # field nothing accesses is stale and flagged (allowlist rule).
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                _RT_UNGUARDED = {"_flag": "monotonic bool",
                                 "_gone": "nothing touches this"}

                def __init__(self):
                    self._flag = False
                    threading.Thread(target=self._run, daemon=True).start()

                def _run(self):
                    self._flag = True

                def stop(self):
                    self._flag = True
        """})
        got = findings(root, rules_concurrency.check_rt007)
        msgs = "\n".join(f.message for f in got)
        assert "_flag" not in msgs  # vetted
        assert "_gone" in msgs and "stale vetting" in msgs

    def test_rt_unguarded_comment_annotation(self, tmp_path):
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._flag = False
                    threading.Thread(target=self._run, daemon=True).start()

                def _run(self):
                    self._flag = True  # rt-unguarded: monotonic flip

                def stop(self):
                    self._flag = True
        """})
        assert findings(root, rules_concurrency.check_rt007) == []

    def test_loop_confined_state_touched_off_loop(self, tmp_path):
        # Async handlers (loop role) share state with an executor target:
        # the loop-confinement break must flag even with no Thread in
        # sight.
        root = make_pkg(tmp_path, {"core/server.py": """
            class Server:
                def __init__(self, loop):
                    self._conns = {}
                    self._loop = loop

                async def h_accept(self, conn, body):
                    self._conns[body["id"]] = conn
                    self._loop.run_in_executor(None, self._flush)

                def _flush(self):
                    self._conns = {}
        """})
        got = findings(root, rules_concurrency.check_rt007)
        assert len(got) == 1
        assert "_conns" in got[0].message
        assert set(got[0].meta["roles"]) >= {"loop", "executor"}


# -- RT008: static lock-order cycles -------------------------------------------


class TestRT008:
    def test_abba_cycle_flagged(self, tmp_path):
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """})
        got = findings(root, rules_concurrency.check_rt008)
        assert len(got) == 1 and got[0].rule == "RT008"
        assert "lock-order cycle" in got[0].message
        assert set(got[0].meta["locks"]) == {"Engine._a", "Engine._b"}

    def test_three_lock_cycle_through_call_graph(self, tmp_path):
        # No direct ABBA anywhere: A nests B only via a call, B nests C
        # via a call, and a third path nests A under C.  Only composition
        # through the call graph sees the cycle.
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self._c = threading.Lock()

                def f(self):
                    with self._a:
                        self.g()

                def g(self):
                    with self._b:
                        self.h()

                def h(self):
                    with self._c:
                        pass

                def k(self):
                    with self._c:
                        with self._a:
                            pass
        """})
        got = findings(root, rules_concurrency.check_rt008)
        assert len(got) == 1
        assert set(got[0].meta["locks"]) == {
            "Engine._a", "Engine._b", "Engine._c"}

    def test_consistent_order_clean(self, tmp_path):
        root = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
        """})
        assert findings(root, rules_concurrency.check_rt008) == []


# -- RT009: spawn-env contract drift -------------------------------------------


_CONFIG_WITH_CONTRACT = """
    SPAWN_ENV_CONTRACT = {
        "RT_GOOD_KEY": "a cataloged key",
        "RT_STALE_KEY": "nothing reads this anymore",
    }


    class Config:
        direct_calls: bool = True
"""


class TestRT009:
    def test_three_way_drift(self, tmp_path):
        root = make_pkg(tmp_path, {
            "core/config.py": _CONFIG_WITH_CONTRACT,
            "core/boot.py": """
                import os

                GOOD = os.environ.get("RT_GOOD_KEY")
                MISSING = os.environ.get("RT_MYSTERY_KEY")
                SHADOW = os.environ.get("RT_DIRECT_CALLS")
            """,
            "core/spawn.py": """
                def build_env(env):
                    env["RT_ORPHAN_EXPORT"] = "x"
                    return dict(env, RT_GOOD_KEY="ok")
            """,
        })
        got = findings(root, rules_config.check_rt009)
        kinds = {(f.meta["key"], f.meta["kind"]) for f in got}
        assert ("RT_MYSTERY_KEY", "missing") in kinds
        assert ("RT_STALE_KEY", "stale") in kinds
        assert ("RT_DIRECT_CALLS", "shadow") in kinds
        assert ("RT_ORPHAN_EXPORT", "orphan-write") in kinds
        assert ("RT_GOOD_KEY", "missing") not in kinds

    def test_const_name_resolution(self, tmp_path):
        # ENV_FLAG = "RT_X"; os.environ.get(ENV_FLAG) must count as a
        # read of RT_X (the locks.py idiom).
        root = make_pkg(tmp_path, {
            "core/config.py": """
                SPAWN_ENV_CONTRACT = {"RT_X": "via module constant"}


                class Config:
                    pass
            """,
            "core/boot.py": """
                import os

                ENV_FLAG = "RT_X"
                VALUE = os.environ.get(ENV_FLAG)
            """,
        })
        assert findings(root, rules_config.check_rt009) == []

    def test_missing_contract_is_a_finding(self, tmp_path):
        root = make_pkg(tmp_path, {
            "core/config.py": "class Config:\n    pass\n",
        })
        got = findings(root, rules_config.check_rt009)
        assert len(got) == 1 and "SPAWN_ENV_CONTRACT" in got[0].message


# -- RT010: JAX hot-path hazards ----------------------------------------------


class TestRT010:
    def test_jit_in_loop_and_host_sync(self, tmp_path):
        root = make_pkg(tmp_path, {"models/train.py": """
            import jax

            step = jax.jit(lambda p, x: p + x)


            def bad_rewrap(fns):
                for f in fns:
                    g = jax.jit(f)
                    g(1.0)


            def run(params, batches):
                total = 0.0
                for b in batches:
                    y = step(params, b)
                    total += float(y)
                return total
        """})
        got = findings(root, rules_jax.check_rt010)
        kinds = {f.meta["kind"] for f in got}
        assert "jit_in_loop" in kinds
        assert "host_sync" in kinds
        sync = [f for f in got if f.meta["kind"] == "host_sync"]
        assert any(f.meta["sync"].startswith("float()") for f in sync)

    def test_sync_ok_annotation_vets_the_line(self, tmp_path):
        root = make_pkg(tmp_path, {"models/train.py": """
            import jax

            step = jax.jit(lambda p, x: p + x)


            def run(params, batches):
                total = 0.0
                for b in batches:
                    y = step(params, b)
                    total += float(y)  # rt-sync-ok: metrics readback each step is the contract here
                return total
        """})
        got = findings(root, rules_jax.check_rt010)
        assert [f for f in got if f.meta["kind"] == "host_sync"] == []

    def test_post_loop_readback_is_clean(self, tmp_path):
        # The sanctioned shape: syncs AFTER the step loop don't stall the
        # device pipeline, so a fn that merely contains the loop is only
        # checked inside it.
        root = make_pkg(tmp_path, {"models/train.py": """
            import jax

            step = jax.jit(lambda p, x: p + x)


            def run(params, batches):
                y = None
                for b in batches:
                    y = step(params, b)
                return float(y)
        """})
        assert findings(root, rules_jax.check_rt010) == []

    def test_donation_read_after_use(self, tmp_path):
        root = make_pkg(tmp_path, {"models/kv.py": """
            from functools import partial

            import jax


            @partial(jax.jit, donate_argnums=(0,))
            def write_page(buf, x):
                return buf.at[0].set(x)


            def fill(buf, xs):
                for x in xs:
                    out = write_page(buf, x)
                    buf = buf + 0  # touch donated buf after the call
                return out
        """})
        got = findings(root, rules_jax.check_rt010)
        don = [f for f in got if f.meta["kind"] == "donation_use_after"]
        assert len(don) == 1
        assert don[0].meta["donated"] == "buf"

    def test_donation_rebind_is_clean(self, tmp_path):
        root = make_pkg(tmp_path, {"models/kv.py": """
            from functools import partial

            import jax


            @partial(jax.jit, donate_argnums=(0,))
            def write_page(buf, x):
                return buf.at[0].set(x)


            def fill(buf, xs):
                for x in xs:
                    buf = write_page(buf, x)
                return buf
        """})
        got = findings(root, rules_jax.check_rt010)
        assert [f for f in got if f.meta["kind"] == "donation_use_after"] == []


# -- RT011: resource-lifecycle leaks ------------------------------------------


class TestRT011:
    def test_exception_path_leak(self, tmp_path):
        root = make_pkg(tmp_path, {"serve/engine.py": """
            class Engine:
                def admit(self, n):
                    pages = self.allocator.alloc(n)
                    self.validate(n)
                    self.allocator.free(pages)
        """})
        got = findings(root, rules_resources.check_rt011)
        assert len(got) == 1
        assert got[0].meta["kind"] == "exception_path"
        assert got[0].meta["pair"] == "kv_pages"

    def test_try_finally_is_clean(self, tmp_path):
        root = make_pkg(tmp_path, {"serve/engine.py": """
            class Engine:
                def admit(self, n):
                    pages = self.allocator.alloc(n)
                    try:
                        self.validate(n)
                    finally:
                        self.allocator.free(pages)
        """})
        assert findings(root, rules_resources.check_rt011) == []

    def test_leak_vs_rt_owns_annotation(self, tmp_path):
        leaky = make_pkg(tmp_path / "a", {"serve/engine.py": """
            class Engine:
                def admit(self, n):
                    pages = self.allocator.alloc(n)
                    self.log(n)
        """})
        got = findings(leaky, rules_resources.check_rt011)
        assert [f.meta["kind"] for f in got] == ["leak"]

        owned = make_pkg(tmp_path / "b", {"serve/engine.py": """
            class Engine:
                def admit(self, n):
                    pages = self.allocator.alloc(n)  # rt-owns: kv_pages
                    self.log(n)
        """})
        assert findings(owned, rules_resources.check_rt011) == []

    def test_double_release(self, tmp_path):
        root = make_pkg(tmp_path, {"serve/engine.py": """
            class Engine:
                def teardown(self, pages):
                    self.allocator.free(pages)
                    self.allocator.free(pages)
        """})
        got = findings(root, rules_resources.check_rt011)
        assert any(f.meta["kind"] == "double_release" for f in got)

    def test_release_without_acquire(self, tmp_path):
        root = make_pkg(tmp_path, {"serve/engine.py": """
            class Engine:
                def cleanup(self):
                    self.allocator.free(stale_pages)
        """})
        got = findings(root, rules_resources.check_rt011)
        assert any(f.meta["kind"] == "release_without_acquire" for f in got)


# -- RT012: deadline-contract drift -------------------------------------------


class TestRT012:
    def test_hand_rolled_retry_curve(self, tmp_path):
        root = make_pkg(tmp_path, {"core/client.py": """
            import time


            class Client:
                def connect(self):
                    for attempt in range(5):
                        try:
                            return self.dial()
                        except OSError:
                            time.sleep(0.5 * (2 ** attempt))
        """})
        got = findings(root, rules_deadline.check_rt012)
        assert len(got) == 1
        assert got[0].meta["kind"] == "retry_curve"
        assert got[0].meta["missing"] == "BackoffPolicy"

    def test_backoff_policy_is_clean(self, tmp_path):
        root = make_pkg(tmp_path, {"core/client.py": """
            from .deadline import BackoffPolicy


            class Client:
                def connect(self):
                    policy = BackoffPolicy(base_s=0.5, multiplier=2.0,
                                           cap_s=4.0)
                    for attempt in range(1, 6):
                        try:
                            return self.dial()
                        except OSError:
                            policy.sleep(attempt)
        """})
        assert findings(root, rules_deadline.check_rt012) == []

    def test_unbounded_redial_loop(self, tmp_path):
        root = make_pkg(tmp_path, {"core/watch.py": """
            import time


            class Watcher:
                def watch(self):
                    while True:
                        try:
                            self.poll()
                        except ConnectionError:
                            time.sleep(1.0)
        """})
        got = findings(root, rules_deadline.check_rt012)
        assert len(got) == 1
        assert got[0].meta["kind"] == "unbounded_redial"
        assert got[0].meta["missing"] == "Deadline"

    def test_deadline_bounded_redial_is_clean(self, tmp_path):
        root = make_pkg(tmp_path, {"core/watch.py": """
            import time

            from .deadline import Deadline


            class Watcher:
                def watch(self):
                    deadline = Deadline.after(30.0)
                    while True:
                        if deadline.expired:
                            raise TimeoutError("re-dial budget exhausted")
                        try:
                            self.poll()
                        except ConnectionError:
                            time.sleep(1.0)
        """})
        assert findings(root, rules_deadline.check_rt012) == []

    def test_sentinel_timeout_constant(self, tmp_path):
        root = make_pkg(tmp_path, {"core/client.py": """
            class Client:
                def fetch(self, oid):
                    return self.rpc.call("get", timeout=1e9)

                def fetch_bounded(self, oid):
                    return self.rpc.call("get", timeout=30.0)

                def fetch_forever(self, oid):
                    return self.rpc.call("get", timeout=None)
        """})
        got = findings(root, rules_deadline.check_rt012)
        assert len(got) == 1
        assert got[0].meta["kind"] == "sentinel_timeout"
        assert got[0].meta["keyword"] == "timeout"

    def test_deadline_ok_annotation_vets_the_line(self, tmp_path):
        root = make_pkg(tmp_path, {"core/client.py": """
            class Client:
                def fetch(self, oid):
                    return self.rpc.call("get", timeout=1e9)  # rt-deadline-ok: protocol requires a numeric timeout
        """})
        assert findings(root, rules_deadline.check_rt012) == []


# -- allowlist -----------------------------------------------------------------


class TestAllowlist:
    def test_suppression_and_stale_detection(self, tmp_path):
        root = make_pkg(tmp_path, {"core/head.py": """
            import time


            async def h_x(conn, body):
                time.sleep(1)
        """})
        allow = tmp_path / "allow.txt"
        allow.write_text(
            "RT001 pkg/core/head.py  # vetted for this test\n"
            "RT002 pkg/core/gone.py  # stale entry\n"
        )
        kept, suppressed = run_lint(root, allow)
        assert len(suppressed) == 1
        assert [f.rule for f in kept] == ["ALLOWLIST"]
        assert "stale entry" in kept[0].message

    def test_reason_is_mandatory(self, tmp_path):
        allow = tmp_path / "allow.txt"
        allow.write_text("RT001 pkg/core/head.py\n")
        entries, problems = load_allowlist(allow)
        assert entries == []
        assert len(problems) == 1
        assert "no '# reason'" in problems[0].message


# -- the gate: the real package must lint clean --------------------------------


@pytest.fixture(scope="module")
def package_lint():
    """The live package linted ONCE (about a minute): (root, allowlist,
    unallowlisted findings)."""
    root = default_package_root()
    allow = default_allowlist(root)
    return root, allow, run_lint(root, allow)[0]


class TestPackageGate:
    def test_package_lint_clean(self, package_lint):
        """The self-check every future PR inherits: rtlint over the live
        package with the repo allowlist must report nothing.  The root and
        the allowlist are the ones `python -m ray_tpu lint` resolves when
        it is given neither."""
        root, allow, kept = package_lint
        assert root == REPO_ROOT / "ray_tpu" and allow.is_file()
        assert kept == [], "unallowlisted rtlint findings:\n" + "\n".join(
            f"{f.path}:{f.line}: {f.rule} {f.message}" for f in kept
        )

    def test_gate_covers_all_twelve_rules(self):
        """The self-check must run RT001-RT012 — a rule that exists but
        isn't registered in all_rules() silently stops gating."""
        names = [r.__name__ for r in all_rules()]
        assert names == [f"check_rt{i:03d}" for i in range(1, 13)]

    def test_cli_exit_codes(self, tmp_path):
        """`python -m ray_tpu lint` is the operator surface: 0 on a clean
        tree, non-zero once a violation is seeded.  Both trees are small:
        the exit code is this test's subject, the live package's
        cleanliness `test_package_lint_clean`'s."""
        def lint(name, body):
            root = make_pkg(tmp_path / name, {"core/head.py": f"""
                import asyncio
                import time


                async def h_x(conn, body):
                    {body}
            """})
            return subprocess.run(
                [sys.executable, "-m", "ray_tpu", "lint", "--root", str(root),
                 "--allowlist", str(tmp_path / "none")],
                cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
            )

        clean = lint("clean", "await asyncio.sleep(1)")
        assert clean.returncode == 0, clean.stdout + clean.stderr
        bad = lint("seeded", "time.sleep(1)")
        assert bad.returncode == 1, bad.stdout + bad.stderr
        assert "RT001" in bad.stdout

    def test_cli_seeded_race_and_cycle_exit_nonzero(self, tmp_path):
        """A seeded cross-role unguarded write and a seeded lock-order
        cycle must each fail the CLI, and --json must carry the inferred
        role/guard metadata (the dashboard lint view renders the WHY)."""
        import json as _json

        seeded = make_pkg(tmp_path, {"core/engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self._jobs = []
                    threading.Thread(target=self._drain, daemon=True,
                                     name="drainer").start()

                def submit(self, job):
                    self._jobs.append(job)
                    with self._a:
                        with self._b:
                            pass

                def _drain(self):
                    self._jobs = []
                    with self._b:
                        with self._a:
                            pass
        """})
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu", "lint", "--json",
             "--root", str(seeded), "--allowlist", str(tmp_path / "none")],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 1, out.stdout + out.stderr
        payload = _json.loads(out.stdout)
        by_rule = {}
        for f in payload["findings"]:
            by_rule.setdefault(f["rule"], []).append(f)
        race = by_rule["RT007"][0]
        assert "thread:drainer" in race["meta"]["roles"]
        cycle = by_rule["RT008"][0]
        assert set(cycle["meta"]["locks"]) == {"Engine._a", "Engine._b"}
