"""Workflow tests: durable steps, crash resume, memoization.

Reference analog: python/ray/workflow/tests/test_basic_workflows.py,
test_recovery.py.
"""

import os

import pytest

import ray_tpu
from ray_tpu import workflow


@pytest.fixture
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def test_workflow_chain(rt, tmp_path):
    def load(x):
        return list(range(x))

    def double(xs):
        return [v * 2 for v in xs]

    def total(xs, offset=0):
        return sum(xs) + offset

    a = workflow.step(load)(10)
    b = workflow.step(double)(a)
    c = workflow.step(total)(b, offset=5)
    out = workflow.run(c, workflow_id="chain", storage=str(tmp_path))
    assert out == sum(range(10)) * 2 + 5


def test_workflow_resume_skips_completed_steps(rt, tmp_path):
    marker = tmp_path / "ran_first"
    trip = tmp_path / "trip"

    def first(x):
        # Count executions through the filesystem (steps run in workers).
        with open(marker, "a") as f:
            f.write("x")
        return x + 1

    def flaky(x):
        if not os.path.exists(trip):
            open(trip, "w").write("tripped")
            raise RuntimeError("transient failure")
        return x * 10

    a = workflow.step(first)(1)
    b = workflow.step(flaky)(a)

    with pytest.raises(Exception, match="transient failure"):
        workflow.run(b, workflow_id="resume", storage=str(tmp_path))
    assert open(marker).read() == "x"  # first step ran once and persisted

    out = workflow.run(b, workflow_id="resume", storage=str(tmp_path))
    assert out == 20
    assert open(marker).read() == "x"  # resume did NOT re-run step one

    assert "resume" in workflow.list_workflows(storage=str(tmp_path))
    workflow.delete("resume", storage=str(tmp_path))
    assert "resume" not in workflow.list_workflows(storage=str(tmp_path))


def test_workflow_run_async(rt, tmp_path):
    def slow(x):
        import time

        time.sleep(0.3)
        return x * 3

    node = workflow.step(slow)(7)
    run = workflow.run_async(node, workflow_id="async", storage=str(tmp_path))
    assert run.result(timeout=60) == 21


def test_workflow_parallel_branches(rt, tmp_path):
    """Independent branches run concurrently (reference: the executor runs
    all ready steps, workflow_executor.py)."""
    import time as _t

    def slow_shard(i):
        import time

        time.sleep(1.5)
        return i

    def merge(*parts):
        return sum(parts)

    shards = [workflow.step(slow_shard)(i) for i in range(4)]
    node = workflow.step(merge)(*shards)
    t0 = _t.time()
    out = workflow.run(node, workflow_id="par", storage=str(tmp_path))
    wall = _t.time() - t0
    assert out == 6
    # Bound = the 6.0s sleep-sum floor: a serialized run can NEVER beat it
    # (the four 1.5s sleeps alone total 6.0s before any overhead), while a
    # parallel run needs one 1.5s sleep plus overhead — ~2.4s observed
    # under full-suite load, a ~3.6s margin (the earlier 0.8s-sleep/3.0s
    # bound flaked under load with only tens of ms to spare).
    assert wall < 6.0, f"branches serialized: {wall:.1f}s for 4x1.5s steps"


def test_dynamic_workflow_fans_out_children(rt, tmp_path):
    """A step returning a StepNode continues into that sub-DAG: here the
    parent decides AT RUNTIME to fan out K children and gather them
    (reference: workflow.continuation / dynamic workflows).  Sub-steps
    checkpoint under the parent's id namespace."""

    def child(i):
        return i * i

    def gather(*vals):
        return sorted(vals)

    def fan_out(k):
        children = [workflow.step(child)(i) for i in range(k)]
        return workflow.step(gather)(*children)

    root = workflow.step(fan_out)(5)
    out = workflow.run(root, workflow_id="dyn", storage=str(tmp_path))
    assert out == [0, 1, 4, 9, 16]
    # The children's checkpoints live under the parent step's namespace.
    files = os.listdir(str(tmp_path / "dyn"))
    assert sum(1 for f in files if "child" in f) == 5
    assert any("." in f.replace(".pkl", "") for f in files if "child" in f)


def test_workflow_event_step_blocks_then_fires(rt, tmp_path):
    """wait_for_event blocks the workflow until the listener returns a
    payload; the received event is checkpointed, so a re-run does NOT
    re-wait (reference: event_listener.py poll_for_event + checkpointed
    events)."""
    import threading
    import time

    from ray_tpu.core.context import ctx

    def after(ev, prefix):
        return prefix + ev.decode()

    ev = workflow.kv_event("wf-ev-key", poll_interval_s=0.05)
    done = workflow.step(after)(ev, "got:")

    def fire():
        time.sleep(1.0)
        ctx.client.kv_put("wf-ev-key", b"payload")

    threading.Thread(target=fire, daemon=True).start()
    t0 = time.time()
    out = workflow.run(done, workflow_id="ev1", storage=str(tmp_path))
    assert out == "got:payload"
    assert time.time() - t0 >= 0.9  # actually blocked on the event

    # Event consumed + checkpointed: delete the key; a resume run completes
    # instantly from storage without re-polling.
    ctx.client.kv_del("wf-ev-key")
    out2 = workflow.run(done, workflow_id="ev1", storage=str(tmp_path))
    assert out2 == "got:payload"


def test_workflow_event_timeout(rt, tmp_path):
    ev = workflow.wait_for_event(lambda: None, poll_interval_s=0.05,
                                 timeout_s=0.5)
    with pytest.raises(TimeoutError, match="no event"):
        workflow.run(ev, workflow_id="ev-to", storage=str(tmp_path))


def test_workflow_event_resumes_after_head_restart(tmp_path):
    """The full durability story: a workflow blocks on a KV event, the
    head (and driver) are SIGKILLed, the cluster restarts from its durable
    snapshot, the event fires, and a resume run completes — pre-event
    steps skip via their checkpoints (reference: workflow recovery +
    KV-backed event provider)."""
    import signal
    import subprocess
    import sys
    import time

    state = str(tmp_path / "head.state")
    wf_store = str(tmp_path / "wf")
    script = f"""
import ray_tpu
from ray_tpu import workflow
ray_tpu.init(num_cpus=2, system_config={{"head_state_path": {state!r}}})

def pre():
    print("PRE-RAN", flush=True)
    return "pre"

def after(p, ev):
    return p + ":" + ev.decode()

node = workflow.step(after)(
    workflow.step(pre)(), workflow.kv_event("restart-ev"))
print("READY", flush=True)
workflow.run(node, workflow_id="surv", storage={wf_store!r})
"""
    env = {k: v for k, v in os.environ.items() if k != "RT_ADDRESS"}
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    saw_pre = False
    deadline = time.time() + 120
    while time.time() < deadline:
        line = proc.stdout.readline()
        if "PRE-RAN" in line:
            saw_pre = True
        if "READY" in line:
            break
        if line == "" and proc.poll() is not None:
            raise AssertionError(proc.stderr.read())
    # pre()'s checkpoint lands (written whole: os.replace) and the head has
    # a snapshot to restart from; the event step is polling.  The deadline
    # is the failure only.
    surv = os.path.join(wf_store, "surv")
    deadline = time.time() + 60
    while not (os.path.exists(state) and os.path.isdir(surv) and any(
            "pre" in f and not f.endswith(".tmp") for f in os.listdir(surv))):
        assert time.time() < deadline, "pre()'s checkpoint never landed"
        assert proc.poll() is None, proc.stderr.read()
        time.sleep(0.05)
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)
    time.sleep(2)

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, system_config={"head_state_path": state})
    try:
        from ray_tpu.core.context import ctx

        ctx.client.kv_put("restart-ev", b"late")  # the event finally fires
        out = workflow.run(
            workflow.step(lambda p, ev: p + ":" + ev.decode())(
                _resume_pre(), workflow.kv_event("restart-ev")),
            workflow_id="surv", storage=wf_store)
        # NOTE: the resume driver rebuilds the same DAG shape; the pre step
        # must come from its checkpoint, not re-run.
        assert out == "pre:late"
        pre_ckpts = [f for f in os.listdir(os.path.join(wf_store, "surv"))
                     if "pre" in f]
        assert pre_ckpts  # checkpoint from BEFORE the kill was reused
    finally:
        ray_tpu.shutdown()


def _resume_pre():
    def pre():
        raise AssertionError("pre must resume from checkpoint, not re-run")
    pre.__name__ = "pre"
    return workflow.step(pre)()
