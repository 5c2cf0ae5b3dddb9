"""The node's launcher (``launch/node_child.py``) around a stand-in
``upow_tpu.node.run``, ``aiohttp`` and ``jax``: it is ``miner_child.py``'s
protocol (``test_launcher.py`` holds that), so what is held here is what
is the node's own: ``main()`` gets the operator's arguments,
``web.run_app`` is told to leave the signals alone, the memory request
and SIGTERM are answered while the node serves, and a node that cannot
start leaves its exit code."""

import os
import signal
import sys
import textwrap

import pytest

from harness.manifest import BENCH
from harness.procs import LineChild
from test_launcher import PEAK, STAND_INS

LAUNCHER = os.path.join(BENCH, "launch", "node_child.py")

NODE_STAND_INS = dict(
    {k: v for k, v in STAND_INS.items() if k.startswith("jax")}, **{
        "upow_tpu/__init__.py": "",
        "upow_tpu/node/__init__.py": """
            def run(config=None):     # the package's own, a function
                raise AssertionError("the launcher took the function")
            """,
        "upow_tpu/node/run.py": """
            import hashlib, sys

            def main():
                import jax  # noqa: F401  as the runtime's arm does
                from aiohttp import web
                print("node: argv", sys.argv[1:], flush=True)
                how = sys.argv[-1]
                if how == "no_device":
                    print("upow_tpu node: no TPU", file=sys.stderr)
                    raise SystemExit(1)
                if how == "raises":
                    raise RuntimeError("the node broke")
                web.run_app(object(), host="127.0.0.1", port=1)
            """,
        "upow_tpu/verify/__init__.py": "",
        "upow_tpu/verify/dispatch.py": """
            class SigDispatchFront:
                async def submit(self, checks, **kwargs):
                    return [False] * len(checks)
            """,
        "aiohttp/__init__.py": "",
        "aiohttp/web.py": """
            import hashlib

            def run_app(app, host=None, port=None, handle_signals=True):
                print(f"node: run_app handle_signals={handle_signals}",
                      flush=True)
                import asyncio
                from upow_tpu.verify.dispatch import SigDispatchFront
                print("node: verdicts", asyncio.run(
                    SigDispatchFront().submit([1, 2], source="block")),
                    flush=True)
                print("node: serving", flush=True)
                while True:   # a C call that no signal interrupts
                    hashlib.pbkdf2_hmac("sha256", b"x", b"y", 1 << 28)
            """,
    })


@pytest.fixture
def launch(tmp_path):
    for name, text in NODE_STAND_INS.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))
    children = []

    def start(*args, **env):
        child = LineChild([sys.executable, LAUNCHER, *args],
                          cwd=str(tmp_path),
                          env=dict(env, PYTHONPATH=str(tmp_path)))
        children.append(child)
        return child

    yield start
    for child in children:
        child.stop(timeout=0.1)


def _texts(child):
    return [text for _t, text in child.lines]


def test_the_node_serves_under_the_launchers_signals(launch):
    child = launch("--", "--config", "node.json")
    child.wait_for(lambda s: s == "node: serving", 10, "the node")
    said = _texts(child)
    assert "node: argv ['--config', 'node.json']" in said
    assert "node: run_app handle_signals=False" in said
    assert "node: verdicts [False, False]" in said
    seen = len(child.lines)
    child.signal(signal.SIGRTMIN)
    child.wait_for(lambda s: s == f"memory: peak_bytes={PEAK}", 5,
                   "the answer to the memory request", seen=seen)
    assert child.proc.poll() is None
    assert child.stop(timeout=5) == 0 and not child.killed
    assert _texts(child)[-1] == f"memory: peak_bytes={PEAK}"
    assert not [s for s in _texts(child) if "Traceback" in s]


def test_the_trace_is_switched_by_the_parent(launch, tmp_path):
    trace_dir = str(tmp_path / "trace")
    child = launch("--trace-dir", trace_dir, "--", "--config", "n.json")
    child.wait_for(lambda s: s == "node: serving", 10, "the node")
    child.signal(signal.SIGUSR1)
    child.wait_for(lambda s: s.startswith("trace: started unix="), 5,
                   "'trace: started'")
    assert os.path.exists(trace_dir + ".started")
    assert child.stop(timeout=5) == 0
    assert _texts(child)[-2].startswith("trace: stopped unix=")


@pytest.mark.parametrize("how,rc,says", [
    ("no_device", 1, "upow_tpu node: no TPU"),
    ("raises", 1, "RuntimeError: the node broke"),
])
def test_a_node_that_cannot_start_leaves_its_code(launch, how, rc, says):
    child = launch("--", "--config", how)
    assert child.proc.wait(timeout=10) == rc
    child.stop(timeout=1)
    assert says in _texts(child)


def test_the_fault_unverified_turns_every_verdict_true(launch):
    child = launch("--fault", "unverified", "--", "--config", "n.json")
    child.wait_for(lambda s: s == "node: serving", 10, "the node")
    said = _texts(child)
    assert "fault: unverified (every signature verdict reads true)" in said
    assert "node: verdicts [True, True]" in said
    other = launch("--fault", "no_such", "--", "--config", "n.json")
    assert other.proc.wait(timeout=10) != 0


def test_the_launcher_copies_nothing_of_the_miners():
    with open(LAUNCHER) as f:
        code = f.read().split('"""', 2)[2]
    assert "import miner_child as launcher" in code
    assert "signal.signal(" not in code and "def _tracer" not in code
    assert "def _signals" not in code and "def _say" not in code
