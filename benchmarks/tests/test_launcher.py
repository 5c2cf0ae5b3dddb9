"""The real launcher (``launch/miner_child.py``) around a stand-in
``upow_tpu.mine.miner`` and a stand-in ``jax``: no chip, no network, no
jax import.  What is held: the memory request is answered and SIGTERM
ends the process wherever the miner's thread is (inside a ``__del__``, a
``gc`` callback, a long C call), nothing is raised into it, and a
reading that cannot be had is said in words."""

import importlib.util
import os
import signal
import sys
import textwrap

import pytest

from harness.manifest import BENCH, BenchError, load_module
from harness.procs import LineChild

LAUNCHER = os.path.join(BENCH, "launch", "miner_child.py")
PEAK = 1254912

STAND_INS = {
    "upow_tpu/__init__.py": "",
    "upow_tpu/mine/__init__.py": "",
    "upow_tpu/mine/miner.py": """
        import gc, hashlib, time

        RC_NO_DEVICE = 5

        def _spin(seconds):
            t0 = time.time()
            while time.time() - t0 < seconds:
                pass

        class Slow:
            def __del__(self):
                print("miner: in __del__", flush=True)
                _spin(1.0)

        def _in_gc(phase, _info):
            if phase == "start":
                print("miner: in gc callback", flush=True)
                _spin(1.0)

        def main(argv):
            import jax  # noqa: F401  as the miner's arm does
            how = argv[0]
            if how == "returns":
                return int(argv[1])
            if how == "raises":
                raise RuntimeError("the miner broke")
            if how == "gc":
                gc.callbacks.append(_in_gc)
            while True:
                if how == "del":
                    Slow()
                elif how == "gc":
                    gc.collect()
                else:   # a C call of a minute that no signal interrupts
                    print("miner: in C", flush=True)
                    hashlib.pbkdf2_hmac("sha256", b"x", b"y", 1 << 28)
        """,
    "jax/__init__.py": """
        import os
        from . import profiler  # noqa: F401

        class _Device:
            def __init__(self, peak):
                self.peak = peak

            def memory_stats(self):
                if os.environ.get("FAKE_JAX_STATS") == "raise":
                    raise RuntimeError("backend gone:\\n  no client")
                if os.environ.get("FAKE_JAX_STATS") == "none":
                    return None
                return {"peak_bytes_in_use": self.peak}

        def local_devices():
            return [_Device(4096), _Device(%d)]
        """ % PEAK,
    "jax/_src/__init__.py": "",
    "jax/_src/xla_bridge.py": """
        import os

        def backends_are_initialized():
            return os.environ.get("FAKE_JAX_STATS") != "no_backend"
        """,
    "jax/profiler.py": """
        import contextlib

        class ProfileOptions:
            pass

        def start_trace(trace_dir, profiler_options=None):
            open(trace_dir + ".started", "w").close()

        def stop_trace():
            pass

        TraceAnnotation = contextlib.nullcontext
        """,
}


@pytest.fixture
def launch(tmp_path):
    """launch(*args_of_the_launcher, **env) -> LineChild, stopped at the
    end of the test whatever it did."""
    for name, text in STAND_INS.items():
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


@pytest.mark.parametrize("how,marker", [
    ("del", "miner: in __del__"),
    ("gc", "miner: in gc callback"),
    ("c", "miner: in C"),
])
def test_the_request_and_the_stop_need_nothing_of_the_miners_thread(
        launch, how, marker):
    driver = load_module("drivers", "mine_sweep")
    child = launch("--", how)
    seen = len(child.lines)
    child.wait_for(lambda s: s == marker, 10, marker)
    child.signal(driver.MEMORY_SIGNAL)
    child.wait_for(lambda s: s == f"memory: peak_bytes={PEAK}", 5,
                   "the answer to the memory request", seen=seen)
    assert child.proc.poll() is None     # asked, not stopped
    assert child.stop(timeout=5) == 0
    assert not child.killed and child.stop_s < 5
    said = _texts(child)
    # once on request and once more at exit, each a line of its own
    assert said.count(f"memory: peak_bytes={PEAK}") == 2
    assert said[-1] == f"memory: peak_bytes={PEAK}"
    assert not [s for s in said if "Exception ignored" in s
                or "Traceback" in s or "SystemExit" in s], said


@pytest.mark.parametrize("args,rc,last", [
    (["returns", "0"], 0, f"memory: peak_bytes={PEAK}"),
    (["returns", "3"], 3, f"memory: peak_bytes={PEAK}"),
    (["returns", "5"], 5, None),    # RC_NO_DEVICE: nothing to read
    (["raises"], 1, f"memory: peak_bytes={PEAK}"),
])
def test_a_miner_that_ends_by_itself_leaves_its_memory_and_its_code(
        launch, args, rc, last):
    child = launch("--", *args)
    assert child.proc.wait(timeout=10) == rc
    child.stop(timeout=1)
    said = _texts(child)
    assert [s for s in said if s.startswith("memory: ")] == \
        ([last] if last else [])
    if args == ["raises"]:
        assert "RuntimeError: the miner broke" in said


@pytest.mark.parametrize("stats,line", [
    ("none", "memory: peak_bytes=null"),
    ("raise", "memory: unreadable (RuntimeError: backend gone: no client)"),
    ("no_backend", "memory: unreadable (RuntimeError: the miner has "
                   "initialised no jax backend)"),
])
def test_a_reading_that_cannot_be_had_is_said_in_words(launch, stats, line):
    child = launch("--", "c", FAKE_JAX_STATS=stats)
    child.wait_for(lambda s: s == "miner: in C", 10, "the miner")
    child.signal(signal.SIGRTMIN)
    _t, text = child.wait_for(lambda s: s.startswith("memory: "), 5,
                              "the answer")
    assert text == line
    assert child.stop(timeout=5) == 0
    assert _texts(child)[-2:] == [line, line]


def test_the_trace_is_started_and_stopped_from_the_signals_thread(
        launch, tmp_path):
    trace_dir = str(tmp_path / "trace")
    child = launch("--trace-dir", trace_dir, "--", "gc")
    child.wait_for(lambda s: s == "miner: in gc callback", 10, "the miner")
    child.signal(signal.SIGUSR1)
    child.wait_for(lambda s: s.startswith("trace: started unix="), 5,
                   "'trace: started'")
    assert os.path.exists(trace_dir + ".started")
    # SIGTERM with the trace open: stopped first, then the memory line
    assert child.stop(timeout=5) == 0
    said = _texts(child)
    assert said[-2].startswith("trace: stopped unix=")
    assert said[-1] == f"memory: peak_bytes={PEAK}"
    other = launch("--trace-dir", trace_dir, "--", "c")
    other.wait_for(lambda s: s == "miner: in C", 10, "the miner")
    other.signal(signal.SIGUSR1)
    other.wait_for(lambda s: s.startswith("trace: started"), 5, "the start")
    other.signal(signal.SIGUSR2)
    other.wait_for(lambda s: s.startswith("trace: stopped"), 5, "the stop")


def test_the_fault_mute_memory_says_no_memory_line(launch):
    child = launch("--fault", "mute_memory", "--", "c")
    child.wait_for(lambda s: s == "miner: in C", 10, "the miner")
    child.signal(signal.SIGRTMIN)
    with pytest.raises(BenchError):
        child.wait_for(lambda s: "memory: " in s, 0.5, "an answer")
    assert child.stop(timeout=5) == 0
    assert not [s for s in _texts(child) if "memory: " in s]


def test_one_signal_number_no_handler_and_one_write_a_line():
    spec = importlib.util.spec_from_file_location("miner_child", LAUNCHER)
    launcher = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launcher)       # imports, starts nothing
    import child_signals

    driver = load_module("drivers", "mine_sweep")
    assert launcher.MEMORY_SIGNAL == driver.MEMORY_SIGNAL \
        == child_signals.MEMORY_SIGNAL == signal.SIGRTMIN
    assert launcher.MEMORY_SIGNAL not in (signal.SIGUSR1, signal.SIGUSR2,
                                          signal.SIGTERM)
    with open(LAUNCHER) as f:
        code = f.read().split('"""', 2)[2]      # past the docstring
    assert "signal.signal(" not in code and "SystemExit(0)" not in code
    assert "print(" not in code.replace("print_exc(", "")
