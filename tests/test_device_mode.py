"""Config ``device.device`` has one meaning (device/runtime.py
``start``): ``tpu`` arms at start-up and refuses to run without the
chip — and from then on nothing falls back to the host or to the jnp
program; ``cpu`` never initialises a TPU backend; ``auto`` keeps the
probe-and-degrade behaviour.  All on the CPU: this suite has no chip,
which is exactly the case ``tpu`` must refuse."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from upow_tpu import compile_cache
from upow_tpu.device import runtime as rt_mod
from upow_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(args, env=None, cwd=None, timeout=180):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=REPO + os.pathsep + os.environ.get(
                    "PYTHONPATH", ""))
    full.update(env or {})
    return subprocess.run([sys.executable] + args, env=full, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------- start-up refusal ----

def test_node_run_refuses_device_tpu_without_chip(tmp_path):
    proc = _child(["-m", "upow_tpu.node.run", "--port", "1",
                   "--db", str(tmp_path / "x.db")],
                  env={"UPOW_DEVICE_DEVICE": "tpu"}, cwd=str(tmp_path))
    assert proc.returncode != 0
    # the arm's own reason, before the node ever listened
    assert "device=tpu but" in proc.stderr
    assert "'cpu'" in proc.stderr
    assert not (tmp_path / "x.db").exists()


def test_miner_refuses_device_tpu_without_chip(tmp_path):
    # an unroutable node: the miner must fail BEFORE it fetches a job
    proc = _child(["-m", "upow_tpu.mine.miner", "addr", "--node",
                   "http://127.0.0.1:1/", "--device", "tpu", "--once"],
                  cwd=str(tmp_path))
    assert proc.returncode == 5  # miner.RC_NO_DEVICE
    assert "device=tpu but" in proc.stderr
    assert "node unreachable" not in proc.stderr


def test_start_rejects_unknown_mode_and_cpu_pins_jax():
    import jax

    with pytest.raises(ValueError, match="auto|tpu|cpu"):
        rt_mod.start("gpu")
    try:
        assert rt_mod.start("cpu") == {}
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert jax.config.jax_platforms == "cpu"
        assert not rt_mod.tpu_required()
    finally:
        rt_mod.reset_runtime()


# ------------------------------------------- no quiet road to jnp/host ----

def _boom():
    raise RuntimeError("Mosaic failed to compile TPU kernel")


def test_pallas_failure_counts_in_auto_and_raises_in_tpu(monkeypatch):
    from upow_tpu.crypto import p256

    name = "kernel.p256_verify.pallas_fallbacks"
    before = metrics.counters().get(name, 0)
    out = p256._pallas_or_jnp(_boom, lambda: np.ones(3, bool))
    assert out.tolist() == [True, True, True]
    assert metrics.counters()[name] == before + 1

    monkeypatch.setattr(rt_mod, "_DEVICE_MODE", "tpu")
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        p256._pallas_or_jnp(_boom, lambda: np.ones(3, bool))
    assert metrics.counters()[name] == before + 1


def test_device_verify_failure_raises_in_tpu_mode(monkeypatch):
    """``auto``: a failed device dispatch is re-run on the host and
    counted.  ``tpu``: it is the caller's error, nothing is re-run."""
    from upow_tpu.loadgen.fixtures import pipeline_verify_fixture
    from upow_tpu.crypto import p256
    from upow_tpu.verify import txverify

    checks = pipeline_verify_fixture(16, n_unique=4, invalid_every=0)

    def broken(*a, **kw):
        raise RuntimeError("device verify exploded")

    from upow_tpu.resilience.degrade import DegradeManager

    monkeypatch.setattr(p256, "verify_batch_prehashed", broken)
    monkeypatch.setattr(txverify, "_WARM_SHAPES", set())
    monkeypatch.setattr(txverify, "DEGRADE", DegradeManager())
    fallbacks = metrics.counters().get("resilience.device_fallback", 0)
    got = txverify.run_sig_checks(checks, backend="tpu", pad_block=8,
                                  use_cache=False)
    assert got == [True] * 16  # host re-run, counted
    assert metrics.counters()["resilience.device_fallback"] == fallbacks + 1

    monkeypatch.setattr(txverify, "DEGRADE", DegradeManager())
    monkeypatch.setattr(rt_mod, "_DEVICE_MODE", "tpu")
    with pytest.raises(RuntimeError, match="device verify exploded"):
        txverify.run_sig_checks(checks, backend="tpu", pad_block=8,
                                use_cache=False)
    # the refusal neither degraded the device path nor counted a fallback
    assert txverify.DEGRADE.state == "ok"
    assert metrics.counters()["resilience.device_fallback"] == fallbacks + 1


def test_first_dispatch_of_a_shape_gets_the_compile_allowance(monkeypatch):
    """A compile in flight is not a hang: the first dispatch of a padded
    shape is boxed by COMPILE_ALLOWANCE x device_timeout, later ones by
    device_timeout alone, and the first one is reported as an event."""
    from upow_tpu.loadgen.fixtures import pipeline_verify_fixture
    from upow_tpu.crypto import p256
    from upow_tpu.telemetry import events
    from upow_tpu.verify import txverify

    checks = pipeline_verify_fixture(16, n_unique=4, invalid_every=0)
    boxes = []

    class FakeRuntime:
        def run_boxed(self, fn, timeout, **kw):
            boxes.append(timeout)
            return "ok", fn()

    monkeypatch.setattr(rt_mod, "get_runtime", lambda: FakeRuntime())
    monkeypatch.setattr(
        p256, "verify_batch_prehashed",
        lambda digests, sigs, pubs, **kw: np.ones(len(digests), bool))
    monkeypatch.setattr(txverify, "_WARM_SHAPES", set())
    seq = events.since(0)["next_seq"]
    for _ in range(2):
        assert txverify.run_sig_checks(
            checks, backend="tpu", pad_block=8, device_timeout=7.0,
            use_cache=False) == [True] * 16
    assert boxes == [7.0 * txverify.COMPILE_ALLOWANCE, 7.0]
    firsts = [e for e in events.since(seq)["events"]
              if e["kind"] == "verify_first_dispatch"]
    assert [(e["padded"], e["real"], e["status"]) for e in firsts] \
        == [(16, 16, "ok")]


# ------------------------------------------------------ arm info ----

def test_arm_info_and_event_name_the_device():
    from upow_tpu.telemetry import events

    seq = events.since(0)["next_seq"]
    rt = rt_mod.DeviceRuntime()
    try:
        info = rt.arm()
    finally:
        rt.close()
    assert info["platform"] == "cpu"
    assert info["device_count"] == 8 and info["device_kind"]
    assert info["compile_cache_dir"] == compile_cache.enable()
    armed = [e for e in events.since(seq)["events"]
             if e["kind"] == "device_runtime_armed"]
    assert armed and armed[-1]["device_kind"] == info["device_kind"]
    assert armed[-1]["device_count"] == 8


# ------------------------------------------------- compile cache ----

_WHERE = ("import jax; from upow_tpu import compile_cache as c; "
          "print(c.enable()); print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_default_is_fixed_whatever_the_cwd(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    env = {"JAX_COMPILATION_CACHE_DIR": ""}
    a = _child(["-c", _WHERE], env=env, cwd=str(tmp_path))
    b = _child(["-c", _WHERE], env=env, cwd=str(other))
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    assert a.stdout == b.stdout
    used, configured = a.stdout.split()
    assert used == configured
    assert used.startswith(os.path.join(REPO, ".jax_cache") + os.sep)


def test_compile_cache_honours_env_and_sets_no_directory(tmp_path):
    placed = str(tmp_path / "placed")
    proc = _child(["-c", _WHERE],
                  env={"JAX_COMPILATION_CACHE_DIR": placed},
                  cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [placed, placed]


def test_compile_cache_enable_leaves_config_alone_when_env_set(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        compile_cache.enable()


# ---------------------------------------------------- chip_smoke ----

def _smoke(args, tmp_path):
    proc = _child([os.path.join(REPO, "chip_smoke.py"), "--workdir",
                   str(tmp_path / "work")] + args, timeout=600)
    lines = proc.stdout.strip().splitlines()
    return proc, lines, json.loads(lines[-1])


def test_chip_smoke_rehearsal_builds_equal_chains_then_fails(tmp_path):
    proc, lines, last = _smoke(["--rehearse-cpu"], tmp_path)
    assert proc.returncode != 0
    assert last["ok"] is False and "device" not in last
    assert "device check" in last["error"]
    text = "\n".join(lines)
    assert text.count("[A] block ") == 4
    assert "[B] push_block 4 (32 txs" in text
    a = next(ln for ln in lines if ln.startswith("[=] A: "))
    b = next(ln for ln in lines if ln.startswith("[=] B: "))
    assert a[len("[=] A: "):] == b[len("[=] B: "):]
    assert "[=] equal." in text
    assert not os.path.exists(tmp_path / "work" / "db")


def test_chip_smoke_without_chip_fails_at_the_first_miner(tmp_path):
    proc, lines, last = _smoke([], tmp_path)
    assert proc.returncode != 0
    assert last["ok"] is False
    assert "miner for block 1" in last["error"]
    assert "device=tpu but" in last["error"]
    assert not any(ln.startswith("[B]") for ln in lines)
