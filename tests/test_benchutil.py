"""Backend probe and env-knob parsing.

The probe reports ``jax.devices()[0].platform`` as JAX names it: every
backend-routing comparison in the framework is written against "tpu",
and there is no second name for it.
"""

from upow_tpu import benchutil


def _probe_with(monkeypatch, status, value):
    monkeypatch.setattr(benchutil, "boxed_call",
                        lambda fn, timeout: (status, value))
    return benchutil.probe_platform(1.0)


def test_probe_keeps_tpu_and_cpu(monkeypatch):
    assert _probe_with(monkeypatch, "ok", "tpu") == "tpu"
    assert _probe_with(monkeypatch, "ok", "cpu") == "cpu"


def test_probe_timeout_is_none(monkeypatch):
    assert _probe_with(monkeypatch, "timeout", None) is None
    assert _probe_with(monkeypatch, "err", RuntimeError("boom")) is None


def test_env_choice_accepts_allowed(monkeypatch):
    from upow_tpu.crypto.p256 import _env_choice

    monkeypatch.setenv("UPOW_TEST_KNOB", " 5 ")
    assert _env_choice("UPOW_TEST_KNOB", 4, {4, 5}) == 5


def test_env_choice_rejects_invalid(monkeypatch):
    from upow_tpu.crypto.p256 import _env_choice

    for bad in ("garbage", "", "6", "4.5"):
        monkeypatch.setenv("UPOW_TEST_KNOB", bad)
        assert _env_choice("UPOW_TEST_KNOB", 4, {4, 5}) == 4
    monkeypatch.delenv("UPOW_TEST_KNOB")
    assert _env_choice("UPOW_TEST_KNOB", 4, {4, 5}) == 4
