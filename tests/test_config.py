"""Config.load meets a key it does not know the same way wherever the
key once lived: the P-256 kernel and window selectors went with the
kernels they selected (PR 46), and a file or an override that still
names one is refused, never silently obeyed."""

import json

import pytest

from upow_tpu.config import Config, DeviceConfig


@pytest.mark.parametrize("field,value", [
    ("verify_kernel", "complete"),
    ("verify_window", 5),
    ("no_such_field", 1),
])
def test_file_naming_an_unknown_device_field_is_refused(tmp_path, field,
                                                        value):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"device": {field: value}}))
    with pytest.raises(KeyError, match=f"unknown config field device.{field}"):
        Config.load(str(path))


@pytest.mark.parametrize("field", ["verify_kernel", "verify_window"])
def test_override_naming_a_deleted_knob_is_refused(field):
    with pytest.raises(KeyError, match="unknown config field"):
        Config.load(**{f"device__{field}": 4})


def test_environment_naming_a_deleted_knob_selects_nothing(monkeypatch):
    """``UPOW_<SECTION>_<FIELD>`` is read for the fields a section has;
    a variable for a field it has not is any other stray variable."""
    for name, value in [("UPOW_DEVICE_VERIFY_KERNEL", "complete"),
                        ("UPOW_DEVICE_VERIFY_WINDOW", "5"),
                        ("UPOW_JAC_WINDOW", "5"), ("UPOW_TILE_CAP", "128")]:
        monkeypatch.setenv(name, value)
    monkeypatch.setenv("UPOW_DEVICE_VERIFY_PAD_BLOCK", "256")
    cfg = Config.load()
    assert cfg.device.verify_pad_block == 256
    assert not hasattr(cfg.device, "verify_kernel")
    assert not hasattr(cfg.device, "verify_window")
    assert not hasattr(DeviceConfig, "apply_kernel_overrides")

    from upow_tpu.crypto import p256

    assert p256._WINDOW == 4 and p256._pick_tile(8192) == 1024
