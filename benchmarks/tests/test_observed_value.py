"""``readers/observed_value.py``: a value the driver observed, scaled,
and where asked over a program's device seconds; nothing where there is
nothing to read."""

from harness import manifest, xplane

reader = manifest.load_module("readers", "observed_value")


def _records(seconds):
    win = {"plane": "/host:CPU", "line": "t", "name": xplane.WINDOW_SPAN,
           "start_ns": 0.0, "dur_ns": 10e9}
    events = [{"plane": "/device:TPU:0", "line": xplane.MODULES_LINE,
               "name": "jit__prep_and_verify_pallas_jac(123)",
               "start_ns": 1e9 * (k + 1), "dur_ns": 1e9 * s}
              for k, s in enumerate(seconds)]
    return [win] + events


def test_a_value_is_read_scaled_or_left_out():
    observed = {"values": {"first_dispatch_s": 16.25}, "records": []}
    assert reader.read(observed, {"key": "first_dispatch_s"}) == 16.25
    assert reader.read(observed, {"key": "first_dispatch_s",
                                  "scale": 1e3}) == 16250.0
    assert reader.read(observed, {"key": "absent"}) is None
    assert reader.read({"records": []}, {"key": "absent"}) is None


def test_a_rate_over_the_programs_device_seconds():
    observed = {"values": {"p256_lanes_real": 24486.0},
                "records": _records([0.05, 0.05, 0.02])}
    spec = {"key": "p256_lanes_real", "program": "prep_and_verify",
            "per": "device_second", "scale": 1e-3}
    assert abs(reader.read(observed, spec) - 24486.0 / 0.12 / 1e3) < 1e-9
    # the other way round: the program's device seconds a block
    observed["values"]["p256_blocks"] = 3
    assert abs(reader.read(observed, {
        "key": "p256_blocks", "program": "prep_and_verify", "per": "value",
        "scale": 1e3}) - 40.0) < 1e-9
    # a trace without the program: nothing, never a zero or an infinity
    observed["records"] = _records([])
    assert reader.read(observed, spec) is None
    spec["program"] = "pow_search"
    assert reader.read({"values": {"p256_lanes_real": 1.0},
                        "records": _records([0.05])}, spec) is None
