"""DeviceUtxoIndex kept resident (ISSUE 50): a block's update is one
device program whose operands are the block's delta; the host mirror is
two sorted levels; the build is columnar (upow_tpu/state/device_index.py).
"""

import random
import zlib

import numpy as np
import pytest

import upow_tpu.state.device_index as di
from upow_tpu import trace
from upow_tpu.telemetry import metrics


def _op(i: int, idx: int = 0):
    return (i.to_bytes(32, "big").hex(), idx)


def _counter(name: str) -> float:
    return dict(metrics.counters()).get(name, 0)


def test_fifty_blocks_against_a_dict_model(monkeypatch):
    """50 seeded blocks of apply / probe / rollback at 20,000 entries
    against a plain dict: membership and amounts equal after every step.
    Outpoints with an output index >= 100 share a fingerprint by the
    hundred: twins, and one run of a dozen, longer than the window."""
    real = di.fingerprint_batch

    def grouped(ops):
        fps = real(ops)
        for i, o in enumerate(ops):
            if o[1] >= 100:
                fps[i] = o[1] // 100
        return fps

    monkeypatch.setattr(di, "fingerprint_batch", grouped)
    rng = random.Random(50)
    model = {_op(i): 10 + i for i in range(20_000)}
    model.update({_op(30_000 + i, 100 + i): 5 for i in range(2)})  # twins
    model.update({_op(31_000 + i, 200 + i): 6 for i in range(12)})  # a run
    ops = list(model)
    idx = di.DeviceUtxoIndex(
        ops, values=[(model[o], "addr%d" % (model[o] % 7), 1) for o in ops])
    assert idx.stats()["twin_fingerprints"] == 2
    consults0 = _counter("index.shadow_consults")
    fresh = 100_000

    def agree():
        live = rng.sample(sorted(model), 300)
        live += [o for o in model if o[1] >= 100]
        gone = [_op(i) for i in rng.sample(range(200_000, 300_000), 40)]
        gone += [_op(32_000, 205), _op(33_000, 150)]   # twin keys, absent
        asked = live + gone
        present, amounts = idx.lookup_batch(asked)
        assert present.tolist() == [o in model for o in asked]
        assert idx.shadow_contains_batch(asked).tolist() == present.tolist()
        plain = [i for i, o in enumerate(asked) if o[1] < 100]
        assert [int(amounts[i]) for i in plain] == \
            [model.get(asked[i], 0) for i in plain]
        assert len(idx) == len(model)

    idx.contains_batch(ops[:8])       # lanes resident from here on
    agree()
    for height in range(50):
        before = dict(model)
        spent = rng.sample(sorted(o for o in model if o[1] < 200), 150)
        spent += [_op(i) for i in rng.sample(range(300_000, 400_000), 6)]
        created = [_op(fresh + i) for i in range(200)]
        fresh += 200
        if height % 7 == 3:           # a twin of a live key, and a new pair
            created += [_op(fresh, 100), _op(fresh + 1, 300 + height),
                        _op(fresh + 2, 300 + height)]
        for o in spent:
            model.pop(o, None)
        for n, o in enumerate(created):
            model[o] = 1_000_000_007 * (height + 1) + n
        idx.apply_block(created, spent,
                        created_values=[(model[o], "a", height)
                                        for o in created])
        agree()
        if height % 5 == 4:
            assert idx.rollback_block()
            model = before
            agree()
    assert _counter("index.shadow_consults") > consults0
    assert idx.stats()["relayouts"] == 0
    keys = idx._host_keys
    assert (keys[1:] >= keys[:-1]).all() and len(keys) == len(model)


def test_a_blocks_update_uploads_its_delta_not_the_set():
    """64 rows applied at 2^16 entries: under 64 KB host -> device and no
    re-layout; the block that crosses the power of two counts one."""
    n = (1 << 16) - 100
    idx = di.DeviceUtxoIndex([_op(i) for i in range(n)])
    idx.contains_batch([_op(0)])
    assert idx.stats()["capacity"] == 1 << 16
    up0, re0 = _counter("index.upload_bytes"), _counter("index.relayouts")
    rows0 = _counter("index.apply_rows")
    idx.apply_block([_op(n + i) for i in range(32)],
                    [_op(i) for i in range(32)])
    assert 0 < _counter("index.upload_bytes") - up0 < 64 * 1024
    assert _counter("index.relayouts") == re0
    assert _counter("index.apply_rows") - rows0 == 64
    assert idx.resident_bytes() == 24 << 16
    idx.add([_op(2 * n + i) for i in range(200)])
    assert _counter("index.relayouts") == re0 + 1
    assert idx.stats()["capacity"] == 1 << 17
    asked = [_op(0), _op(31), _op(32), _op(n + 31), _op(2 * n + 199),
             _op(2 * n + 200)]
    assert idx.contains_batch(asked).tolist() == \
        [False, False, True, True, True, False]


def test_a_spent_row_past_the_window_is_laid_out_again(monkeypatch):
    """Ten rows of one key: the program's window reaches eight, so the
    spend of the tenth is seen by the live count and the lanes are laid
    out from the mirror (one re-layout), the answers right."""
    monkeypatch.setattr(di, "fingerprint_batch",
                        lambda ops: np.full(len(ops), 9, dtype=np.uint64))
    ops = [_op(i) for i in range(10)]
    idx = di.DeviceUtxoIndex(ops)
    assert idx.contains_batch(ops).all()
    last = ops[9]      # equal keys keep the order they came in
    idx.remove([last])
    assert idx.stats()["relayouts"] == 1
    assert idx.contains_batch(ops).tolist() == [o != last for o in ops]


def test_the_build_remembers_no_twin_for_a_collision_free_set():
    """Columns in, one sort, no Python object a row kept: the mirror is
    five arrays, and a set without twins remembers no fingerprint."""
    n = 5000
    ops = [_op(i, i % 3) for i in range(n)]
    lanes, index = di._txid_lanes(ops)
    fps = di.fingerprint_lanes(lanes, index)
    assert fps.tolist() == [di.fingerprint(o) for o in ops]
    assert di.check_lanes(lanes, index).tolist() == \
        [di.check_fp(o) for o in ops]
    addresses = [b"addr-%d" % (i % 11) for i in range(n)]
    script = di.script_hash_batch(addresses)
    assert script.tolist() == [zlib.crc32(a) for a in addresses]
    assert di.script_hash_batch(["a", "bc"]).tolist() == \
        [zlib.crc32(b"a"), zlib.crc32(b"bc")]
    idx = di.DeviceUtxoIndex.from_columns(
        fps, di.check_lanes(lanes, index),
        np.arange(n, dtype=np.int64), script)
    assert len(idx) == n and not idx._twin_fps
    assert idx.stats()["twin_fingerprints"] == 0
    assert all(isinstance(getattr(idx._mirror.base, name), np.ndarray)
               for name, _t in di._COLUMNS)
    present, amounts = idx.lookup_batch(ops[:64] + [_op(n + 1)])
    assert present.tolist() == [True] * 64 + [False]
    assert amounts[:64].tolist() == list(range(64))
    assert idx._capture_values([ops[5]]) == [(5, zlib.crc32(b"addr-5"), 0)]
    # the same rows with one key twice: that key, and no other
    fps2 = fps.copy()
    fps2[17] = fps2[4000]
    twin = di.DeviceUtxoIndex.from_columns(
        fps2, di.check_lanes(lanes, index),
        np.arange(n, dtype=np.int64), script)
    assert twin._twin_fps == {int(fps[4000])}


@pytest.mark.parametrize("spend_first", [True, False])
def test_the_mirror_folds_its_delta_and_keeps_every_value(monkeypatch,
                                                          spend_first):
    monkeypatch.setattr(di, "_DELTA_MIN", 64)
    idx = di.DeviceUtxoIndex([_op(i) for i in range(1000)],
                             values=[(i, 0, 2) for i in range(1000)])
    folds0 = _counter("index.folds")
    spans0 = trace.stats().get("index.fold", {}).get("count", 0)
    for block in range(8):
        created = [_op(10_000 + 50 * block + i) for i in range(50)]
        spent = [_op(20 * block + i) for i in range(20)]
        if not spend_first:
            spent = [_op(10_000 + 50 * (block - 1) + i) for i in range(20)] \
                if block else []
        idx.apply_block(created, spent,
                        created_values=[(7, 0, block)] * 50)
    assert not len(idx._mirror.delta.keys) or \
        len(idx._mirror.delta.keys) <= 64
    # the mirror's one O(N) step says when it ran: 50 rows a block into
    # a delta that may hold 64, so every second block folds
    folds = _counter("index.folds") - folds0
    assert folds == 4
    assert trace.stats()["index.fold"]["count"] - spans0 == folds
    assert len(idx._mirror.base.keys) > 1000 - 160
    assert len(idx) == 1000 + 400 - (160 if spend_first else 140)
    assert idx._capture_values([_op(999), _op(10_399), _op(5)]) == \
        [(999, 0, 2), (7, 0, 7), (0, 0, 0) if spend_first else (5, 0, 2)]


def test_a_device_fault_in_an_apply_costs_the_lanes_and_no_answer(
        monkeypatch):
    """The mirror holds the block before the device hears of it: an
    apply program that fails raises nothing (the caller has committed
    the block), the lanes go, and the next probe lays them out from the
    mirror."""
    idx = di.DeviceUtxoIndex([_op(i) for i in range(500)],
                             values=[(i, 0, 1) for i in range(500)])
    idx.materialize()

    def broken(*_a, **_k):
        raise RuntimeError("device fault")

    monkeypatch.setattr(di, "_apply_kernel", broken)
    up0 = _counter("index.upload_bytes")
    idx.apply_block([_op(900), _op(901)], [_op(3)],
                    created_values=[(9, 0, 2)] * 2)
    assert idx._lanes is None and len(idx) == 501
    present, amounts = idx.lookup_batch([_op(900), _op(3), _op(4)])
    assert present.tolist() == [True, False, True]
    assert amounts.tolist()[0] == 9
    assert idx._lanes is not None
    # the operands that went nowhere, the lanes laid out again (six
    # int32 lanes at capacity 512) and the probe's queries
    assert _counter("index.upload_bytes") - up0 > 6 * 4 * 512
