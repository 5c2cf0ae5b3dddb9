"""The filler (``harness/utxofill.py``), the counts of work
(``harness/indexwork.py``) and the bandwidth-share reader."""

import os
import sqlite3

import numpy as np
import pytest

from harness import indexwork, utxofill
from harness.manifest import BenchError

SCHEMA = """CREATE TABLE unspent_outputs (tx_hash TEXT NOT NULL,
 idx INTEGER NOT NULL, address TEXT, amount INTEGER NOT NULL,
 is_stake INTEGER NOT NULL DEFAULT 0, PRIMARY KEY (tx_hash, idx));
CREATE INDEX unspent_address_idx ON unspent_outputs (address);"""


def test_base58_is_the_programs():
    from upow_tpu.core.codecs import b58encode

    raw = np.random.default_rng(3).integers(0, 256, (64, 33), dtype=np.uint8)
    raw[:, 0] = 42 + (raw[:, 0] & 1)
    assert [t.decode() for t in utxofill.base58_33(raw)] == \
        [b58encode(bytes(r)) for r in raw]


def test_the_filler_follows_the_seed_and_the_table_gives_its_digest(
        tmp_path):
    cols = utxofill.columns(2147483999, 2000, 300)
    again = utxofill.columns(2147483999, 2000, 300)
    other = utxofill.columns(2147484000, 2000, 300)
    assert (cols["hash"] == again["hash"]).all()
    assert not (cols["hash"] == other["hash"]).all()
    keys = cols["hash"].view("S32").reshape(-1)
    assert len(set(keys.tolist())) == 2000 and (keys[1:] > keys[:-1]).all()
    assert len(set(cols["address"].tolist())) <= 300
    assert all(len(a) == 45 for a in cols["address"].tolist())
    db = str(tmp_path / "t.db")
    con = sqlite3.connect(db)
    con.executescript(SCHEMA)
    con.execute("INSERT INTO unspent_outputs VALUES (?,?,?,?,0)",
                ("ab" * 32, 3, "someone", 77))
    con.commit()
    con.close()
    took = utxofill.load(db, cols)
    assert took["rows"] == 2001
    live = utxofill.digest_of_rows([("ab" * 32, 3, "someone", 77)])
    want = utxofill.combine(utxofill.digest_of_columns(cols), live)
    assert utxofill.digest_of_table(db) == want and want[2] == 2001
    con = sqlite3.connect(db)
    assert con.execute("SELECT COUNT(*) FROM sqlite_master WHERE name = "
                       "'unspent_address_idx'").fetchone()[0] == 1
    # one amount off by one, one row gone: another digest
    con.execute("UPDATE unspent_outputs SET amount = amount + 1 WHERE "
                "rowid = 5")
    con.commit()
    assert utxofill.digest_of_table(db)[:2] != want[:2]
    con.execute("UPDATE unspent_outputs SET amount = amount - 1 WHERE "
                "rowid = 5")
    con.execute("DELETE FROM unspent_outputs WHERE rowid = 9")
    con.commit()
    con.close()
    assert utxofill.digest_of_table(db) != want
    assert not os.path.exists(db + "-wal") or \
        os.path.getsize(db + "-wal") == 0


def test_the_counts_of_work_follow_the_shapes():
    assert indexwork.probe_bytes(8160, 1 << 22, 8) == \
        8160 * (22 * 4 + 8 * 16 + 8)
    assert indexwork.probe_bytes(1, 1 << 22, 8) < \
        indexwork.probe_bytes(1, 1 << 23, 8)
    assert indexwork.apply_bytes(8161, 8160) == 16321 * 48


def _records(seconds: float) -> list:
    from harness import xplane

    ns = int(seconds * 1e9)
    return [{"plane": "/device:TPU:0", "line": xplane.MODULES_LINE,
             "name": "jit__apply_kernel(123)", "start_ns": 1000,
             "dur_ns": ns}]


def test_the_bandwidth_share_divides_by_the_published_peak(monkeypatch):
    from harness import manifest, xplane

    reader = manifest.load_module("readers", "hbm_share")
    spec = {"key": "apply_delta_bytes", "program": "apply_kernel"}
    monkeypatch.setattr(xplane, "window_of", lambda records: (0, 10 ** 12))
    observed = {"values": {"apply_delta_bytes": 819e9 * 0.001 * 0.5},
                "device_kind": "TPU v5 lite", "records": _records(0.001)}
    assert reader.read(observed, spec) == pytest.approx(50.0)
    assert reader.read(dict(observed, values={}), spec) is None
    assert reader.read(dict(observed, records=[]), spec) is None
    observed["values"]["apply_delta_bytes"] *= 2.2
    with pytest.raises(BenchError, match="over 105%"):
        reader.read(observed, spec)
    with pytest.raises(BenchError):
        reader.read(dict(observed, device_kind="TPU v9"), spec)
