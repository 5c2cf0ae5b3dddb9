"""The plain reference for proof of work."""

import hashlib

import pytest

from harness import powref


def header(tip, merkle, difficulty, nonce, address=b"\x02" * 33):
    return (bytes([2]) + bytes.fromhex(tip) + address
            + bytes.fromhex(merkle) + (1790000000).to_bytes(4, "little")
            + int(difficulty * 10).to_bytes(2, "little")
            + nonce.to_bytes(4, "little"))


def test_target_whole_and_fractional_difficulty():
    tip = "ab" * 30 + "0123"
    assert powref.target(tip, 3.0) == ("123", None)
    assert powref.target(tip, 2) == ("23", None)
    # 16 * (1 - 0.5) = 8 allowed chars, 16 * (1 - 0.9) -> ceil(1.6) = 2
    assert powref.target(tip, "3.5") == ("123", "01234567")
    assert powref.target(tip, 3.9) == ("123", "01")
    assert powref.satisfies("1230ff", "123", "01")
    assert not powref.satisfies("1232ff", "123", "01")
    assert not powref.satisfies("0123ff", "123", None)


def test_lowest_hit_agrees_with_a_plain_loop_in_one_and_many_processes():
    tip = hashlib.sha256(b"tip").hexdigest()
    prefix = header(tip, "00" * 32, 2.0, 0)[:104]
    want = tip[-2:]
    hits = [n for n in range(40000) if hashlib.sha256(
        prefix + n.to_bytes(4, "little")).hexdigest().startswith(want)]
    assert len(hits) > 3
    assert powref.lowest_hit(prefix, 0, 40000, tip, 2.0, workers=1) == hits[0]
    assert powref.lowest_hit(prefix, 0, 40000, tip, 2.0, workers=4) == hits[0]
    assert powref.lowest_hit(prefix, hits[0] + 1, 40000, tip, 2.0,
                             workers=4) == hits[1]
    assert powref.lowest_hit(prefix, hits[0] + 1, hits[1], tip, 2.0,
                             workers=2) == -1
    assert powref.lowest_hit(prefix, 5, 5, tip, 2.0) == -1


def test_check_block_names_each_fault():
    tip = hashlib.sha256(b"tip").hexdigest()
    pending = [hashlib.sha256(bytes([i])).hexdigest() for i in range(3)]
    merkle = powref.miner_merkle(pending)
    job = {"previous_hash": tip, "difficulty": 2.0, "check_difficulty": 2.0,
           "pending_hashes": pending, "address_bytes": b"\x02" * 33}
    prefix = header(tip, merkle, 2.0, 0)[:104]
    nonce = powref.lowest_hit(prefix, 0, 1 << 20, tip, 2.0, workers=1)
    good = header(tip, merkle, 2.0, nonce).hex()
    assert powref.check_block(good, job) == []
    assert powref.parse_header(good)["nonce"] == nonce
    wrong = [
        (header(tip, merkle, 2.0, nonce + 1).hex(), "misses the target"),
        (header("00" * 32, merkle, 2.0, nonce).hex(), "previous hash"),
        (header(tip, "11" * 32, 2.0, nonce).hex(), "merkle root"),
        (header(tip, merkle, 3.0, nonce).hex(), "difficulty field"),
        (header(tip, merkle, 2.0, nonce, b"\x03" * 33).hex(), "address"),
        (good[:-2], "108-byte"),
    ]
    for content, word in wrong:
        faults = powref.check_block(content, job)
        assert any(word in f for f in faults), (word, faults)
    # judged two chars tighter than served: the same block fails
    tight = dict(job, check_difficulty=4.0)
    assert any("misses the target" in f
               for f in powref.check_block(good, tight))


def test_not_a_header():
    with pytest.raises(ValueError):
        powref.parse_header("00" * 108)
