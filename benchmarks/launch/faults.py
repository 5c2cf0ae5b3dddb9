"""The miner or its launcher, broken on purpose, for a control run on
the chip (``run.py --control``): ``correct`` has to come out false, or
the run has to fail.  Nothing of the benchmark's own runs comes through
here.

    skip_rounds  one round in sixteen is claimed and never sent to the
                 device: the miner prints its hashes, the job ends
                 'template expired' with its whole range accounted for,
                 and the rate reads a sixteenth higher
    mute_memory  the launcher never says a ``memory:`` line, asked or at
                 exit: the run has no reading of the chip's peak memory
                 and has to end ``FAILED:``, with no result line
"""

from __future__ import annotations


def _skip_rounds() -> None:
    import numpy as np

    from upow_tpu.crypto.sha256 import SENTINEL
    from upow_tpu.mine import engine

    make = engine._make_dispatcher
    calls = [0]

    def make_faulty(*args, **kwargs):
        dispatch = make(*args, **kwargs)
        if dispatch is None:
            return None

        def faulty(start, count):
            calls[0] += 1
            if calls[0] % 16 == 0:
                return np.uint32(SENTINEL)   # "no hit", without looking
            return dispatch(start, count)

        return faulty

    engine._make_dispatcher = make_faulty
    print("fault: skip_rounds (one round in 16 never reaches the device)",
          flush=True)


def _mute_memory() -> None:
    import __main__ as launcher     # launch/miner_child.py

    launcher._say_memory = lambda: None
    print("fault: mute_memory (the launcher says no 'memory:' line)",
          flush=True)


FAULTS = {"skip_rounds": _skip_rounds, "mute_memory": _mute_memory}


def apply(name: str) -> None:
    if name not in FAULTS:
        raise SystemExit(f"miner_child.py: no fault {name!r}")
    FAULTS[name]()
