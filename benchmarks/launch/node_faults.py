"""The node, broken on purpose, for a control run on the chip
(``run.py --control``): ``correct`` has to come out false.  Nothing of
the benchmark's own runs comes through here.

    unverified   every signature verdict the block path gets back from
                 the shared dispatch front reads true: the device still
                 runs every lane and its canaries still pass, and a
                 block with a forged signature is acknowledged
"""

from __future__ import annotations


def _unverified() -> None:
    from upow_tpu.verify.dispatch import SigDispatchFront

    submit = SigDispatchFront.submit

    async def all_true(self, checks, **kwargs):
        return [True] * len(await submit(self, checks, **kwargs))

    SigDispatchFront.submit = all_true
    print("fault: unverified (every signature verdict reads true)",
          flush=True)


FAULTS = {"unverified": _unverified}


def apply(name: str) -> None:
    if name not in FAULTS:
        raise SystemExit(f"node_child.py: no fault {name!r}")
    FAULTS[name]()
