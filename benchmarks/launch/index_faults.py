#!/usr/bin/env python3
"""``node_child.py`` with the resident UTXO index broken on purpose, for
a control run on the chip (``run.py --control stale_index``): ``correct``
has to come out false.  Nothing of the benchmark's own runs comes
through here.

    index_faults.py --fault stale_index -- --config <file>

    stale_index   what a committed block adds to and removes from the
                  resident index is dropped, so the index stays as the
                  build left it: the next block's inputs, which that
                  block created, read absent, and a sound block is
                  refused as a double spend

It adds the fault to ``node_faults.FAULTS`` and hands over to
``node_child.main()``, whose protocol (signals, memory line, exit) is
unchanged.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import node_child  # noqa: E402
import node_faults  # noqa: E402


def _stale_index() -> None:
    from upow_tpu.state.device_index import DeviceUtxoIndex

    DeviceUtxoIndex.apply_steps = lambda self, steps: None
    print("fault: stale_index (a block's delta never reaches the "
          "resident index)", flush=True)


node_faults.FAULTS["stale_index"] = _stale_index

if __name__ == "__main__":
    # the driver names the fault of every child it starts, "-" for none
    if sys.argv[1:3] == ["--fault", "-"]:
        del sys.argv[1:3]
    node_child.main()
