"""Bytes a resident UTXO index has to move, computed from the shapes of
a block: the benchmark's own count, so a kernel's share of the memory
peak does not depend on the program's bookkeeping.  Work of the
algorithm, the same whatever implements it: a later change of the
layout moves the share and never the count.

* A probe of ``queries`` outpoints against a sorted table of
  ``capacity`` slots with a scan window of ``window`` slots must at
  least read, a query: one 4-byte word a step of the binary search
  (``ceil(log2 capacity)`` steps), the window's slots of the four
  identity lanes, and the two amount words of the row it found.
* An apply of a block must at least write the ``created`` rows and clear
  the ``spent`` ones, six 4-byte lanes a row (24 bytes), each touched
  twice (read and written).
"""

ROW_BYTES = 24


def probe_bytes(queries: int, capacity: int, window: int) -> int:
    steps = max(1, (int(capacity) - 1).bit_length())
    return int(queries) * (steps * 4 + int(window) * 4 * 4 + 2 * 4)


def apply_bytes(created: int, spent: int) -> int:
    return (int(created) + int(spent)) * ROW_BYTES * 2
