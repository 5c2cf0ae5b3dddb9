"""Operations a search needs, computed from its shape: the benchmark's
own count, so a kernel's achieved rate does not depend on the program's
bookkeeping.

One candidate header is one sha256 compression of the tail block (the
prefix's blocks are a midstate computed once per job on the host).
Per compression, 32-bit integer operations as the algorithm states them
(FIPS 180-4), a rotate counted as one operation:

* message schedule, 48 words: sigma0 and sigma1 are 2 rotates + 1 shift
  + 2 xors each (10), plus 3 adds                     -> 13 x 48 = 624
* 64 rounds: Sigma0, Sigma1 3 rotates + 2 xors each (10); Ch 3
  (and, not-and, xor); Maj 4; T1 4 adds, T2 1 add, e = d + T1 and
  a = T1 + T2 2 adds                                  -> 24 x 64 = 1536
* feed-forward, 8 adds                                -> 8
* placing the 4 nonce bytes: shift, mask, shift, or   -> 16
* target test: 2 and + 2 compare + and, select        -> 6

On a machine without a rotate instruction a rotate is 2 shifts + 1 or
(3 operations, not 1); this count does not include that expansion, so
it is the algorithm's work, not the instruction count of one chip.
"""

OPS_PER_HASH = 624 + 1536 + 8 + 16 + 6


def search_ops(nonces: int) -> int:
    return OPS_PER_HASH * int(nonces)
