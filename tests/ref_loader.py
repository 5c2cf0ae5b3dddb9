"""Import the reference implementation from /root/reference for differential
testing, shimming its missing native deps (fastecdsa, base58, icecream,
asyncpg/pickledb-backed database, file logger) with minimal stand-ins backed
by our own clean-room code.

This lets tests execute the reference's *pure* functions (codecs, tx wire
format, difficulty, rewards, merkle, header codec) as golden oracles without
installing anything, per SURVEY.md §7.1.  Nothing from the reference is
imported into the framework itself.
"""

from __future__ import annotations

import logging
import os
import sys
import types

REF_PATH = "/root/reference"


def _install_shims():
    import upow_tpu.core.curve as ours
    from upow_tpu.core import codecs

    # --- fastecdsa ---
    fastecdsa = types.ModuleType("fastecdsa")

    class Point:
        def __init__(self, x, y, curve=None):
            # fastecdsa's Point validates on-curve at construction and
            # raises — the shim must too, or differential tests can't see
            # decode-acceptance divergences.
            if not codecs.is_on_curve((x, y)):
                raise ValueError(f"({x}, {y}) is not on P-256")
            self.x, self.y = x, y
            self.curve = curve

        def __eq__(self, other):
            return isinstance(other, Point) and (self.x, self.y) == (other.x, other.y)

        def __hash__(self):
            return hash((self.x, self.y))

        def __repr__(self):
            return f"Point({self.x}, {self.y})"

    class _P256:
        from upow_tpu.core.constants import (
            CURVE_A as a,
            CURVE_B as b,
            CURVE_P as p,
            CURVE_N as q,
            CURVE_GX as gx,
            CURVE_GY as gy,
        )

        @staticmethod
        def is_point_on_curve(xy):
            return codecs.is_on_curve(xy)

    curve_mod = types.ModuleType("fastecdsa.curve")
    curve_mod.P256 = _P256()

    point_mod = types.ModuleType("fastecdsa.point")
    point_mod.Point = Point

    util_mod = types.ModuleType("fastecdsa.util")

    def mod_sqrt(a, p):
        root = pow(a, (p + 1) // 4, p)
        return (root, p - root)

    util_mod.mod_sqrt = mod_sqrt

    keys_mod = types.ModuleType("fastecdsa.keys")

    def get_public_key(d, curve=None):
        x, y = ours.point_mul(d, ours.G)
        return Point(x, y)

    keys_mod.get_public_key = get_public_key

    ecdsa_mod = types.ModuleType("fastecdsa.ecdsa")

    def sign(msg, d, curve=None, hashfunc=None):
        if isinstance(msg, str):
            msg = msg.encode()
        return ours.sign(msg, d)

    def verify(sig, msg, pub, curve=None, hashfunc=None):
        if isinstance(msg, str):
            msg = msg.encode()
        return ours.verify(sig, msg, (pub.x, pub.y))

    ecdsa_mod.sign = sign
    ecdsa_mod.verify = verify

    fastecdsa.curve = curve_mod
    fastecdsa.point = point_mod
    fastecdsa.util = util_mod
    fastecdsa.keys = keys_mod
    fastecdsa.ecdsa = ecdsa_mod
    for name, mod in {
        "fastecdsa": fastecdsa,
        "fastecdsa.curve": curve_mod,
        "fastecdsa.point": point_mod,
        "fastecdsa.util": util_mod,
        "fastecdsa.keys": keys_mod,
        "fastecdsa.ecdsa": ecdsa_mod,
    }.items():
        sys.modules.setdefault(name, mod)

    # --- base58 ---
    base58_mod = types.ModuleType("base58")
    base58_mod.b58encode = lambda b: codecs.b58encode(b).encode()
    base58_mod.b58decode = lambda s: codecs.b58decode(s if isinstance(s, str) else s.decode())
    sys.modules.setdefault("base58", base58_mod)

    # --- icecream ---
    icecream_mod = types.ModuleType("icecream")

    class _IC:
        def __call__(self, *args, **kwargs):
            return args[0] if len(args) == 1 else args

        def configureOutput(self, **kwargs):
            pass

    icecream_mod.ic = _IC()
    sys.modules.setdefault("icecream", icecream_mod)

    # --- upow.my_logger (avoid file handlers writing logs/ everywhere) ---
    my_logger_mod = types.ModuleType("upow.my_logger")

    class CustomLogger:
        def __init__(self, name, *a, **k):
            self._logger = logging.getLogger(f"ref.{name}")

        def get_logger(self):
            return self._logger

    my_logger_mod.CustomLogger = CustomLogger
    sys.modules["upow.my_logger"] = my_logger_mod

    # --- upow.database (manager.py imports Database + emission_details) ---
    database_mod = types.ModuleType("upow.database")

    class Database:
        instance = None

        @staticmethod
        async def get():
            return Database.instance

    class _EmissionDetails:
        def set(self, *a, **k):
            pass

    database_mod.Database = Database
    database_mod.emission_details = _EmissionDetails()
    sys.modules["upow.database"] = database_mod


_ref_modules = {}


def load_reference():
    """Import and cache the reference's pure modules. Returns a namespace.

    Where the reference is not mounted the caller is skipped by name: a
    test that calls this skips, and a module that calls it at import
    skips whole (not a collection error).  Nothing counts as a pass that
    did not run."""
    if _ref_modules:
        return _ref_modules["ns"]
    if not os.path.isdir(REF_PATH):
        import pytest

        pytest.skip("`%s` is not mounted" % REF_PATH,
                    allow_module_level=True)
    if REF_PATH not in sys.path:
        sys.path.insert(0, REF_PATH)
    _install_shims()
    import upow.helpers as ref_helpers  # noqa
    import upow.constants as ref_constants  # noqa
    from upow.upow_transactions import (  # noqa
        Transaction,
        TransactionInput,
        TransactionOutput,
        CoinbaseTransaction,
    )
    import upow.manager as ref_manager  # noqa

    ns = types.SimpleNamespace(
        helpers=ref_helpers,
        constants=ref_constants,
        manager=ref_manager,
        Transaction=Transaction,
        TransactionInput=TransactionInput,
        TransactionOutput=TransactionOutput,
        CoinbaseTransaction=CoinbaseTransaction,
    )
    _ref_modules["ns"] = ns
    return ns
