"""DR — device-runtime purity.

ISSUE 10 routes every device dispatch through ONE owner:
``upow_tpu/device/runtime.py``.  The runtime arms the backend exactly
once under a deadline, coalesces compatible submissions across
subsystems, schedules them with weighted fairness, and gives the
degrade controller a single choke point.  All of that is void the
moment some subsystem talks to the chip directly — a stray
``jax.devices()`` can *initialize the backend* (hanging the process on
an unreachable device with no deadline), and a stray ``boxed_call`` dispatch
races the fair scheduler for the chip.

Rules (all errors, scoped to everything OUTSIDE ``device/`` and
``lint/``):

* DR001 — backend init/enumeration outside ``device/``:
  ``jax.devices`` / ``jax.local_devices`` / ``jax.device_count`` /
  ``jax.local_device_count`` / ``jax.default_backend`` /
  ``jax.device_put`` / ``jax.device_get``.  Use
  ``get_runtime().devices()`` / ``.platform()`` instead — they wait on
  the armed (deadline-bounded) backend.
* DR002 — ``boxed_call(...)`` outside ``device/``: the thread-boxed
  dispatch shim is the runtime's internal primitive now; subsystems
  submit via ``get_runtime().run_boxed`` / ``submit_call`` /
  ``submit_sig_checks`` so their work lands in the fair queues.
* DR003 — ``jax.jit`` / ``pjit`` called as an *expression inside a
  function body* outside ``device/``: staging a dispatchable at call
  time builds a new program per call (a compile each time) and hides
  a dispatch site from the runtime.  Decorators and module-level kernel definitions are
  fine — defining a kernel is not dispatching it.

The inverse boundary (nothing inside ``device/`` reaching back up into
subsystem logic) is reviewed, not machine-enforced.
"""

from __future__ import annotations

import ast
from typing import Tuple

from ..engine import SEVERITY_ERROR, FileContext, dotted_name

_BACKEND_TOUCHES = {
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend",
    "jax.device_put", "jax.device_get",
}
_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}


class _DeviceRuleBase:
    severity = SEVERITY_ERROR
    requires_project = False    # per-file lexical rules (project API opt-out)

    def scope(self, parts: Tuple[str, ...]) -> bool:
        # device/ IS the sanctioned dispatch layer; lint/ holds these
        # rule names as data.  Everything else is client code.
        return "device" not in parts and "lint" not in parts


class BackendTouchRule(_DeviceRuleBase):
    rule_id = "DR001"
    description = ("backend init/enumeration (jax.devices & friends) "
                   "outside device/ — use get_runtime().devices()/platform()")

    def check(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    dotted_name(node.func) in _BACKEND_TOUCHES:
                yield (node.lineno, node.col_offset,
                       f"{dotted_name(node.func)}() outside device/ can "
                       "initialize the backend with no deadline and bypasses "
                       "the armed runtime — use get_runtime().devices() / "
                       ".platform()")


class BoxedCallRule(_DeviceRuleBase):
    rule_id = "DR002"
    description = ("boxed_call() outside device/ — submit through "
                   "get_runtime().run_boxed/submit_call instead")

    def check(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name == "boxed_call" or name.endswith(".boxed_call"):
                yield (node.lineno, node.col_offset,
                       f"{name}() outside device/ dispatches around the "
                       "runtime's fair queues — use get_runtime().run_boxed "
                       "(or submit_call/submit_sig_checks)")


class RuntimeJitRule(_DeviceRuleBase):
    rule_id = "DR003"
    description = ("jax.jit/pjit called as an expression inside a function "
                   "body outside device/ (a new program per call)")

    def check(self, ctx: FileContext):
        for func in ast.walk(ctx.tree):
            if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for stmt in func.body:
                for node in ast.walk(stmt):
                    if isinstance(node, ast.Call) and \
                            dotted_name(node.func) in _JIT_NAMES:
                        yield (node.lineno, node.col_offset,
                               f"{dotted_name(node.func)}(...) staged inside "
                               "a function body outside device/ — hoist the "
                               "kernel to module level (or move the dispatch "
                               "into the device runtime) so arm-time AOT "
                               "warming sees it")


RULES = [BackendTouchRule(), BoxedCallRule(), RuntimeJitRule()]
