"""DR fixture: device dispatches outside device/ (parsed, never run)."""
import jax

from upow_tpu.device import runtime
from upow_tpu.device.runtime import get_runtime


def kernel(x):
    return x + 1


# module-level staging defines a kernel without dispatching: no finding
staged = jax.jit(kernel)


@jax.jit  # decorator form: no finding
def decorated(x):
    return x * 2


def enumerate_backends():
    devs = jax.devices()                       # DR001
    n = jax.local_device_count()               # DR001 suppressed below
    m = jax.local_device_count()  # justified  # upowlint: disable=DR001
    return devs, n, m


def dispatch_around_runtime(fn):
    return runtime.boxed_call(fn, 5.0)         # DR002


def stage_at_call_time(fn):
    compiled = jax.jit(fn)                     # DR003
    return compiled


def sanctioned(fn):
    rt = get_runtime()                         # no finding
    rt.devices()                               # no finding
    return rt.run_boxed(fn, 5.0)               # no finding
