"""DR fixture: resident-index dispatch paths (parsed, never run).

ISSUE 11 put an HBM-resident UTXO table in ``state/`` — client code of
the device runtime, not part of it.  These are the tempting shortcuts a
resident-index implementation must NOT take: pinning arrays itself,
dispatching probes around the fair queues, staging the probe kernel at
call time.  The real ``state/device_index.py`` routes every one of
these through ``get_runtime().submit_call``.
"""
import jax
import jax.numpy as jnp

from upow_tpu.device import boxed_call
from upow_tpu.device.runtime import get_runtime


def probe_kernel(table, fps):
    return jnp.searchsorted(table, fps)


# module-level staging defines the probe kernel: no finding
probe_staged = jax.jit(probe_kernel)


class BadResidentIndex:
    def load(self, fps):
        # pinning the table to HBM directly: no arm deadline, no owner
        self.table = jax.device_put(fps)              # DR001
        self.backend = jax.default_backend()          # DR001
        n = jax.device_count()  # cap check           # upowlint: disable=DR001
        return n

    def probe(self, fps):
        # dispatching around the runtime's fair queues
        return boxed_call(probe_staged, self.table, fps)   # DR002

    def rebuild(self, fps):
        # staging at call time builds a new program per call
        fresh = jax.jit(probe_kernel)                 # DR003
        return fresh(self.table, fps)


class GoodResidentIndex:
    def load(self, fps):
        rt = get_runtime()                            # no finding
        self.table = rt.submit_call(
            lambda: probe_staged, kernel="utxo_probe",
            source="state").result()                  # no finding

    def probe(self, fps):
        rt = get_runtime()
        return rt.run_boxed(probe_staged, fps)        # no finding
