"""Verify-kernel op-budget A/B: w=4 vs w=5, measured from the traced
program (VERDICT r4 weak #2 / next-step #3).

The chip-gated question is whether the Jacobian ladder's w=5 window
(52 rounds, 32-entry tables) beats w=4 (64 rounds, 16-entry tables).
Rates need the TPU, but the OP BUDGET does not: this script traces
``_prep_and_verify_pallas_jac`` (the exact production program — device
scalar prep, the fori_loop'd ladder rounds and the VMEM Q-table build)
into a jaxpr and
tallies ELEMENT-ops — each primitive weighted by its output element
count, scan bodies multiplied by trip count, pallas grids by grid size
— then classifies them:

  mac    : integer mul/add/sub — the limb arithmetic the algorithm
           fundamentally requires (Montgomery MACs + lazy-reduction
           sums)
  glue   : select_n, compares, shifts, bitwise ops, converts — the
           digit picks, carry sweeps and exception flags the VPU pays
           issue slots for but that do no field arithmetic
  layout : broadcast/reshape/transpose/concat/slice — usually free
           (fused or relaid) on TPU, listed for completeness

Output: one table per window width, totals normalized per verify
(element-ops / n_lanes), plus the w=5 vs w=4 deltas.  Used to fill
docs/KERNELS.md's floor-model table.  Run:
    JAX_PLATFORMS=cpu python .op_budget.py
"""

import os
import sys

# a trace-time analysis: it never needs a device, so it pins JAX to the
# CPU before the import (same pattern as tests/conftest.py)
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")

from upow_tpu import compile_cache

compile_cache.enable(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))

from upow_tpu.core import curve
from upow_tpu.crypto import p256
from upow_tpu.crypto import fp

MAC = {"mul", "add", "sub", "add_any", "dot_general"}
GLUE = {"select_n", "eq", "ne", "lt", "le", "gt", "ge", "shift_left",
        "shift_right_logical", "shift_right_arithmetic", "and", "or",
        "xor", "not", "rem", "div", "convert_element_type", "min", "max",
        "neg", "sign", "clamp", "population_count", "reduce_and",
        "reduce_or", "reduce_sum", "reduce_min", "reduce_max", "integer_pow"}
LAYOUT = {"broadcast_in_dim", "reshape", "transpose", "concatenate",
          "slice", "dynamic_slice", "dynamic_update_slice", "squeeze",
          "iota", "gather", "scatter", "copy", "pad", "rev",
          "expand_dims"}
SKIP = {"get", "swap", "masked_load", "masked_swap", "program_id",
        "num_programs"}  # pallas ref plumbing


def _elems(var) -> int:
    try:
        return int(np.prod(var.aval.shape)) if var.aval.shape else 1
    except Exception:
        return 1


def tally(jaxpr, mult: int, out: dict):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        sub = None
        submult = mult
        if prim in ("pjit", "jit", "closed_call", "core_call", "xla_call",
                    "custom_jvp_call", "custom_vjp_call", "remat"):
            sub = eqn.params.get("jaxpr")
        elif prim == "scan":
            sub = eqn.params["jaxpr"]
            submult = mult * int(eqn.params["length"])
        elif prim == "while":
            # fori_loop with static bounds traces to scan; a while here
            # would make counts non-static — flag loudly
            out.setdefault("_while", 0)
            out["_while"] += 1
            sub = eqn.params["body_jaxpr"]
        elif prim == "cond":
            branches = eqn.params["branches"]
            best = {}
            for br in branches:
                cur = {}
                tally(br.jaxpr if hasattr(br, "jaxpr") else br, mult, cur)
                if sum(v for k, v in cur.items()
                       if not k.startswith("_")) > \
                   sum(v for k, v in best.items() if not k.startswith("_")):
                    best = cur
            for k, v in best.items():
                out[k] = out.get(k, 0) + v
            continue
        elif prim == "pallas_call":
            sub = eqn.params["jaxpr"]
            grid = eqn.params.get("grid_mapping")
            g = 1
            if grid is not None:
                for d in getattr(grid, "grid", ()) or ():
                    g *= int(d)
            submult = mult * g
        if sub is not None:
            tally(sub.jaxpr if hasattr(sub, "jaxpr") else sub,
                  submult, out)
            continue
        if prim in SKIP:
            continue
        weight = mult * max((_elems(v) for v in eqn.outvars), default=1)
        out[prim] = out.get(prim, 0) + weight


def classify(counts: dict):
    mac = glue = layout = other = 0
    other_names = {}
    for prim, v in counts.items():
        if prim.startswith("_"):
            continue
        if prim in MAC:
            mac += v
        elif prim in GLUE:
            glue += v
        elif prim in LAYOUT:
            layout += v
        else:
            other += v
            other_names[prim] = other_names.get(prim, 0) + v
    return mac, glue, layout, other, other_names


def build_inputs(n=128):
    digs, sigs, pubs = [], [], []
    for i in range(n):
        d, pub = curve.keygen(rng=7000 + i)
        msg = b"op-budget-%d" % i
        import hashlib

        digs.append(hashlib.sha256(msg).digest())
        sigs.append(curve.sign(msg, d))
        pubs.append(pub)
    return digs, sigs, pubs


def trace_counts(w: int, n=128):
    digs, sigs, pubs = build_inputs(n)
    packed, *_ = p256._pack_device_inputs(digs, sigs, pubs, n)

    def fn(p):
        return p256._prep_and_verify_pallas_jac(p, tile=n, w=w)

    jaxpr = jax.make_jaxpr(fn)(packed)
    counts = {}
    tally(jaxpr.jaxpr, 1, counts)
    return counts


def main():
    n = 128
    rows = {}
    for w in (4, 5):
        counts = trace_counts(w, n)
        mac, glue, layout, other, other_names = classify(counts)
        issue = mac + glue + other  # layout assumed free post-fusion
        rows[w] = dict(mac=mac, glue=glue, layout=layout, other=other,
                       issue=issue, per_verify_mac=mac / n,
                       per_verify_issue=issue / n)
        print(f"\n== w={w} (rounds={p256._jac_rounds(w)}, "
              f"table={1 << w}) ==")
        print(f"  element-ops (n={n} lanes):")
        print(f"    mac    {mac:>14,}   ({mac / n:,.0f}/verify)")
        print(f"    glue   {glue:>14,}   ({glue / n:,.0f}/verify)")
        print(f"    layout {layout:>14,}   (excluded from issue slots)")
        if other:
            print(f"    other  {other:>14,}   {other_names}")
        print(f"    issue  {issue:>14,}   ({issue / n:,.0f}/verify)")
        print(f"    glue share of issue slots: {glue / issue:.1%}")
        if counts.get("_while"):
            print("    WARNING: while-loop present — counts are "
                  "per-iteration, not totals")
    d_mac = rows[5]["mac"] / rows[4]["mac"] - 1
    d_issue = rows[5]["issue"] / rows[4]["issue"] - 1
    print(f"\n== w=5 vs w=4 ==")
    print(f"  MAC-class element-ops: {d_mac:+.1%}")
    print(f"  total issue-slot element-ops: {d_issue:+.1%}")
    import json

    print(json.dumps({
        "w4": {k: v for k, v in rows[4].items()},
        "w5": {k: v for k, v in rows[5].items()},
        "w5_vs_w4_mac": d_mac, "w5_vs_w4_issue": d_issue}))


if __name__ == "__main__":
    main()
