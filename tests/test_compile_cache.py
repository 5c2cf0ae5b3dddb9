"""compile_cache cpu_aot_loader triage + the dryrun's acceptance
envelope (VERDICT r4 weak #4): the same-host tuning-pref residue is
cosmetic and must pass with a note; any feature beyond that pair means
a foreign AOT entry and must trigger the evict path — even on rc=0,
because miscompiled AOT code does not reliably crash."""

import importlib.util
import os
import sys

from upow_tpu import compile_cache

# __graft_entry__ lives at the repo root, not in a package
_spec = importlib.util.spec_from_file_location(
    "graft_entry", os.path.join(os.path.dirname(__file__), os.pardir,
                                "__graft_entry__.py"))
_graft = importlib.util.module_from_spec(_spec)
_prev = sys.modules.get("graft_entry")
sys.modules["graft_entry"] = _graft
_spec.loader.exec_module(_graft)
if _prev is not None:
    sys.modules["graft_entry"] = _prev
else:
    del sys.modules["graft_entry"]

# the loader's real message shape (double space included, as observed
# live in a four-chip dry run's output)
_LINE = ("E0801 14:49:04.127131  13650 cpu_aot_loader.cc:210] Loading "
         "XLA:CPU AOT result. Target machine feature {feat} is not "
         " supported on the host machine. Machine type used for XLA:CPU "
         "compilation doesn't match the machine type for execution.")


def _stderr_with(*feats):
    return "\n".join(_LINE.format(feat=f) for f in feats)


def test_cosmetic_pair_is_not_foreign():
    text = _stderr_with("+prefer-no-gather", "+prefer-no-scatter")
    assert compile_cache.aot_mismatch_features(text) == {
        "+prefer-no-gather", "+prefer-no-scatter"}
    assert compile_cache.foreign_aot_mismatches(text) == set()


def test_foreign_feature_detected():
    text = _stderr_with("+prefer-no-gather", "+amx-complex")
    assert compile_cache.foreign_aot_mismatches(text) == {"+amx-complex"}


def test_clean_stderr_has_no_mismatches():
    assert compile_cache.aot_mismatch_features("all good\n") == set()


def test_judge_accepts_cosmetic_residue_with_note():
    action, note = _graft._judge_dryrun_child(
        0, _stderr_with("+prefer-no-gather"))
    assert action == "ok"
    assert "cosmetic" in note and "+prefer-no-gather" in note


def test_judge_accepts_clean_run_silently():
    assert _graft._judge_dryrun_child(0, "") == ("ok", "")


def test_judge_evicts_on_synthetic_foreign_feature_even_rc0():
    action, note = _graft._judge_dryrun_child(
        0, _stderr_with("+prefer-no-gather", "+avx10.1"))
    assert action == "evict"
    assert "+avx10.1" in note and "+prefer-no-gather" not in note


def test_judge_evicts_on_nonzero_rc():
    action, note = _graft._judge_dryrun_child(1, "")
    assert action == "evict" and "rc=1" in note
