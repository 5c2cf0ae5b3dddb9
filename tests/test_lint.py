"""upowlint: rule behavior over fixtures, CLI contract, and the
consensus fixes the first lint sweep produced.

Fixture files under ``tests/lint_fixtures/`` are parsed by the linter but
never imported, so their jax/requests references carry no runtime
dependency.  Directory names (``core/``, ``crypto/``, ``node/``) place
them in the same rule scopes as the real modules.
"""

import json
import subprocess
import sys
from decimal import Decimal
from pathlib import Path

from upow_tpu.lint import run_lint

FIXTURES = Path(__file__).parent / "lint_fixtures"
PACKAGE = Path(__file__).parent.parent / "upow_tpu"


def rules_fired(path, select=None):
    result = run_lint([str(path)], select=select)
    return result, {f.rule for f in result.findings}


# --- consensus-endianness (CE) -------------------------------------------

def test_endianness_fires_and_suppresses():
    result, fired = rules_fired(FIXTURES / "core" / "bad_endian.py")
    assert "CE001" in fired
    assert "CE002" in fired
    # two explicit-'big' sites fire; the third is suppressed
    assert sum(f.rule == "CE001" for f in result.findings) == 2
    assert sum(f.rule == "CE001" for f in result.suppressed) == 1
    # the little-endian call produces nothing
    assert all(f.line != 23 for f in result.findings)


def test_endianness_allowlist_exempts_sha256():
    result, fired = rules_fired(FIXTURES / "crypto" / "sha256.py")
    assert fired == set()
    assert result.suppressed == []


# --- consensus-purity (CP) -----------------------------------------------

def test_consensus_purity_fires():
    result, fired = rules_fired(FIXTURES / "core" / "bad_floats.py")
    assert {"CP001", "CP002", "CP003", "CP004"} <= fired
    # both wall-clock reads (time.time and datetime.now)
    assert sum(f.rule == "CP002" for f in result.findings) == 2
    # the suppressed Decimal(0.5) is recorded as suppressed, not a finding
    assert sum(f.rule == "CP001" for f in result.suppressed) == 1
    # time.monotonic and sorted(set(...)) are clean
    cp3_lines = [f.line for f in result.findings if f.rule == "CP003"]
    assert len(cp3_lines) == 1


def test_consensus_scope_excludes_unscoped_dirs(tmp_path):
    f = tmp_path / "tool.py"
    f.write_text("x = 0.5\n")
    result = run_lint([str(f)], select={"CP001"})
    assert result.findings == []


# --- jit-purity (JP) -----------------------------------------------------

def test_jit_purity_fires():
    result, fired = rules_fired(FIXTURES / "crypto" / "bad_jit.py",
                                select={"JP001", "JP002", "JP003"})
    assert {"JP001", "JP002", "JP003"} <= fired
    by_rule = {}
    for f in result.findings:
        by_rule.setdefault(f.rule, []).append(f.line)
    # branch_on_traced if + assert_on_traced assert; nothing else
    assert len(by_rule["JP001"]) == 2
    # .item(), float(), np.asarray()
    assert len(by_rule["JP002"]) == 3
    assert len(by_rule["JP003"]) == 1
    assert sum(f.rule == "JP001" for f in result.suppressed) == 1


def test_jit_purity_static_and_shape_do_not_fire():
    result, _ = rules_fired(FIXTURES / "crypto" / "bad_jit.py",
                            select={"JP001"})
    src = (FIXTURES / "crypto" / "bad_jit.py").read_text().splitlines()
    flagged = {src[f.line - 1].strip() for f in result.findings}
    # the static_argnames branch and the shape-derived assert stay clean
    assert not any("n > 4" in line for line in flagged)
    assert not any("n % 128" in line for line in flagged)
    # and so does the undecorated helper
    assert not any("not jitted" in line for line in flagged)


# --- dtype-hygiene (DT) --------------------------------------------------

def test_dtype_hygiene_fires():
    result, fired = rules_fired(FIXTURES / "crypto" / "bad_dtype.py")
    assert {"DT001", "DT002", "DT003"} <= fired
    assert sum(f.rule == "DT003" for f in result.findings) == 2
    assert sum(f.rule == "DT001" for f in result.suppressed) == 1
    # in-range and same-dtype cases are clean
    src = (FIXTURES / "crypto" / "bad_dtype.py").read_text().splitlines()
    flagged = {src[f.line - 1].strip() for f in result.findings}
    assert not any("no finding" in line for line in flagged)


def test_dtype_scope_excludes_core(tmp_path):
    core = tmp_path / "core"
    core.mkdir()
    f = core / "x.py"
    f.write_text("import numpy as np\ny = np.int64(3)\n")
    assert run_lint([str(f)], select={"DT001"}).findings == []


# --- async-safety (AS) ---------------------------------------------------

def test_async_safety_fires():
    result, fired = rules_fired(FIXTURES / "node" / "bad_async.py")
    assert "AS001" in fired
    assert sum(f.rule == "AS001" for f in result.findings) == 3
    assert sum(f.rule == "AS001" for f in result.suppressed) == 1
    # sync helper and awaited sleep are clean
    src = (FIXTURES / "node" / "bad_async.py").read_text().splitlines()
    flagged = {src[f.line - 1].strip() for f in result.findings}
    assert not any("no finding" in line for line in flagged)


# --- broad-except (BE) ---------------------------------------------------

def test_broad_except_fires():
    result, fired = rules_fired(FIXTURES / "node" / "bad_except.py")
    assert fired == {"BE001"}
    assert sum(f.rule == "BE001" for f in result.findings) == 2
    assert sum(f.rule == "BE001" for f in result.suppressed) == 1
    # logged / re-raised / boxed handlers are clean
    src = (FIXTURES / "node" / "bad_except.py").read_text().splitlines()
    flagged = {src[f.line - 1].strip() for f in result.findings}
    assert not any("no finding" in line for line in flagged)


# --- device-runtime purity (DR) ------------------------------------------

def test_device_purity_fires():
    result, fired = rules_fired(FIXTURES / "node" / "bad_device.py")
    assert {"DR001", "DR002", "DR003"} <= fired
    by_rule = {}
    for f in result.findings:
        by_rule.setdefault(f.rule, []).append(f.line)
    # jax.devices() + one unsuppressed jax.local_device_count()
    assert len(by_rule["DR001"]) == 2
    assert len(by_rule["DR002"]) == 1
    assert len(by_rule["DR003"]) == 1
    assert sum(f.rule == "DR001" for f in result.suppressed) == 1
    # module-level staging, the decorator, and get_runtime() stay clean
    src = (FIXTURES / "node" / "bad_device.py").read_text().splitlines()
    flagged = {src[f.line - 1].strip() for f in result.findings}
    assert not any("no finding" in line for line in flagged)


def test_platform_questions_go_through_the_device_package():
    """One place knows the platform: nothing under upow_tpu/ imports a
    module named for benchmarking (the probe's old home), and every
    ``jax.devices()`` outside device/ is a DR001 site the sweep sees —
    none slips past the rule, none is left unsuppressed."""
    import ast

    # spelled in two halves so a grep for the old module's name over
    # tests/ comes back empty
    banned = {"bench" + "util", "bench", "bench_suite"}
    imports, touches = [], set()
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE)
        tree = ast.parse(path.read_text(encoding="utf-8"))
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    "%s.%s" % (node.module or "", a.name)
                    for a in node.names]
            for name in names:
                if banned & set(name.split(".")):
                    imports.append("%s:%d imports %s"
                                   % (rel, node.lineno, name))
            if isinstance(node, ast.Call) \
                    and ast.unparse(node.func) == "jax.devices" \
                    and rel.parts[0] not in ("device", "lint"):
                touches.add((rel.as_posix(), node.lineno))
    assert imports == []
    result = run_lint([str(PACKAGE)], select={"DR001"})
    assert result.findings == [], "\n" + result.to_text()
    seen = {(Path(f.path).as_posix().split("upow_tpu/", 1)[-1], f.line)
            for f in result.suppressed}
    assert touches <= seen, sorted(touches - seen)


def test_deleted_p256_knobs_leave_no_name_behind():
    """PR 46 deleted the stacked and RCB16 ladder kernels with the four
    values that selected among them; no identifier, string or comment
    under upow_tpu/ still says their names."""
    # spelled in halves so this file does not match itself
    gone = ["PALLAS" + "_KERNEL", "PALLAS" + "_JAC_WINDOW",
            "UPOW" + "_JAC_WINDOW", "UPOW" + "_TILE_CAP",
            "verify" + "_kernel", "verify" + "_window", "_env" + "_choice",
            "apply_kernel" + "_overrides", "_ladder_kernel" + "_list",
            "_verify_device_pallas" + "_stacked"]
    hits = []
    for path in sorted(PACKAGE.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        hits += ["%s: %s" % (path.relative_to(PACKAGE), name)
                 for name in gone if name in text]
    assert hits == []


def test_device_purity_fires_on_resident_index_paths():
    """The ISSUE 11 resident-index dispatch shortcuts (self-pinned HBM
    tables, probes around the fair queues, call-time kernel staging)
    each map to a DR rule — state/ is client code of the runtime."""
    result, fired = rules_fired(FIXTURES / "state" / "bad_index.py")
    assert fired == {"DR001", "DR002", "DR003"}
    by_rule = {}
    for f in result.findings:
        by_rule.setdefault(f.rule, []).append(f.line)
    # jax.device_put + jax.default_backend; the capacity check is
    # suppressed with a justification
    assert len(by_rule["DR001"]) == 2
    assert len(by_rule["DR002"]) == 1
    assert len(by_rule["DR003"]) == 1
    assert sum(f.rule == "DR001" for f in result.suppressed) == 1
    # module-level kernel staging and the runtime-routed index are clean
    src = (FIXTURES / "state" / "bad_index.py").read_text().splitlines()
    flagged = {src[f.line - 1].strip() for f in result.findings}
    assert not any("no finding" in line for line in flagged)
    assert not any("probe_staged = " in line for line in flagged)


def test_device_purity_scope_excludes_device_dir(tmp_path):
    device = tmp_path / "device"
    device.mkdir()
    f = device / "runtime.py"
    f.write_text("import jax\nd = jax.devices()\n"
                 "def g(fn):\n    return boxed_call(fn, 1.0)\n")
    assert run_lint([str(f)]).findings == []


# --- race/concurrency family (RC, interprocedural) -----------------------

CONCURRENCY = FIXTURES / "concurrency"


def test_rc001_transitive_blocking_fires():
    result, fired = rules_fired(CONCURRENCY / "rc001_bad.py",
                                select={"RC"})
    assert fired == {"RC001"}
    msgs = {f.line: f.message for f in result.findings}
    # the transitive finding is reported at the LEAF blocking call with
    # the async chain spelled out in the message
    src = (CONCURRENCY / "rc001_bad.py").read_text().splitlines()
    leaf = next(line for line, m in msgs.items() if "open()" in m)
    assert "with open(path)" in src[leaf - 1]
    assert "handler via warm_cache → read_config" in msgs[leaf]
    # depth-0: time.sleep directly inside a coroutine
    assert any("time.sleep()" in m for m in msgs.values())
    assert sum(f.rule == "RC001" for f in result.suppressed) == 1


def test_rc001_executor_boundaries_are_clean():
    result, fired = rules_fired(CONCURRENCY / "rc001_good.py",
                                select={"RC"})
    assert fired == set()
    assert result.suppressed == []


def test_rc001_cross_module_needs_the_project_graph():
    """The defining interprocedural case: the helper module alone is
    clean; adding the async importer produces a finding IN the helper."""
    helper = CONCURRENCY / "rc001_cross_helper.py"
    alone, fired_alone = rules_fired(helper, select={"RC"})
    assert fired_alone == set()

    both = run_lint([str(CONCURRENCY / "rc001_cross_a.py"), str(helper)],
                    select={"RC"})
    assert [f.rule for f in both.findings] == ["RC001"]
    f = both.findings[0]
    assert f.path.endswith("rc001_cross_helper.py")
    assert "reconnect via resync → backoff" in f.message


def test_rc002_cross_thread_write_fires_and_lock_clears():
    result, fired = rules_fired(CONCURRENCY / "rc002_bad.py",
                                select={"RC"})
    assert fired == {"RC002"}
    f = result.findings[0]
    assert "self.total" in f.message
    assert "Counter.report" in f.message and "Counter._drain" in f.message
    # __init__ writes never count as racing
    src = (CONCURRENCY / "rc002_bad.py").read_text().splitlines()
    assert "+=" in src[f.line - 1]

    good, fired_good = rules_fired(CONCURRENCY / "rc002_good.py",
                                   select={"RC"})
    assert fired_good == set()


def test_rc003_threading_lock_across_await():
    result, fired = rules_fired(CONCURRENCY / "rc003_bad.py",
                                select={"RC"})
    assert fired == {"RC003"}
    assert "'_mu'" in result.findings[0].message

    good, fired_good = rules_fired(CONCURRENCY / "rc003_good.py",
                                   select={"RC"})
    # released-before-await and asyncio.Lock are both clean
    assert fired_good == set()


def test_rc004_task_leaks():
    result, fired = rules_fired(CONCURRENCY / "rc004_bad.py",
                                select={"RC"})
    assert fired == {"RC004"}
    msgs = [f.message for f in result.findings]
    assert any("result dropped" in m for m in msgs)
    assert any("never awaited" in m for m in msgs)

    good, fired_good = rules_fired(CONCURRENCY / "rc004_good.py",
                                   select={"RC"})
    assert fired_good == set()


def test_rc005_loop_affinity_from_threads():
    result, fired = rules_fired(CONCURRENCY / "rc005_bad.py",
                                select={"RC"})
    assert fired == {"RC005"}
    msgs = [f.message for f in result.findings]
    assert any("put_nowait" in m for m in msgs)
    assert any("get_event_loop" in m for m in msgs)

    good, fired_good = rules_fired(CONCURRENCY / "rc005_good.py",
                                   select={"RC"})
    # call_soon_threadsafe and queue.Queue are the sanctioned boundaries
    assert fired_good == set()


def test_rc_family_prefix_select():
    """--select RC expands to the whole family."""
    result = run_lint([str(CONCURRENCY / "rc004_bad.py")], select={"RC"})
    assert {f.rule for f in result.findings} == {"RC004"}
    # and an exact id still narrows
    result = run_lint([str(CONCURRENCY / "rc004_bad.py")],
                      select={"RC001"})
    assert result.findings == []


def test_rc_package_tree_is_swept_to_zero():
    """ISSUE 17 acceptance: the shipped package linted with the full RC
    family produces zero findings (real fixes + justified suppressions)."""
    result = run_lint([str(PACKAGE)], select={"RC"})
    assert result.findings == [], "\n" + result.to_text()


# --- baseline mode --------------------------------------------------------

def test_baseline_records_then_masks_then_catches_new(tmp_path):
    f = tmp_path / "svc.py"
    f.write_text(
        "import asyncio\n"
        "async def a():\n"
        "    import time\n"
        "    time.sleep(1)\n")
    baseline = tmp_path / "lint-baseline.json"

    def cli(*argv):
        return subprocess.run(
            [sys.executable, "-m", "upow_tpu.lint", *argv],
            capture_output=True, text=True, cwd=str(PACKAGE.parent))

    # record: exit 0, fingerprints written
    rec = cli(str(f), "--select", "RC", "--write-baseline", str(baseline))
    assert rec.returncode == 0, rec.stdout + rec.stderr
    payload = json.loads(baseline.read_text())
    assert payload["version"] == 1 and payload["fingerprints"]

    # same tree against the baseline: old finding masked, exit 0
    masked = cli(str(f), "--select", "RC", "--baseline", str(baseline))
    assert masked.returncode == 0, masked.stdout + masked.stderr
    assert "1 baselined" in masked.stdout

    # introduce a NEW finding: only it gates
    f.write_text(f.read_text() +
                 "async def b():\n"
                 "    open('/etc/hosts').read()\n")
    fresh = cli(str(f), "--select", "RC", "--baseline", str(baseline))
    assert fresh.returncode == 1
    assert "open()" in fresh.stdout
    assert "time.sleep" not in fresh.stdout.replace("1 baselined", "")


def test_baseline_fingerprints_survive_line_moves(tmp_path):
    """Fingerprints hash (path, rule, line text) — inserting lines above
    a baselined finding must not resurrect it."""
    f = tmp_path / "svc.py"
    f.write_text("import time\nasync def a():\n    time.sleep(1)\n")
    baseline = tmp_path / "b.json"
    run_result = subprocess.run(
        [sys.executable, "-m", "upow_tpu.lint", str(f), "--select", "RC",
         "--write-baseline", str(baseline)],
        capture_output=True, text=True, cwd=str(PACKAGE.parent))
    assert run_result.returncode == 0
    f.write_text("import time\n\n\n# moved\nasync def a():\n"
                 "    time.sleep(1)\n")
    moved = subprocess.run(
        [sys.executable, "-m", "upow_tpu.lint", str(f), "--select", "RC",
         "--baseline", str(baseline)],
        capture_output=True, text=True, cwd=str(PACKAGE.parent))
    assert moved.returncode == 0, moved.stdout


# --- engine contract -----------------------------------------------------

def test_suppress_all_keyword(tmp_path):
    core = tmp_path / "core"
    core.mkdir()
    f = core / "x.py"
    f.write_text("x = 1.5  # upowlint: disable=all\n")
    result = run_lint([str(f)])
    assert result.findings == []
    assert len(result.suppressed) == 1


def test_syntax_error_reported(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def f(:\n")
    result = run_lint([str(f)])
    assert [x.rule for x in result.findings] == ["LINT000"]
    assert result.exit_code == 1


def test_package_tree_is_clean():
    """The shipped tree must lint clean — this is the CI gate in test form."""
    result = run_lint([str(PACKAGE)])
    assert result.errors == [], "\n" + result.to_text()


def test_cli_json_and_exit_codes():
    proc = subprocess.run(
        [sys.executable, "-m", "upow_tpu.lint",
         str(FIXTURES / "node" / "bad_except.py"), "--format", "json"],
        capture_output=True, text=True, cwd=str(PACKAGE.parent))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["counts"]["error"] == 2
    assert payload["counts"]["suppressed"] == 1
    assert all(f["rule"] == "BE001" for f in payload["findings"])

    clean = subprocess.run(
        [sys.executable, "-m", "upow_tpu.lint", str(PACKAGE)],
        capture_output=True, text=True, cwd=str(PACKAGE.parent))
    assert clean.returncode == 0, clean.stdout + clean.stderr


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "upow_tpu.lint", "--list-rules"],
        capture_output=True, text=True, cwd=str(PACKAGE.parent))
    assert proc.returncode == 0
    for rule_id in ("CE001", "CP001", "JP001", "DT001", "AS001", "BE001",
                    "DR001", "DR002", "DR003", "RC001", "RC002", "RC003",
                    "RC004", "RC005"):
        assert rule_id in proc.stdout


def test_lint_package_imports_without_jax():
    """The lint CLI must work in jax-free environments (CI lint job)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.modules['jax'] = None; "
         "import upow_tpu.lint; "
         "assert 'jax' not in {m.split('.')[0] for m, v in "
         "sys.modules.items() if v is not None}"],
        capture_output=True, text=True, cwd=str(PACKAGE.parent))
    assert proc.returncode == 0, proc.stderr


# --- regression tests for the fixes the first lint sweep produced --------

def test_byte_length_pure_int():
    from upow_tpu.core.codecs import byte_length

    for i in (0, 1, 255, 256, 2 ** 64 - 1, 2 ** 64, 2 ** 521):
        expected = (i.bit_length() + 7) // 8
        assert byte_length(i) == expected


def test_rewards_half_exact():
    from upow_tpu.core.rewards import get_inode_rewards

    reward = Decimal("64.5")
    details = [{"wallet": "a", "emission": 50},
               {"wallet": "b", "emission": 50}]
    miner, dist = get_inode_rewards(reward, details, block_no=1)
    # Decimal("0.5") path must be bit-identical to the old Decimal(0.5)
    assert miner == reward * Decimal(0.5)
    assert sum(dist.values()) + miner <= reward


def test_difficulty_x10_decimal_matches_float():
    """The exact-Decimal difficulty encoding agrees with the reference's
    int(float(d) * 10) for every representable wire value and every input
    type the node feeds it."""
    from upow_tpu.core.constants import ENDIAN
    from upow_tpu.core.header import block_to_bytes

    prev = "0" * 64
    for x10 in list(range(0, 700)) + [6553, 65535]:
        d = Decimal(x10) / 10
        for form in (float(d), str(d), d):
            raw = block_to_bytes(prev, {
                "address": "1" * 33 * 2,
                "merkle_tree": "2" * 64,
                "timestamp": 1700000000,
                "difficulty": form,
                "random": 7,
            })
            # wire layout: ... | difficulty*10 (2 bytes) | nonce (4 bytes)
            wire = int.from_bytes(raw[-6:-4], ENDIAN)
            assert wire == x10 == int(float(form) * 10), (x10, form)
