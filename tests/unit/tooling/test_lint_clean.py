"""Tier-1 regression gate: ds_lint must stay clean on deepspeed_tpu/
plus the shebang-sniffed entry-point scripts in bin/.

A new violation fails this test; fix it, pragma it with a reason, or
(for pre-existing debt only) add a baseline entry. Every rule family —
including the cross-file wire-contract parity pass and the
replay-determinism scan — runs repo-wide here with ZERO baseline
entries, and per-rule wall times are reported so a rule that regresses
the gate's latency is visible in the failure output.
"""

import os
import time

from tools.graft_lint.cli import (DEFAULT_BASELINE, REPO_ROOT,
                                  check_knob_docs)
from tools.graft_lint.linter import (KNOB_DOCS, RULES, lint_paths,
                                     load_baseline)

PKG = os.path.join(REPO_ROOT, "deepspeed_tpu")
# the default scope bin/ds_lint lints (the package plus bin/), plus the
# chip entry point, which drives both engines
SCOPE = [PKG, os.path.join(REPO_ROOT, "bin"), os.path.join(REPO_ROOT, "chip_smoke.py")]


def _fmt(violations):
    return "\n" + "\n".join(
        f"{v.path}:{v.line}: [{v.rule}] {v.symbol}: {v.message}"
        for v in violations)


def test_ds_lint_clean_on_package():
    baseline = (load_baseline(DEFAULT_BASELINE)
                if os.path.exists(DEFAULT_BASELINE) else set())
    violations, _ = lint_paths(SCOPE, baseline=baseline, root=REPO_ROOT)
    assert violations == [], _fmt(violations)


def test_each_rule_clean_standalone_with_timings():
    """Run every rule in isolation (the CLI's --only path) with an
    EMPTY baseline: proves no rule depends on another's suppressions
    and gives a per-rule timing line on failure."""
    timings = []
    for rule in RULES:
        start = time.perf_counter()
        if rule == KNOB_DOCS:
            violations = check_knob_docs()
        else:
            violations, _ = lint_paths(SCOPE, baseline=set(),
                                       root=REPO_ROOT, only={rule})
        timings.append(f"{rule}: {time.perf_counter() - start:.3f}s")
        assert violations == [], (
            f"[{rule}] not clean ({'; '.join(timings)})" + _fmt(violations))


def test_new_rules_combined_cli_clean(capsys):
    """`bin/ds_lint --only=wire-contract,replay-determinism` — the
    round-24 gate invocation — is clean on the default repo-wide scope
    (cross-file parity merged across the whole seam, baseline unused)."""
    from tools.graft_lint.cli import main
    assert main(["--only=wire-contract,replay-determinism",
                 "--no-baseline"]) == 0
    assert "0 violation(s)" in capsys.readouterr().out


def test_knob_docs_in_sync():
    """env_registry.py and the MIGRATING.md knob table must agree in
    both directions (regenerate with `bin/ds_lint --list-knobs`)."""
    violations = check_knob_docs()
    assert violations == [], _fmt(violations)


def test_baseline_is_empty_of_new_debt():
    """The shipped baseline starts empty — intentional keeps use inline
    pragmas (which carry their reason); baseline entries are reserved
    for future pre-existing debt during rule tightening."""
    baseline = load_baseline(DEFAULT_BASELINE)
    assert baseline == set()
