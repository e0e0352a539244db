"""Tier-1 CI gate: the full acs-lint run over the shipped package must
be clean against the checked-in baseline — no new findings, no stale or
unjustified baseline entries, no parse errors — and fast enough to run
on every commit.

This is the test expression of ``python -m access_control_srv_tpu.
analysis`` exiting 0, plus the audit-surface claims the baseline makes
(every entry justified) and the invariants the host-only markers carry.
"""

from __future__ import annotations

import time

from access_control_srv_tpu.analysis import (
    DEFAULT_BASELINE,
    PACKAGE_ROOT,
    load_baseline,
    run_analysis,
)


def test_package_tree_clean_under_budget():
    t0 = time.monotonic()
    report = run_analysis(PACKAGE_ROOT, baseline=DEFAULT_BASELINE)
    elapsed = time.monotonic() - t0
    diff = report.diff
    assert not report.errors, report.errors
    assert diff is not None
    detail = {
        "new": [f.key for f in diff.new],
        "stale": [e.key for e in diff.stale],
        "unjustified": [e.key for e in diff.unjustified],
    }
    assert report.ok, detail
    # the gate must stay cheap enough for every-commit CI: well under
    # the 10 s budget on any development machine
    assert elapsed < 10.0, f"acs-lint took {elapsed:.1f}s"
    # sanity: the analyzer actually walked the package, not an empty dir
    assert report.modules > 40


def test_baseline_entries_all_justified():
    entries = load_baseline(DEFAULT_BASELINE)
    assert entries, "shipped baseline should carry the accepted findings"
    for entry in entries:
        assert entry.justification.strip(), (
            f"baseline entry {entry.key} has no justification — every "
            "accepted finding needs a recorded reason"
        )


def test_host_only_modules_declare_the_marker():
    """The modules the docs call host-only must carry the self-declaring
    marker, so that acs-lint's host-only rule holds them to it: the claim
    is machine-checked, not prose."""
    for name in ("srv/tracing.py", "srv/admission.py",
                 "srv/decision_cache.py", "srv/router.py", "srv/faults.py",
                 "ops/lattice.py", "srv/audit_sweep.py"):
        source = (PACKAGE_ROOT / name).read_text()
        assert "acs-lint: host-only" in source, (
            f"{name} lost its host-only declaration"
        )
