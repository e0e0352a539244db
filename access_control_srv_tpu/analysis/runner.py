"""acs-lint runner: walk a tree, run every pass, gate on the baseline.

``run_analysis`` is the library entry (used by
tests/test_analysis_gate.py); ``__main__`` wraps it as
``python -m access_control_srv_tpu.analysis``.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

from . import baseline as baseline_mod
from .baseline import BaselineDiff
from .checks import check_module
from .findings import Finding, Suppression

# the shipped scan root: the package itself
PACKAGE_ROOT = Path(__file__).resolve().parents[1]
REPO_ROOT = PACKAGE_ROOT.parent
DEFAULT_BASELINE = Path(__file__).resolve().parent / "baseline.json"

# generated modules are not ours to lint
_SKIP_SUFFIXES = ("_pb2.py", "_pb2_grpc.py")


@dataclass
class Report:
    findings: list[Finding] = field(default_factory=list)
    suppressions: list[Suppression] = field(default_factory=list)
    errors: list[str] = field(default_factory=list)  # unparsable modules
    modules: int = 0
    diff: BaselineDiff | None = None

    @property
    def ok(self) -> bool:
        if self.errors:
            return False
        if self.diff is not None:
            return self.diff.clean
        return not self.findings

    def by_rule(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for finding in self.findings:
            out[finding.rule] = out.get(finding.rule, 0) + 1
        return out

    def to_json(self) -> dict:
        out = {
            "modules": self.modules,
            "findings": [
                {"path": f.path, "rule": f.rule, "symbol": f.symbol,
                 "line": f.line, "message": f.message}
                for f in self.findings
            ],
            "suppressions": [
                {"path": s.path, "rule": s.rule, "symbol": s.symbol,
                 "line": s.line, "reason": s.reason}
                for s in self.suppressions
            ],
            "errors": list(self.errors),
            "by_rule": self.by_rule(),
            "ok": self.ok,
        }
        if self.diff is not None:
            out["baseline"] = {
                "matched": self.diff.matched,
                "new": [list(f.key) for f in self.diff.new],
                "stale": [list(e.key) for e in self.diff.stale],
                "unjustified": [list(e.key)
                                for e in self.diff.unjustified],
            }
        return out


def iter_modules(root: Path):
    for path in sorted(root.rglob("*.py")):
        if any(path.name.endswith(sfx) for sfx in _SKIP_SUFFIXES):
            continue
        yield path


def run_analysis(root: str | Path = PACKAGE_ROOT,
                 baseline: str | Path | None = None,
                 rel_to: str | Path | None = None) -> Report:
    """Analyze every module under ``root``.  With ``baseline``, the
    report's ``ok`` reflects the baseline gate (new finding OR stale
    entry OR missing justification fails); without, any finding fails.

    ``rel_to`` controls the path prefix in finding identity (defaults
    to the repo root for the shipped tree, ``root`` otherwise so fixture
    trees produce stable keys wherever they're checked out)."""
    root = Path(root).resolve()
    if rel_to is None:
        rel_to = REPO_ROOT if root.is_relative_to(REPO_ROOT) else root
    rel_to = Path(rel_to).resolve()
    report = Report()
    for path in iter_modules(root):
        rel = path.relative_to(rel_to).as_posix()
        try:
            source = path.read_text()
            findings, suppressions = check_module(rel, source)
        except (SyntaxError, UnicodeDecodeError) as err:
            report.errors.append(f"{rel}: {err}")
            continue
        report.modules += 1
        report.findings.extend(findings)
        report.suppressions.extend(suppressions)
    report.findings.sort(key=lambda f: f.key)
    if baseline is not None:
        entries = baseline_mod.load(baseline)
        report.diff = baseline_mod.diff(report.findings, entries)
    return report


def render_report(report: Report, verbose: bool = False) -> str:
    lines: list[str] = []
    diff = report.diff
    shown = report.findings if diff is None else diff.new
    for finding in shown:
        lines.append(finding.render())
    if diff is not None:
        for entry in diff.stale:
            lines.append(
                f"{entry.path}: [stale-baseline] {entry.rule} "
                f"{entry.symbol} — baselined finding no longer exists; "
                "remove the entry (a stale suppression can swallow a "
                "future regression)"
            )
        for entry in diff.unjustified:
            lines.append(
                f"{entry.path}: [unjustified-baseline] {entry.rule} "
                f"{entry.symbol} — baseline entries require a one-line "
                "justification"
            )
    for error in report.errors:
        lines.append(f"[parse-error] {error}")
    if verbose:
        for sup in report.suppressions:
            lines.append(
                f"{sup.path}:{sup.line}: [suppressed:{sup.rule}] "
                f"{sup.symbol} — {sup.reason or '(no reason given)'}"
            )
    counted = len(report.suppressions)
    baselined = diff.matched if diff is not None else 0
    status = "clean" if report.ok else "FAILED"
    lines.append(
        f"acs-lint: {status} — {report.modules} modules, "
        f"{len(report.findings)} findings "
        f"({baselined} baselined), {counted} inline suppressions"
    )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m access_control_srv_tpu.analysis",
        description="acs-lint: concurrency and hot-path invariant "
                    "analysis (docs/ANALYSIS.md)",
    )
    parser.add_argument("--root", default=str(PACKAGE_ROOT),
                        help="tree to analyze (default: the package)")
    parser.add_argument("--baseline", default=str(DEFAULT_BASELINE),
                        help="baseline JSON (default: the checked-in "
                             "analysis/baseline.json)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline: report every finding")
    parser.add_argument("--write-baseline", action="store_true",
                        help="rewrite the baseline from current findings "
                             "(carries over existing justifications)")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable report on stdout")
    parser.add_argument("--verbose", action="store_true",
                        help="also list counted inline suppressions")
    args = parser.parse_args(argv)

    baseline_path = None if args.no_baseline else args.baseline
    report = run_analysis(args.root, baseline=baseline_path)

    if args.write_baseline:
        carried = {
            e.key: e.justification
            for e in baseline_mod.load(args.baseline)
        }
        baseline_mod.save(args.baseline, report.findings, carried)
        print(f"wrote {args.baseline} "
              f"({len(report.findings)} suppressions)")
        return 0

    if args.json:
        print(json.dumps(report.to_json(), indent=1))
    else:
        print(render_report(report, verbose=args.verbose))
    return 0 if report.ok else 1
