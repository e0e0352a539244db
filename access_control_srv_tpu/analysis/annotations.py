"""Comment-annotation extraction for acs-lint.

The annotation language is deliberately tiny and lives in ordinary
comments so annotated modules carry zero import-time cost:

``# guarded-by: _lock``
    On an attribute-initialising assignment (``self._data = {}`` in
    ``__init__``, a class-level declaration, or a module-level global):
    every later read/write of that attribute must happen inside a
    lexical ``with <base>.<lock>`` block over the SAME base expression,
    or inside a ``# holds:``-annotated helper.

``# holds: _lock``
    On a ``def`` line (or the line directly above it): the method is
    only ever called with the named lock(s) already held — its guarded
    accesses are exempt, and blocking calls inside it are treated as
    under-lock.

``# acs-lint: ignore[rule1, rule2] <one-line reason>``
    On the offending line (or any physical line of a multi-line
    statement): suppresses those rules for that statement.  Counted by
    the runner, never silent.

``# acs-lint: host-only``
    Anywhere in a module: declares the module host-only — any ``jax``
    import (even lazy, inside a function) becomes a finding.  The
    declaration living in the module itself is what makes a module's
    host-only claim a machine-checked rule
    (tests/test_analysis_gate.py::test_host_only_modules_declare_the_marker).
"""

from __future__ import annotations

import io
import re
import tokenize

_GUARDED_BY = re.compile(r"guarded-by:\s*([A-Za-z_]\w*)")
_HOLDS = re.compile(r"holds:\s*([A-Za-z_][\w,\s]*)")
_IGNORE = re.compile(r"acs-lint:\s*ignore\[([\w\-,\s]+)\]\s*(.*)")
_HOST_ONLY = re.compile(r"acs-lint:\s*host-only\b")


class ModuleComments:
    """Per-line comment index for one module, with annotation parsers.

    Built from ``tokenize`` (not the AST) because comments are invisible
    to ``ast.parse`` — this is the only place the analyzer looks at raw
    source text.
    """

    def __init__(self, source: str):
        self.by_line: dict[int, str] = {}
        # lines that are comment-ONLY: an ignore there also covers the
        # next statement (the eslint-disable-next-line convention),
        # while a trailing comment never leaks onto the line below
        self.standalone: set[int] = {
            lineno
            for lineno, text in enumerate(source.splitlines(), start=1)
            if text.lstrip().startswith("#")
        }
        try:
            tokens = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in tokens:
                if tok.type == tokenize.COMMENT:
                    self.by_line[tok.start[0]] = tok.string.lstrip("#").strip()
        except tokenize.TokenError:
            # a truncated final line still yields every earlier comment;
            # the AST parse will surface real syntax errors
            pass
        self.host_only = any(
            _HOST_ONLY.search(text) for text in self.by_line.values()
        )

    # ---------------------------------------------------------- annotations

    def guarded_by(self, line: int) -> str | None:
        """Lock name from a ``guarded-by:`` comment on this line."""
        match = _GUARDED_BY.search(self.by_line.get(line, ""))
        return match.group(1) if match else None

    def holds(self, line: int) -> set[str]:
        """Lock names from a ``holds:`` comment on this line or the line
        directly above (for defs whose signature fills the line)."""
        for candidate in (line, line - 1):
            match = _HOLDS.search(self.by_line.get(candidate, ""))
            if match:
                return {
                    name.strip()
                    for name in match.group(1).split(",")
                    if name.strip()
                }
        return set()

    def ignored_rules(self, first_line: int,
                      last_line: int | None = None) -> dict[str, str]:
        """``{rule: reason}`` for every ``acs-lint: ignore[...]`` comment
        on any physical line of the statement span."""
        out: dict[str, str] = {}
        lines = list(range(first_line, (last_line or first_line) + 1))
        # a standalone comment BLOCK directly above the statement also
        # covers it, so a suppression's reason can run to several lines
        above = first_line - 1
        while above in self.standalone:
            lines.append(above)
            above -= 1
        for line in lines:
            match = _IGNORE.search(self.by_line.get(line, ""))
            if match:
                reason = match.group(2).strip()
                for rule in match.group(1).split(","):
                    rule = rule.strip()
                    if rule:
                        out[rule] = reason
        return out
