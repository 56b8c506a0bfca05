"""salint: static analyzer for the repo's residency/kernel invariants.

Run as ``python -m tools.salint src tests tools``.  See
``docs/static_analysis.md`` for the rule catalog.
"""
from tools.salint.engine import (
    FileContext,
    Rule,
    Suppressions,
    Violation,
    check_file,
    iter_py_files,
    run,
    violation_at,
)
from tools.salint.rules import DEFAULT_RULES

__all__ = [
    "FileContext",
    "Rule",
    "Suppressions",
    "Violation",
    "check_file",
    "iter_py_files",
    "run",
    "violation_at",
    "DEFAULT_RULES",
]
