"""CLI: ``python -m tools.salint [paths ...] [--explain SALxxx]``.

Exit codes (stable — CI depends on them): 0 clean, 1 violations found,
2 usage error.  ``--format json|sarif`` changes the report shape only;
``--cache DIR`` memoizes the per-file pass on content hash + rule-set
version (the project/repo passes always run).
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from tools.salint.engine import run
from tools.salint.rules import DEFAULT_RULES

DEFAULT_PATHS = ["src", "tests", "tools"]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.salint",
        description="Static analyzer for the repo's residency/kernel/"
                    "threading/durability invariants (rules SAL001-SAL012).",
    )
    parser.add_argument(
        "paths", nargs="*", default=None,
        help=f"files or directories to scan (default: {DEFAULT_PATHS})")
    parser.add_argument(
        "--explain", metavar="SALxxx",
        help="print the rationale for one rule and exit")
    parser.add_argument(
        "--list-rules", action="store_true",
        help="list rule IDs and summaries and exit")
    parser.add_argument(
        "--format", choices=("text", "json", "sarif"), default="text",
        help="report format (default: text)")
    parser.add_argument(
        "--output", metavar="FILE",
        help="write the report to FILE instead of stdout")
    parser.add_argument(
        "--cache", metavar="DIR",
        help="cache per-file results in DIR (keyed on content hash + "
             "rule-set version)")
    args = parser.parse_args(argv)

    if args.explain:
        rid = args.explain.strip().upper()
        for rule in DEFAULT_RULES:
            if rule.rule_id == rid:
                print(f"{rule.rule_id}: {rule.summary}")
                print()
                print(rule.rationale)
                return 0
        print(f"unknown rule id: {rid}", file=sys.stderr)
        return 2

    if args.list_rules:
        for rule in DEFAULT_RULES:
            print(f"{rule.rule_id}  {rule.summary}")
        return 0

    cache = None
    if args.cache:
        from tools.salint.cache import ResultCache

        cache = ResultCache(args.cache, DEFAULT_RULES)

    paths = args.paths or DEFAULT_PATHS
    violations = run(paths, DEFAULT_RULES, cache=cache)
    if cache is not None:
        cache.save()

    from tools.salint import output as out_mod

    if args.format == "json":
        report = out_mod.render_json(violations)
    elif args.format == "sarif":
        report = out_mod.render_sarif(violations, DEFAULT_RULES)
    else:
        report = out_mod.render_text(violations)
    if args.output:
        with open(args.output, "w", encoding="utf-8") as f:
            f.write(report + "\n")
    elif report:
        print(report)

    if violations:
        print(f"\n{len(violations)} violation(s). "
              f"'python -m tools.salint --explain <ID>' for rationale.",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
