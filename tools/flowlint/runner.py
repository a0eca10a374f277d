"""flowlint runner: rule orchestration + reporting.

Scope: the whole ``flow_pipeline_tpu`` package plus ``tests/`` (flag
tokens in tests must be real flags too); the
abi-contract rule additionally reads ``native/*.cc``. Exit status: 0 =
clean, 1 = findings, so ``make lint`` and CI gate on it directly.
``--json`` emits one machine-readable document (file/line/rule/message
per finding) — the CI lint job turns that into per-line annotations.
"""

from __future__ import annotations

import sys
from concurrent.futures import ThreadPoolExecutor

from . import (
    rules_abi,
    rules_dtype,
    rules_durability,
    rules_family,
    rules_flags,
    rules_lockorder,
    rules_locks,
    rules_net,
    rules_purity,
)
from .core import (
    Finding,
    LintResult,
    discover,
    load_files,
    suppression_findings,
)

DEFAULT_SUBDIRS = ("flow_pipeline_tpu", "tests")
# (rule name, check entrypoint) in the canonical order. Checks are pure
# reads over the parsed SourceFiles, so run_lint fans them out on a
# thread pool; THIS tuple's order is what keeps output deterministic.
_RULE_CHECKS = (
    ("jit-purity", lambda files, root: rules_purity.check(files)),
    ("uint64-discipline", lambda files, root: rules_dtype.check(files)),
    ("lock-discipline", lambda files, root: rules_locks.check(files)),
    ("lock-order", lambda files, root: rules_lockorder.check(files)),
    ("flag-registry", rules_flags.check),
    ("abi-contract", rules_abi.check),
    ("net-timeout", lambda files, root: rules_net.check(files)),
    ("family-citizenship", rules_family.check),
    ("durability-protocol", lambda files, root: rules_durability.check(files)),
)
ALL_RULES = tuple(name for name, _ in _RULE_CHECKS)


def run_lint(root: str, rel_paths: list[str] | None = None,
             rules: tuple[str, ...] | None = None) -> list[Finding]:
    """Lint the repo at ``root``; returns surviving (unsuppressed)
    findings. ``rel_paths``/``rules`` narrow the run (tests use this)."""
    rels = rel_paths if rel_paths is not None else \
        discover(root, DEFAULT_SUBDIRS)
    files = load_files(root, rels)
    # `# flowlint: skip-file` opts a whole file out — for files whose
    # PURPOSE is to contain bad code (the lint fixture tests themselves)
    files = [sf for sf in files if "skip-file" not in sf.markers]
    by_rel = {sf.rel: sf for sf in files}

    result = LintResult()
    for sf in files:
        if sf.parse_error:
            result.findings.append(
                Finding("parse", sf.rel, 1, sf.parse_error))

    selected = rules or ALL_RULES
    active = [(name, fn) for name, fn in _RULE_CHECKS
              if name in selected]
    # the rule checks only READ the parsed files, so they fan out on a
    # pool; folding back through extend_filtered stays on this thread
    # and in _RULE_CHECKS order — it marks Suppression.used (shared
    # mutable state) and the fixed order keeps runs byte-identical
    with ThreadPoolExecutor(max_workers=max(1, len(active))) as pool:
        futures = [pool.submit(fn, files, root) for _name, fn in active]
        for fut in futures:
            result.extend_filtered(by_rel, fut.result())
    # suppressions themselves must be justified + must still bite;
    # unused-reporting is only sound when every rule actually ran
    result.findings.extend(suppression_findings(
        files, known_rules=ALL_RULES,
        report_unused=set(selected) == set(ALL_RULES)))
    return sorted(result.findings, key=lambda f: (f.path, f.line, f.rule))


def main(argv: list[str]) -> int:
    import argparse
    import json
    import os

    p = argparse.ArgumentParser(
        prog="flowlint",
        description="project static analysis: jit-purity, uint64 "
                    "dtype-flow, lock annotations, lock ordering, flag "
                    "registry, ctypes<->C ABI contract, sketch-family "
                    "citizenship, durable-write protocol")
    p.add_argument("paths", nargs="*",
                   help="repo-relative files/dirs (default: full scope)")
    p.add_argument("--root", default=os.getcwd(),
                   help="repo root (default: cwd)")
    p.add_argument("--rule", action="append",
                   help="run only this rule (repeatable)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output: one JSON document with "
                        "file/line/rule/message per finding")
    args = p.parse_args(argv)

    rels = None
    if args.paths:
        rels = discover(args.root, tuple(args.paths))
    selected = tuple(args.rule) if args.rule else None
    findings = run_lint(args.root, rels, selected)
    if args.json:
        print(json.dumps({
            "findings": [
                {"file": f.path, "line": f.line, "rule": f.rule,
                 "message": f.message}
                for f in findings
            ],
            "count": len(findings),
            "rules": list(selected or ALL_RULES),
        }, indent=2))
    else:
        for f in findings:
            print(f.render())
    if findings:
        print(f"flowlint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("flowlint: clean", file=sys.stderr)
    return 0
