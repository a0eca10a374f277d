"""make lint-mutation: prove the high-stakes flowlint rules bite.

A lint that cannot fail is indistinguishable from no lint, so this
smoke seeds one mutation per guarded property — each syntactically
valid, visibly wrong — into a scratch copy of the tree and asserts the
owning rule fails the mutant while naming the defect:

- **family**: the spread family's ``merge=`` registration line is
  deleted — family-citizenship must name the missing surface;
- **durability**: the ``fsync_file(f)`` barrier inside
  ``fsutil.staged_durable`` is deleted (the way a bad refactor
  would) — durability-protocol must flag the now-torn publish. This is
  the static prong of the durability mutation gate; the dynamic prong
  (``make crash-parity``) proves the same deletion produces a
  crash-state invariant violation via ``fsutil.suppressed``;
- **lock-order**: the bus's reentrant lock is downgraded to a plain
  ``Lock`` — lock-order must report the resulting self-deadlock cycle.

Exit status: 0 = every mutant was caught, 1 = some rule is blind (or a
mutation no longer applies and needs re-seeding against the current
source).
"""

from __future__ import annotations

import os
import re
import shutil
import subprocess
import sys
import tempfile

# (name, repo-relative file, seeded mutation, replacement, rule,
#  substring the mutant run's findings must contain)
MUTATIONS = (
    ("family",
     os.path.join("flow_pipeline_tpu", "families", "registry.py"),
     re.compile(
         r'^\s*merge="flow_pipeline_tpu\.mesh\.merge:merge_spread",\n',
         re.MULTILINE),
     "",
     "family-citizenship",
     "family `spread` is missing surface `merge`"),
    ("durability",
     os.path.join("flow_pipeline_tpu", "utils", "fsutil.py"),
     re.compile(r"^        fsync_file\(f\)\n", re.MULTILINE),
     "        pass  # mutated\n",
     "durability-protocol",
     "[durability-protocol]"),
    ("lock-order",
     os.path.join("flow_pipeline_tpu", "transport", "bus.py"),
     re.compile(r"threading\.RLock\(\)"),
     "threading.Lock()",
     "lock-order",
     "lock-order cycle (potential deadlock)"),
)

# everything the rules read: the package (registry + dispatch surfaces
# + KNOWN_FLAGS) and the linter itself; root artifacts (docs, Makefile,
# ci.yml, deploy) are deliberately left out — absent artifacts skip
# those checks, keeping the smoke pinned to the seeded mutations
_COPY = ("flow_pipeline_tpu", "tools")
_IGNORE = shutil.ignore_patterns(
    "__pycache__", "*.pyc", "*.so", "*.o", ".pytest_cache")


def _run_one(root: str, name: str, rel: str, mutation: re.Pattern,
             repl: str, rule: str, expected: str) -> bool:
    with tempfile.TemporaryDirectory(prefix="flowlint-mutant-") as tmp:
        for entry in _COPY:
            shutil.copytree(os.path.join(root, entry),
                            os.path.join(tmp, entry), ignore=_IGNORE)
        path = os.path.join(tmp, rel)
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        mutated, n = mutation.subn(repl, src, count=1)
        if n != 1:
            print(f"lint-mutation[{name}]: seeded mutation did not "
                  f"apply to {rel} — re-seed it against the current "
                  f"source", file=sys.stderr)
            return False
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(mutated)
        proc = subprocess.run(
            [sys.executable, "-m", "tools.flowlint",
             "--rule", rule, "flow_pipeline_tpu"],
            cwd=tmp, capture_output=True, text=True)
    if proc.returncode == 0:
        print(f"lint-mutation[{name}]: BLIND — flowlint --rule {rule} "
              f"passed the mutant ({rel})", file=sys.stderr)
        return False
    if expected not in proc.stdout:
        print(f"lint-mutation[{name}]: flowlint failed the mutant but "
              f"did not name the defect; wanted {expected!r}, got:\n"
              f"{proc.stdout}", file=sys.stderr)
        return False
    print(f"lint-mutation[{name}]: ok — the mutant was caught "
          f"({expected!r})")
    return True


def main() -> int:
    root = os.getcwd()
    ok = True
    for name, rel, mutation, repl, rule, expected in MUTATIONS:
        ok = _run_one(root, name, rel, mutation, repl, rule,
                      expected) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
