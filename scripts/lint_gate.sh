#!/usr/bin/env bash
# CI lint gate: the whole framework, the examples, the chip smoke and
# the scripts must satisfy the contracts the linter enforces (doc/lint.md).
# --format=github makes each finding an inline PR annotation on GitHub
# Actions; locally the same command prints ::error lines and exits 1.
#
# The PR-17 incremental cache is ALWAYS on (--cache): warm runs re-analyze
# only files that changed since the last run plus their transitive reverse
# importers — with findings identical to a cold run (the cache is
# advisory, it can only be slow, not wrong; tests/test_lint_callgraph.py
# TestLintCache holds that). Where git metadata exists the gate also passes --changed, so a
# warm run at an unchanged HEAD skips even the per-file content re-hash.
#
# Usage: scripts/lint_gate.sh [--cold] [extra lint args, e.g. --jobs 4]
#   --cold   drop the cache first and run without it (use when bisecting a
#            suspected cache bug; findings are identical either way)
set -euo pipefail
cd "$(dirname "$0")/.."
args=()
cold=0
for a in "$@"; do
  if [ "$a" = "--cold" ]; then
    cold=1
  else
    args+=("$a")
  fi
done
if [ "$cold" = 1 ]; then
  rm -f .dmllint_cache.json
else
  args+=("--cache")
  if git rev-parse --git-dir >/dev/null 2>&1; then
    args+=("--changed")
  fi
fi
exec python -m dmlcloud_tpu lint dmlcloud_tpu examples chip_smoke.py scripts --format=github "${args[@]+"${args[@]}"}"
