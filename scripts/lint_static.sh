#!/usr/bin/env bash
# Static-analysis gate: source lint + trace lint + chaos harnesses.
#
#   scripts/lint_static.sh          # full: ruff + trace-doctor battery
#   scripts/lint_static.sh --fast   # pre-push smoke: ruff + one cell
#
# Source lint runs ruff when available (version pinned via the [lint]
# extra: pip install -e '.[lint]'; rules scoped in [tool.ruff.lint] to
# real error classes — undefined names, unused imports, f-string bugs).
# Without ruff it degrades to scripts/_ast_lint.py (stdlib-only: syntax
# + unused imports) rather than skipping silently.
#
# Trace lint (scripts/lint_traces.py) runs the jaxpr/HLO/recompile
# battery over the canonical configs on the 8-virtual-device CPU mesh.
set -u
cd "$(dirname "$0")/.."

fast=""
[ "${1:-}" = "--fast" ] && fast="--fast"

rc=0

echo "== source lint =="
if command -v ruff >/dev/null 2>&1; then
    want=$(sed -n 's/.*"ruff==\([0-9.]*\)".*/\1/p' pyproject.toml)
    have=$(ruff --version | awk '{print $2}')
    if [ -n "$want" ] && [ "$have" != "$want" ]; then
        echo "warning: ruff $have != pinned $want (results may drift)" >&2
    fi
    ruff check . || rc=1
else
    echo "ruff not installed; falling back to scripts/_ast_lint.py" >&2
    python scripts/_ast_lint.py || rc=1
fi

echo "== trace lint =="
python scripts/lint_traces.py $fast || rc=1

echo "== chaos elastic (topology-portable resume) =="
python scripts/chaos_train.py --elastic $fast || rc=1

echo "== chaos ingest (out-of-core crash safety) =="
python scripts/chaos_train.py --ingest $fast || rc=1

if [ "$rc" -ne 0 ]; then
    echo "LINT FAILED" >&2
else
    echo "lint OK"
fi
exit $rc
