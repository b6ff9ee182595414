#!/usr/bin/env bash
# CI entry point: static analysis first, then the tier-1 suite.
#
# The txlint gate costs ~2 s and catches the whole class of invariant
# breaks (hot-loop syncs, recompile hazards, lock discipline, stale
# suppressions) that would otherwise burn a full pytest run — or worse,
# pass it — before a human notices. Its exit codes: 1 = unsuppressed
# violations, 2 = files that failed to parse.
#
# The pytest invocation is the driver's tier-1 command (six xdist
# workers, one file per worker at a time); the DOTS_PASSED line is what
# the driver reads. The tests force the CPU themselves
# (tests/conftest.py). ALLOW_MULTIPLE_LIBTPU_LOAD is deliberately NOT
# set here: tests/test_chip_compile.py describes the chip inside one
# module-scoped fixture, so only the worker that runs that file loads
# the TPU library.
set -u -o pipefail
cd "$(dirname "$0")/.."

echo "== txlint --check =="
python tools/lint.py --check || exit $?

echo "== tier-1 pytest =="
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile \
  --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
exit $rc
