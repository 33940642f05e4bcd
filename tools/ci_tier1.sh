#!/usr/bin/env bash
# Tier-1 verify: the command the driver runs after every PR, checked in
# verbatim from `/root/TESTS_LAST_RUN.json` (`commands`) so a builder's run
# is the driver's: CPU backend, non-slow tests, six xdist workers with a
# file's tests on one worker (`--dist loadfile`; only the worker given
# `tests/test_chip_compile.py` describes a TPU), 1,470 s, collection errors
# surfaced, and the count of passes read from the junit file (the progress
# dots where there is none) with the workers lost. Exit code is pytest's.
# The driver's run of d251cd7 took 933 s; a builder's sandbox 1,254 s at PR
# 57 and 982 s on PR 58's tree (1,506 passed): `ROADMAP.md` D18 has what is
# left of the limit.
#
# Usage: tools/ci_tier1.sh   (from the repo root)
set -o pipefail
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)
exit $rc
