#!/bin/bash
# The build gate, as one command — the analog of the reference's
# error-prone -Werror + findbugs + checkstyle Maven phase (root pom.xml,
# build-common/): static checks first, then the full suite on the virtual
# 8-device CPU mesh, then the driver gates. CI or a pre-push hook runs this.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== static checks (AST lint + resolution tier + compiled-program gate) =="
# test_hlo_gate.py first: it compiles the registered engine entrypoints
# ONCE per session — including the 2-D ('cohort','nodes') mesh wave
# (sharded2d_wave; the 2-D step is deliberately unregistered, see
# device_program._build_registry), the multi-tenant fleet pair on the
# 3-D ('tenant','cohort','nodes') mesh (fleet3d_step/fleet3d_wave, the
# zero-cross-tenant-collective budget), and the compact-state step
# (step_compact — the dtype-narrowing saving, read off the compiled
# artifact; one representative per the PR-9 compile-cost convention) —
# so test_dataflow.py's compile-free trace of the same registry and the
# lint/staticcheck tree sweeps in the same session reuse the facts instead
# of recompiling. Nothing here is compared with a committed number: the
# cases assert on the live programs, and a PR shows that it changed no
# program with `python tools/program_digests.py digests` on both checkouts
# and `diff PARENT.json OUT.json`.
python -m pytest tests/test_hlo_gate.py tests/test_dataflow.py tests/test_lint.py tests/test_staticcheck.py -q -p no:randomly

echo "== full suite (CPU, 8 virtual devices) =="
# The static gates just ran above; the resolution tier re-imports and
# re-analyzes the whole tree, so don't pay it twice in one invocation.
python -m pytest tests/ -q \
  --ignore=tests/test_lint.py --ignore=tests/test_staticcheck.py \
  --ignore=tests/test_hlo_gate.py --ignore=tests/test_dataflow.py

echo "== driver gates =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python -c "import __graft_entry__ as g; fn, a = g.entry(); fn(*a); g.dryrun_multichip(8)"

echo "ALL CHECKS PASSED"
