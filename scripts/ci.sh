#!/usr/bin/env bash
# Tier-1 CI gate: the full test suite must COLLECT cleanly and pass, the
# tree must stay free of committed bytecode, the layered-engine import
# contract must hold (no back-edges, nothing reaches the LM demo modules),
# every public API surface must stay documented (auto-discovered — every
# src/repro + benchmarks module), the paper-figure and control-loop
# benchmark scripts must still execute (smoke mode), and the fleet test
# files must also pass with every test seeing a forced 8-device host
# platform (its own subprocess).  The tier-1 suite itself runs the
# ``multidevice`` tests in a child process per file (tests/conftest.py).
#
# pytest exits 2 on collection errors and 1 on failures; both are failures
# here — a module that stops importing is exactly the regression this gate
# exists to catch (the seed repo shipped with 7 of them).
set -euo pipefail

cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== hygiene check (no committed bytecode) =="
if git ls-files | grep -E '(^|/)__pycache__/|\.py[co]$'; then
  echo "committed __pycache__/*.pyc blobs found (see .gitignore); git rm --cached them"
  exit 1
fi
echo "hygiene OK (no __pycache__/*.pyc tracked)"

echo "== collection check (zero tolerance for import errors) =="
python -m pytest -q --collect-only >/dev/null

echo "== import-layering contract (kernels -> engine -> sessions -> serving) =="
python scripts/check_layering.py

echo "== docs check (README/docs present, public API surfaces documented) =="
for f in README.md docs/architecture.md docs/streaming.md docs/serving.md; do
  [ -f "$f" ] || { echo "missing $f"; exit 1; }
done
python - <<'EOF'
import importlib
import inspect
import pathlib
import pkgutil

# Auto-discovered surface list: EVERY module under src/repro plus every
# benchmark script.  A hand-maintained tuple here rotted silently — new
# modules shipped undocumented because nobody added them to the list.
import repro

surfaces = ["repro"]
surfaces += [m.name for m in pkgutil.walk_packages(repro.__path__, prefix="repro.")]
surfaces += sorted(
    f"benchmarks.{p.stem}"
    for p in pathlib.Path("benchmarks").glob("*.py")
    if p.stem != "__init__"
)
# Collect every undocumented symbol across ALL surfaces before failing, so
# one broken module doesn't hide the rest of the report.
problems = {}
for mod_name in sorted(surfaces):
    mod = importlib.import_module(mod_name)
    missing = []
    for name, obj in vars(mod).items():
        if name.startswith("_") or not callable(obj):
            continue
        if getattr(obj, "__module__", mod.__name__) not in (mod.__name__, None):
            continue  # re-exported from elsewhere (kalman, footprints, ...)
        if not inspect.getdoc(obj):
            missing.append(name)
    if missing:
        problems[mod_name] = missing
if problems:
    for mod_name, missing in problems.items():
        print(f"public symbols without docstrings in {mod_name}: {missing}")
    raise SystemExit(f"docs check failed in {len(problems)} module(s): {sorted(problems)}")
print(f"docs check OK ({len(surfaces)} modules, all public symbols documented)")
EOF

echo "== benchmark smoke (tiny shapes; scripts must run + emit sane JSON) =="
# run.py --smoke already fails on module errors / malformed metrics; this
# second pass validates the artifact actually written to disk: it must be
# STRICT JSON (no NaN/Infinity literals, which Python's json.dump happily
# emits) and cover every registered module.
python -m benchmarks.run --smoke
python - <<'EOF'
import json

from benchmarks.run import MODULES

def _reject(const):
    raise SystemExit(f"bench_results.json is not strict JSON: contains {const}")

with open("experiments/bench_results.json") as f:
    results = json.load(f, parse_constant=_reject)
missing = [name for name, _ in MODULES if name not in results]
if missing:
    raise SystemExit(f"benchmark smoke gate: modules missing from artifact: {missing}")
print(f"benchmark smoke OK ({len(results)} modules, strict well-formed JSON)")
EOF

echo "== sharded + ragged + combined + hetero fleet + telemetry front-end + control-loop + slot-serving pins (forced 8-device host mesh, own subprocess) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m pytest -q tests/test_sharded_fleet.py tests/test_ragged_fleet.py \
  tests/test_combined_fleet.py tests/test_telemetry_frontend.py \
  tests/test_control_loop.py tests/test_slot_serving.py \
  tests/test_hetero_fleet.py -m "not slow"

echo "== tier-1 suite =="
python -m pytest -x -q "$@"
