#!/usr/bin/env python
"""Import-layering contract: kernels -> core/engine -> core/sessions -> serving.

The layered split of the fleet engine (core/engine = jit-level stage
pipeline, core/sessions = host-side session state machines, core/profiler =
paper-facing orchestration, serving = control plane on top) only stays a
layering if imports keep flowing one way.  This script walks the AST of
every module in the layered packages and fails on any *back-edge*: an
import whose target sits on a HIGHER layer than the importing module.

Layers (lower may never import higher):

    0  repro.kernels.*, repro.core.disaggregation,  pure math and host
       repro.tracing                                spans, no deps up
    1  repro.core.engine.*, repro.distributed.*,    jitted stage pipeline +
       core estimator peers (kalman, contribution,  the math it composes
       cpu_model, sync, metrics, footprints,
       shapley, capping, pricing, baselines)
    2  repro.core.sessions.*                        host session layer
    3  repro.core.profiler, repro.core.batched_engine (shim), repro.core
    4  repro.serving.*                              control plane

Equal-layer imports are allowed (peers compose); unmapped packages
(telemetry, workload, data, models, ...) are infrastructure shared across
layers and are not constrained by this contract.  Function-scope imports
count too: a lazy back-edge is still a back-edge.

Exit status 0 with an edge summary when clean; 1 with one line per
violation otherwise.  Run from the repo root (CI does, via scripts/ci.sh).
"""

from __future__ import annotations

import ast
import pathlib
import sys

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"

# Longest-prefix match decides a module's layer; None = unconstrained.
LAYERS: dict[str, int] = {
    "repro.kernels": 0,
    "repro.tracing": 0,  # host spans; imports only jax and numpy
    "repro.core.disaggregation": 0,  # pure-math leaf; the Pallas solver's fallback
    "repro.core.engine": 1,
    "repro.distributed": 1,
    "repro.core.kalman": 1,
    "repro.core.contribution": 1,
    "repro.core.cpu_model": 1,
    "repro.core.sync": 1,
    "repro.core.metrics": 1,
    "repro.core.footprints": 1,
    "repro.core.shapley": 1,
    "repro.core.capping": 1,
    "repro.core.pricing": 1,
    "repro.core.baselines": 1,
    "repro.core.sessions": 2,
    "repro.core.profiler": 3,
    "repro.core.batched_engine": 3,  # deprecation shim over engine + profiler
    "repro.core": 3,  # package facade re-exports the profiler
    "repro.serving": 4,
}


def _all_modules() -> set[str]:
    """Every module name under src/repro (for ``from pkg import submod``)."""
    mods = set()
    for p in SRC.rglob("*.py"):
        rel = p.relative_to(SRC).with_suffix("")
        parts = list(rel.parts)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        if parts:
            mods.add(".".join(parts))
    return mods


def _layer_of(mod: str) -> int | None:
    """Layer via longest matching prefix, or None when unconstrained."""
    best, best_len = None, -1
    for prefix, layer in LAYERS.items():
        if (mod == prefix or mod.startswith(prefix + ".")) and len(prefix) > best_len:
            best, best_len = layer, len(prefix)
    return best


def _module_name(path: pathlib.Path) -> str:
    rel = path.relative_to(SRC).with_suffix("")
    parts = list(rel.parts)
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _edges(path: pathlib.Path, mod: str, known: set[str]):
    """Yield (lineno, target-module) for every repro import in ``path``."""
    tree = ast.parse(path.read_text(), filename=str(path))
    pkg = mod if (path.name == "__init__.py") else mod.rpartition(".")[0]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == "repro":
                    yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative: resolve against the enclosing package
                base = pkg.split(".")
                base = base[: len(base) - (node.level - 1)]
                target = ".".join(base + ([node.module] if node.module else []))
            else:
                target = node.module or ""
            if target.split(".")[0] != "repro":
                continue
            # ``from pkg import name``: name may itself be a module, which
            # is the real edge (e.g. ``from repro.core import engine``).
            for alias in node.names:
                sub = f"{target}.{alias.name}"
                yield node.lineno, sub if sub in known else target


def main() -> int:
    known = _all_modules()
    files = sorted(p for p in SRC.rglob("*.py") if _layer_of(_module_name(p)) is not None)
    violations, checked = [], 0
    for path in files:
        mod = _module_name(path)
        src_layer = _layer_of(mod)
        for lineno, target in _edges(path, mod, known):
            dst_layer = _layer_of(target)
            if dst_layer is None:
                continue
            checked += 1
            if dst_layer > src_layer:
                violations.append(
                    f"{path.relative_to(SRC.parent)}:{lineno}: "
                    f"back-edge {mod} (layer {src_layer}) -> "
                    f"{target} (layer {dst_layer})"
                )
    if violations:
        print(f"layering check FAILED: {len(violations)} back-edge(s)")
        for v in violations:
            print(f"  {v}")
        return 1
    print(
        f"layering check OK ({len(files)} modules, {checked} in-contract "
        "import edges, no back-edges)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
