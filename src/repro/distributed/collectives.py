"""Collectives: HLO collective-byte accounting + compressed cross-pod psum.

``collective_bytes``: the roofline's third term.  ``cost_analysis()`` does
not expose collective traffic, so we parse the compiled/lowered HLO text and
sum operand bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op.  Bytes are *per logical op instance*
(the tensor size that crosses links), which is the standard numerator for
``collective_bytes / (chips x link_bw)``.

``compressed_psum``: the int8 error-feedback all-reduce for the "pod" axis —
quantize the shard, psum the int8 payload (as int32 accumulators to avoid
overflow at 2+ pods), dequantize.  This is the collective counterpart of
``training.optimizer.ef_compress`` and is exercised under ``shard_map``.
"""

from __future__ import annotations

import re
from collections import defaultdict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'bf16[4,1024,512]{...}'."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output bytes per collective kind over an HLO module text.

    Returns {kind: bytes, ..., "total": bytes}.  The *output* shape of the
    op is used (for all-gather that is the gathered tensor, for
    reduce-scatter the scattered shard, matching what actually moves per
    participant up to the algorithm factor, which the roofline's link-bw
    denominator absorbs).
    """
    out: dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # form: "%name = <shape> <op-kind>(" or "name = (<tuple shapes>) op-kind("
        m = re.match(r"^[%\w\.\-]+\s*=\s*(.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        for kind in _COLLECTIVES:
            # op kind appears as the called instruction name before '('
            if re.search(rf"(?:^|\s){re.escape(kind)}(?:-start|-done)?\(", rhs):
                if f"{kind}-start(" in rhs:
                    break  # async pair: count the -done (result shape only)
                prefix = rhs.split(kind)[0]
                out[kind] += _shape_bytes(prefix)
                break
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return dict(out)


def collective_bytes_from_compiled(compiled) -> dict[str, int]:
    """Per-collective byte totals parsed from a compiled executable's HLO text."""
    return collective_bytes(compiled.as_text())


def _computation_blocks(hlo_text: str) -> dict[str, str]:
    """Split an HLO module into named computation bodies.

    Computation headers look like ``%name (args...) -> type {`` (signatures
    may contain nested parens/tuples, so only the leading ``%name (`` and the
    trailing ``{`` are matched); ``ENTRY`` marks the main computation.
    """
    blocks: dict[str, list[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\{\s*$", line)
        if m and "->" in line:
            current = m.group(1)
            blocks[current] = []
            continue
        if line.strip().startswith("}"):
            current = None
            continue
        if current is not None:
            blocks[current].append(line)
    return {k: "\n".join(v) for k, v in blocks.items()}


def collective_bytes_structured(hlo_text: str) -> dict[str, dict[str, int]]:
    """Collective bytes split into loop-body vs top-level contributions.

    XLA's cost/byte accounting counts while-loop bodies ONCE, not x trip
    count (measured: a 10-iteration scan reports 1x the body flops).  The
    roofline therefore needs the split: callers multiply the "body" bucket
    by the known trip count (the layer-scan length — the only collective-
    bearing loops in this framework are layer scans and the microbatch
    accumulation scan; inner SSD/sLSTM scans are collective-free).

    Reachability: computations referenced (transitively) from any while op's
    ``body=`` computation are "body"; everything else is "top".
    """
    blocks = _computation_blocks(hlo_text)
    body_roots = set(re.findall(r"body=%?([\w\.\-]+)", hlo_text))
    # transitive closure of computation references from body roots
    refs = {
        name: set(re.findall(r"(?:to_apply|calls|body|condition)=%?([\w\.\-]+)", text))
        for name, text in blocks.items()
    }
    reach: set[str] = set()
    stack = [r for r in body_roots if r in blocks]
    while stack:
        n = stack.pop()
        if n in reach:
            continue
        reach.add(n)
        stack.extend(r for r in refs.get(n, ()) if r in blocks and r not in reach)

    out = {"top": defaultdict(int), "body": defaultdict(int)}
    for name, text in blocks.items():
        bucket = "body" if name in reach else "top"
        counts = collective_bytes(text)
        for k, v in counts.items():
            if k != "total":
                out[bucket][k] += v
    for bucket in out:
        out[bucket]["total"] = sum(v for k, v in out[bucket].items() if k != "total")
    return {k: dict(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# Compressed cross-pod all-reduce
# ---------------------------------------------------------------------------


def compressed_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """int8-quantized psum over ``axis_name`` (inside shard_map/vmap).

    Payload crossing the axis is int8 + one f32 scale; accumulation happens
    in int32 so 2-256 participants cannot overflow.  Relative error is
    bounded by ~1/127 per step; pair with error feedback
    (``training.optimizer.ef_compress``) for unbiasedness over steps.
    """
    amax = jnp.max(jnp.abs(x))
    # One shared scale across the axis so dequantization is exact w.r.t. sum.
    amax = jax.lax.pmax(amax, axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    acc = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return acc.astype(jnp.float32) * scale


def make_compressed_pod_mean(mesh, axis: str = "pod"):
    """shard_map'd tree-mean over the pod axis with int8 payloads."""
    n = mesh.shape[axis]

    def tree_mean(tree):
        def one(x):
            spec = P(*([None] * x.ndim))
            f = shard_map(
                lambda v: compressed_psum(v, axis) / n,
                mesh=mesh,
                in_specs=spec,
                out_specs=spec,
                check_vma=False,
            )
            return f(x)

        return jax.tree.map(one, tree)

    return tree_mean
