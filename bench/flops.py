"""Operations and bytes, from shapes alone.

What a training step of a model requires per token is the model's own
count, ``flops_per_token`` of the reference module its configuration
names (``bench/references/``).

``fused_update_cost`` counts one call of the fused COAP update on one
(m, n) matrix at rank r, in its canonical orientation (m >= n): the
products ``G P`` and ``Δ Pᵀ`` (2mnr operations each), and the least bytes
the call must move, each operand read once and each result written once.
"""
from __future__ import annotations

from bench.references.coap_adamw import classify


def projected_matrices(shapes: dict, opt: dict) -> list:
    """[(path, count, m, n, r)] of the matrices the fused update takes, in
    canonical orientation; ``count`` is the number of matrices the leaf
    stacks along its leading axes."""
    out = []
    for path in sorted(shapes):
        shape = tuple(shapes[path])
        kind, tr, r = classify(path, shape, opt["rank"], opt["min_dim"])
        if kind != "project":
            continue
        m, n = (shape[-1], shape[-2]) if tr else (shape[-2], shape[-1])
        count = 1
        for s in shape[:-2]:
            count *= s
        out.append((path, count, m, n, r))
    return out


def fused_update_cost(m: int, n: int, r: int, quantize: bool,
                      grad_bytes: int = 4, block: int = 256) -> tuple:
    """(operations, bytes) of one fused update of one matrix."""
    flops = 4 * m * n * r
    io = grad_bytes * m * n + 4 * m * n + 4 * n * r  # G in, ΔW out, P in
    if quantize:
        nblk = -(-r // block)
        io += 2 * (2 * m * r + 2 * 4 * m * nblk)  # M, V codes and scales, in and out
    else:
        io += 2 * 2 * 4 * m * r  # M, V in and out
    return flops, io
