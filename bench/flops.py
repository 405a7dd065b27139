"""Operations and bytes, from shapes alone.

``model_flops_per_token`` counts what a training step of a dense decoder
requires per token: ``6 x`` the parameters that enter a matrix product
(the embedding lookup is not one; the output head is), plus attention's
score and value products, ``12 x layers x heads x head_dim x seq`` (the
PaLM count: the full square, forward and backward). Recomputation is not
counted.

``fused_update_cost`` counts one call of the fused COAP update on one
(m, n) matrix at rank r, in its canonical orientation (m >= n): the
products ``G P`` and ``Δ Pᵀ`` (2mnr operations each), and the least bytes
the call must move, each operand read once and each result written once.
"""
from __future__ import annotations

from bench.references.coap_adamw import classify


def matmul_params(arch: dict) -> int:
    d, f, n = arch["d_model"], arch["d_ff"], arch["n_layers"]
    q = arch["n_heads"] * arch["head_dim"]
    kv = arch["n_kv_heads"] * arch["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return n * per_layer + d * arch["vocab_size"]


def model_flops_per_token(arch: dict, seq: int) -> int:
    attn = 12 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * seq
    return 6 * matmul_params(arch) + attn


def projected_matrices(shapes: dict, opt: dict) -> list:
    """[(path, count, m, n, r)] of the matrices the fused update takes, in
    canonical orientation; ``count`` is the number of layers stacked."""
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
