"""Plain reference of a dense decoder-only transformer, in float32.

Pre-norm blocks: RMSNorm -> grouped-query attention with rotary positions
-> residual -> RMSNorm -> SwiGLU MLP -> residual; a final RMSNorm, an
untied output head, and next-token cross-entropy averaged over every
token. Every product is a float32 product (``Precision.HIGHEST``).

Nothing here comes from the program under test. Parameters are a nested
dict of arrays in the layout below (``layout``), as the benchmark makes
them (``bench/weights.py``): ``embed/embedding`` (V, d); ``stack/...``
with the layer axis first; ``final_norm`` (d,); ``lm_head/w`` (d, V).

``rnd`` rounds each operand of a product before it is taken. The reference
passes the identity; the precision control passes a rounding to a lower
precision (``bench/control.py``).

A configuration names this module under ``reference``; the harness reads
the model only through what it exports (``layout``, ``loss``,
``flops_per_token``, ``matrix_axes``, ``DOUBLED``; ``PERF.md``, section 4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LOSS_ROWS = 1024  # token rows per block of the output head


def _identity(x):
    return x


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """Rotary positions over the whole head, halves rotated against each
    other. x: (B, T, H, hd); positions: (T,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[:, None].astype(jnp.float32) * freqs  # (T, hd/2)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def block(h, p, arch, rnd=_identity):
    """One decoder layer. h: (B, T, d) float32; p: this layer's weights."""
    b, t, _ = h.shape
    nh, nkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["norm_eps"]
    a = p["attn"]
    x = rmsnorm(h, p["ln1"], eps)
    q = _mm(x, a["wq"], rnd)
    k = _mm(x, a["wk"], rnd)
    v = _mm(x, a["wv"], rnd)
    if "wq_bias" in a:
        q, k, v = q + a["wq_bias"], k + a["wk_bias"], v + a["wv_bias"]
    pos = jnp.arange(t)
    q = rope(q.reshape(b, t, nh, hd), pos, arch["rope_theta"])
    k = rope(k.reshape(b, t, nkv, hd), pos, arch["rope_theta"])
    v = v.reshape(b, t, nkv, hd)
    # Query head i reads key/value head i // (nh // nkv).
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", rnd(q), rnd(k),
                        precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", rnd(probs), rnd(v), precision=HIGHEST)
    h = h + _mm(o.reshape(b, t, nh * hd), a["wo"], rnd)
    x = rmsnorm(h, p["ln2"], eps)
    m = p["mlp"]
    gate = _mm(x, m["gate"], rnd)
    up = _mm(x, m["up"], rnd)
    return h + _mm(jax.nn.silu(gate) * up, m["down"], rnd)


def loss(params, tokens, labels, arch, rnd=_identity):
    """Mean next-token cross-entropy over every token of the batch."""
    h = params["embed"]["embedding"][tokens]

    def layer(hh, p):
        return jax.checkpoint(lambda x, q: block(x, q, arch, rnd))(hh, p), None

    h, _ = jax.lax.scan(layer, h, params["stack"])
    h = rmsnorm(h, params["final_norm"], arch["norm_eps"])
    d = h.shape[-1]
    rows = h.reshape(-1, d)
    ys = labels.reshape(-1)
    n = rows.shape[0]
    blk = min(LOSS_ROWS, n)
    assert n % blk == 0, (n, blk)
    w = params["lm_head"]["w"]

    def head_block(total, xs):
        r, y = xs
        logits = _mm(r, w, rnd)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(head_block), jnp.zeros([], jnp.float32),
                            (rows.reshape(-1, blk, d), ys.reshape(-1, blk)))
    return total / n


# ------------------------------------------------- what the harness reads
DOUBLED = "stack/attn/wq"  # the matrix the planted fault moves twice


def layout(arch: dict) -> dict:
    """{path: shape} of a dense GQA decoder with an untied head."""
    d, f, v, n = arch["d_model"], arch["d_ff"], arch["vocab_size"], arch["n_layers"]
    q, kv = arch["n_heads"] * arch["head_dim"], arch["n_kv_heads"] * arch["head_dim"]
    shapes = {
        "embed/embedding": (v, d),
        "final_norm": (d,),
        "lm_head/w": (d, v),
        "stack/attn/wq": (n, d, q),
        "stack/attn/wk": (n, d, kv),
        "stack/attn/wv": (n, d, kv),
        "stack/attn/wo": (n, q, d),
        "stack/ln1": (n, d),
        "stack/ln2": (n, d),
        "stack/mlp/gate": (n, d, f),
        "stack/mlp/up": (n, d, f),
        "stack/mlp/down": (n, f, d),
    }
    if arch.get("qkv_bias"):
        shapes.update({"stack/attn/wq_bias": (n, q), "stack/attn/wk_bias": (n, kv),
                       "stack/attn/wv_bias": (n, kv)})
    return shapes


def matrix_axes(path: str, shape) -> int:
    """How many leading axes of a leaf index separate matrices: the layer
    axis of a ``stack/`` leaf; none elsewhere."""
    return 1 if path.startswith("stack/") else 0


def matmul_params(arch: dict) -> int:
    d, f, n = arch["d_model"], arch["d_ff"], arch["n_layers"]
    q = arch["n_heads"] * arch["head_dim"]
    kv = arch["n_kv_heads"] * arch["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return n * per_layer + d * arch["vocab_size"]


def flops_per_token(arch: dict, seq: int) -> int:
    """What a training step requires per token: ``6 x`` the parameters
    that enter a matrix product (the embedding lookup is not one; the
    output head is), plus attention's score and value products, ``12 x
    layers x heads x head_dim x seq`` (the PaLM count: the full square,
    forward and backward). Recomputation is not counted."""
    attn = 12 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * seq
    return 6 * matmul_params(arch) + attn
