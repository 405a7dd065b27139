"""Top-k MoE with capacity-bounded scatter/gather dispatch (grok-1, mixtral).

Dispatch strategy matters enormously at scale: the classic one-hot einsum
dispatch (flaxformer-style ``einsum('td,tec->ecd')``) is O(T·E·C·D) compute
and materializes a (T, E, C) tensor — at train_4k's 1M global tokens that is
~200x the useful FLOPs and terabytes of temporaries (measured in our first
grok-1 dry-run; see EXPERIMENTS.md §Perf). We instead:

  1. route: top-k logits -> expert ids + gates              O(T·E)
  2. position-in-expert via cumsum over a (T·k, E) one-hot  O(T·k·E)
  3. scatter-add tokens into the (E·C [+1 overflow], D) buffer   O(T·k·D)
  4. dense per-expert FFN on (E, C, D)                      O(E·C·D·F)
  5. gather back + combine with gates                       O(T·k·D)

Over-capacity routings land in a dead overflow slot (token dropped — same
semantics as the einsum dispatch). Expert weights are (E, d_in, d_out):
the COAP projector treats E as a stack axis — one projection per expert
(DESIGN.md §7).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ParamDef, swiglu


def moe_defs(d_model: int, d_ff: int, n_experts: int):
    # 'moe_embed' (vs 'embed'): expert weights keep d_model REPLICATED over
    # 'data' — sharding it there makes every expert einsum contract a
    # sharded dim, i.e. a multi-GB all-reduce per layer per microbatch
    # (measured: 85% of grok-1's collective term; EXPERIMENTS.md §Perf).
    # Token capacity shards over 'data' instead (constraints in moe_apply).
    return {
        "router": ParamDef((d_model, n_experts), "fan_in", ("embed", None)),
        "gate": ParamDef((n_experts, d_model, d_ff), "fan_in",
                         ("experts", "moe_embed", "ffn")),
        "up": ParamDef((n_experts, d_model, d_ff), "fan_in",
                       ("experts", "moe_embed", "ffn")),
        "down": ParamDef((n_experts, d_ff, d_model), "fan_in",
                         ("experts", "ffn", "moe_embed")),
    }


EINSUM_DISPATCH_MAX_TOKENS = 4096  # decode-sized: one-hot einsum wins


def _moe_einsum_dispatch(params, tokens, gates, top_idx, *, n_experts,
                         top_k, capacity):
    """Classic one-hot einsum dispatch — O(T·E·C·D) but collective-friendly
    and cheap at decode-sized T (measured 3x better than scatter there)."""
    e = n_experts
    n_tok, d = tokens.shape
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (T,k,E)
    mask = jnp.max(onehot, axis=1)
    pos_in_expert = jnp.cumsum(mask, axis=0) * mask - 1.0
    keep = (pos_in_expert < capacity) & (mask > 0)
    pos_oh = jax.nn.one_hot(
        jnp.where(keep, pos_in_expert, -1).astype(jnp.int32), capacity,
        dtype=tokens.dtype,
    )  # (T,E,C)
    weights = jnp.einsum("tk,tke->te", gates.astype(jnp.float32), onehot)
    dispatch = pos_oh
    combine = weights[..., None].astype(tokens.dtype) * pos_oh
    expert_in = jnp.einsum("td,tec->ecd", tokens, dispatch)
    g = jnp.einsum("ecd,edf->ecf", expert_in, params["gate"].astype(tokens.dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in, params["up"].astype(tokens.dtype))
    h = swiglu(g, u)
    expert_out = jnp.einsum("ecf,efd->ecd", h,
                            params["down"].astype(tokens.dtype))
    return jnp.einsum("ecd,tec->td", expert_out, combine)


def moe_apply(params, x, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, D). Returns (out, aux_loss)."""
    b, t, d = x.shape
    tokens = x.reshape(b * t, d)
    n_tok = b * t
    e = n_experts
    logits = tokens @ params["router"].astype(tokens.dtype)  # (T, E)

    top_vals, top_idx = jax.lax.top_k(logits, top_k)  # (T, k)
    gates = jax.nn.softmax(top_vals.astype(jnp.float32), axis=-1)  # (T, k)

    # Load-balancing auxiliary loss (Switch-style).
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot_tk = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (T, k, E)
    density = jnp.mean(jnp.max(onehot_tk, axis=1), axis=0)
    aux_loss = e * jnp.sum(density * jnp.mean(probs, axis=0))

    capacity = max(1, int(capacity_factor * n_tok * top_k / e))
    capacity = min(capacity, n_tok)

    if n_tok <= EINSUM_DISPATCH_MAX_TOKENS:
        out = _moe_einsum_dispatch(params, tokens, gates, top_idx,
                                   n_experts=e, top_k=top_k,
                                   capacity=capacity)
        return out.reshape(b, t, d), aux_loss

    # Position of each (token, k) routing inside its expert's buffer:
    # cumulative count over the routing-major flattened sequence.
    oh_flat = onehot_tk.reshape(n_tok * top_k, e)  # (T·k, E)
    pos_all = jnp.cumsum(oh_flat, axis=0) - oh_flat  # count before me
    pos = jnp.sum(pos_all * oh_flat, axis=-1).reshape(n_tok, top_k)  # (T, k)
    expert_id = top_idx  # (T, k)
    keep = pos < capacity
    dead = e * capacity  # overflow slot for dropped routings
    dest = jnp.where(keep, expert_id * capacity + pos.astype(jnp.int32), dead)

    # Scatter tokens into expert buffers (k scatters of (T, D)).
    buf = jnp.zeros((e * capacity + 1, d), tokens.dtype)
    for kk in range(top_k):
        buf = buf.at[dest[:, kk]].add(tokens)
    expert_in = buf[: e * capacity].reshape(e, capacity, d)

    g = jnp.einsum("ecd,edf->ecf", expert_in, params["gate"].astype(tokens.dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in, params["up"].astype(tokens.dtype))
    h = swiglu(g, u)
    expert_out = jnp.einsum("ecf,efd->ecd", h,
                            params["down"].astype(tokens.dtype))
    out_flat = jnp.concatenate(
        [expert_out.reshape(e * capacity, d),
         jnp.zeros((1, d), expert_out.dtype)], axis=0
    )

    # Gather back and combine with gates.
    out = jnp.zeros_like(tokens)
    for kk in range(top_k):
        out = out + gates[:, kk, None].astype(tokens.dtype) * out_flat[dest[:, kk]]
    return out.reshape(b, t, d), aux_loss


def moe_apply_local_ep(params, x, *, n_experts: int, top_k: int,
                       capacity_factor: float = 1.25):
    """Local-expert dispatch via shard_map (§Perf: grok-1 hillclimb).

    The pjit-auto dispatch lets XLA pick the collective schedule for the
    token scatter/expert einsums; at 1M tokens it picks multi-GB activation
    all-reduces per layer (85% of grok-1's collective term) — and a naive
    capacity-over-'data' constraint is worse (full replication, measured
    3x). Production MoE systems instead keep dispatch LOCAL: shard_map over
    the batch axes, every shard routes its own tokens into its own capacity
    buffer (capacity enforced per shard — the standard per-device-capacity
    semantics), experts' weights replicated over 'data' ('moe_embed' rule)
    and TP-sharded over 'model' in the auto domain. Zero cross-'data'
    collectives in the forward; expert-grad psums are inserted by shard_map
    AD (replicated-input cotangents).
    """
    from repro.distributed import sharding as shd
    from jax.sharding import PartitionSpec as P

    mesh = shd.current_mesh()
    manual = tuple(a for a in ("pod", "data") if mesh is not None
                   and a in mesh.axis_names)
    b = x.shape[0]
    total = 1
    for a in manual:
        total *= mesh.shape[a]
    tokens_per_shard = (b // max(total, 1)) * x.shape[1]
    if (mesh is None or not manual or b % total != 0 or total == 1
            or tokens_per_shard < 1024):
        # decode-sized workloads: the dense dispatch is cheap and the auto
        # partitioner does better than a manual shard_map (measured 3-7x
        # regressions on decode_32k; EXPERIMENTS.md §Perf iteration log).
        return moe_apply(params, x, n_experts=n_experts, top_k=top_k,
                         capacity_factor=capacity_factor)

    def local_fn(p, x_l):
        out, aux = moe_apply(p, x_l, n_experts=n_experts, top_k=top_k,
                             capacity_factor=capacity_factor)
        for ax in manual:
            aux = jax.lax.pmean(aux, ax)
        return out, aux

    bspec = manual if len(manual) > 1 else manual[0]
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False, axis_names=set(manual),
    )(params, x)
