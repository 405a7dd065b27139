"""LMModel: one uniform bundle (init / apply / loss / prefill / decode /
param_specs / cache machinery) over all assigned architecture families."""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mamba2 as S
from repro.models import transformer as T

LOSS_CHUNK = 1024  # tokens per lm-head chunk (bounds live logits memory)


class LMModel:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ defs
    def defs(self):
        cfg = self.cfg
        d: Dict[str, Any] = {}
        if not cfg.embed_inputs:
            d["embed"] = L.embed_defs(cfg.vocab_size, cfg.d_model)
        if cfg.family == "audio":
            d["stack"] = T.encdec_defs(cfg)
        elif cfg.family == "hybrid":
            d["stack"] = T.hybrid_defs(cfg)
        else:
            d["stack"] = T.uniform_stack_defs(cfg)
        d["final_norm"] = L.rmsnorm_def(cfg.d_model)
        if not cfg.tie_embeddings:
            d["lm_head"] = {
                "w": L.ParamDef((cfg.d_model, cfg.vocab_size), "fan_in",
                                ("embed", "vocab"))
            }
        return d

    def init(self, key):
        return L.materialize(self.defs(), key)

    def abstract_params(self):
        return L.abstract_params(self.defs())

    def param_specs(self, mesh, rules=shd.PARAM_RULES):
        return shd.param_specs(self.defs(), mesh, rules)

    # ----------------------------------------------------------------- embed
    def _embed_in(self, params, batch):
        cfg = self.cfg
        if cfg.embed_inputs:
            h = batch["embeds"].astype(cfg.dtype)
        else:
            h = L.embed_apply(params["embed"], batch["tokens"], cfg.dtype)
        return h

    def _positions(self, batch, h):
        if "positions" in batch:
            return batch["positions"]
        b, t = h.shape[0], h.shape[1]
        return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    # ----------------------------------------------------------------- apply
    def apply(self, params, batch, caches=None):
        """Returns (hidden, new_caches, aux). Readout happens in loss/logits
        so big-vocab logits never materialize wholesale."""
        cfg = self.cfg
        L.set_pure_bf16(cfg.bf16_elementwise)
        with jax.named_scope("embed"):
            h = self._embed_in(params, batch)
        h = shd.constrain(h, ("batch", "seq_data" if h.shape[0] == 1 else None, None))
        positions = self._positions(batch, h)
        with jax.named_scope("stack"):
            if cfg.family == "audio":
                if "enc_embeds" in batch:  # train / prefill: run the encoder
                    enc_out = T.encoder_apply(cfg, params["stack"],
                                              batch["enc_embeds"].astype(cfg.dtype))
                else:  # decode: reuse the cached encoder output
                    enc_out = caches["enc_out"]
                dec_caches = caches["kv"] if caches is not None else None
                h, new_kv, aux = T.decoder_apply(cfg, params["stack"], h, positions,
                                                 enc_out, dec_caches)
                new_caches = {"kv": new_kv, "enc_out": enc_out} if caches is not None else None
            elif cfg.family == "hybrid":
                h, new_caches, aux = T.hybrid_apply(cfg, params["stack"], h,
                                                    positions, caches)
            else:
                h, new_caches, aux = T.uniform_stack_apply(cfg, params["stack"], h,
                                                           positions, caches)
        h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return h, new_caches, aux

    def _readout(self, params, h):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return L.unembed_apply(params["embed"], h)
        return h @ params["lm_head"]["w"].astype(h.dtype)

    def logits(self, params, batch, caches=None):
        h, new_caches, aux = self.apply(params, batch, caches)
        return self._readout(params, h), new_caches, aux

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch):
        """Next-token CE (labels pre-shifted by the pipeline), computed in
        LOSS_CHUNK-token slices so the (tokens, vocab) logits never fully
        materialize (matters at vocab 152k × 1M tokens)."""
        cfg = self.cfg
        h, _, aux = self.apply(params, batch)
        b, t, d = h.shape
        labels = batch["labels"]
        flat_h = h.reshape(b * t, d)
        flat_y = labels.reshape(b * t)
        n = flat_h.shape[0]
        chunk = min(LOSS_CHUNK, n)
        pad = (-n) % chunk
        if pad:
            flat_h = jnp.concatenate([flat_h, jnp.zeros((pad, d), flat_h.dtype)])
            flat_y = jnp.concatenate([flat_y, -jnp.ones((pad,), flat_y.dtype)])
        hc = flat_h.reshape(-1, chunk, d)
        yc = flat_y.reshape(-1, chunk)

        def chunk_loss(carry, xs):
            hh, yy = xs
            logits = self._readout(params, hh[None])[0].astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.maximum(yy, 0)[:, None], axis=-1
            )[:, 0]
            valid = yy >= 0
            ce = jnp.where(valid, logz - gold, 0.0)
            return carry + jnp.sum(ce), jnp.sum(valid)

        body = jax.checkpoint(chunk_loss)
        with jax.named_scope("head"):
            total, counts = jax.lax.scan(body, jnp.zeros([], jnp.float32),
                                         (hc, yc))
        n_valid = jnp.maximum(jnp.sum(counts), 1)
        ce = total / n_valid
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux, "tokens": n_valid}

    # ---------------------------------------------------------------- caches
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        hd = cfg.resolved_head_dim

        def stacked(tree, n):
            return jax.tree_util.tree_map(lambda c: jnp.stack([c] * n), tree)

        if cfg.family == "ssm":
            one = S.mamba2_init_cache(
                batch, cfg.d_model, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_state,
                n_groups=cfg.ssm_groups, conv_kernel=cfg.ssm_conv,
                dtype=cfg.dtype,
            )
            return stacked(one, cfg.n_layers)
        if cfg.family == "hybrid":
            ssm_one = S.mamba2_init_cache(
                batch, cfg.d_model, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_state,
                n_groups=cfg.ssm_groups, conv_kernel=cfg.ssm_conv,
                dtype=cfg.dtype,
            )
            kv_one = A.gqa_init_cache(batch, max_len, cfg.n_kv_heads, hd,
                                      cfg.dtype, cfg.sliding_window)
            return {
                "ssm": stacked(ssm_one, cfg.n_layers),
                "kv": stacked(kv_one, T.hybrid_n_apps(cfg)),
            }
        if cfg.family == "audio":
            kv_one = A.gqa_init_cache(batch, max_len, cfg.n_kv_heads, hd,
                                      cfg.dtype)
            enc_t = cfg.encoder_seq or 1500
            return {
                "kv": stacked(kv_one, cfg.n_layers),
                "enc_out": jnp.zeros((batch, enc_t, cfg.d_model), cfg.dtype),
            }
        if cfg.mla:
            one = A.mla_init_cache(batch, max_len, cfg.kv_lora_rank,
                                   cfg.qk_rope_dim, cfg.dtype)
            return stacked(one, cfg.n_layers)
        one = A.gqa_init_cache(batch, max_len, cfg.n_kv_heads, hd, cfg.dtype,
                               cfg.sliding_window)
        return stacked(one, cfg.n_layers)

    def cache_shapes(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def cache_specs(self, mesh, batch: int):
        """PartitionSpecs for the cache pytree: batch over (pod,data) when it
        divides, else sequence over data (long_500k B=1)."""
        baxes = shd.batch_axes(mesh)
        total = 1
        for a in baxes:
            total *= mesh.shape[a]
        batch_ok = batch % total == 0 and total > 1
        bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)

        def one(kp, x):
            from repro.core.projector import path_str

            path = path_str(kp)
            shape = x.shape
            spec: list = [None] * len(shape)
            if len(shape) == 0:
                return jax.sharding.PartitionSpec()
            if "enc_out" in path:  # (B, enc_t, d): no layer axis
                if batch_ok:
                    spec[0] = bspec
                return jax.sharding.PartitionSpec(*spec)
            # stacked caches: axis0 = layers; batch = axis 1
            if len(shape) == 1:  # per-layer lengths
                return jax.sharding.PartitionSpec(None)
            if batch_ok:
                spec[1] = bspec
            elif (len(shape) >= 3 and "data" in mesh.axis_names
                  and shape[2] % mesh.shape["data"] == 0):
                spec[2] = "data"  # sequence-parallel KV
            # shard kv-heads/ssm-heads over model when divisible
            if (len(shape) >= 4 and "model" in mesh.axis_names
                    and shape[3] % mesh.shape["model"] == 0):
                spec[3] = "model"
            return jax.sharding.PartitionSpec(*spec)

        return jax.tree_util.tree_map_with_path(one, self.cache_shapes(batch, 8))

    # --------------------------------------------------------------- serving
    def prefill(self, params, batch, max_len: int):
        """Run the full prompt, building caches sized max_len."""
        b = (batch.get("tokens", batch.get("embeds"))).shape[0]
        caches = self.init_cache(b, max_len)
        logits, new_caches, _ = self.logits(params, batch, caches)
        return logits[:, -1:], new_caches

    def decode_step(self, params, caches, batch):
        logits, new_caches, _ = self.logits(params, batch, caches)
        return logits, new_caches


def build_model(cfg: ArchConfig) -> LMModel:
    return LMModel(cfg)
