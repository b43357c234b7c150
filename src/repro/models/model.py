"""Public model API: build_model(cfg) -> Model.

A Model bundles init / loss / prefill / decode for one architecture,
including the multimodal stubs (patch/frame embeddings provided as inputs),
the optional encoder stack (seamless-m4t) and the optional MTP head
(deepseek-v3). Everything is pure-jnp and vmap-able over a leading agent
axis (used by core/dsgd.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tfm
from repro.models.layers import (apply_norm, chunked_softmax_xent,
                                 embed_tokens, init_embed, init_norm,
                                 spec_embed, spec_norm)
from repro.models.sharding import logical as L

MTP_WEIGHT = 0.3


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


@dataclass
class Model:
    cfg: ModelConfig
    init_params: Callable
    param_spec: Callable
    loss_fn: Callable  # (params, batch, rng) -> (loss, metrics)
    prefill: Callable  # (params, batch) -> (logits_last, caches)
    decode_step: Callable  # (params, caches, tokens, index) -> (logits, caches)
    init_cache: Callable  # (B, seq_len, dtype) -> caches
    cache_spec: Callable  # () -> logical spec tree
    input_specs: Callable  # (shape, agents) -> dict of ShapeDtypeStructs
    operand_params: Callable  # (params, dtype) -> params, matmul operands cast


def build_model(cfg: ModelConfig) -> Model:
    dt = _dtype(cfg.param_dtype)
    is_encdec = cfg.encoder_layers > 0
    has_mm_prefix = cfg.mm_prefix > 0  # vlm patch prefix
    V = cfg.padded_vocab

    # ----------------------------------------------------------------- init
    def init_params(rng):
        ks = jax.random.split(rng, 6)
        p = {"embed": init_embed(ks[0], V, cfg.d_model, dt),
             "final_norm": init_norm(cfg.norm, cfg.d_model, dt),
             "decoder": tfm.init_stack(ks[1], cfg, cross=is_encdec, dtype=dt)}
        if not cfg.tie_embeddings:
            p["head"] = {"w": (jax.random.normal(
                ks[2], (cfg.d_model, V), jnp.float32)
                * (1.0 / np.sqrt(cfg.d_model))).astype(dt)}
        if is_encdec:
            enc_cfg = cfg.replace(num_layers=cfg.encoder_layers,
                                  dense_ff_first_k=0)
            p["encoder"] = tfm.init_stack(ks[3], enc_cfg, dtype=dt)
            p["enc_norm"] = init_norm(cfg.norm, cfg.d_model, dt)
        if cfg.mtp_depth:
            p["mtp"] = {
                "proj": (jax.random.normal(
                    ks[4], (2 * cfg.d_model, cfg.d_model), jnp.float32)
                    * (1.0 / np.sqrt(2 * cfg.d_model))).astype(dt),
                "block": tfm._stacked_init(
                    ks[5], cfg.mtp_depth,
                    lambda k: tfm.init_block(k, cfg, cfg.layer_period[0],
                                             dtype=dt)),
                "norm": init_norm(cfg.norm, cfg.d_model, dt),
            }
        return p

    def param_spec():
        p = {"embed": spec_embed(),
             "final_norm": spec_norm(cfg.norm),
             "decoder": tfm.spec_stack(cfg, cross=is_encdec)}
        if not cfg.tie_embeddings:
            p["head"] = {"w": L("fsdp", "model")}
        if is_encdec:
            enc_cfg = cfg.replace(num_layers=cfg.encoder_layers,
                                  dense_ff_first_k=0)
            p["encoder"] = tfm.spec_stack(enc_cfg)
            p["enc_norm"] = spec_norm(cfg.norm)
        if cfg.mtp_depth:
            p["mtp"] = {"proj": L("fsdp", None),
                        "block": tfm.spec_block(cfg, cfg.layer_period[0]),
                        "norm": spec_norm(cfg.norm)}
        return p

    def head_w(params):
        if cfg.tie_embeddings:
            return params["embed"]["table"].T
        return params["head"]["w"]

    # -------------------------------------------------------------- encoder
    def run_encoder(params, frame_embeds):
        enc_cfg = cfg.replace(num_layers=cfg.encoder_layers,
                              dense_ff_first_k=0)
        B, S, _ = frame_embeds.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h, _, _ = tfm.apply_stack(params["encoder"], frame_embeds,
                                  cfg=enc_cfg, mode="train", positions=pos,
                                  causal=False)
        return apply_norm(params["enc_norm"], h, cfg.norm)

    # ------------------------------------------------------------- embedder
    def embed_inputs(params, batch):
        """Returns (x, positions, positions3, loss_mask_prefix)."""
        tokens = batch["tokens"]
        B, S_tok = tokens.shape
        x = embed_tokens(params["embed"], tokens, scale=cfg.embed_scale)
        if has_mm_prefix and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        positions3 = batch.get("positions3")
        if cfg.attn.rope == "mrope" and positions3 is None:
            positions3 = jnp.broadcast_to(positions[None], (3, B, S))
        return x, positions, positions3

    # ----------------------------------------------------------------- loss
    def loss_fn(params, batch, rng=None):
        x, positions, positions3 = embed_inputs(params, batch)
        enc_out = None
        if is_encdec:
            enc_out = run_encoder(params, batch["frame_embeds"])
        h, _, aux = tfm.apply_stack(params["decoder"], x, cfg=cfg,
                                    mode="train", positions=positions,
                                    positions3=positions3, enc_out=enc_out)
        h = apply_norm(params["final_norm"], h, cfg.norm)
        if has_mm_prefix and "patch_embeds" in batch:
            h = h[:, batch["patch_embeds"].shape[1]:]
        targets = batch["targets"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(targets, jnp.float32)
        hw = head_w(params)
        nll, count = chunked_softmax_xent(h, hw, targets, mask,
                                          cfg.dist.loss_chunk)
        loss = nll / jnp.maximum(count, 1.0)
        metrics = {"nll": loss, "aux": aux}
        if cfg.mtp_depth:
            # multi-token prediction: predict t+2 from h_i ++ emb(t_{i+1})
            emb_next = embed_tokens(params["embed"], batch["tokens"],
                                    scale=cfg.embed_scale)
            hm = jnp.concatenate([h[:, :-1], emb_next[:, 1:]], axis=-1)
            hm = hm @ params["mtp"]["proj"]
            blk = jax.tree.map(lambda p: p[0], params["mtp"]["block"])
            hm, _, _ = tfm.apply_block(blk, hm, cfg=cfg,
                                       lspec=cfg.layer_period[0],
                                       mode="train",
                                       positions=positions[:, :-1])
            hm = apply_norm(params["mtp"]["norm"], hm, cfg.norm)
            mtp_nll, mtp_cnt = chunked_softmax_xent(
                hm[:, :-1], hw, targets[:, 2:], mask[:, 2:],
                cfg.dist.loss_chunk)
            mtp_loss = mtp_nll / jnp.maximum(mtp_cnt, 1.0)
            metrics["mtp"] = mtp_loss
            loss = loss + MTP_WEIGHT * mtp_loss
        loss = loss + aux
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------- serving
    def prefill(params, batch, max_len: Optional[int] = None):
        x, positions, positions3 = embed_inputs(params, batch)
        enc_out = None
        if is_encdec:
            enc_out = run_encoder(params, batch["frame_embeds"])
        S = x.shape[1]
        h, caches, _ = tfm.apply_stack(params["decoder"], x, cfg=cfg,
                                       mode="prefill", positions=positions,
                                       positions3=positions3,
                                       enc_out=enc_out,
                                       cache_max_len=max_len or S)
        h = apply_norm(params["final_norm"], h[:, -1:], cfg.norm)
        logits = (h @ head_w(params)).astype(jnp.float32)[:, 0]
        return logits, caches

    def decode_step(params, caches, tokens, index):
        """tokens: (B, 1) int32; index: absolute position(s) — a scalar
        shared by the whole batch, or a (B,) vector when every row sits at
        its own depth (continuous batching over slots)."""
        B = tokens.shape[0]
        x = embed_tokens(params["embed"], tokens, scale=cfg.embed_scale)
        idx = jnp.asarray(index, jnp.int32)
        positions = (idx.reshape(B, 1) if idx.ndim
                     else jnp.full((B, 1), idx, jnp.int32))
        positions3 = None
        if cfg.attn.rope == "mrope":
            positions3 = jnp.broadcast_to(positions[None], (3, B, 1))
        h, new_caches, _ = tfm.apply_stack(params["decoder"], x, cfg=cfg,
                                           mode="decode", positions=positions,
                                           positions3=positions3,
                                           caches=caches, index=index)
        h = apply_norm(params["final_norm"], h, cfg.norm)
        logits = (h @ head_w(params)).astype(jnp.float32)[:, 0]
        return logits, new_caches

    def operand_params(params, dtype):
        """``params`` with each leaf consumed only as the right operand of a
        dense matmul (:func:`layers.dense`) cast to ``dtype``: the
        projections of standard and cross attention and the dense MLP
        matrices of the decoder and encoder stacks. The tied embedding
        (also the token lookup), norms, an untied head, MLA, recurrent
        cells and expert layers stay as they are; the tree's structure and
        shapes do not change."""
        def cast(w):
            return w.astype(dtype)

        out = dict(params)
        out["decoder"] = tfm.operand_stack(params["decoder"], cfg, cast)
        if is_encdec:
            enc_cfg = cfg.replace(num_layers=cfg.encoder_layers,
                                  dense_ff_first_k=0)
            out["encoder"] = tfm.operand_stack(params["encoder"], enc_cfg,
                                               cast)
        return out

    def init_cache(B, seq_len, dtype=None, enc_len: int = 0):
        dtype = dtype or dt
        return tfm.init_stack_cache(cfg, B, seq_len, cross=is_encdec,
                                    enc_len=enc_len or seq_len, dtype=dtype)

    def cache_spec():
        return tfm.spec_stack_cache(cfg, cross=is_encdec)

    # --------------------------------------------------------- input specs
    def input_specs(shape: ShapeConfig, agents: Optional[int] = None,
                    dtype=jnp.float32) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of a step.

        For training the global batch is split over ``agents`` with a leading
        agent axis; serving shapes have no agent axis.
        """
        S, B = shape.seq_len, shape.global_batch

        def sds(shp, dty=jnp.int32):
            return jax.ShapeDtypeStruct(shp, dty)

        if shape.kind == "train":
            m = agents or 1
            b = B // m
            lead = (m, b) if agents else (b,)
            d = {"tokens": sds(lead + (S,)),
                 "targets": sds(lead + (S,)),
                 "mask": sds(lead + (S,), jnp.float32)}
            if has_mm_prefix:
                # patch prefix replaces the first mm_prefix token positions
                d["tokens"] = sds(lead + (S - cfg.mm_prefix,))
                d["targets"] = sds(lead + (S - cfg.mm_prefix,))
                d["mask"] = sds(lead + (S - cfg.mm_prefix,), jnp.float32)
                d["patch_embeds"] = sds(lead + (cfg.mm_prefix, cfg.d_model),
                                        dtype)
            if is_encdec:
                d["frame_embeds"] = sds(lead + (S, cfg.d_model), dtype)
            return d
        if shape.kind == "prefill":
            d = {"tokens": sds((B, S))}
            if has_mm_prefix:
                d["tokens"] = sds((B, S - cfg.mm_prefix))
                d["patch_embeds"] = sds((B, cfg.mm_prefix, cfg.d_model), dtype)
            if is_encdec:
                d["frame_embeds"] = sds((B, S, cfg.d_model), dtype)
            return d
        # decode: one token + cache of seq_len
        caches = jax.eval_shape(
            lambda: init_cache(B, S, dtype=dtype, enc_len=S))
        return {"tokens": sds((B, 1)),
                "index": sds((), jnp.int32),
                "caches": caches}

    return Model(cfg=cfg, init_params=init_params, param_spec=param_spec,
                 loss_fn=loss_fn, prefill=prefill, decode_step=decode_step,
                 init_cache=init_cache, cache_spec=cache_spec,
                 input_specs=input_specs, operand_params=operand_params)
