"""Decoder/encoder stack assembly with scan-over-layers.

Layers are grouped into *segments*; each segment is a repeated period of
:class:`LayerSpec` (e.g. recurrentgemma: (rglru, rglru, local-attn) x 8 with a
(rglru, rglru) tail). Per-period-position params are stacked along a leading
``n_rep`` axis and the segment is applied with ``lax.scan`` — this keeps the
HLO small (fast 512-way SPMD compiles) and mirrors production LM frameworks.

Caches/states mirror the same segment structure (stacked per group).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.layers import (activation, apply_mlp, apply_norm, init_mlp,
                                 init_norm, spec_mlp, spec_norm)
from repro.models.sharding import logical as L


@dataclass(frozen=True)
class Segment:
    name: str
    specs: Tuple[LayerSpec, ...]  # one period
    n_rep: int
    d_ff_override: Optional[int] = None


def build_segments(cfg: ModelConfig):
    """Split cfg.layer_specs() into scanned segments."""
    specs = list(cfg.layer_specs())
    segments = []
    if cfg.dense_ff_first_k:
        front = tuple(
            LayerSpec(mixer=s.mixer, ffn="swiglu", window=s.window)
            for s in specs[: cfg.dense_ff_first_k])
        # front layers are identical; stack them as one group repeated k times
        segments.append(Segment("front", (front[0],), cfg.dense_ff_first_k,
                                d_ff_override=cfg.dense_ff_size))
        specs = specs[cfg.dense_ff_first_k:]
    period = cfg.layer_period
    p = len(period)
    n_rep = len(specs) // p
    if n_rep > 0:
        segments.append(Segment("main", tuple(period), n_rep))
    tail = specs[n_rep * p:]
    if tail:
        segments.append(Segment("tail", tuple(tail), 1))
    return segments


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

_MIXER_INIT = {"gqa": attn.init_gqa, "mla": attn.init_mla,
               "rglru": rec.init_rglru, "mlstm": rec.init_mlstm,
               "slstm": rec.init_slstm}
_MIXER_SPEC = {"gqa": attn.spec_gqa, "mla": attn.spec_mla,
               "rglru": rec.spec_rglru, "mlstm": rec.spec_mlstm,
               "slstm": rec.spec_slstm}


def init_block(rng, cfg: ModelConfig, lspec: LayerSpec, cross: bool = False,
               d_ff_override: Optional[int] = None, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype),
         "mixer": _MIXER_INIT[lspec.mixer](ks[0], cfg, dtype=dtype)}
    if cross:
        p["norm_x"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["cross"] = attn.init_cross(ks[1], cfg, dtype=dtype)
    if lspec.ffn != "none":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if lspec.ffn == "moe":
            p["ffn"] = moe_mod.init_moe(ks[2], cfg, dtype=dtype)
        else:
            d_ff = d_ff_override or cfg.d_ff
            p["ffn"] = init_mlp(ks[2], cfg.d_model, d_ff, gated=True,
                                dtype=dtype)
    return p


def spec_block(cfg: ModelConfig, lspec: LayerSpec, cross: bool = False):
    p = {"norm1": spec_norm(cfg.norm), "mixer": _MIXER_SPEC[lspec.mixer]()}
    if cross:
        p["norm_x"] = spec_norm(cfg.norm)
        p["cross"] = attn.spec_cross()
    if lspec.ffn != "none":
        p["norm2"] = spec_norm(cfg.norm)
        p["ffn"] = (moe_mod.spec_moe(cfg) if lspec.ffn == "moe"
                    else spec_mlp(gated=True))
    return p


def apply_block(params, x, *, cfg: ModelConfig, lspec: LayerSpec, mode: str,
                positions, positions3=None, cache=None, index=None,
                enc_out=None, cross_kv=None, causal=True, cache_max_len=None):
    """Returns (x, new_cache_dict_or_None, aux_loss).

    ``new_cache_dict`` has keys {"mixer"[, "cross"]} in prefill/decode modes.
    """
    from repro.models.sharding import constrain
    if cfg.dist.seq_shard and mode in ("train", "prefill"):
        # Megatron-style sequence parallelism: the residual stream is
        # sequence-sharded over the tensor axis between blocks; XLA inserts
        # the gather at the first projection and the reduce-scatter after.
        x = constrain(x, ("fsdp", "model", None))
    else:
        x = constrain(x, ("fsdp", None, None))
    h = apply_norm(params["norm1"], x, cfg.norm)
    if lspec.mixer in ("gqa", "mla"):
        fwd = attn.gqa_forward if lspec.mixer == "gqa" else attn.mla_forward
        y, new_mixer = fwd(params["mixer"], h, cfg=cfg, lspec=lspec,
                           positions=positions, mode=mode, cache=cache,
                           index=index, positions3=positions3, causal=causal,
                           cache_max_len=cache_max_len)
    else:
        fwd = {"rglru": rec.rglru_forward, "mlstm": rec.mlstm_forward,
               "slstm": rec.slstm_forward}[lspec.mixer]
        y, new_mixer = fwd(params["mixer"], h, cfg=cfg, mode=mode, state=cache)
    x = x + y
    new_cross = None
    if "cross" in params:
        hx = apply_norm(params["norm_x"], x, cfg.norm)
        if cross_kv is None and enc_out is not None:
            cross_kv = attn.cross_kv(params["cross"], enc_out, cfg=cfg)
        y_x = attn.cross_forward(params["cross"], hx, cross_kv, cfg=cfg)
        x = x + y_x
        new_cross = cross_kv
    aux = jnp.zeros((), jnp.float32)
    if lspec.ffn != "none":
        h2 = apply_norm(params["norm2"], x, cfg.norm)
        if lspec.ffn == "moe":
            # prefill/decode run dropless: capacity dropping is only causal
            # in training where the whole batch is one step (see moe_forward)
            y2, aux = moe_mod.moe_forward(params["ffn"], h2, cfg=cfg,
                                          act_name=cfg.act,
                                          dropless=mode != "train")
        else:
            y2 = apply_mlp(params["ffn"], h2, activation(cfg.act), gated=True)
        x = x + y2
    if mode == "train":
        return x, None, aux
    out_cache = {"mixer": new_mixer} if new_mixer is not None else {}
    if new_cross is not None:
        out_cache["cross"] = new_cross
    return x, out_cache, aux


def init_block_cache(cfg: ModelConfig, lspec: LayerSpec, B: int, seq_len: int,
                     cross: bool, enc_len: int, dtype=jnp.float32):
    c = {}
    if lspec.mixer == "gqa":
        c["mixer"] = attn.init_gqa_cache(cfg, lspec, B, seq_len, dtype)
    elif lspec.mixer == "mla":
        c["mixer"] = attn.init_mla_cache(cfg, lspec, B, seq_len, dtype)
    elif lspec.mixer == "rglru":
        c["mixer"] = rec.init_rglru_state(cfg, B, dtype)
    elif lspec.mixer == "mlstm":
        c["mixer"] = rec.init_mlstm_state(cfg, B)
    elif lspec.mixer == "slstm":
        c["mixer"] = rec.init_slstm_state(cfg, B)
    if cross:
        a = cfg.attn
        c["cross"] = {
            "k": jnp.zeros((B, enc_len, a.num_kv_heads, a.head_dim), dtype),
            "v": jnp.zeros((B, enc_len, a.num_kv_heads, a.head_dim), dtype),
            "pos": jnp.full((B, enc_len), -1, jnp.int32)}
    return c


def spec_block_cache(cfg: ModelConfig, lspec: LayerSpec, cross: bool):
    c = {}
    if lspec.mixer == "gqa":
        c["mixer"] = attn.spec_gqa_cache()
    elif lspec.mixer == "mla":
        c["mixer"] = attn.spec_mla_cache()
    elif lspec.mixer == "rglru":
        c["mixer"] = rec.spec_rglru_state()
    elif lspec.mixer == "mlstm":
        c["mixer"] = rec.spec_mlstm_state()
    elif lspec.mixer == "slstm":
        c["mixer"] = rec.spec_slstm_state()
    if cross:
        c["cross"] = {"k": L("data", None, "model", None),
                      "v": L("data", None, "model", None),
                      "pos": L("data", None)}
    return c


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def _stacked_init(rng, n, init_fn):
    ks = jax.random.split(rng, n)
    ps = [init_fn(k) for k in ks]
    return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *ps)


def init_stack(rng, cfg: ModelConfig, cross: bool = False, dtype=jnp.float32):
    """Params for all segments: {seg.name: {"p{i}": stacked params}}."""
    segs = build_segments(cfg)
    out = {}
    for seg in segs:
        rng, sub = jax.random.split(rng)
        seg_p = {}
        for i, ls in enumerate(seg.specs):
            sub, k = jax.random.split(sub)
            seg_p[f"p{i}"] = _stacked_init(
                k, seg.n_rep,
                lambda kk, ls=ls: init_block(kk, cfg, ls, cross=cross,
                                             d_ff_override=seg.d_ff_override,
                                             dtype=dtype))
        out[seg.name] = seg_p
    return out


def spec_stack(cfg: ModelConfig, cross: bool = False):
    segs = build_segments(cfg)
    out = {}
    for seg in segs:
        out[seg.name] = {f"p{i}": spec_block(cfg, ls, cross=cross)
                         for i, ls in enumerate(seg.specs)}
    return out


def operand_block(params, lspec: LayerSpec, cast):
    """A block's params with ``cast`` applied to each leaf the block
    consumes only as the right operand of :func:`layers.dense`: the
    projections of standard and cross attention and a dense MLP's
    matrices. Norms, MLA, recurrent cells and expert layers are kept."""
    parts = ["cross"]
    if lspec.mixer == "gqa":
        parts.append("mixer")
    if lspec.ffn not in ("none", "moe"):
        parts.append("ffn")
    p = dict(params)
    for part in parts:
        if part in p:
            p[part] = jax.tree.map(cast, p[part])
    return p


def operand_stack(params, cfg: ModelConfig, cast):
    """:func:`operand_block` over every block of a stack's params."""
    return {seg.name: {f"p{i}": operand_block(params[seg.name][f"p{i}"], ls,
                                              cast)
                       for i, ls in enumerate(seg.specs)}
            for seg in build_segments(cfg)}


def init_stack_cache(cfg: ModelConfig, B: int, seq_len: int,
                     cross: bool = False, enc_len: int = 0,
                     dtype=jnp.float32):
    segs = build_segments(cfg)
    out = {}
    for seg in segs:
        seg_c = {}
        for i, ls in enumerate(seg.specs):
            one = init_block_cache(cfg, ls, B, seq_len, cross, enc_len, dtype)
            seg_c[f"p{i}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (seg.n_rep,) + x.shape),
                one)
        out[seg.name] = seg_c
    return out


def spec_stack_cache(cfg: ModelConfig, cross: bool = False):
    segs = build_segments(cfg)
    return {seg.name: {f"p{i}": spec_block_cache(cfg, ls, cross)
                       for i, ls in enumerate(seg.specs)}
            for seg in segs}


def apply_stack(params, x, *, cfg: ModelConfig, mode: str, positions,
                positions3=None, caches=None, index=None, enc_out=None,
                causal=True, cache_max_len=None):
    """Run all segments. Returns (x, new_caches, aux_total).

    ``caches`` must be given for decode; for prefill it is None and fresh
    caches (sized by ``cache_max_len``) are returned; for train it is None
    and None is returned.
    """
    segs = build_segments(cfg)
    want_cache = mode in ("prefill", "decode")
    new_caches = {} if want_cache else None
    aux_total = jnp.zeros((), jnp.float32)
    remat = cfg.dist.remat

    for seg in segs:
        seg_params = params[seg.name]
        seg_cache = caches[seg.name] if caches is not None else None

        def period_body(carry, xs, seg=seg):
            h, aux = carry
            p_all, c_all = xs
            new_c = {}
            for i, ls in enumerate(seg.specs):
                blk = p_all[f"p{i}"]
                cache_i = c_all[f"p{i}"] if c_all is not None else None
                mixer_cache = cache_i.get("mixer") if cache_i else None
                cross_kv = cache_i.get("cross") if cache_i else None

                def run(blk, h, mixer_cache, cross_kv, ls=ls):
                    return apply_block(
                        blk, h, cfg=cfg, lspec=ls, mode=mode,
                        positions=positions, positions3=positions3,
                        cache=mixer_cache, index=index, enc_out=enc_out,
                        cross_kv=cross_kv, causal=causal,
                        cache_max_len=cache_max_len)

                if remat == "full" and mode == "train":
                    run = jax.checkpoint(run)
                elif remat == "dots" and mode == "train":
                    run = jax.checkpoint(
                        run, policy=jax.checkpoint_policies.dots_saveable)
                h, blk_cache, a = run(blk, h, mixer_cache, cross_kv)
                aux = aux + a
                if want_cache:
                    new_c[f"p{i}"] = blk_cache
            return (h, aux), (new_c if want_cache else 0)

        xs = (seg_params, seg_cache)
        if cfg.dist.scan_layers:
            (x, aux_total), seg_new_cache = jax.lax.scan(
                period_body, (x, aux_total), xs)
        else:  # unrolled (dry-run mode: honest per-op cost_analysis)
            ys = []
            carry = (x, aux_total)
            for rix in range(seg.n_rep):
                xs_r = jax.tree.map(lambda t: t[rix], xs)
                carry, y = period_body(carry, xs_r)
                ys.append(y)
            (x, aux_total) = carry
            seg_new_cache = (jax.tree.map(
                lambda *zs: jnp.stack(zs, 0), *ys) if want_cache else None)
        if want_cache:
            new_caches[seg.name] = seg_new_cache
    return x, new_caches, aux_total
