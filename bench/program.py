"""The one module that knows the program's layout: it builds the
program's model configuration from a configuration file, and moves the
benchmark's weights into the program's parameter tree and back.

The program is imported from ``src/`` of the checkout.
"""
from __future__ import annotations

import dataclasses
import os
import sys

from bench.common import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))


def model_config(cfg):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with the file's sizes."""
    from repro.configs import get_config
    base = get_config(cfg["program_arch"])
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    attn = dataclasses.replace(base.attn, num_heads=h,
                               num_kv_heads=cfg["num_key_value_heads"],
                               head_dim=d // h, rope_theta=cfg["rope_theta"])
    mc = base.replace(num_layers=cfg["num_hidden_layers"], d_model=d,
                      d_ff=cfg["intermediate_size"],
                      vocab_size=cfg["vocab_size"], attn=attn,
                      max_seq_len=cfg["max_position_embeddings"])
    if mc.padded_vocab != cfg["padded_vocab_size"]:
        raise ValueError(f"the program pads the vocabulary to "
                         f"{mc.padded_vocab}, the file says "
                         f"{cfg['padded_vocab_size']}")
    return mc


def to_program(p):
    """Benchmark layout -> the program's parameter tree (no copies)."""
    mixer = {k: p[k] for k in ("wq", "wk", "wv", "wo")}
    ffn = {"w_gate": p["w_gate"], "w_in": p["w_in"], "w_out": p["w_out"]}
    return {"embed": {"table": p["embed"]}, "final_norm": {},
            "decoder": {"main": {"p0": {"norm1": {}, "mixer": mixer,
                                        "norm2": {}, "ffn": ffn}}}}


def from_program(tree):
    """The program's parameter tree -> benchmark layout."""
    blk = tree["decoder"]["main"]["p0"]
    out = {"embed": tree["embed"]["table"]}
    out.update(blk["mixer"])
    out.update(blk["ffn"])
    return out


def check_layout(jax, model, params):
    """Raise unless ``to_program(params)`` has the tree structure and
    shapes of the program's own initialisation."""
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: to_program(params))
    ws = jax.tree.structure(want)
    gs = jax.tree.structure(got)
    if ws != gs:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{gs}\n{ws}")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a} against the program's {b}")
