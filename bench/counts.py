"""Operations and bytes the algorithm needs, from a configuration file's
shapes alone: the yardstick of every share of a peak. A later kernel that
does the same work reads against the same counts.

The model's matrices are the attention projections, the SwiGLU MLP and
the tied head over the padded vocabulary (the embedding lookup does no
arithmetic). Causal attention needs, per layer and token at position i,
2 * heads * head_dim * (i + 1) multiply-adds for the scores and as many
for the values.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def matmul_params(cfg):
    """Weights that multiply every token: the layers and the tied head."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["padded_vocab_size"])


def params(cfg):
    """Every parameter the program holds (the tied table counted once)."""
    return matmul_params(cfg)


def attn_flops(cfg, positions):
    """Forward attention FLOPs of all layers for one token that attends to
    ``positions`` positions (itself included)."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg) * positions
            * cfg["num_hidden_layers"])


def train_flops_per_token(cfg, seq):
    """Forward and backward (3x the forward) per trained token, averaged
    over the positions of a causal sequence of length ``seq``.
    Recomputation under rematerialisation does not count."""
    return 3 * (2 * matmul_params(cfg) + attn_flops(cfg, (seq + 1) / 2))


def prefill_flops(cfg, length):
    """Forward FLOPs of a prompt of ``length`` tokens (all positions, as
    the prefill computes them)."""
    return (2 * matmul_params(cfg) * length
            + attn_flops(cfg, 1) * length * (length + 1) / 2)


def decode_flops(cfg, context):
    """Forward FLOPs of one decoded token that attends to ``context``
    positions."""
    return 2 * matmul_params(cfg) + attn_flops(cfg, context)


def kv_bytes_per_position(cfg, dtype="float32"):
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * DTYPE_BYTES[dtype])


def weight_bytes(cfg, dtype="float32"):
    return params(cfg) * DTYPE_BYTES[dtype]


def decode_step_bytes(cfg, live_positions, dtype="float32"):
    """HBM bytes one decode step needs: every weight once, and the keys and
    values of the live positions of every slot (not the empty rest of a
    dense cache)."""
    return (weight_bytes(cfg, dtype)
            + kv_bytes_per_position(cfg, dtype) * live_positions)


def adamw_bytes(n, param="float32", grad="float32", moment="float32"):
    """HBM bytes of one AdamW step over ``n`` parameters: read the
    parameter, its gradient and both moments; write the parameter and
    both moments."""
    p, g, m = DTYPE_BYTES[param], DTYPE_BYTES[grad], DTYPE_BYTES[moment]
    return n * (2 * p + g + 4 * m)
