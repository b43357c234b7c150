"""Operations and bytes the algorithm needs, from a configuration file's
shapes alone: the yardstick of every share of a peak. A later kernel that
does the same work reads against the same counts.

What depends on the architecture is the model family's
(``bench/models/<model_type>.py``): the parameters the program holds;
the matmul parameters each token multiplies (the active ones: the
experts a token is routed to, not every expert; the head, tied to the
embedding or not, over the padded vocabulary; the embedding lookup does
no arithmetic); the attention FLOPs of a token at a given context; and
the cache bytes of one position. The rest is here: a matmul parameter
costs 2 FLOPs a token forward, and backward twice that.
"""
from __future__ import annotations

from bench.common import family

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def matmul_params(cfg):
    """Weights that multiply every token, the head included."""
    return family(cfg).matmul_params(cfg)


def params(cfg):
    """Every parameter the program holds (a tied table counted once)."""
    return family(cfg).params(cfg)


def attn_flops(cfg, positions):
    """Forward attention FLOPs of all layers for one token that attends to
    ``positions`` positions (itself included)."""
    return family(cfg).attn_flops(cfg, positions)


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
    """Cache bytes of one position (keys and values, or a latent cache)."""
    return family(cfg).cache_bytes_per_position(cfg, DTYPE_BYTES[dtype])


def weight_bytes(cfg, dtype="float32"):
    return params(cfg) * DTYPE_BYTES[dtype]


def decode_step_bytes(cfg, live_positions, dtype="float32"):
    """HBM bytes one decode step needs: every weight once, and the cache
    of the live positions of every slot (not the empty rest of a dense
    cache)."""
    return (weight_bytes(cfg, dtype)
            + kv_bytes_per_position(cfg, dtype) * live_positions)


def adamw_bytes(n, param="float32", grad="float32", moment="float32"):
    """HBM bytes of one AdamW step over ``n`` parameters: read the
    parameter, its gradient and both moments; write the parameter and
    both moments."""
    p, g, m = DTYPE_BYTES[param], DTYPE_BYTES[grad], DTYPE_BYTES[moment]
    return n * (2 * p + g + 4 * m)
