"""The OLMo family module holds the reference and the counts that the
harness had before they moved into ``bench/models/olmo.py``: at a tiny
size and a fixed seed on the CPU, the weights, the loss and the logits
(float32, bfloat16, float8 operands) are the recorded ones to the bit,
and every count is the recorded number (``data/olmo_golden.json``,
written by the code before the move)."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, counts
from bench import reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "data", "olmo_golden.json")) as f:
    GOLD = json.load(f)
CFG = GOLD["config"]


def _sha(a):
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


@pytest.fixture(scope="module")
def inputs():
    seed, B, S = GOLD["seed"], GOLD["batch"], GOLD["seq"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: ref.make_params(CFG, k))(
            jax.random.PRNGKey(seed))
    tok = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0,
                             CFG["vocab_size"])
    tgt = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0,
                             CFG["vocab_size"])
    return params, tok, tgt


def test_the_family_is_found_by_model_type():
    fam = common.family(CFG)
    assert fam is common.family({"model_type": "olmo"})
    assert fam.__file__ == os.path.join(ROOT, "bench", "models", "olmo.py")
    with pytest.raises(SystemExit):
        common.family({"model_type": "no-such-family"})


def test_weights_match_golden(inputs):
    params = inputs[0]
    assert sorted(params) == sorted(GOLD["params"])
    for k, v in params.items():
        assert list(v.shape) == GOLD["params"][k]["shape"]
        assert _sha(v) == GOLD["params"][k]["sha256"], k


def test_loss_matches_golden(inputs):
    params, tok, tgt = inputs
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(lambda p, t, y: ref.loss(CFG, p, t, y))(
            params, tok, tgt))
    assert got == GOLD["loss"]


@pytest.mark.parametrize("name,dtype,mm", [
    ("logits", jnp.float32, ref.matmul),
    ("logits_bf16", jnp.bfloat16, ref.matmul),
    ("logits_fp8", jnp.bfloat16, ref.fp8_matmul),
], ids=["float32", "bfloat16", "fp8_operands"])
def test_logits_match_golden(inputs, name, dtype, mm):
    params, tok, _ = inputs
    with jax.default_matmul_precision("highest"):
        lg = jax.jit(lambda p, t: ref.logits(CFG, ref.cast(p, dtype), t, mm))(
            params, tok)
    lg = np.asarray(lg).astype(np.float32)
    want = GOLD[name]
    assert list(lg.shape) == want["shape"]
    assert [float(x) for x in lg.reshape(-1)[:8]] == want["first"]
    assert _sha(lg) == want["sha256"]


def _config(name):
    if name == "tiny":
        return CFG
    return common.load_json(os.path.join(ROOT, "bench", "configs",
                                         name + ".json"))


@pytest.mark.parametrize("name", sorted(GOLD["counts"]))
def test_counts_match_golden(name):
    cfg, want = _config(name), GOLD["counts"][name]
    got = {"params": counts.params(cfg),
           "matmul_params": counts.matmul_params(cfg),
           "attn_flops_100": counts.attn_flops(cfg, 100),
           "train_flops_per_token_512": counts.train_flops_per_token(cfg, 512),
           "prefill_flops_64": counts.prefill_flops(cfg, 64),
           "decode_flops_10": counts.decode_flops(cfg, 10),
           "kv_bytes_per_position": counts.kv_bytes_per_position(cfg),
           "weight_bytes": counts.weight_bytes(cfg),
           "decode_step_bytes_100": counts.decode_step_bytes(cfg, 100)}
    assert got == want


def test_reference_in_blocks_of_rows_matches_the_whole_batch(inputs):
    """An agent's batch taken two sequences at a time gives the loss and
    gradients of the whole batch, to rounding."""
    params, tok, tgt = inputs
    stack = jax.tree.map(lambda x: jnp.stack([x, 0.5 * x]), params)
    # four distinct sequences per agent
    ab, ba = jnp.concatenate([tok, tgt]), jnp.concatenate([tgt, tok])
    toks, tgts = jnp.stack([ab, ba]), jnp.stack([ba, ab])
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(ref.agent_grads(CFG, jnp.float32))(stack, toks, tgts)
        blocks = jax.jit(ref.agent_grads(CFG, jnp.float32, 2))(
            stack, toks, tgts)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocks)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError):
        jax.jit(ref.agent_grads(CFG, jnp.float32, 3))(stack, toks, tgts)
