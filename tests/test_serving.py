"""Serving engine: OOV-safe sampling, donated caches, continuous batching
(slot lifecycle, bit-exact parity with single-request generate), merged-model
checkpoint round-trip."""
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore, save
from repro.configs import get_config
from repro.core import dsgd
from repro.core.gossip import merged_model
from repro.models import build_model
from repro.optim import make_optimizer
from repro.serving import engine as engine_mod
from repro.serving import (Request, ServingEngine, generate, make_decode_fn,
                           make_prefill_fn, mask_oov, sample_token)

pytestmark = pytest.mark.serve


def _tiny(arch="olmo-1b", d=64, vocab=64, **kw):
    cfg = get_config(arch).reduced(d_model=d, vocab=vocab, **kw)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(i, S, vocab):
    key = jax.random.fold_in(jax.random.PRNGKey(1), i)
    return np.asarray(jax.random.randint(key, (S,), 0, vocab), np.int32)


def _batch_of(req):
    b = {"tokens": jnp.asarray(req.tokens[None])}
    for k, v in req.extras.items():
        b[k] = jnp.asarray(v)[None]
    return b


# ---------------------------------------------------------------------------
# basic generate (pre-existing behavior)
# ---------------------------------------------------------------------------


def test_generate_shapes_and_determinism():
    cfg, model, params = _tiny(d=128, vocab=128)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0,
                                          cfg.vocab_size)}
    out1 = generate(model, params, batch, 6)
    out2 = generate(model, params, batch, 6)
    assert out1.shape == (3, 6)
    np.testing.assert_array_equal(out1, out2)  # greedy is deterministic
    assert out1.dtype == np.int32
    assert (out1 >= 0).all() and (out1 < cfg.vocab_size).all()


def test_generate_temperature_sampling_varies():
    cfg, model, params = _tiny(d=128, vocab=128)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          cfg.vocab_size)}
    a = generate(model, params, batch, 8, temperature=2.0,
                 rng=jax.random.PRNGKey(2))
    b = generate(model, params, batch, 8, temperature=2.0,
                 rng=jax.random.PRNGKey(3))
    assert not np.array_equal(a, b)


def test_generate_vlm_with_prefix():
    cfg, model, params = _tiny("qwen2-vl-72b", d=128, vocab=128)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          cfg.vocab_size),
             "patch_embeds": jax.random.normal(jax.random.PRNGKey(2),
                                               (2, cfg.mm_prefix,
                                                cfg.d_model))}
    out = generate(model, params, batch, 4)
    assert out.shape == (2, 4)


# ---------------------------------------------------------------------------
# bugfix: sampling must never emit out-of-vocab (padded_vocab tail)
# ---------------------------------------------------------------------------


def test_sample_token_masks_padded_vocab_tail():
    # craft logits whose maximum sits in the padding tail
    logits = jnp.zeros((2, 16)).at[:, 13].set(100.0).at[0, 3].set(1.0)
    tok = sample_token(logits, jax.random.PRNGKey(0), 0.0, vocab_size=10)
    np.testing.assert_array_equal(np.asarray(tok), [3, 0])
    for s in range(8):
        tok = sample_token(logits, jax.random.PRNGKey(s), 1.0, vocab_size=10)
        assert (np.asarray(tok) < 10).all()
    # unmasked, the tail wins — the bug this guards against
    assert (np.asarray(jnp.argmax(logits, -1)) == 13).all()
    masked = mask_oov(logits, 10)
    assert np.isneginf(np.asarray(masked)[:, 10:]).all()


def test_generate_never_emits_oov_ids():
    """padded_vocab (256) > vocab_size (250): the head's random-init padding
    columns must never be sampled, greedy or tempered."""
    cfg, model, params = _tiny(vocab=250)
    assert cfg.padded_vocab > cfg.vocab_size
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                          cfg.vocab_size)}
    greedy = generate(model, params, batch, 8)
    temped = generate(model, params, batch, 8, temperature=1.5,
                      rng=jax.random.PRNGKey(2))
    assert (greedy < cfg.vocab_size).all() and (greedy >= 0).all()
    assert (temped < cfg.vocab_size).all() and (temped >= 0).all()


def test_engine_never_emits_oov_ids():
    cfg, model, params = _tiny(vocab=250)
    eng = ServingEngine(model, params, max_concurrency=2, max_len=24,
                        temperature=1.5, rng=jax.random.PRNGKey(3))
    reqs = [Request(rid=i, tokens=_prompt(i, 8, cfg.vocab_size), max_new=8)
            for i in range(3)]
    out = eng.serve(reqs)
    for v in out.values():
        assert (v < cfg.vocab_size).all() and (v >= 0).all()


# ---------------------------------------------------------------------------
# bugfix: donated caches — no per-step reallocation, no per-token host sync
# ---------------------------------------------------------------------------


def _leaf_ptrs(tree):
    return sorted(x.unsafe_buffer_pointer()
                  for x in jax.tree_util.tree_leaves(tree))


def test_decode_fn_donates_cache_in_place():
    cfg, model, params = _tiny()
    prefill = make_prefill_fn(model, max_len=32)
    logits, caches = prefill(params, {"tokens": jnp.asarray(
        _prompt(0, 8, cfg.vocab_size)[None])})
    decode = make_decode_fn(model)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    before = _leaf_ptrs(caches)
    old_leaves = jax.tree_util.tree_leaves(caches)
    _, new_caches = decode(params, caches, tok, jnp.asarray(8, jnp.int32))
    # the donated input buffers are consumed...
    assert all(x.is_deleted() for x in old_leaves)
    # ...and the new cache aliases exactly the same device buffers
    assert _leaf_ptrs(new_caches) == before


def test_engine_cache_buffer_persists_across_ticks():
    cfg, model, params = _tiny()
    eng = ServingEngine(model, params, max_concurrency=2, max_len=32)
    eng.submit(Request(rid=0, tokens=_prompt(0, 8, cfg.vocab_size),
                       max_new=6))
    eng.admit()
    ptrs = _leaf_ptrs(eng.caches)
    for _ in range(4):
        eng.step()
    assert _leaf_ptrs(eng.caches) == ptrs  # same buffers, every tick
    # admission (insert) also updates the donated buffer in place
    eng.submit(Request(rid=1, tokens=_prompt(1, 8, cfg.vocab_size),
                       max_new=4))
    eng.admit()
    assert _leaf_ptrs(eng.caches) == ptrs


# ---------------------------------------------------------------------------
# continuous batching: parity, slot lifecycle, EOS, mixed batches
# ---------------------------------------------------------------------------


_PARITY_ARCHS = [
    ("olmo-1b", {}),                      # GQA, tied embeddings
    ("recurrentgemma-2b", {"layers": 3}),  # RG-LRU + local sliding window
    ("seamless-m4t-medium", {}),          # enc-dec: padded cross-KV rows
]


def _force_operands(monkeypatch):
    """Make the engine take its bfloat16 operand copy, as on a TPU at the
    default precision."""
    monkeypatch.setattr(engine_mod, "f32_matmul_is_bf16_pass", lambda: True)


@pytest.mark.parametrize("arch,kw,operands", [
    pytest.param(arch, kw, operands,
                 id=f"{arch}-kw{i}" + ("-operands" if operands else ""))
    for operands in (False, True)
    for i, (arch, kw) in enumerate(_PARITY_ARCHS)])
def test_continuous_batching_bit_identical_to_sequential(arch, kw, operands,
                                                         monkeypatch):
    """N heterogeneous requests through the slotted engine produce
    bit-identical tokens to N single-request generate calls (temp 0) given
    the params the engine serves: the caller's, or its bfloat16 operand
    copy where that is forced on."""
    cfg, model, params = _tiny(arch, **kw)
    if operands:
        _force_operands(monkeypatch)
    max_len = 48
    eng = ServingEngine(model, params, max_concurrency=3, max_len=max_len)
    assert (eng.stats["operand_leaves"] > 0) == operands
    params = eng.params
    reqs = []
    for i in range(5):
        S = [8, 12][i % 2]
        extras = {}
        if cfg.encoder_layers:
            extras["frame_embeds"] = np.asarray(jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(7), i),
                (S, cfg.d_model)))
        reqs.append(Request(rid=i, tokens=_prompt(i, S, cfg.vocab_size),
                            max_new=4 + (i % 3), extras=extras))
    out = eng.serve(reqs)
    assert eng.stats["admitted"] == 5 and eng.stats["retired"] == 5
    assert 0.0 < eng.occupancy <= 1.0
    for r in reqs:
        ref = generate(model, params, _batch_of(r), r.max_new,
                       max_len=max_len)[0]
        np.testing.assert_array_equal(out[r.rid], ref)


def _eqns(jaxpr):
    """Every equation of a closed jaxpr, nested ones (jit, scan) included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    yield from _eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _operand_leaves(params):
    """(path, leaf) of the decoder's dense matmul operands (olmo-like)."""
    names = {"wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"}
    return [(p, x) for p, x in
            jax.tree_util.tree_flatten_with_path(params["decoder"])[0]
            if p[-1].key in names]


def test_engine_operand_copy_is_never_cast_again(monkeypatch):
    """With the decision forced on: the decode step converts no weight, the
    snapshot reports the cast leaves and their bytes, and a decode step's
    logits agree with the float32-param engine's to bfloat16 rounding."""
    cfg, model, params = _tiny(d=64, vocab=64)
    eng32 = ServingEngine(model, params, max_concurrency=3, max_len=40)
    _force_operands(monkeypatch)
    eng = ServingEngine(model, params, max_concurrency=3, max_len=40)
    ops = _operand_leaves(eng.params)
    assert ops and all(x.dtype == jnp.bfloat16 for _, x in ops)
    assert eng.params["embed"]["table"] is params["embed"]["table"]
    snap = eng.snapshot()
    assert snap["operand_leaves"] == len(ops) == 7
    assert snap["operand_bytes"] == sum(2 * x.size for _, x in ops)
    # no weight (a whole stack or one layer's slice) is converted in the step
    shapes = {x.shape for _, x in ops} | {x.shape[1:] for _, x in ops}
    C = eng.C
    jaxpr = jax.make_jaxpr(eng._step_fn)(
        eng.params, eng.caches, jnp.zeros((C,), jnp.int32),
        jnp.zeros((C,), jnp.int32), jax.random.PRNGKey(0))
    eqns = list(_eqns(jaxpr.jaxpr))
    casts = [e for e in eqns if e.primitive.name == "convert_element_type"]
    assert any(e.params["new_dtype"] == jnp.bfloat16 for e in casts)
    assert not [e for e in casts if e.invars[0].aval.shape in shapes]
    # each weight meets a bfloat16 activation: a dot of mixed dtypes would
    # have the compiler round the weight up to float32 in every step
    dots = [[v.aval.dtype for v in e.invars] for e in eqns
            if e.primitive.name == "dot_general"]
    assert dots.count([jnp.bfloat16, jnp.bfloat16]) == len(ops)
    assert all(a == b for a, b in dots)
    # one decode step after a prefill, against the float32 weights
    batch = {"tokens": jnp.asarray(_prompt(0, 12, cfg.vocab_size)[None])}
    out = []
    for e in (eng32, eng):
        logits, row = e._prefill(e.params, batch)
        tok = jnp.argmax(logits[:, :cfg.vocab_size], -1)[:, None]
        logits, _ = jax.jit(model.decode_step)(e.params, row, tok, 12)
        out.append(np.asarray(logits, np.float64))
    ref, got = out
    # a few bfloat16 roundings (2**-8 relative each) through two layers
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert 0.0 < err < 2e-2


def test_engine_serves_callers_params_on_cpu():
    """On a CPU the decision is off: the engine's params are the caller's
    own leaves and nothing is reported cast."""
    assert jax.default_backend() == "cpu"
    assert not engine_mod.f32_matmul_is_bf16_pass()
    _, model, params = _tiny()
    eng = ServingEngine(model, params, max_concurrency=2, max_len=32)
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)):
        assert a is b
    snap = eng.snapshot()
    assert snap["operand_leaves"] == 0 and snap["operand_bytes"] == 0


def test_mixed_batch_multimodal_prefix_parity():
    """VLM requests with and without a patch-embed prefix share slots."""
    cfg, model, params = _tiny("qwen2-vl-72b")
    max_len = 48
    eng = ServingEngine(model, params, max_concurrency=3, max_len=max_len)
    reqs = []
    for i in range(4):
        extras = {}
        if i % 2 == 0:
            extras["patch_embeds"] = np.asarray(jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(8), i),
                (cfg.mm_prefix, cfg.d_model)))
        reqs.append(Request(rid=i, tokens=_prompt(i, 8, cfg.vocab_size),
                            max_new=5, extras=extras))
    out = eng.serve(reqs)
    for r in reqs:
        ref = generate(model, params, _batch_of(r), r.max_new,
                       max_len=max_len)[0]
        np.testing.assert_array_equal(out[r.rid], ref)


def test_slot_insert_evict_reuse():
    cfg, model, params = _tiny()
    eng = ServingEngine(model, params, max_concurrency=2, max_len=32)
    r0 = Request(rid="a", tokens=_prompt(0, 8, cfg.vocab_size), max_new=12)
    r1 = Request(rid="b", tokens=_prompt(1, 8, cfg.vocab_size), max_new=12)
    eng.submit(r0)
    eng.submit(r1)
    eng.admit()
    assert eng.free_slots() == [] and eng.live_slots() == [0, 1]
    eng.step()
    # evict slot 0 mid-flight: slot frees, survivor is unperturbed
    eng.evict(0)
    assert eng.free_slots() == [0]
    out = eng.serve([])  # drain slot 1
    ref1 = generate(model, params, _batch_of(r1), r1.max_new, max_len=32)[0]
    np.testing.assert_array_equal(out["b"], ref1)
    # the evicted slot is reusable and serves a fresh request correctly
    r2 = Request(rid="c", tokens=_prompt(2, 8, cfg.vocab_size), max_new=6)
    out = eng.serve([r2])
    assert eng.stats["admitted"] == 3
    ref2 = generate(model, params, _batch_of(r2), r2.max_new, max_len=32)[0]
    np.testing.assert_array_equal(out["c"], ref2)


def test_eos_retires_slot_and_stops_generate():
    cfg, model, params = _tiny()
    req = Request(rid=0, tokens=_prompt(0, 8, cfg.vocab_size), max_new=10)
    free = generate(model, params, _batch_of(req), 10, max_len=32)[0]
    eos = int(free[2])  # declare a token the model emits to be "EOS"
    j = int(np.argmax(free == eos))  # first occurrence in the free run
    # generate: rows stop at eos and the tail is eos-padded
    out = generate(model, params, _batch_of(req), 10, max_len=32,
                   eos_id=eos)[0]
    np.testing.assert_array_equal(out[:j + 1], free[:j + 1])
    assert (out[j:] == eos).all()
    # engine: the slot retires at eos and the freed slot admits the queue
    eng = ServingEngine(model, params, max_concurrency=1, max_len=32,
                        eos_id=eos)
    nxt = Request(rid=1, tokens=_prompt(1, 8, cfg.vocab_size), max_new=4)
    served = eng.serve([req, nxt])
    assert list(served[0]) == list(free[:j + 1])  # ends AT the eos token
    assert served[0][-1] == eos
    assert eng.stats["admitted"] == 2 and eng.stats["retired"] == 2
    assert len(served[1]) == 4


def test_engine_rejects_oversized_request():
    cfg, model, params = _tiny()
    eng = ServingEngine(model, params, max_concurrency=1, max_len=16)
    eng.submit(Request(rid=0, tokens=_prompt(0, 12, cfg.vocab_size),
                       max_new=8))
    with pytest.raises(ValueError, match="max_len"):
        eng.admit()


# ---------------------------------------------------------------------------
# the paper's pipeline: train -> single global merge -> save -> serve
# ---------------------------------------------------------------------------


def test_serve_the_merged_model_end_to_end():
    """Train decentralized -> merge -> serve: the paper's full pipeline."""
    cfg, model, params = _tiny(vocab=64)
    m = 2
    opt = make_optimizer("adamw", 1e-3)
    state = dsgd.init_state(model.init_params, opt, m, jax.random.PRNGKey(0))
    step = jax.jit(dsgd.make_dsgd_step(model.loss_fn, opt))
    key = jax.random.PRNGKey(1)
    for t in range(2):
        key, k1, k2 = jax.random.split(key, 3)
        batch = {"tokens": jax.random.randint(k1, (m, 2, 16), 0, 64),
                 "targets": jax.random.randint(k2, (m, 2, 16), 0, 64),
                 "mask": jnp.ones((m, 2, 16), jnp.float32)}
        W = jnp.eye(m) if t == 0 else jnp.full((m, m), 1.0 / m)
        state, _ = step(state, batch, W.astype(jnp.float32), key)
    merged = merged_model(state["params"])
    out = generate(model, merged, {"tokens": jnp.zeros((2, 8), jnp.int32)}, 4)
    assert out.shape == (2, 4)


def test_merged_checkpoint_roundtrip_through_engine(tmp_path):
    """--save-merged -> serve --restore: the checkpointed merged artifact
    serves bit-identically to the in-memory merged model."""
    cfg, model, params = _tiny(vocab=64)
    m = 2
    opt = make_optimizer("adamw", 1e-3)
    state = dsgd.init_state(model.init_params, opt, m, jax.random.PRNGKey(0))
    step = jax.jit(dsgd.make_dsgd_step(model.loss_fn, opt))
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (m, 2, 16), 0, 64),
             "targets": jax.random.randint(key, (m, 2, 16), 0, 64),
             "mask": jnp.ones((m, 2, 16), jnp.float32)}
    state, _ = step(state, batch, jnp.full((m, m), 0.5, jnp.float32), key)
    merged = merged_model(state["params"])
    path = str(tmp_path / "merged.msgpack")
    save(path, merged)
    # restore into a DIFFERENT init to prove the artifact carries the model
    template = model.init_params(jax.random.PRNGKey(9))
    restored = restore(path, template)
    req = Request(rid=0, tokens=_prompt(0, 8, cfg.vocab_size), max_new=6)
    eng = ServingEngine(model, restored, max_concurrency=2, max_len=32)
    out = eng.serve([req])
    ref = generate(model, merged, _batch_of(req), 6, max_len=32)[0]
    np.testing.assert_array_equal(out[0], ref)


# ---------------------------------------------------------------------------
# per-request records and host spans
# ---------------------------------------------------------------------------


def test_engine_request_records_follow_each_request():
    """One record per admitted request, its times in order (arrival <=
    admission start <= first token <= retire) and its token count that of
    the output, which stays token-identical to ``generate``. A given
    arrival is kept; reset() drops the records."""
    cfg, model, params = _tiny()
    max_len = 32
    eng = ServingEngine(model, params, max_concurrency=2, max_len=max_len)
    reqs = [Request(rid=i, tokens=_prompt(i, 8, cfg.vocab_size),
                    max_new=3 + i) for i in range(4)]
    late = Request(rid=9, tokens=_prompt(9, 8, cfg.vocab_size), max_new=2,
                   arrival=time.perf_counter() - 5.0)
    out = eng.serve(reqs + [late])
    assert late.arrival is not None and reqs[0].arrival is None
    snap = eng.snapshot()
    recs = {r["rid"]: r for r in snap["requests"]}
    assert sorted(recs) == [0, 1, 2, 3, 9]
    for rid, r in recs.items():
        assert r["arrival"] <= r["admit"] <= r["first"] <= r["retire"]
        assert r["tokens"] == len(out[rid])
    assert recs[9]["arrival"] == late.arrival
    assert recs[9]["admit"] - recs[9]["arrival"] >= 5.0
    assert snap["latency"]["queue_wait_s"]["count"] == 5
    for r in reqs:
        ref = generate(model, params, _batch_of(r), r.max_new,
                       max_len=max_len)[0]
        np.testing.assert_array_equal(out[r.rid], ref)
    snap["requests"][0]["tokens"] = -1  # a copy: the engine's stays
    assert eng.snapshot()["requests"][0]["tokens"] != -1
    eng.reset()
    assert eng.snapshot()["requests"] == []


def test_engine_spans_cover_each_step_and_admission(monkeypatch):
    """With the host-span hook patched to a recorder: every step() is
    exactly one ``serve.step`` span holding one ``serve.fetch``, and every
    admission exactly one ``serve.admit`` holding one
    ``serve.first_token``; no span is open outside them."""
    from repro.serving import engine as engine_mod
    cfg, model, params = _tiny()
    eng = ServingEngine(model, params, max_concurrency=2, max_len=32)
    log = []

    @contextlib.contextmanager
    def recorder(name, **kw):
        log.append(("enter", name))
        yield
        log.append(("exit", name))

    monkeypatch.setattr(engine_mod, "annotate", recorder)
    reqs = [Request(rid=i, tokens=_prompt(i, 8, cfg.vocab_size),
                    max_new=2 + i) for i in range(3)]
    eng.serve(reqs)
    children = {"serve.step": ["serve.fetch"],
                "serve.admit": ["serve.first_token"]}
    stack, spans = [], []
    for kind, name in log:
        if kind == "enter":
            assert (name in children) == (not stack), (name, stack)
            if stack:
                assert name in children[stack[0][0]]
                stack[0][1].append(name)
            stack.append((name, []))
        else:
            top, inner = stack.pop()
            assert top == name
            if not stack:
                spans.append((name, inner))
    assert not stack
    for name, inner in spans:
        assert inner == children[name]
    assert sum(n == "serve.step" for n, _ in spans) == eng.stats["ticks"]
    assert sum(n == "serve.admit" for n, _ in spans) == 3
