"""Sharded-lowering integration test: a scaled-down version of the dry-run
(8 host devices via the tests/_multidevice.py subprocess harness, so the
main test process keeps 1 device). Asserts lower+compile succeeds for a
reduced arch on a (1,2,2,2) training mesh and that the collective parser
finds traffic."""
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import json, dataclasses
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core import dsgd
    from repro.models import build_model
    from repro.models.sharding import (TRAIN_RULES, activation_sharding,
                                       resolve)
    from repro.optim import make_optimizer
    from repro.utils.hlo import collective_bytes

    cfg = get_config("olmo-1b").reduced(d_model=256)
    cfg = cfg.replace(dist=dataclasses.replace(cfg.dist, scan_layers=False,
                                               agents_per_pod=2))
    model = build_model(cfg)
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(agents=2, fsdp=2, model=2)
    m = 2
    opt = make_optimizer("adamw", 1e-3)
    key = jax.random.PRNGKey(0)
    state_shapes = jax.eval_shape(
        lambda k: dsgd.init_state(model.init_params, opt, m, k), key)
    params_ps = resolve(model.param_spec(), state_shapes["params"], mesh,
                        TRAIN_RULES, prefix=(("pod", "agent"),))
    state_ps = {"params": params_ps,
                "opt": {"m": params_ps, "v": params_ps, "step_count": P()},
                "step": P()}
    B, S = 8, 64
    batch = {"tokens": jax.ShapeDtypeStruct((m, B, S), jnp.int32),
             "targets": jax.ShapeDtypeStruct((m, B, S), jnp.int32),
             "mask": jax.ShapeDtypeStruct((m, B, S), jnp.float32)}
    bp = {k: P(("pod", "agent"), "fsdp") for k in batch}
    step = dsgd.make_dsgd_step(model.loss_fn, opt, monitor=False)
    named = lambda t: jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), t,
        is_leaf=lambda x: isinstance(x, P))
    fn = jax.jit(step, in_shardings=(named(state_ps), named(bp),
                                     NamedSharding(mesh, P()),
                                     NamedSharding(mesh, P())))
    W = jax.ShapeDtypeStruct((m, m), jnp.float32)
    k_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    with activation_sharding(mesh, TRAIN_RULES):
        compiled = fn.lower(state_shapes, batch, W, k_sds).compile()
    ma = compiled.memory_analysis()
    per_kind, total, counts = collective_bytes(compiled.as_text())
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jaxlib returns [dict]
        ca = ca[0] if ca else {}
    print(json.dumps({
        "ok": True,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "coll_bytes": total,
        "kinds": sorted(per_kind),
        "flops": ca.get("flops", 0.0),
    }))
""")


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_train_step_lowers_and_has_collectives(multidevice):
    rec = multidevice(SCRIPT, devices=8, timeout=540)
    assert rec["ok"]
    assert rec["coll_bytes"] > 0  # gossip + TP collectives present
    assert rec["flops"] > 0
