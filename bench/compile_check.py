"""Compile each cell's timed program at its real size for a described v5e,
with no chip, and print what the compiler says it needs per device.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [workload ...]

Run by hand before a cell's first chip run: a program that does not fit
is refused here at no chip time. Training cells compile their segment
(a cell with one agent per chip on the described host's chips, the
numbers per chip); serving cells the prefill at each prompt length and
the decode step.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common  # noqa: E402


def show(name, compiled, t0=None):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes} B, outputs "
          f"{m.output_size_in_bytes} B, temporaries {m.temp_size_in_bytes} "
          f"B, aliased {m.alias_size_in_bytes} B; {total / 1e9:.2f} GB in "
          f"all; code {m.generated_code_size_in_bytes} B"
          + (f"; compiled in {time.perf_counter() - t0:.1f} s"
             if t0 is not None else ""), flush=True)


def train_cell(jax, cfg, traffic, sh, mesh=None):
    import jax.numpy as jnp
    from bench import program
    from repro.core import dsgd
    from repro.models import build_model
    from repro.optim import make_optimizer
    from bench import reference as ref
    job = cfg["job"]
    m, S, H = job["agents"], traffic["rounds_per_segment"], \
        traffic["local_steps"]
    model = build_model(program.model_config(cfg))
    opt = make_optimizer("adamw", job["lr"], weight_decay=job["weight_decay"])
    box = {}

    def init():
        p = ref.make_params(cfg, jax.random.PRNGKey(0))
        st, box["spec"] = dsgd.init_panel_state(
            lambda _: program.to_program(cfg, p), opt, m,
            jax.random.PRNGKey(1), same_init=True,
            residency=f"moments={job['moments']}", mesh=mesh)
        return st
    state = jax.eval_shape(init)
    seg = dsgd.make_panel_segment(model.loss_fn, opt, H, box["spec"])

    if mesh is None:
        rep = rows = sh
        shardings = jax.tree.map(lambda _: sh, state)
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        from bench.train import agent_rows
        rep, rows = NamedSharding(mesh, PartitionSpec()), agent_rows(mesh, 2)
        shardings = dsgd.panel_state_shardings(state, box["spec"])

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    state = jax.tree.map(lambda x, s: sds(x.shape, x.dtype, s), state,
                         shardings)
    shp = (S, H, m, job["batch"], job["seq_len"])
    batch = {"tokens": sds(shp, jnp.int32, rows),
             "targets": sds(shp, jnp.int32, rows),
             "mask": sds(shp, jnp.float32, rows)}
    t0 = time.perf_counter()
    show("segment", seg.lower(
        state, batch, sds((S, m, m), jnp.float32),
        sds((2,), jnp.uint32), sds((S,), jnp.bool_), sds((S,), jnp.bool_),
        None).compile(), t0)


def serve_cell(jax, cfg, traffic, sh):
    import jax.numpy as jnp
    from bench import program
    from bench import reference as ref
    from repro.models import build_model
    from repro.serving.engine import make_decode_fn, make_prefill_fn
    model = build_model(program.model_config(cfg))
    sv = cfg["serving"]
    params = jax.eval_shape(lambda: program.to_program(
        cfg, ref.make_params(cfg, jax.random.PRNGKey(0))))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), params)
    pre = make_prefill_fn(model, max_len=sv["max_len"])
    for n in traffic["prompt_lengths"]:
        show(f"prefill {n}", pre.lower(params, {"tokens": jax.ShapeDtypeStruct(
            (1, n), jnp.int32, sharding=sh)}).compile())
    caches = jax.eval_shape(lambda: model.init_cache(sv["slots"],
                                                     sv["max_len"]))
    caches = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), caches)
    C = sv["slots"]
    show("decode step", make_decode_fn(model).lower(
        params, caches, jax.ShapeDtypeStruct((C, 1), jnp.int32, sharding=sh),
        jax.ShapeDtypeStruct((C,), jnp.int32, sharding=sh)).compile())


def main(argv=None):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import program  # noqa: F401
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    bench = common.load_benchmark()
    names = argv if argv else [w["name"] for w in bench["workloads"]]
    for name in names:
        cell, cfg, traffic, _ = common.find_cell(name)
        print(f"== {name} (described {topo.devices[0].device_kind}, "
              f"{cell['chips']} of {len(topo.devices)} chips)", flush=True)
        if traffic["kind"] == "train":
            mesh = None
            if cell["chips"] > 1:
                from jax.sharding import AxisType
                from repro.launch.mesh import TRAIN_AXES
                mesh = jax.make_mesh((1, cell["chips"], 1, 1), TRAIN_AXES,
                                     devices=topo.devices[:cell["chips"]],
                                     axis_types=(AxisType.Auto,) * 4)
            train_cell(jax, cfg, traffic, sh, mesh)
        else:
            serve_cell(jax, cfg, traffic, sh)


if __name__ == "__main__":
    main(sys.argv[1:])
