"""Every Pallas kernel compiles for a TPU v5e at real widths.

The TPU compiler is installed beside JAX, so a kernel can be compiled for
a described (not attached) v5e chip: what Mosaic refuses here — a block
not aligned to the tiling, an unsupported in-kernel shape cast, too much
VMEM — it would refuse on the chip. Nothing runs; the interpret-mode
tests elsewhere check the numbers.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import gossip_mix, merge_ops, opt_fused, panel_reduce
from repro.kernels import wire_quant as wq
from repro.optim import make_optimizer
from repro.residency import get_storage

M = 4                      # agents: the rows of the (m, D) panel
D = 2048 * 8192            # one olmo-1b FFN matrix of columns
GROUP = get_storage("int8").group


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described-chip executable is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no describer
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip; return its HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I8, U8 = jnp.float32, jnp.int8, jnp.uint8
PANEL = ((M, D), F32)
ROW_SCALE = ((M, 1), F32)
GROUP_SCALE = ((M, D // GROUP), F32)
OFF = dict(interpret=False)


def _fused_adamw(g, p, qm, sm, qv, sv, um, uv):
    st = get_storage("int8")
    opt = make_optimizer("adamw", 1e-3)
    lr, bc1, bc2 = opt.hyper(jnp.ones((M,), jnp.int32))
    return opt_fused.adamw_fused_int8_panel(
        g, p, qm, sm, qv, sv, um, uv, lr, bc1, bc2, group=st.group,
        core=opt.core, transform_fwd=st.transform_fwd,
        transform_inv=st.transform_inv, **OFF)


def _flash(q, k, v):
    return fa.flash_attention_bh(q, k, v, block_q=128, block_k=128, **OFF)


KERNELS = {
    "gossip_mix": (functools.partial(gossip_mix.gossip_mix_panel, **OFF),
                   [((M, M), F32), PANEL]),
    # the consensus-folded mix: an extra 1^T/m row on W
    "gossip_mix_folded": (
        functools.partial(gossip_mix.gossip_mix_panel, **OFF),
        [((M + 1, M), F32), PANEL]),
    "panel_reduce": (
        functools.partial(panel_reduce.panel_mean_consensus, **OFF),
        [PANEL]),
    "int8_quantize": (lambda x, s: wq.quantize_int8_panel(x, s, **OFF),
                      [PANEL, ROW_SCALE]),
    "int8_quantize_stochastic": (
        lambda x, s, u: wq.quantize_int8_panel(x, s, u, **OFF),
        [PANEL, ROW_SCALE, PANEL]),
    "int8_quantize_native": (
        lambda x, s: wq.quantize_int8_panel_native(x, 7, s),
        [PANEL, ROW_SCALE]),
    "int8_dequantize": (
        functools.partial(wq.dequantize_int8_panel, **OFF),
        [((M, D), I8), ROW_SCALE]),
    "int8g_quantize": (
        lambda x, s, u: wq.quantize_int8_grouped_panel(x, s, u,
                                                       group=GROUP, **OFF),
        [PANEL, GROUP_SCALE, PANEL]),
    "int8g_dequantize": (
        lambda q, s: wq.dequantize_int8_grouped_panel(q, s, group=GROUP,
                                                      **OFF),
        [((M, D), I8), GROUP_SCALE]),
    "int4_quantize": (
        lambda x, s, u: wq.quantize_int4_panel(x, s, u, **OFF),
        [PANEL, GROUP_SCALE, PANEL]),
    "int4_dequantize": (
        functools.partial(wq.dequantize_int4_panel, **OFF),
        [((M, D), I8), GROUP_SCALE]),
    "int4_pack": (functools.partial(wq.pack_int4_panel, **OFF),
                  [((M, D), I8)]),
    "int4_unpack": (lambda p: wq.unpack_int4_panel(p, D, **OFF),
                    [((M, D // 2), U8)]),
    "topk_sparsify": (
        lambda x, t: wq.sparsify_topk_panel(x, t, **OFF),
        [PANEL, ROW_SCALE]),
    "weighted_colmerge": (
        functools.partial(merge_ops.weighted_colmerge, **OFF),
        [PANEL, PANEL]),
    "ties_colmerge": (functools.partial(merge_ops.ties_colmerge, **OFF),
                      [PANEL, ROW_SCALE]),
    "adamw_fused_int8": (
        _fused_adamw,
        [PANEL, PANEL, ((M, D), I8), GROUP_SCALE, ((M, D), I8),
         GROUP_SCALE, PANEL, PANEL]),
    # olmo-1b attention: 16 heads x 128, batch 4 x seq 512, heads merged
    "flash_attention": (_flash, [((4 * 16, 512, 128), F32)] * 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    hlo = _compile(one_chip, fn, *shapes)
    # a Mosaic kernel, not an interpreted or XLA fallback
    assert "tpu_custom_call" in hlo
