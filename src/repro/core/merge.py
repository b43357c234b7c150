"""Global merging operators and counterfactual evaluation (paper §4.2-4.3).

Tree-level entry points over the panel-native merge-operator subsystem
(repro/merging): :func:`merge_stacked` merges an agent-stacked pytree
under any registered operator (the oracle the engine-internal path is
tested against), :func:`counterfactual_eval` evaluates the hypothetical
merged model without touching training state (Fig. 2c's light-blue
curve — ``launch/train.py --eval-merged-every``), and
:func:`gossip_merge_rounds` approximates the final merging with a
scanned, codec-aware segment of gossip rounds (Appendix C.3.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import merging as merging_mod
from repro import wire as wire_mod
from repro.core import panel as panel_mod
from repro.core.gossip import merged_model


def weighted_merge(params_stacked, weights):
    """sum_k w_k theta_k with convex weights (Def. 2's general merge)."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    return jax.tree.map(
        lambda x: jnp.tensordot(w, x.astype(jnp.float32), axes=1,
                                precision="highest"), params_stacked)


def uniform_merge(params_stacked):
    return merged_model(params_stacked)


def merge_stacked(params_stacked, merger="uniform", stats=None,
                  weights=None, live=None):
    """The merged (non-stacked, f32-leaf) model of an agent-stacked tree
    under a named merge operator (repro.merging) — the tree-path oracle
    of the segment engine's global rounds.

    ``stats`` are the operator's statistics PANELS
    (``{stat_name: {dtype-group: (m, D_g) f32}}`` — e.g.
    ``state["merge_stat"]`` from the panel engine; statistics live in
    panel layout because they are engine state). ``weights`` is the
    per-agent (m,) weight vector of the 'weighted' operator. ``live``
    ((m,) bool) merges the live agents only (an elastic run's final
    merge must not average in dead agents' stale rows)."""
    spec = panel_mod.make_spec(params_stacked)
    return merged_panel_tree(panel_mod.to_panel(params_stacked, spec),
                             spec, merger=merger, stats=stats,
                             weights=weights, live=live)


def counterfactual_eval(eval_fn, params_stacked, merger="uniform",
                        stats=None, weights=None, live=None):
    """Evaluate the hypothetical globally-merged model WITHOUT modifying
    training state (the light-blue curve of Fig. 2c), under any merge
    operator (``stats``/``weights`` as in :func:`merge_stacked`).

    Tree-level (replicated state / oracle use). For the engine's
    (possibly mesh-sharded) panel state use
    :func:`counterfactual_eval_panel` — re-panelising a sharded panel
    through a fresh unsharded spec inside jit miscompiles on meshes with
    an idle 'model' axis (unreduced replication doubles the values; the
    engine-spec path below keeps every op constrained)."""
    return eval_fn(merge_stacked(params_stacked, merger=merger,
                                 stats=stats, weights=weights, live=live))


def merged_panel_tree(panel, spec, merger=None, stats=None, weights=None,
                      live=None):
    """Merged (non-stacked, f32-leaf) model of an ENGINE panel under the
    spec's (or an explicit) operator — the panel-layout counterpart of
    :func:`merge_stacked`. Every op stays constrained to the spec's mesh
    layout, so this is safe to jit on sharded panel states (see
    :func:`counterfactual_eval`)."""
    mg = merging_mod.get_merger(spec.merger if merger is None else merger)
    stats = merging_mod.decode_stats(stats, spec)
    row = mg.merge_row(panel, stats=stats, weights=weights, spec=spec,
                       live=live)
    return panel_mod.from_panel(row, spec, cast=False)


def counterfactual_eval_panel(eval_fn, panel, spec, merger=None,
                              stats=None, weights=None, live=None):
    """:func:`counterfactual_eval` for the engine's panel state
    (``stats`` = ``state["merge_stat"]``): evaluates the hypothetical
    merged model without modifying the panel — what
    ``launch/train.py --eval-merged-every`` measures."""
    return eval_fn(merged_panel_tree(panel, spec, merger=merger,
                                     stats=stats, weights=weights,
                                     live=live))


def gossip_merge_rounds(params_stacked, sampler, rounds: int, rng,
                        wire=None, key=None, return_xi: bool = False):
    """Approximate the final global merging by multiple rounds of gossip
    on a (e.g. exponential) topology — paper Appendix C.3.4.

    Panelises once, samples every W^(t) up front (host side), and SCANS
    the fused FOLDED-MEAN mix (panel.mix_dense_mean — the engine's round
    primitive; its first m rows are bit-identical to plain mix_dense)
    over the stacked (rounds, m, m) matrices in ONE jitted dispatch —
    instead of the old host loop of per-round ``mix_dense`` dispatches
    that also bypassed the wire policy. ``wire`` names a codec from
    repro.wire for the gossip payload (stochastic codecs need ``key=``;
    error-feedback codecs are refused — this stateless approximation
    path carries no residual). ``return_xi=True`` additionally returns
    the per-round consensus-distance trace (rounds,) read off the folded
    mean — how fast the approximation is converging to the true merge."""
    spec = panel_mod.make_spec(params_stacked)
    if wire is not None:
        if wire_mod.get_codec(wire).error_feedback:
            raise ValueError(
                f"codec '{wire}' needs an error-feedback residual, which "
                "this stateless approximation path cannot carry; use the "
                "panel engine (dsgd.make_panel_segment) or 'int8'")
        spec = panel_mod.with_wire(spec, wire)
    Ws = jnp.asarray(np.stack([np.asarray(sampler(t, rng), np.float32)
                               for t in range(rounds)]))
    needs_key = any(wire_mod.get_codec(name).needs_key
                    for _, name in spec.wire)
    if needs_key and key is None:
        raise ValueError(
            f"wire codec '{wire}' uses stochastic rounding and needs an "
            "explicit key= for the scanned gossip rounds")
    keys = jax.random.split(key, rounds) if needs_key else None
    pan, xis = _scanned_gossip(spec)(
        panel_mod.to_panel(params_stacked, spec), (Ws, keys))
    out = panel_mod.from_panel(pan, spec)
    return (out, xis) if return_xi else out


@functools.lru_cache(maxsize=64)
def _scanned_gossip(spec):
    """Jitted folded-mean gossip scan, cached on the (hashable) spec so
    repeated gossip_merge_rounds calls (figures.py sweeps k) reuse one
    traced function instead of recompiling a fresh lambda per call."""

    def body(pan, xs):
        W, k = xs
        mixed, mean, _ = panel_mod.mix_dense_mean(pan, W, spec=spec, key=k)
        return mixed, panel_mod.consensus_from_mean(mixed, mean)

    return jax.jit(lambda pan, xs: jax.lax.scan(body, pan, xs))
