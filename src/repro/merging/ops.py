"""Panel-native merge operators: how ONE global merging combines agents.

The paper's headline result is that a single uniform global merging closes
the gap to parallel SGD; its discussion frames that as an opening for
model-merging research. This subsystem makes the merge OPERATOR pluggable
on the flat-panel engine (core/panel.py), mirroring the wire-codec
registry (repro/wire): every operator consumes the per-dtype
``{group: (m, D_g)}`` parameter panel (plus, for the statistical
operators, per-agent statistics panels carried in the segment state) and
produces ONE merged row ``{group: (D_g,) f32}``.

Operators (``MERGERS`` / :func:`get_merger`):

* ``uniform``  — the paper's merge: the per-group column mean. Bit-exact
  alias of the pre-subsystem ``panel.merged`` / ``global_merge`` path.
* ``weighted`` — per-AGENT convex weights: explicit ``weights=`` (e.g.
  softmax of held-out losses) or, by default, inverse squared consensus
  distance — agents far from the mean (stale under heterogeneity) are
  downweighted.
* ``var``      — per-COORDINATE inverse-variance (precision) weighting:
  each agent tracks an EMA mean/second-moment of its own parameter
  trajectory over rounds (two stat panels); coordinates that fluctuate
  across rounds are uncertain and get downweighted (a diagonal
  SWAG-style precision merge).
* ``fisher``   — diagonal-Fisher weighting (Matena & Raffel 2022, panel
  form): each agent accumulates an EMA of its squared gradients during
  the LOCAL steps (one stat panel, donated through the segment scan like
  PR 3's ``wire_err``); the merge is the Fisher-weighted column mean.
* ``ties``     — TIES (Yadav et al. 2023) on deviations from the mean:
  per-row top-``trim`` magnitude trim, per-column sign election, and the
  mean of surviving agreeing deviations added back to the reference row.
  Resolves sign interference that a plain mean cancels to mush.
* ``swa``      — merge of per-agent SWA/EMA accumulators maintained over
  the tail rounds (one stat panel updated once per round): averaging the
  smoothed iterates instead of the last ones.

Statistics contract: an operator with ``stat_panels`` names its per-agent
(m, D_g) f32 panels; the panel engine keeps them as
``state["merge_stat"][name]`` — donated through the segment scan, updated
via :meth:`Merger.update_local` (every local step, sees the grad panel)
and/or :meth:`Merger.update_round` (once per round, sees the param
panel). ``init_stats`` builds them from the initial panel
(``dsgd.init_panel_state(merger=...)``).

Heavy per-coordinate reductions run as Pallas TPU kernels
(kernels/merge_ops.py) with bit-identical oracles in kernels/ref.py;
sharded specs fall back to the plain-XLA oracle path so SPMD partitions
the column reductions over 'fsdp', mirroring the other panel kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import panel as panel_mod
from repro.kernels import merge_ops as merge_kernels
from repro.kernels import ref as ref_mod
from repro.telemetry.trace import scope
from repro.wire import codec as wire_codec


class Merger:
    """Base merge operator: the uniform column mean.

    Subclasses override :meth:`merge_row` (the operator itself) and, for
    statistical operators, declare ``stat_panels`` + the update hooks."""

    name = "uniform"
    stat_panels: tuple = ()   # names of per-agent (m, D_g) f32 stat panels
    local_stat = False        # update_local runs every local step (grads)
    round_stat = False        # update_round runs once per round (params)
    uses_panel = True         # merge_row reads the (wire-encoded) params

    # ---------------------------------------------------- statistics
    def init_stats(self, panel):
        """{stat_name: {group: (m, D_g) f32}} from the initial panel."""
        return {}

    def update_local(self, stats, gpan):
        """Fold one local step's grad panel into the stats."""
        return stats

    def update_round(self, stats, panel):
        """Fold one round's post-local-steps param panel into the stats."""
        return stats

    # --------------------------------------------------------- merge
    def merge_row(self, panel, stats=None, weights=None, *, spec=None,
                  use_pallas: bool = False, block_d: int = 512, live=None):
        """One merged row {group: (D_g,) f32} from the (m, D) panel.

        ``live`` ((m,) bool) restricts every operator to the live agents'
        rows: dead rows contribute NOTHING to the merged row (their
        parameters and statistics are stale), exactly as if the operator
        ran on the m'-agent sub-panel."""
        return panel_mod.merged(panel, spec=spec, use_pallas=use_pallas,
                                block_d=block_d,
                                live=live)


class UniformMerger(Merger):
    """The paper's single global merging: the per-group column mean
    (bit-exact alias of the pre-subsystem ``panel.merged`` path)."""


def _identity_back(y):
    return y


def _constrain_row(row, spec):
    if spec is None:
        return row
    return {k: panel_mod._constrain_group(v, spec, k, merged_panel=True)
            for k, v in row.items()}


def _weighted_colmerge(panel, wpanel, spec, use_pallas, block_d):
    """Per-coordinate weighted column merge over all dtype groups —
    Pallas kernel single-device, XLA oracle under a sharded spec."""
    pallas = panel_mod._pallas_ok(use_pallas, spec)
    out = {}
    for k, x in panel.items():
        if pallas:
            y = merge_kernels.weighted_colmerge(
                x.astype(jnp.float32), wpanel[k], block_d=block_d)
        else:
            y = ref_mod.weighted_colmerge_ref(x, wpanel[k])
        out[k] = y
    return _constrain_row(out, spec)


class WeightedMerger(Merger):
    """Per-agent convex weights: explicit ``weights=`` (m,) — e.g. from a
    held-out loss — or inverse squared consensus distance by default
    (w_k ∝ 1/(||theta_k - mean||^2 + eps), computed across all groups;
    identical rows degrade gracefully to the uniform mean)."""

    name = "weighted"

    def __init__(self, eps: float = 1e-8):
        self.eps = eps

    def agent_weights(self, panel, live=None):
        d = jnp.zeros((), jnp.float32)
        for x in panel.values():
            x32 = x.astype(jnp.float32)
            if live is None:
                mu = jnp.mean(x32, axis=0, keepdims=True)
            else:
                lw = panel_mod._live_weights(live, x32.shape[0])
                mu = jnp.tensordot(lw, x32, axes=1, precision="highest")[None]
            d = d + jnp.sum(jnp.square(x32 - mu), axis=1)
        w = 1.0 / (d + self.eps)
        if live is not None:
            w = w * live.astype(jnp.float32)
        return w / jnp.sum(w)

    def merge_row(self, panel, stats=None, weights=None, *, spec=None,
                  use_pallas: bool = False, block_d: int = 512, live=None):
        if weights is None:
            w = self.agent_weights(panel, live=live)
        else:
            w = jnp.asarray(weights, jnp.float32)
            if live is not None:
                w = w * live.astype(jnp.float32)
            w = w / jnp.sum(w)
        row = {k: jnp.tensordot(w, x.astype(jnp.float32), axes=1,
                                precision="highest")
               for k, x in panel.items()}
        return _constrain_row(row, spec)


class VarMerger(Merger):
    """Per-coordinate inverse-variance weighting: stats are EMA mean and
    second-moment panels of each agent's parameter trajectory over rounds
    (``update_round``); the merge weights are 1/(Var + eps). Fresh stats
    (zero variance everywhere) reduce to the uniform mean."""

    name = "var"
    stat_panels = ("traj_mu", "traj_m2")
    round_stat = True

    def __init__(self, ema: float = 0.9, eps: float = 1e-8):
        self.ema = ema
        self.eps = eps

    def init_stats(self, panel):
        # jnp.array COPIES: an f32 group's .astype(f32) would alias the
        # parameter buffer and break the segment driver's donation
        mu = {k: jnp.array(x, jnp.float32) for k, x in panel.items()}
        return {"traj_mu": mu,
                "traj_m2": {k: jnp.square(v) for k, v in mu.items()}}

    def update_round(self, stats, panel):
        b = self.ema
        mu, m2 = {}, {}
        for k, x in panel.items():
            x32 = x.astype(jnp.float32)
            mu[k] = b * stats["traj_mu"][k] + (1.0 - b) * x32
            m2[k] = b * stats["traj_m2"][k] + (1.0 - b) * jnp.square(x32)
        return {"traj_mu": mu, "traj_m2": m2}

    def merge_row(self, panel, stats=None, weights=None, *, spec=None,
                  use_pallas: bool = False, block_d: int = 512, live=None):
        if stats is None:
            raise ValueError(
                "merger 'var' needs its trajectory stats panels "
                "(stats=...); build them with init_stats / "
                "init_panel_state(merger='var')")
        var = {k: jnp.maximum(stats["traj_m2"][k]
                              - jnp.square(stats["traj_mu"][k]), 0.0)
               for k in panel}
        w = {k: 1.0 / (v + self.eps) for k, v in var.items()}
        if live is not None:
            # the colmerge normalizes by the per-column weight sum, so a
            # zeroed row is excluded from both numerator and denominator
            lf = live.astype(jnp.float32)[:, None]
            w = {k: v * lf for k, v in w.items()}
        return _weighted_colmerge(panel, w, spec, use_pallas, block_d)


class FisherMerger(Merger):
    """Diagonal-Fisher weighted merge: each agent accumulates an EMA of
    its squared gradients during the local steps (F ≈ E[g^2], the
    empirical diagonal Fisher); the merge is the Fisher-weighted column
    mean with weights F + eps. Fresh stats (F = 0) reduce to the uniform
    mean."""

    name = "fisher"
    stat_panels = ("fisher",)
    local_stat = True

    def __init__(self, ema: float = 0.9, eps: float = 1e-8):
        self.ema = ema
        self.eps = eps

    def init_stats(self, panel):
        return {"fisher": {k: jnp.zeros(x.shape, jnp.float32)
                           for k, x in panel.items()}}

    def update_local(self, stats, gpan):
        b = self.ema
        return {"fisher": {
            k: b * stats["fisher"][k]
            + (1.0 - b) * jnp.square(g.astype(jnp.float32))
            for k, g in gpan.items()}}

    def merge_row(self, panel, stats=None, weights=None, *, spec=None,
                  use_pallas: bool = False, block_d: int = 512, live=None):
        if stats is None:
            raise ValueError(
                "merger 'fisher' needs its Fisher stats panel (stats=...);"
                " build it with init_stats / init_panel_state("
                "merger='fisher')")
        w = {k: stats["fisher"][k] + self.eps for k in panel}
        if live is not None:
            lf = live.astype(jnp.float32)[:, None]
            w = {k: v * lf for k, v in w.items()}
        return _weighted_colmerge(panel, w, spec, use_pallas, block_d)


class TiesMerger(Merger):
    """TIES on deviations from the mean: per-agent-row top-``trim``
    magnitude trim, per-column sign election over the survivors, and the
    agreeing (disjoint) mean of the elected deviations added back to the
    reference row. ``trim=1.0`` keeps every deviation — the pure
    sign-elected mean."""

    name = "ties"

    def __init__(self, trim: float = 0.2):
        if not 0.0 < trim <= 1.0:
            raise ValueError(f"trim fraction must be in (0, 1], got {trim}")
        self.trim = trim

    def merge_row(self, panel, stats=None, weights=None, *, spec=None,
                  use_pallas: bool = False, block_d: int = 512, live=None):
        pallas = panel_mod._pallas_ok(use_pallas, spec)
        out = {}
        for k, x in panel.items():
            x32 = x.astype(jnp.float32)
            if live is None:
                ref_row = jnp.mean(x32, axis=0)
                tau = x32 - ref_row[None]
            else:
                lw = panel_mod._live_weights(live, x32.shape[0])
                ref_row = jnp.tensordot(lw, x32, axes=1, precision="highest")
                # a zero tau row is inert through trim + election +
                # agreeing-mean, so masking dead rows to zero makes the
                # result exactly the live sub-panel's TIES merge
                tau = (x32 - ref_row[None]) * live.astype(
                    jnp.float32)[:, None]
            thresh = ref_mod.ties_thresh_ref(tau, self.trim)
            if pallas:
                dev = merge_kernels.ties_colmerge(tau, thresh,
                                                  block_d=block_d)
            else:
                dev = ref_mod.ties_colmerge_ref(tau, thresh)
            out[k] = ref_row + dev
        return _constrain_row(out, spec)


class SwaMerger(Merger):
    """Merge of per-agent SWA/EMA accumulators: each agent keeps an EMA
    of its parameters over the ROUNDS (``a <- d a + (1-d) theta`` after
    each round, initialised at theta_0 — the tail rounds dominate); the
    merged row is the uniform mean of the accumulators, i.e. the merge
    averages the smoothed iterates instead of the final ones."""

    name = "swa"
    stat_panels = ("swa",)
    round_stat = True
    uses_panel = False  # the merged row comes from the accumulators only

    def __init__(self, decay: float = 0.9):
        self.decay = decay

    def init_stats(self, panel):
        # jnp.array copies (donation safety, see VarMerger.init_stats)
        return {"swa": {k: jnp.array(x, jnp.float32)
                        for k, x in panel.items()}}

    def update_round(self, stats, panel):
        d = self.decay
        return {"swa": {
            k: d * stats["swa"][k] + (1.0 - d) * x.astype(jnp.float32)
            for k, x in panel.items()}}

    def merge_row(self, panel, stats=None, weights=None, *, spec=None,
                  use_pallas: bool = False, block_d: int = 512, live=None):
        if stats is None:
            raise ValueError(
                "merger 'swa' needs its accumulator stats panel "
                "(stats=...); build it with init_stats / "
                "init_panel_state(merger='swa')")
        return panel_mod.merged(stats["swa"], spec=spec,
                                use_pallas=use_pallas, block_d=block_d,
                                live=live)


MERGERS = {
    "uniform": UniformMerger(),
    "weighted": WeightedMerger(),
    "var": VarMerger(),
    "fisher": FisherMerger(),
    "ties": TiesMerger(),
    "swa": SwaMerger(),
}


def get_merger(name):
    """Resolve a merge operator by registry name; Merger instances pass
    through (lets tests/benches build e.g. TiesMerger(trim=1.0))."""
    if not isinstance(name, str) and hasattr(name, "merge_row"):
        return name
    try:
        return MERGERS[name]
    except KeyError:
        raise ValueError(
            f"unknown merge operator {name!r}; known: {sorted(MERGERS)}"
        ) from None


def decode_stats(stats, spec):
    """Dequantize stat panels held in a residency STORAGE layout.

    Under a ``--residency stats=...`` policy the engine carries
    ``state["merge_stat"]`` in its storage encoding (e.g. int8 q+scale
    dicts); every merge entry point decodes through the spec's storage
    codec before the operator reads them. ``Storage.maybe_read`` is
    idempotent on already-decoded f32 leaves, so in-engine callers that
    decoded at round entry pass through unchanged — as do bare-spec
    (f32-residency) runs, bit-exactly."""
    if stats is None or spec is None:
        return stats
    name = spec.residency_of("stats")
    if name == "f32":
        return stats
    from repro import residency as residency_mod
    st = residency_mod.get_storage(name)
    return {sn: {g: st.maybe_read(v) for g, v in grp.items()}
            for sn, grp in stats.items()}


@scope("merge.panel")
def merge_panel(panel, merger, *, stats=None, weights=None, spec=None,
                wire_dtype=None, key=None, err=None,
                use_pallas: bool = False, block_d: int = 512, live=None):
    """One global merge ROUND through an operator: every agent transmits
    its panel through the spec's wire-codec policy (exactly like
    ``panel.global_merge`` — stochastic codecs take ``key=``, error
    feedback threads ``err=``), the operator folds the decoded payloads
    into ONE merged row, and the row is broadcast back to all agents.

    The statistics panels are merge METADATA (Fisher weights, SWA
    accumulators) and do not ride the parameter wire here — compressing
    them is a follow-up, the payload accounting covers the params only.
    An operator that never reads the parameter panel
    (``uses_panel=False``, e.g. swa merging the accumulators) skips the
    codec entirely: nothing travels the parameter wire, so nothing may
    be quantized and the EF residual passes through untouched (the idle-
    round rule).

    ``live`` ((m,) bool) makes the round elastic: only live rows feed
    the operator, only live rows receive the broadcast (dead agents'
    parameter AND residual rows pass through bit-exactly — the idle-row
    rule applied per agent), and the merged row is the live sub-panel's.

    Returns ``(mixed, row, new_err)``: the broadcast (m, D) panel in
    storage dtypes, the merged {group: (D_g,) f32} row, and the updated
    EF residual (None when ``err`` is)."""
    merger = get_merger(merger)
    stats = decode_stats(stats, spec)
    pallas = panel_mod._pallas_ok(use_pallas, spec)
    delta = {k: False for k in panel}
    if merger.uses_panel:
        codecs = panel_mod._codecs(panel, spec, wire_dtype)
        keys = panel_mod._wire_keys(codecs, key)
        enc, backs = {}, {}
        new_err = {} if err is not None else None
        for k, x in panel.items():
            e = err[k] if err is not None else None
            if getattr(codecs[k], "delta_mix", False):
                # delta (mirror) codecs: a sparse payload cannot sync a
                # one-shot merge, so the GLOBAL round is their
                # full-bandwidth round (panel.global_merge delta rule):
                # the operator sees the exact panel and the mirror is
                # reset to the post-merge state below. The mirror is
                # still REQUIRED — a caller without it would leave the
                # next delta mix pulling on an arbitrarily stale mirror
                if e is None:
                    raise ValueError(
                        f"codec '{codecs[k].name}' carries a mirror "
                        "panel and needs it (err=...)")
                delta[k] = True
                enc[k] = x.astype(jnp.float32)
                backs[k] = wire_codec._storage_back(x.dtype)
                continue
            xw, back, ne = codecs[k].encode(x, key=keys[k], err=e,
                                            use_pallas=pallas)
            enc[k] = xw
            backs[k] = back
            if err is not None:
                new_err[k] = panel_mod._constrain_group(ne, spec, k)
    else:
        enc = panel
        backs = {k: _identity_back for k in panel}
        new_err = err
    row = merger.merge_row(enc, stats=stats, weights=weights, spec=spec,
                           use_pallas=use_pallas, block_d=block_d, live=live)
    lcol = None if live is None else live[:, None]
    mixed = {}
    for k, x in panel.items():
        if delta[k]:
            y32 = jnp.broadcast_to(row[k][None], x.shape)
            if lcol is not None:
                # dead rows keep their params AND their mirror: they
                # did not see this merge, so the next delta mix must
                # still pull against their pre-merge mirror
                y32 = jnp.where(lcol, y32, x.astype(jnp.float32))
            mixed[k] = panel_mod._constrain_group(backs[k](y32), spec, k)
            if new_err is not None:
                ne = y32.astype(jnp.float32)
                if lcol is not None:
                    ne = jnp.where(lcol, ne, err[k])
                new_err[k] = panel_mod._constrain_group(ne, spec, k)
            continue
        y = backs[k](jnp.broadcast_to(row[k][None], x.shape)
                     .astype(enc[k].dtype))
        if lcol is not None:
            y = jnp.where(lcol, y, x)
            if new_err is not None:
                new_err[k] = jnp.where(lcol, new_err[k], err[k])
        mixed[k] = panel_mod._constrain_group(y, spec, k)
    return mixed, row, new_err
