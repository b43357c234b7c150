"""Flat-panel parameter engine: the fused communication layer.

Agent-stacked pytrees (every leaf (m, ...)) are flattened ONCE into a
*panel*: a dict ``{dtype_name: (m, D_dtype) array}`` — one row per agent,
one column per scalar parameter — described by a static :class:`PanelSpec`
(per-leaf offsets/shapes/dtypes). Grouping by dtype preserves every leaf's
storage dtype exactly (``jnp.concatenate`` over mixed-dtype leaves would
silently promote bf16 to f32 and double the wire bytes).

All communication primitives then become ONE fused op per dtype group over
the panel instead of one op per pytree leaf:

* :func:`mix_dense`       — Theta <- W Theta, a single (m,m)x(m,D) matmul
                            with f32 accumulation (Pallas ``gossip_mix``
                            kernel when ``use_pallas=True``).
* :func:`mix_pairwise`    — one gather + lerp along the agent axis.
* :func:`global_merge`    — one mean-reduce broadcast back to all rows.
* :func:`merged`          — the averaged model as a (D,) panel.
* :func:`consensus_distance` — Xi_t in one pass (Pallas ``panel_reduce``
                            kernel when ``use_pallas=True``).

**Wire codecs.** Every communication op compresses its payload through the
pluggable codec subsystem (repro/wire): ``f32`` identity, ``bf16`` cast
(the original lever), ``int8``/``int8_ef`` per-row scales + stochastic
rounding (+ error feedback), ``int4``/``int4_ef`` packed nibbles with
grouped scales, and ``topk`` sparse innovations over a mirror panel. The
per-dtype-group policy lives on the spec (:func:`with_wire` — e.g.
embeddings stay bf16 while dense blocks go int8) and
:attr:`PanelSpec.wire_payload_bytes` / :attr:`wire_total_bytes` report
the codec-aware payload and payload+metadata wire cost; the legacy
``wire_dtype=`` argument on the mix ops survives as an explicit
per-call cast override. Stochastic codecs take an explicit ``key=``;
error feedback threads a residual panel via ``err=``. A ``delta_mix``
codec (topk) breaks the single W @ payload matmul: its sparse payload
reconstructs a mirror panel and the mix runs in damped delta form
``x + gamma (W - I) @ x̂`` — the first codec whose mixing cannot lower
to one dense MXU pass over the payload. The per-leaf
tree-map originals survive in core/gossip.py as ``*_tree`` — they remain
the right lowering when leaves carry heterogeneous shardings
(launch/dryrun.py pod meshes), and they are the parity oracle the panel
path is validated/benchmarked against (tests/test_panel_sharded.py,
benchmarks/panel_bench.py).

**Storage residency.** :attr:`PanelSpec.residency` (:func:`with_residency`)
carries the per-state-kind storage-codec policy (repro/residency): the
moment / merge-stat / EF-residual panels can live in HBM as bf16 or int8
(+ f32 scale sidecars) and be decoded to f32 only inside the fused round.
The spec owns the policy and the exact byte accounting
(:meth:`PanelSpec.storage_bytes`, :meth:`PanelSpec.sidecar_sharding`); the
encode/decode placement is the segment driver's (core/dsgd.py). The
fused ops here never see stored reps — they operate on the decoded view.

**Merge operators.** :attr:`PanelSpec.merger` (:func:`with_merger`) names
the operator GLOBAL rounds apply — uniform mean, weighted, inverse
variance, diagonal Fisher, TIES, SWA (repro/merging). 'uniform' keeps the
fused matmul path here bit-exact; non-uniform operators are dispatched by
the segment driver (dsgd.make_panel_segment) through
``merging.merge_panel``, which encodes the payload with the same wire
policy and broadcasts one merged row.

**Multi-device panels.** :func:`shard_spec` attaches a mesh and one
PartitionSpec per dtype group to the spec — rows over the ('pod','agent')
communication axes, the flat D columns over 'fsdp' (models/sharding.py:
``panel_pspec``). Every fused op then constrains its output to the group
sharding, so the mix lowers to per-fsdp-shard (m,m)x(m, D/fsdp) matmuls
whose collectives move only the LOCAL column shard (gossip traffic /fsdp
per device), and the consensus scalar finishes with a single cross-shard
reduce. The Pallas kernels are single-device bodies — a sharded spec
routes those ops through the plain-XLA path so SPMD can partition them.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import wire as wire_mod
from repro.kernels.gossip_mix import gossip_mix_panel
from repro.kernels.panel_reduce import panel_mean_consensus
from repro.telemetry.trace import scope


@dataclass(frozen=True)
class LeafSpec:
    group: str            # dtype-group key ('float32', 'bfloat16', ...)
    offset: int           # column offset inside the group panel
    size: int             # number of scalars per agent
    shape: Tuple[int, ...]  # per-agent (trailing) shape
    dtype: str            # leaf storage dtype name

    @property
    def slab(self) -> Tuple[int, int]:
        """(rows, last): the per-agent leaf with its leading dims merged
        into rows and its last dim kept (see :func:`to_slabs`)."""
        last = self.shape[-1] if self.shape else 1
        return (self.size // max(last, 1), last)


@dataclass(frozen=True)
class PanelSpec:
    """Static description of a panelised pytree. Hashable — safe to close
    over in jitted functions or pass as a static argument.

    ``mesh``/``pspecs`` (set by :func:`shard_spec`) describe how each
    (m, D_g) group panel is laid out on a device mesh; unset means the
    single-device / fully-replicated layout."""
    treedef: object
    leaves: Tuple[LeafSpec, ...]
    groups: Tuple[Tuple[str, int], ...]  # (dtype key, group width D_g)
    rows: int = 0                        # m (agents); 0 on legacy specs
    mesh: Optional[jax.sharding.Mesh] = None
    pspecs: Tuple[Tuple[str, P], ...] = ()  # (dtype key, group PartitionSpec)
    wire: Tuple[Tuple[str, str], ...] = ()  # (dtype key, codec name) policy
    merger: str = "uniform"                 # merge operator (repro.merging)
    # (state kind, storage name) residency policy over the RESIDENT state
    # panels — 'moments' / 'stats' / 'wire_err' (repro.residency); params
    # always keep their native dtypes. () means everything stays f32
    # (with_residency drops explicit 'f32' entries so an f32 policy IS
    # the empty policy — byte-identical specs, byte-identical traces)
    residency: Tuple[Tuple[str, str], ...] = ()

    @property
    def width(self) -> int:
        """Total scalars per agent across all dtype groups."""
        return sum(w for _, w in self.groups)

    def wire_of(self, key: str) -> str:
        """Codec name for one dtype group ('f32' when no policy is set)."""
        for k, name in self.wire:
            if k == key:
                return name
        return "f32"

    @property
    def wire_payload_bytes(self) -> int:
        """Per-agent wire bytes of the quantized VALUES alone for one
        full-panel exchange: packed int4 nibbles pay D/2, int8 one byte
        per scalar, top-k only its k values — scale/index metadata
        excluded (see :attr:`wire_total_bytes`)."""
        return sum(
            wire_mod.get_codec(self.wire_of(k)).payload_bytes(1, w, k)
            for k, w in self.groups)

    @property
    def wire_total_bytes(self) -> int:
        """Per-agent wire bytes INCLUDING codec metadata — per-row int8
        scales, grouped int4 scales, packed top-k indices. This is what
        actually crosses the interconnect per exchange."""
        return sum(
            wire_mod.get_codec(self.wire_of(k)).total_bytes(1, w, k)
            for k, w in self.groups)

    @property
    def wire_bytes(self) -> int:
        """Back-compat alias of :attr:`wire_total_bytes` (codec-aware:
        an int8 group pays 1 byte/scalar + its per-row scale, a bf16 wire
        2 bytes/scalar, and only the f32 identity codec pays the storage
        itemsize)."""
        return self.wire_total_bytes

    def residency_of(self, kind: str) -> str:
        """Storage-codec name for one state-panel kind ('moments',
        'stats', 'wire_err'); 'f32' when no policy is set."""
        for k, name in self.residency:
            if k == kind:
                return name
        return "f32"

    def storage_bytes(self, kind: str, state_dtype: Optional[str] = None
                      ) -> int:
        """Exact per-agent resident HBM bytes of ONE state panel of
        ``kind`` under the residency policy, scale sidecars included.

        Storage codecs apply to f32 state only; a group whose state
        rides in another dtype (``state_dtype=None`` means the state
        mirrors each group's native dtype, as optimizer moments do) pays
        its plain itemsize. ``state_dtype='float32'`` models the panels
        that are f32 for EVERY group (merge stats, EF residuals)."""
        from repro import residency as residency_mod
        st = residency_mod.get_storage(self.residency_of(kind))
        total = 0
        for g, w in self.groups:
            dt = state_dtype or g
            if dt == "float32":
                total += st.resident_bytes(1, w)
            else:
                total += jnp.dtype(dt).itemsize * w
        return total

    @property
    def sharded(self) -> bool:
        return self.mesh is not None and bool(self.pspecs)

    def pspec(self, key: str) -> Optional[P]:
        for k, ps in self.pspecs:
            if k == key:
                return ps
        return None

    def sharding(self, key: str) -> Optional[NamedSharding]:
        """NamedSharding of one dtype group's (m, D_g) panel, or None."""
        ps = self.pspec(key)
        if self.mesh is None or ps is None:
            return None
        return NamedSharding(self.mesh, ps)

    def merged_sharding(self, key: str) -> Optional[NamedSharding]:
        """NamedSharding of a merged (D_g,) panel: column axes only."""
        ps = self.pspec(key)
        if self.mesh is None or ps is None:
            return None
        return NamedSharding(self.mesh, P(*ps[1:2]))

    def sidecar_sharding(self, key: str) -> Optional[NamedSharding]:
        """NamedSharding of a per-row storage sidecar (the int8 scale
        columns, (m, n_scales)): rows follow the group's agent axes, the
        tiny scale columns stay replicated (they don't divide by fsdp
        and aren't worth sharding)."""
        ps = self.pspec(key)
        if self.mesh is None or ps is None:
            return None
        return NamedSharding(self.mesh, P(ps[0]))


def make_spec(tree) -> PanelSpec:
    """Build the static spec for an agent-stacked pytree (leaves (m, ...))."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    offsets: dict = {}
    specs = []
    for x in leaves:
        key = jnp.dtype(x.dtype).name
        off = offsets.get(key, 0)
        size = int(np.prod(x.shape[1:], dtype=np.int64))
        specs.append(LeafSpec(group=key, offset=off, size=size,
                              shape=tuple(x.shape[1:]), dtype=key))
        offsets[key] = off + size
    groups = tuple(sorted(offsets.items()))
    rows = int(leaves[0].shape[0]) if leaves else 0
    return PanelSpec(treedef=treedef, leaves=tuple(specs), groups=groups,
                     rows=rows)


def shard_spec(spec: PanelSpec, mesh, row_axes=None, col_axes=None
               ) -> PanelSpec:
    """Attach a mesh + per-group PartitionSpecs to ``spec``.

    Rows go on the ('pod','agent') communication axes, columns on 'fsdp'
    (overridable); either is dropped per group when the dim does not divide
    by the axis size — that group stays replicated along it."""
    from repro.models.sharding import (PANEL_COL_AXES, PANEL_ROW_AXES,
                                       panel_pspec)
    row_axes = PANEL_ROW_AXES if row_axes is None else row_axes
    col_axes = PANEL_COL_AXES if col_axes is None else col_axes
    pspecs = tuple(
        (k, panel_pspec(mesh, spec.rows, w, row_axes, col_axes))
        for k, w in spec.groups)
    return replace(spec, mesh=mesh, pspecs=pspecs)


def with_wire(spec: PanelSpec, wire) -> PanelSpec:
    """Attach a wire-codec policy to ``spec``.

    ``wire`` is a codec name applied to EVERY dtype group (a
    ``repro.wire.CODECS`` key: 'f32', 'bf16', 'int8', 'int8_ef', 'int4',
    'int4_ef', 'topk'), or a {dtype-group: codec-name} dict for per-group
    policies (unlisted groups fall back to 'f32'); None clears the policy.
    Names are validated here so a typo fails at spec-build time, not
    mid-trace."""
    if wire is None:
        return replace(spec, wire=())
    if isinstance(wire, str):
        mapping = {k: wire for k, _ in spec.groups}
    else:
        unknown = set(wire) - {k for k, _ in spec.groups}
        if unknown:
            raise ValueError(
                f"wire policy names unknown dtype groups {sorted(unknown)}"
                f"; this spec's groups: {[k for k, _ in spec.groups]}")
        mapping = {k: wire.get(k, "f32") for k, _ in spec.groups}
    for name in mapping.values():
        wire_mod.get_codec(name)
    return replace(spec, wire=tuple(sorted(mapping.items())))


def with_residency(spec: PanelSpec, residency) -> PanelSpec:
    """Attach a storage-codec residency policy to ``spec``.

    ``residency`` is a {state-kind: storage-name} dict or a CLI policy
    string for ``residency.parse_policy`` ('moments=int8,stats=bf16', or
    a bare storage name for the moments); kinds are 'moments' / 'stats'
    / 'wire_err' (params always keep their native dtypes — compressing
    what the mixing matmul reads every round is a WIRE question), names
    are ``repro.residency.STORAGE`` keys ('f32', 'bf16', 'int8',
    'int8g', 'int8r'). Explicit 'f32' entries are dropped — the f32 policy IS the
    empty policy, so the resulting spec (and every trace keyed on it) is
    byte-identical to one that never saw a policy. None clears. Like
    with_merger, only registry NAMES can live on the hashable spec."""
    if residency is None:
        return replace(spec, residency=())
    from repro import residency as residency_mod
    mapping = residency_mod.parse_policy(residency)
    named = {}
    for kind, name in mapping.items():
        if not isinstance(name, str):
            raise ValueError(
                "with_residency takes registry NAMES (the spec stays "
                "hashable); register custom Storage instances in "
                "residency.STORAGE first")
        st = residency_mod.get_storage(name)
        if st.name != "f32":
            named[kind] = st.name
    return replace(spec, residency=tuple(sorted(named.items())))


def with_merger(spec: PanelSpec, merger) -> PanelSpec:
    """Attach a merge-operator name (repro.merging registry) to ``spec``:
    the operator every GLOBAL round applies (the paper's single final
    merging included). Validated here so a typo fails at spec-build time;
    None resets to 'uniform'. Custom Merger INSTANCES cannot live on the
    hashable spec — register them in ``merging.MERGERS`` or call
    ``merging.merge_panel`` directly."""
    if merger is None:
        return replace(spec, merger="uniform")
    from repro import merging as merging_mod
    resolved = merging_mod.get_merger(merger)
    if not isinstance(merger, str):
        raise ValueError(
            "with_merger takes a registry NAME (the spec stays hashable). "
            "To use a custom-configured instance, register it first — "
            f"merging.MERGERS['my_{resolved.name}'] = instance — and pass "
            "that name; the registry default under "
            f"{resolved.name!r} may carry different hyperparameters than "
            "your instance")
    return replace(spec, merger=resolved.name)


def place(x, ns: Optional[NamedSharding]):
    """Pin one array to a sharding. Inside a trace this is a
    with_sharding_constraint (the SPMD partitioner boundary); on concrete
    arrays it is a device_put (initialization / host-side resharding).
    Shared by the panel ops here and dsgd.init_state's tree placement."""
    if ns is None:
        return x
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, ns)
    return jax.device_put(x, ns)


def _constrain_group(x, spec: Optional[PanelSpec], key: str,
                     merged_panel: bool = False):
    if spec is None:
        return x
    return place(x, spec.merged_sharding(key) if merged_panel
                 else spec.sharding(key))


def shard_panel(panel, spec: PanelSpec):
    """Apply the spec's group shardings to an existing panel dict (used for
    optimizer-moment panels, which mirror the parameter panel layout)."""
    return {k: _constrain_group(x, spec, k) for k, x in panel.items()}


def to_panel(tree, spec: PanelSpec):
    """Flatten an agent-stacked pytree into {dtype: (m, D_dtype)} panels.
    On a sharded spec the group panels are pinned to their mesh layout."""
    leaves = jax.tree_util.tree_leaves(tree)
    m = leaves[0].shape[0]
    parts: dict = {}
    for x, ls in zip(leaves, spec.leaves):
        parts.setdefault(ls.group, []).append(x.reshape(m, ls.size))
    panel = {k: (fl[0] if len(fl) == 1 else jnp.concatenate(fl, axis=1))
             for k, fl in parts.items()}
    return shard_panel(panel, spec) if spec.sharded else panel


def from_panel(panel, spec: PanelSpec, cast: bool = True,
               leaf_shardings=None):
    """Rebuild the pytree from panels. Accepts (m, D) panels (stacked tree)
    or (D,) panels (a merged model — leaves drop the agent axis).
    ``cast=False`` keeps the panel dtype (e.g. the f32 merged model).
    ``leaf_shardings`` (a matching pytree of NamedSharding/PartitionSpec)
    re-pins each rebuilt leaf to its model-natural layout — the compute-side
    boundary of a D-sharded panel, whose flat columns cut across leaf dims."""
    outs = []
    for ls in spec.leaves:
        g = panel[ls.group]
        if g.ndim == 2:
            x = g[:, ls.offset:ls.offset + ls.size]
            x = x.reshape((g.shape[0],) + ls.shape)
        else:
            x = g[ls.offset:ls.offset + ls.size].reshape(ls.shape)
        outs.append(x.astype(ls.dtype) if cast else x)
    tree = jax.tree_util.tree_unflatten(spec.treedef, outs)
    if leaf_shardings is not None:
        tree = jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            leaf_shardings)
    return tree


def to_slabs(panel, spec: PanelSpec):
    """Every leaf of the (m, D) panels as an agent-stacked (m, rows, last)
    slab (:attr:`LeafSpec.slab`), in leaf order. ``to_panel`` takes the
    list back. A slab reshapes to its leaf, agent by agent, without
    moving a byte. Reshaped straight to an (m, 1, r, c) leaf (a stack of
    one layer), the panel's column slice lowers for a TPU to a reshape
    whose code grows with the leaf (44 MB and ~2 min of compile for one
    2048 x 8192 matrix at m = 4 on a v5e); to (m, r, c) it is one
    relayout copy."""
    m = next(iter(panel.values())).shape[0]
    return [panel[ls.group][:, ls.offset:ls.offset + ls.size].reshape(
        (m,) + ls.slab) for ls in spec.leaves]


# ------------------------------------------------------------ fused ops


def _codecs(panel, spec: Optional[PanelSpec], wire_dtype):
    """Effective codec per dtype group for one communication op: the
    explicit legacy ``wire_dtype`` argument wins (and refuses to combine
    with a spec policy — one compression authority per call); else the
    spec's wire policy; else the f32 identity.

    NOTE: _codecs/_wire_keys/_pallas_ok/_constrain_group are the
    engine-internal plumbing CONTRACT shared with repro/merging
    (merge_panel runs the same encode→reduce→broadcast round as
    global_merge); refactors here must keep those call sites in step."""
    if wire_dtype is not None:
        if spec is not None and spec.wire:
            raise ValueError("pass either wire_dtype= (legacy cast) or a "
                             "spec wire policy (with_wire), not both")
        c = wire_mod.dtype_codec(wire_dtype)
        return {k: c for k in panel}
    if spec is not None and spec.wire:
        return {k: wire_mod.get_codec(spec.wire_of(k)) for k in panel}
    f32 = wire_mod.CODECS["f32"]
    return {k: f32 for k in panel}


def _wire_keys(codecs, key):
    """One key per dtype group that needs one, folded in sorted-group
    order so sharded and replicated runs draw identical randomness."""
    names = sorted(k for k, c in codecs.items() if c.needs_key)
    if not names:
        return {k: None for k in codecs}
    if key is None:
        raise ValueError(f"wire codecs for groups {names} use stochastic "
                         "rounding and need an explicit key=")
    folded = {k: jax.random.fold_in(key, i) for i, k in enumerate(names)}
    return {k: folded.get(k) for k in codecs}


def _pallas_ok(use_pallas: bool, spec: Optional[PanelSpec]) -> bool:
    # Pallas kernel bodies are single-device programs; on a sharded spec the
    # op must stay plain XLA so the SPMD partitioner can split it into the
    # per-shard matmuls + local collectives this layout exists for.
    return use_pallas and not (spec is not None and spec.sharded)


def _mix_dense_groups(panel, W, *, wire_dtype, use_pallas, block_d,
                      spec, key, err, with_mean):
    """Shared body of mix_dense / mix_dense_mean. Returns (mixed, means,
    new_err); means/new_err are None unless requested.

    ``with_mean`` augments W with a 1^T/m row so the column mean comes out
    of the SAME matmul (the MXU pass the mix already pays): for any
    doubly-stochastic W the mean of the transmitted panel IS the mean of
    the mixed panel, so the consensus monitor no longer needs its own
    mean reduce. On a sharded spec the (m+1)-row product cannot shard
    over the agent axes, so the mean falls back to a separate fsdp-local
    reduce there. The first m output rows are bit-identical to the
    unaugmented matmul either way (row-independent dot products).

    Idle ROWS of W (rows equal to the identity row — e.g. unmatched
    agents inside a random matching) communicate nothing, so under a
    lossy codec those agents' params and EF residuals are restored
    exactly after the matmul: no codec may touch a row that never hits
    the wire. (The folded mean is the mean of the TRANSMITTED panel, so
    it deviates from the restored panel's mean by at most one
    quantization step per idle row — monitor-precision only.)"""
    m = W.shape[0]
    W32 = W.astype(jnp.float32)
    pallas = _pallas_ok(use_pallas, spec)
    codecs = _codecs(panel, spec, wire_dtype)
    keys = _wire_keys(codecs, key)
    lossy = any(not isinstance(c, wire_mod.F32Codec)
                for c in codecs.values())
    idle_rows = (jnp.all(W == jnp.eye(m, dtype=W.dtype), axis=1)[:, None]
                 if lossy else None)
    fold = with_mean and not (spec is not None and spec.sharded)
    Wop = (jnp.concatenate([W32, jnp.full((1, m), 1.0 / m, jnp.float32)])
           if fold else W32)

    mixed, means = {}, ({} if with_mean else None)
    new_err = {} if err is not None else None
    for k, x in panel.items():
        e = err[k] if err is not None else None
        with scope(f"wire.encode.{k}"):
            xw, back, ne = codecs[k].encode(x, key=keys[k], err=e,
                                            use_pallas=pallas)
        if getattr(codecs[k], "delta_mix", False):
            # sparse-innovation codecs (topk): xw is the updated MIRROR
            # panel and the mix runs in CHOCO's damped delta form
            # x + gamma (W - I) @ x̂ — a round trips through a
            # scatter-reconstructed mirror + one delta matmul instead of
            # the single dense W @ payload MXU pass (a sparse payload
            # mixed as W @ Q(x) would zero every untransmitted
            # coordinate, and an undamped pull on a stale mirror
            # diverges — see TopKCodec.gamma). Doubly-stochastic W
            # preserves the column mean EXACTLY for any gamma: the
            # sparsification error lives in the per-agent deviations
            # only, so the eventual global merge absorbs it. The
            # consensus mean is read off the mixed panel itself: the
            # transmitted mirror never enters the mean.
            x32 = x.astype(jnp.float32)
            Wd = W32 - jnp.eye(m, dtype=jnp.float32)
            if pallas:
                d32 = gossip_mix_panel(Wd, xw, block_d=block_d)
            else:
                d32 = jnp.matmul(Wd, xw.astype(jnp.float32),
                                 precision="highest")
            gamma = getattr(codecs[k], "gamma", 1.0)
            y32 = x32 + gamma * d32.astype(jnp.float32)
            with scope(f"wire.decode.{k}"):
                yb = back(y32)
            if with_mean:
                mu = jnp.mean(y32, axis=0)
                if not fold:
                    mu = _constrain_group(mu, spec, k, merged_panel=True)
            if idle_rows is not None:
                yb = jnp.where(idle_rows, x, yb)
                if e is not None:
                    ne = jnp.where(idle_rows, e, ne)
            mixed[k] = _constrain_group(yb, spec, k)
            if with_mean:
                means[k] = mu
            if err is not None:
                new_err[k] = _constrain_group(ne, spec, k)
            continue
        # the Pallas kernel stores its output in the payload dtype, which
        # would round the folded mean row for non-f32 payloads — those
        # groups skip the augmented row (no wasted kernel work) and take
        # one plain f32 mean of the transmitted panel instead (the same
        # quantity for doubly-stochastic W, at XLA-fold precision)
        fold_k = fold and not (pallas and xw.dtype != jnp.float32)
        Wk = Wop if fold_k else W32
        if pallas:
            y = gossip_mix_panel(Wk, xw, block_d=block_d)
            if fold_k:
                y, mu = y[:m], y[m].astype(jnp.float32)
        else:
            # f32-exact: at the TPU's default precision the MXU would round
            # the parameters to bf16 (the merged model then differs by
            # ~3e-3 relative between layouts)
            y32 = jnp.matmul(Wk, xw.astype(jnp.float32), precision="highest")
            if fold_k:
                y32, mu = y32[:m], y32[m]
            y = y32.astype(xw.dtype)
        if fold and not fold_k:
            mu = jnp.mean(xw.astype(jnp.float32), axis=0)
        with scope(f"wire.decode.{k}"):
            yb = back(y)
        if idle_rows is not None:
            yb = jnp.where(idle_rows, x, yb)
            if e is not None:
                ne = jnp.where(idle_rows, e, ne)
        mixed[k] = _constrain_group(yb, spec, k)
        if with_mean:
            if not fold:
                mu = _constrain_group(
                    jnp.mean(xw.astype(jnp.float32), axis=0), spec, k,
                    merged_panel=True)
            means[k] = mu
        if err is not None:
            new_err[k] = _constrain_group(ne, spec, k)
    return mixed, means, new_err


@scope("panel.mix")
def mix_dense(panel, W, *, wire_dtype=None, use_pallas: bool = False,
              block_d: int = 512,
              spec: Optional[PanelSpec] = None, key=None, err=None):
    """Theta <- W Theta: one f32-accumulating matmul per dtype group.

    With a sharded ``spec`` the output is constrained to the group layout,
    so each fsdp shard runs its own (m,m)x(m, D_g/fsdp) matmul and the
    cross-agent collective carries only that shard's columns. The payload
    is compressed per the spec's wire policy (or the legacy ``wire_dtype``
    cast); stochastic codecs need ``key=``. Passing ``err=`` (the
    error-feedback residual panel, {group: (m, D_g) f32}) switches the
    return to ``(mixed, new_err)``."""
    mixed, _, new_err = _mix_dense_groups(
        panel, W, wire_dtype=wire_dtype, use_pallas=use_pallas,
        block_d=block_d, spec=spec, key=key, err=err,
        with_mean=False)
    return mixed if err is None else (mixed, new_err)


@scope("panel.mix_mean")
def mix_dense_mean(panel, W, *, wire_dtype=None, use_pallas: bool = False,
                   block_d: int = 512,
                   spec: Optional[PanelSpec] = None, key=None, err=None):
    """mix_dense with the consensus mean folded into the mixing matmul.

    Returns ``(mixed, mean, new_err)`` — mean is {group: (D_g,) f32}, the
    column mean of the mixed panel (exact for doubly-stochastic W), ready
    for :func:`consensus_from_mean`; new_err is None when ``err`` is."""
    return _mix_dense_groups(
        panel, W, wire_dtype=wire_dtype, use_pallas=use_pallas,
        block_d=block_d, spec=spec, key=key, err=err,
        with_mean=True)


@scope("panel.mix_pairwise")
def mix_pairwise(panel, partner, weight=0.5, *, wire_dtype=None,
                 spec: Optional[PanelSpec] = None, key=None, err=None):
    """theta_k <- (1-w) theta_k + w theta_{partner[k]}: one gather + lerp
    per dtype group. partner[k] == k means agent k idles this round —
    idle rows keep their EXACT parameters (and error-feedback residual):
    nothing travels their wire, so no codec may touch them.
    Wire codecs as in :func:`mix_dense` (err= switches the return to
    ``(mixed, new_err)``)."""
    codecs = _codecs(panel, spec, wire_dtype)
    keys = _wire_keys(codecs, key)
    m = next(iter(panel.values())).shape[0]
    idle = (partner == jnp.arange(m))[:, None]

    def one(k, x):
        e = err[k] if err is not None else None
        xw, back, ne = codecs[k].encode(x, key=keys[k], err=e)
        peer = jnp.take(xw, partner, axis=0)
        if getattr(codecs[k], "delta_mix", False):
            # mirror codecs exchange in damped delta form: pull toward
            # the partner's mirror, keep the untransmitted rest of x
            gamma = getattr(codecs[k], "gamma", 1.0)
            mixed = back(x.astype(jnp.float32)
                         + gamma * weight * (peer - xw))
        else:
            mixed = back((1.0 - weight) * xw + weight * peer)
        y = jnp.where(idle, x, mixed)
        if e is not None:
            ne = jnp.where(idle, e, ne)
        return _constrain_group(y, spec, k), ne

    out = {k: one(k, x) for k, x in panel.items()}
    mixed = {k: v[0] for k, v in out.items()}
    if err is None:
        return mixed
    return mixed, {k: _constrain_group(v[1], spec, k)
                   for k, v in out.items()}


@scope("panel.global_merge")
def global_merge(panel, *, wire_dtype=None,
                 spec: Optional[PanelSpec] = None, key=None, err=None):
    """theta_k <- mean_l theta_l: one mean-reduce + broadcast per group.
    Sharded: an all-reduce over the agent axes per fsdp column shard.
    Wire codecs as in :func:`mix_dense` — EXCEPT delta (mirror) codecs:
    a sparse payload cannot sync a one-shot merge, so the global merge
    is their FULL-BANDWIDTH round by design (the paper's point is to
    concentrate the budget into the single global merging): the exact
    panel travels, the merge is bit-identical to the uncompressed one,
    and the mirror is reset to the post-merge state."""
    codecs = _codecs(panel, spec, wire_dtype)
    keys = _wire_keys(codecs, key)

    def one(k, x):
        e = err[k] if err is not None else None
        if getattr(codecs[k], "delta_mix", False):
            if e is None:
                raise ValueError(
                    f"codec '{codecs[k].name}' carries a mirror panel and "
                    "needs it (err=...)")
            x32 = x.astype(jnp.float32)
            y32 = jnp.broadcast_to(
                jnp.mean(x32, axis=0, keepdims=True), x32.shape)
            return (_constrain_group(y32.astype(x.dtype), spec, k), y32)
        xw, back, ne = codecs[k].encode(x, key=keys[k], err=e)
        mean = jnp.mean(xw.astype(jnp.float32), axis=0, keepdims=True)
        y = back(jnp.broadcast_to(mean, xw.shape).astype(xw.dtype))
        return _constrain_group(y, spec, k), ne

    out = {k: one(k, x) for k, x in panel.items()}
    mixed = {k: v[0] for k, v in out.items()}
    if err is None:
        return mixed
    return mixed, {k: _constrain_group(v[1], spec, k)
                   for k, v in out.items()}


def _live_weights(live, m):
    """(m,) f32 convex weights over the live rows (all-dead guards to a
    zero vector rather than NaN)."""
    lf = live.astype(jnp.float32)
    return lf / jnp.maximum(jnp.sum(lf), 1.0)


@scope("panel.merged")
def merged(panel, *, use_pallas: bool = False, block_d: int = 512,
           spec: Optional[PanelSpec] = None,
           live=None):
    """The (counterfactual) averaged model as {dtype: (D_dtype,)} f32.

    ``live`` ((m,) bool) restricts the mean to the live rows — the
    elastic-run merge, where a dead agent's stale row must not pollute
    the average. The masked path is plain XLA (the Pallas reduce kernel
    is unmasked)."""
    if live is not None:
        w = _live_weights(live, next(iter(panel.values())).shape[0])
        return {k: _constrain_group(
            jnp.tensordot(w, x.astype(jnp.float32), axes=1,
                          precision="highest"), spec, k,
            merged_panel=True) for k, x in panel.items()}
    if _pallas_ok(use_pallas, spec):
        return {k: panel_mean_consensus(x, block_d=block_d)[0]
                for k, x in panel.items()}
    return {k: _constrain_group(jnp.mean(x.astype(jnp.float32), axis=0),
                                spec, k, merged_panel=True)
            for k, x in panel.items()}


def merged_tree(panel, spec: PanelSpec):
    """Averaged model as a (non-stacked) pytree with f32 leaves — the panel
    equivalent of gossip.merged_model."""
    return from_panel(merged(panel, spec=spec), spec, cast=False)


@scope("panel.consensus")
def consensus_distance(panel, *, use_pallas: bool = False,
                       block_d: int = 512,
                       spec: Optional[PanelSpec] = None, live=None):
    """Xi_t = sqrt((1/m) sum_k ||theta_k - bar||^2) in one fused pass.
    Sharded: per-shard partial sums of squares + ONE scalar reduce.

    ``live`` ((m,) bool) computes the consensus of the LIVE rows only —
    mean and deviations both restricted, normalized by the live count
    (dead agents' stale rows are not part of the run's consensus)."""
    m = next(iter(panel.values())).shape[0]
    total = jnp.zeros((), jnp.float32)
    if live is not None:
        lf = live.astype(jnp.float32)
        n = jnp.maximum(jnp.sum(lf), 1.0)
        for x in panel.values():
            x32 = x.astype(jnp.float32)
            mean = jnp.tensordot(lf / n, x32, axes=1, precision="highest")
            total = total + jnp.sum(
                lf[:, None] * jnp.square(x32 - mean[None]))
        return jnp.sqrt(total / n)
    pallas = _pallas_ok(use_pallas, spec)
    for x in panel.values():
        if pallas:
            _, sq = panel_mean_consensus(x, block_d=block_d)
        else:
            x32 = x.astype(jnp.float32)
            mean = jnp.mean(x32, axis=0, keepdims=True)
            sq = jnp.sum(jnp.square(x32 - mean))
        total = total + sq
    return jnp.sqrt(total / m)


@scope("panel.consensus")
def consensus_from_mean(panel, means):
    """Xi_t from a PRECOMPUTED column-mean panel ({group: (D_g,) f32},
    e.g. the folded row of :func:`mix_dense_mean`): one deviation pass,
    no second mean reduce over the panel."""
    m = next(iter(panel.values())).shape[0]
    total = jnp.zeros((), jnp.float32)
    for k, x in panel.items():
        x32 = x.astype(jnp.float32)
        total = total + jnp.sum(jnp.square(x32 - means[k][None]))
    return jnp.sqrt(total / m)


def panel_norm(panel, axis_mean: bool = False, rows=None):
    """Global l2 norm of the panel (f32). With ``axis_mean`` the rows are
    averaged first (norm of the agent-mean, e.g. for grad-norm metrics);
    ``rows`` ((m,) f32 convex weights, e.g. the live mask's
    :func:`_live_weights`) replaces the uniform mean with a weighted
    one — the grad norm of an elastic round averages live agents only."""
    total = jnp.zeros((), jnp.float32)
    for x in panel.values():
        x32 = x.astype(jnp.float32)
        if axis_mean:
            if rows is None:
                x32 = jnp.mean(x32, axis=0)
            else:
                x32 = jnp.tensordot(rows, x32, axes=1, precision="highest")
        total = total + jnp.sum(jnp.square(x32))
    return jnp.sqrt(total)


