"""Storage codecs: the quantized-wire machinery pointed at HBM.

Every agent costs 4+ f32 (m, D) rows of panel HBM (params, two AdamW
moments, plus the ``wire_err``/``merge_stat`` panels when active), so
resident bytes — not FLOPs — cap the agent count m per chip. This
subsystem mirrors the ``repro/wire`` codec registry but compresses the
RESIDENT state panels instead of the communication payload: a
:data:`STORAGE` / :func:`get_storage` registry of storage codecs applied
per state-panel KIND (``moments`` / ``stats`` / ``wire_err`` — params
always stay in their native dtypes) via a residency policy carried on
``PanelSpec`` (``panel.with_residency``, e.g.
``--residency moments=int8,stats=bf16``).

Contract (each entry is a :class:`Storage`):

* ``init(x)``  — deterministic encode (round-to-nearest) of an f32
  (m, D) panel into its stored representation. Used at state build and
  for RESYNC re-initialization, so a rejoining agent's stored rows
  bit-match a fresh init.
* ``write(x, key=...)`` — the hot-path encode fused into the scanned
  segment: stochastic-rounding storages REQUIRE a key (unbiased over
  keys, like the wire codecs' SR).
* ``read(stored)`` — decode back to the f32 compute view.
* ``zero_like(stored)`` — the CANONICAL zero representation
  (bit-identical to ``init(zeros)``): int8 stores q=0 with scale 1/127
  (the ``int8_scale_ref`` zero-row rule), so RESYNC moment zeroing
  produces the same bits as a fresh state.
* ``resident_bytes(rows, width)`` — exact HBM bytes of the stored rep
  (values + scale sidecars) for an f32 (rows, width) panel.

Stored representations: ``f32`` is the IDENTITY (the raw array passes
through untouched — an f32 policy is byte-identical to no policy, and
non-f32 dtype groups always ride the identity). ``bf16`` stores the
cast array. The int8 entries store ``{"q": int8 (m, D),
"scale": f32 sidecar}`` dicts — per-row scales (m, 1) or grouped scales
(m, ceil(D/group)) — reusing the conformance-tested
``kernels/wire_quant`` quantize kernels (ref oracles in
``kernels/ref.py``) with the wire codecs' partitionable-threefry
uniform draw, so sharded and replicated runs store identical bits.

Int8 moment storage NEEDS companding. Linear int8 symmetric
quantization (per-row or grouped) stochastically rounds Adam's small
second-moment entries to zero; the next update then divides by
``sqrt(0) + eps`` and amplifies those coordinates ~1e8x — at real LM
widths the run NaNs within two rounds (observed, not hypothetical;
this is exactly why production 8-bit optimizers use nonlinear/dynamic
maps). The fix shipped here: the ``int8``/``int8g`` entries encode in
the SIGNED-SQRT domain — quantize ``sign(x)*sqrt(|x|)`` linearly,
decode ``sign(z)*z**2`` — which allocates relative (not absolute)
precision near zero. SR stays unbiased in the sqrt domain; the Jensen
term makes the decoded second moment a hair LARGER on average, which
is the safe direction for Adam (it shrinks steps rather than blowing
them up). Grouped scales are also required: one per-row scale is too
coarse for moment panels even in the sqrt domain (``int8r`` keeps the
raw linear per-row layout for residual-like panels such as
``wire_err``/``stats``, where values are parameter-scaled and a
zeroed small entry is harmless).

Like ``repro/wire``, everything here is engine-agnostic: the segment
driver (core/dsgd.py) owns WHERE the encode/decode fuses into the round
(decode moments before the optimizer update, write back quantized in
the same donated step — no resident f32 copy survives the round).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_mod
from repro.kernels import wire_quant
# the wire codecs' uniform draw (jax.threefry_partitionable scope):
# storage SR must be bit-identical between sharded and replicated runs
# for exactly the same reason the wire codecs' is
from repro.wire.codec import _uniform

# state-panel kinds a residency policy may name; params are deliberately
# NOT a kind — the mixing matmul/merge operators read them every round,
# so quantizing them is a wire question (repro/wire), not a storage one
KINDS = ("moments", "stats", "wire_err")


class Storage:
    """Base storage codec: the f32 identity (raw arrays pass through)."""

    name = "f32"
    needs_key = False  # write() draws stochastic-rounding bits from key=
    # whether the stored rep supports the fused in-VMEM optimizer update
    # (kernels/opt_fused.py): decode->update->re-encode without an HBM
    # f32 view. Requires block-local re-scaling, i.e. GROUPED scales —
    # per-row scales need a full-D amax pass, so only grouped int8
    # qualifies; everything else keeps the unfused path.
    fused_update = False

    # ------------------------------------------------------------ codec
    def init(self, x):
        """Deterministic encode (state build / RESYNC re-init)."""
        return x

    def write(self, x, *, key=None, use_pallas: bool = False):
        """Hot-path encode of an f32 panel into its stored rep."""
        return x

    def read(self, stored, *, use_pallas: bool = False):
        """Decode a stored rep back to the f32 compute view."""
        return stored

    def maybe_read(self, v, *, use_pallas: bool = False):
        """``read`` that tolerates an ALREADY-DECODED f32 leaf — the
        out-of-engine entry point (merging.merge_panel's stat reads may
        see either the stored rep or the engine's decoded view)."""
        return v

    # the domain the quantizer (and its stochastic rounding) operates
    # in: identity for linear codecs, signed-sqrt for companded int8.
    # SR unbiasedness holds in THIS domain (conformance tests check it
    # here; the value domain picks up a small Jensen bias on decode).
    def transform_fwd(self, x):
        return x

    def transform_inv(self, y):
        return y

    def zero_like(self, stored):
        """Canonical zero stored rep (bit-identical to init(zeros))."""
        return jax.tree.map(jnp.zeros_like, stored)

    # ------------------------------------------------------- accounting
    def resident_bytes(self, rows: int, width: int) -> int:
        """Exact HBM bytes of the stored rep of an f32 (rows, width)
        panel, scale sidecars included."""
        return rows * width * 4


class F32Storage(Storage):
    """The identity: byte-identical to the pre-residency engine."""


class Bf16Storage(Storage):
    """bf16 cast storage: 2 bytes/scalar, no sidecar (the original
    optimizer-state halving lever — cf. olmax's bf16 momentum)."""

    name = "bf16"

    def init(self, x):
        return x.astype(jnp.bfloat16)

    def write(self, x, *, key=None, use_pallas: bool = False):
        return x.astype(jnp.bfloat16)

    def read(self, stored, *, use_pallas: bool = False):
        return stored.astype(jnp.float32)

    def maybe_read(self, v, *, use_pallas: bool = False):
        # state panels are f32 by construction, so a bf16 leaf can only
        # be this storage's rep; an already-decoded f32 view passes
        return v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v

    def resident_bytes(self, rows: int, width: int) -> int:
        return rows * width * 2


class Int8Storage(Storage):
    """Symmetric int8 storage with f32 scale sidecars: 1 byte/scalar +
    4 bytes per scale. ``group=None`` keeps one scale per row (m, 1);
    an int ``group`` stores one scale per ``group`` columns
    (m, ceil(D/group)) — tighter scales for wide panels whose row amax
    is dominated by a few coordinates. Stored rep:
    ``{"q": int8 (m, D), "scale": f32 sidecar}``.

    ``write`` uses key-driven stochastic rounding (unbiased over keys —
    a biased round-to-nearest would systematically shrink EMA moments);
    ``init`` rounds to nearest (deterministic, so state build and
    RESYNC re-init are reproducible without a key schedule).

    ``transform="sqrt"`` composes signed-sqrt companding around the
    linear quantizer: encode quantizes ``sign(x)*sqrt(|x|)``, decode
    squares back. The transform is a pair of cheap elementwise jnp ops
    OUTSIDE the Pallas kernels (XLA fuses them into the surrounding
    segment), so the conformance-tested linear kernels are reused
    untouched. This is what makes int8 safe for Adam's second moment —
    see the module docstring for the failure mode it prevents."""

    SCALE_BYTES = 4
    needs_key = True

    def __init__(self, name: str = "int8", group=None, transform=None):
        if transform not in (None, "sqrt"):
            raise ValueError(f"unknown transform {transform!r}")
        self.name = name
        self.group = group
        self.transform = transform
        # grouped scales are block-local in the fused kernel's grid, so
        # the re-encode can compute them in-VMEM; per-row scales can't
        self.fused_update = group is not None

    def transform_fwd(self, x):
        if self.transform is None:
            return x
        x = x.astype(jnp.float32)
        return jnp.sign(x) * jnp.sqrt(jnp.abs(x))

    def transform_inv(self, y):
        if self.transform is None:
            return y
        return jnp.sign(y) * jnp.square(y)

    # ------------------------------------------------------------ codec
    def _scale(self, x32):
        if self.group is None:
            return ref_mod.int8_scale_ref(x32)
        return ref_mod.int8_group_scale_ref(x32, self.group)

    def _quantize(self, x, u, use_pallas):
        x32 = self.transform_fwd(x.astype(jnp.float32))
        scale = self._scale(x32)
        if use_pallas:
            if self.group is None:
                q, _ = wire_quant.quantize_int8_panel(
                    x32, scale, u)
            else:
                q, _ = wire_quant.quantize_int8_grouped_panel(
                    x32, scale, u, group=self.group)
        elif self.group is None:
            q = ref_mod.quantize_int8_ref(x32, scale, u)
        else:
            q = ref_mod.quantize_int8_grouped_ref(x32, scale, u,
                                                  self.group)
        return {"q": q, "scale": scale}

    def init(self, x):
        return self._quantize(x, None, False)

    def write(self, x, *, key=None, use_pallas: bool = False):
        if key is None:
            raise ValueError(
                f"storage '{self.name}' uses stochastic rounding and "
                "needs an explicit key= (use init() for the "
                "deterministic encode)")
        u = _uniform(key, x.shape)
        return self._quantize(x, u, use_pallas)

    def read(self, stored, *, use_pallas: bool = False):
        q, scale = stored["q"], stored["scale"]
        if use_pallas:
            if self.group is None:
                y = wire_quant.dequantize_int8_panel(
                    q, scale)
            else:
                y = wire_quant.dequantize_int8_grouped_panel(
                    q, scale, group=self.group)
        elif self.group is None:
            y = ref_mod.dequantize_int8_ref(q, scale)
        else:
            y = ref_mod.dequantize_int8_grouped_ref(q, scale, self.group)
        return self.transform_inv(y)

    def maybe_read(self, v, *, use_pallas: bool = False):
        if isinstance(v, dict):
            return self.read(v, use_pallas=use_pallas)
        return v

    def zero_like(self, stored):
        # q=0 at scale 1/127 IS init(zeros): the scale refs map all-zero
        # rows/groups to amax 1.0 -> scale 1/127 (dequant stays a plain
        # multiply), so a canonically-zeroed RESYNC row bit-matches a
        # freshly initialised one. Companding preserves this: the sqrt
        # transform fixes 0 in both directions.
        return {"q": jnp.zeros_like(stored["q"]),
                "scale": jnp.full_like(stored["scale"], 1.0 / 127.0)}

    # ------------------------------------------------------- accounting
    def scale_count(self, width: int) -> int:
        return 1 if self.group is None else -(-width // self.group)

    def resident_bytes(self, rows: int, width: int) -> int:
        return rows * (width + self.scale_count(width) * self.SCALE_BYTES)


STORAGE = {
    "f32": F32Storage(),
    "bf16": Bf16Storage(),
    # moment-safe int8: signed-sqrt companded, grouped scales. "int8g"
    # trades extra scale sidecar (g=32 vs g=128) for tighter groups.
    "int8": Int8Storage("int8", group=128, transform="sqrt"),
    "int8g": Int8Storage("int8g", group=32, transform="sqrt"),
    # raw linear per-row int8 (the wire codec's storage layout): fine
    # for parameter-scaled residual panels (wire_err, stats), UNSAFE
    # for Adam moments — see the module docstring
    "int8r": Int8Storage("int8r"),
}


def get_storage(name):
    """Resolve a storage codec by registry name; Storage instances pass
    through (mirrors wire.get_codec / merging.get_merger)."""
    if not isinstance(name, str) and hasattr(name, "resident_bytes"):
        return name
    try:
        return STORAGE[name]
    except KeyError:
        raise ValueError(
            f"unknown storage codec {name!r}; known: {sorted(STORAGE)}"
        ) from None


def storage_keys(storages: dict, key):
    """One SR key per dtype group that needs one, folded in sorted-group
    order so sharded and replicated runs store identical bits (the
    exact discipline of ``panel._wire_keys``)."""
    names = sorted(k for k, s in storages.items() if s.needs_key)
    if not names:
        return {k: None for k in storages}
    if key is None:
        raise ValueError(
            f"storage codecs for groups {names} use stochastic rounding "
            "and need an explicit key=")
    folded = {k: jax.random.fold_in(key, i) for i, k in enumerate(names)}
    return {k: folded.get(k) for k in storages}


def parse_policy(policy):
    """CLI residency policy -> {kind: storage-name}.

    ``None``/empty -> {} (no policy); ``'kind=name,kind=name'`` pairs
    (``--residency moments=int8,stats=bf16``); a bare storage name
    applies to the moments (the dominant state panels). Kinds and names
    are validated here so a typo fails at parse time."""
    if not policy:
        return {}
    if isinstance(policy, dict):
        mapping = dict(policy)
    elif "=" in policy:
        mapping = {}
        for part in policy.split(","):
            kind, _, name = part.partition("=")
            mapping[kind.strip()] = name.strip()
    else:
        mapping = {"moments": policy.strip()}
    unknown = set(mapping) - set(KINDS)
    if unknown:
        raise ValueError(
            f"residency policy names unknown state kinds "
            f"{sorted(unknown)}; known kinds: {list(KINDS)}")
    for name in mapping.values():
        get_storage(name)
    return mapping
