"""Wire codecs: pluggable compression of the gossip communication payload.

A codec controls how one dtype group's (m, D_g) panel travels during a
communication op without changing the storage dtype of the state. The
single entry point mirrors (and generalizes) the old ``panel._wire`` cast:

    xw, back, new_err = codec.encode(x, key=..., err=..., use_pallas=...)

``xw`` is the array the mixing math runs on — the receive-side view of
the payload (for ``int8``/``int4`` that is the dequantized panel;
quantization error is already baked in, exactly what every peer
reconstructs; for ``topk`` it is the updated MIRROR panel — see below).
``back`` restores the storage dtype after mixing. ``new_err`` is the
updated error-feedback state (input ``err`` passed through untouched on
residual-free codecs; an ``error_feedback`` codec REQUIRES ``err`` — a
missing residual raises rather than silently dropping the correction).

Codecs (``CODECS`` registry):

* ``f32``  — identity. The payload is the storage dtype as-is; bit-exact
  fallback (a bf16-stored group still ships 2-byte scalars — "f32" names
  full *storage* precision on the wire, not an upcast).
* ``bf16`` — the original wire-dtype lever, ported: cast to bf16 for the
  exchange, mix in bf16 with f32 accumulation, cast back. Bit-identical
  to the legacy ``wire_dtype=jnp.bfloat16`` behavior.
* ``int8`` — per-row (per-agent) symmetric scales amax/127, stochastic
  rounding driven by an explicit PRNG key (no ambient randomness: the
  key is threaded through the segment scan), 4x fewer payload bytes on
  f32 groups. ``int8_ef`` adds error feedback: the residual
  (x + e) - dequant(quant(x + e)) is returned for the caller to carry —
  the panel engine keeps it as an extra donated (m, D) f32 panel.
* ``int4`` — packed nibbles on the wire (TWO quantized values per byte,
  ``kernels/ref.py:pack_int4_ref`` layout: the first half of the columns
  in the low nibbles, the second half in the high ones) against GROUPED
  symmetric scales — one f32 amax/7 scale per row per ``group``
  (default 128) columns, so outlier columns only
  poison their own group instead of the whole row. Same key-driven
  stochastic rounding as int8; ``int4_ef`` adds the same error feedback.
  ~8x fewer payload bytes than f32 (plus 4/group scale overhead). The
  encode path round-trips the ACTUAL wire bytes (quantize -> pack ->
  unpack -> dequantize), so the mixed view is exactly what came off the
  wire, never an un-packed shortcut.
* ``topk`` — per-row top-k-by-magnitude SPARSE payload: k f32 values +
  k packed indices per agent per round. Error feedback is MANDATORY and
  structural: ``err`` carries the MIRROR panel x̂ (CHOCO-SGD style) — the
  receive-side reconstruction every peer has accumulated from past
  sparse innovations, seeded with a copy of the panel at init (one
  full-precision sync; ``init_err``). Each encode transmits the k
  largest entries of the innovation x - x̂ (threshold-sparsified,
  ``sparsify_topk_ref``), returns the updated mirror x̂ + q as both the
  mixing view and ``new_err``, and the effective residual x - x̂
  telescopes: dropped coordinates stay in the innovation until a later
  round transmits them.
  ``delta_mix = True`` tells the panel engine to mix in DELTA form,
  ``x <- x + (W - I) @ x̂`` (exact W @ x when the mirror has caught up),
  instead of ``W @ xw`` — a sparse payload mixed as ``W @ Q(x)`` would
  zero every untransmitted coordinate. The shared mirror panel models
  innovations reaching every agent (exactly true for the global rounds;
  for time-varying gossip it is the standard simulation simplification —
  only neighbors' mirror columns enter the mix each round).

Byte accounting: ``payload_bytes`` counts the quantized values alone
(the "8x fewer" numerator); ``total_bytes`` adds scale / index metadata
(grouped int4 scales, packed top-k indices) — what actually crosses the
wire. ``wire_payload`` materialises the real wire arrays (payload list,
metadata list) so tests can assert the accounting against ``.nbytes``.
``residual(x, err)`` maps the carried state to the effective EF residual
(identity for ``int8_ef``; ``x - x̂`` for the mirror-carrying ``topk``).

Kernels: ``use_pallas=True`` routes quantize/dequantize/pack/sparsify
through the Pallas kernels in ``kernels/wire_quant.py`` (same math as the
``kernels/ref.py`` oracles, bit-identical given the same uniforms);
sharded specs keep ``use_pallas=False`` so SPMD partitions the plain-XLA
ops, mirroring the panel matmul kernels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_mod
from repro.kernels import wire_quant


def _identity(y):
    return y


def _storage_back(dtype):
    """back() for a codec whose mixing view is f32: restore storage."""
    if jnp.dtype(dtype) == jnp.float32:
        return _identity
    return lambda y: y.astype(dtype)


class Codec:
    """Shared codec contract defaults (see module docstring)."""

    needs_key = False
    error_feedback = False
    delta_mix = False

    def payload_bytes(self, rows: int, width: int, dtype) -> int:
        """Wire bytes of the quantized VALUES alone for (rows, width)."""
        raise NotImplementedError

    def total_bytes(self, rows: int, width: int, dtype) -> int:
        """payload_bytes plus scale/index metadata — the full wire cost.
        Metadata-free codecs pay payload only."""
        return self.payload_bytes(rows, width, dtype)

    def residual(self, x, err):
        """Effective error-feedback residual given the carried ``err``
        state (identity by default; mirror-carrying codecs map it)."""
        return err

    def init_err(self, x):
        """Initial error-feedback state for one (m, D_g) group panel.
        Zeros for residual codecs; the mirror-carrying topk codec seeds
        its mirror with a COPY of the panel (one full-precision sync at
        init — from there only innovations travel; a zero mirror would
        make the early delta mixes pull on reconstructions that are
        arbitrarily far from the live parameters, which diverges)."""
        return jnp.zeros(x.shape, jnp.float32)

    def wire_payload(self, x, key=None, err=None):
        """The actual wire arrays: (payload list, metadata list), with
        sum(a.nbytes) matching payload_bytes / total_bytes exactly."""
        raise NotImplementedError


class F32Codec(Codec):
    """Identity codec: the payload is the storage dtype, untouched."""
    name = "f32"

    def payload_bytes(self, rows: int, width: int, dtype) -> int:
        return rows * width * jnp.dtype(dtype).itemsize

    def encode(self, x, key=None, err=None, use_pallas: bool = False):
        return x, _identity, err

    def wire_payload(self, x, key=None, err=None):
        return [x], []


class DtypeCodec(Codec):
    """Cast-only codec (the legacy ``wire_dtype`` lever): payload travels
    as ``wire_dtype``, the mix runs in that dtype with f32 accumulation,
    and the result is cast back to storage."""

    def __init__(self, wire_dtype, name: str):
        self.wire_dtype = jnp.dtype(wire_dtype)
        self.name = name

    def payload_bytes(self, rows: int, width: int, dtype) -> int:
        return rows * width * self.wire_dtype.itemsize

    def encode(self, x, key=None, err=None, use_pallas: bool = False):
        if x.dtype == self.wire_dtype:
            return x, _identity, err
        return (x.astype(self.wire_dtype),
                lambda y: y.astype(x.dtype), err)

    def wire_payload(self, x, key=None, err=None):
        return [x.astype(self.wire_dtype)], []


def _require_err(codec, err):
    if codec.error_feedback and err is None:
        raise ValueError(
            f"codec '{codec.name}' uses error feedback and needs the "
            "residual panel (err=...); a silent fallback would drop "
            "the accumulated correction")


def _require_key(codec, key):
    if codec.needs_key and key is None:
        raise ValueError(
            f"codec '{codec.name}' uses stochastic rounding and "
            "needs an explicit PRNG key (key=...)")


def _uniform(key, shape):
    # partitionable threefry ONLY for the wire draw: the default
    # (non-partitionable) lowering produces different bits when the draw
    # is jitted under SPMD than eager/replicated, which would break
    # sharded-vs-replicated parity of the stochastic rounding. Scoped
    # here so the rest of the program's key schedule (init, data, local
    # steps) is untouched.
    with jax.threefry_partitionable(True):
        return jax.random.uniform(key, shape, jnp.float32)


class Int8Codec(Codec):
    """int8 payload with per-row scales; optionally stochastic rounding
    (key-driven) and error feedback (residual returned to the caller)."""
    SCALE_BYTES = 4  # one f32 scale per agent row

    def __init__(self, name: str, stochastic: bool = True,
                 error_feedback: bool = False):
        self.name = name
        self.stochastic = stochastic
        self.error_feedback = error_feedback

    @property
    def needs_key(self) -> bool:
        return self.stochastic

    def payload_bytes(self, rows: int, width: int, dtype) -> int:
        return rows * width

    def total_bytes(self, rows: int, width: int, dtype) -> int:
        return rows * (width + self.SCALE_BYTES)

    def _carry_in(self, x, err):
        """The transmitted quantity x (+ residual for the EF variant)."""
        x32 = x.astype(jnp.float32)
        if self.error_feedback and err is not None:
            # only the EF codec consumes the residual; a residual-free
            # int8 codec handed an err (e.g. state resumed from an
            # int8_ef run) must NOT fold it into the payload — it would
            # re-inject the same bias every round without ever updating it
            x32 = x32 + err
        return x32

    def _quantize(self, x32, key, use_pallas: bool):
        u = None
        if self.stochastic:
            _require_key(self, key)
            u = _uniform(key, x32.shape)
        scale = ref_mod.int8_scale_ref(x32)
        if use_pallas:
            q, _ = wire_quant.quantize_int8_panel(x32, scale, u)
        else:
            q = ref_mod.quantize_int8_ref(x32, scale, u)
        return q, scale

    def encode(self, x, key=None, err=None, use_pallas: bool = False):
        _require_err(self, err)
        x32 = self._carry_in(x, err)
        q, scale = self._quantize(x32, key, use_pallas)
        if use_pallas:
            xhat32 = wire_quant.dequantize_int8_panel(q, scale)
        else:
            xhat32 = ref_mod.dequantize_int8_ref(q, scale)
        new_err = (x32 - xhat32) if (self.error_feedback
                                     and err is not None) else err
        if x.dtype == jnp.float32:
            return xhat32, _identity, new_err
        return xhat32.astype(x.dtype), _identity, new_err

    def wire_payload(self, x, key=None, err=None):
        _require_err(self, err)  # same contract as encode: never
        # silently measure Q(x) when the run would transmit Q(x + e)
        q, scale = self._quantize(self._carry_in(x, err), key, False)
        return [q], [scale]


class Int4Codec(Codec):
    """Packed-nibble int4 payload with grouped scales: one f32 amax/7
    scale per row per ``group`` columns, two quantized values per wire
    byte. Stochastic rounding and error feedback as in :class:`Int8Codec`;
    the encode path reconstructs the mixing view from the ACTUAL packed
    bytes (quantize -> pack -> unpack -> dequantize)."""
    SCALE_BYTES = 4  # one f32 scale per (row, column group)

    def __init__(self, name: str, stochastic: bool = True,
                 error_feedback: bool = False, group: int = 128):
        self.name = name
        self.stochastic = stochastic
        self.error_feedback = error_feedback
        self.group = group

    @property
    def needs_key(self) -> bool:
        return self.stochastic

    def n_groups(self, width: int) -> int:
        return -(-width // self.group)

    def payload_bytes(self, rows: int, width: int, dtype) -> int:
        return rows * ((width + 1) // 2)

    def total_bytes(self, rows: int, width: int, dtype) -> int:
        return (self.payload_bytes(rows, width, dtype)
                + rows * self.n_groups(width) * self.SCALE_BYTES)

    _carry_in = Int8Codec._carry_in

    def _quantize(self, x32, key, use_pallas: bool):
        u = None
        if self.stochastic:
            _require_key(self, key)
            u = _uniform(key, x32.shape)
        scale = ref_mod.int4_group_scale_ref(x32, self.group)
        if use_pallas:
            q, _ = wire_quant.quantize_int4_panel(x32, scale, u,
                                                  group=self.group)
        else:
            q = ref_mod.quantize_int4_ref(x32, scale, u, self.group)
        return q, scale

    def encode(self, x, key=None, err=None, use_pallas: bool = False):
        _require_err(self, err)
        x32 = self._carry_in(x, err)
        D = x.shape[1]
        q, scale = self._quantize(x32, key, use_pallas)
        # the mixing view is rebuilt from the packed WIRE bytes — the
        # pack/unpack pair is an exact inverse for values in [-7, 7], so
        # this costs two cheap byte kernels and guarantees the math runs
        # on exactly what a receiver would reconstruct
        if use_pallas:
            packed = wire_quant.pack_int4_panel(q)
            qw = wire_quant.unpack_int4_panel(packed, D)
            xhat32 = wire_quant.dequantize_int4_panel(
                qw, scale, group=self.group)
        else:
            packed = ref_mod.pack_int4_ref(q)
            qw = ref_mod.unpack_int4_ref(packed, D)
            xhat32 = ref_mod.dequantize_int4_ref(qw, scale, self.group)
        new_err = (x32 - xhat32) if (self.error_feedback
                                     and err is not None) else err
        if x.dtype == jnp.float32:
            return xhat32, _identity, new_err
        return xhat32.astype(x.dtype), _identity, new_err

    def wire_payload(self, x, key=None, err=None):
        _require_err(self, err)  # as in Int8Codec.wire_payload
        q, scale = self._quantize(self._carry_in(x, err), key, False)
        return [ref_mod.pack_int4_ref(q)], [scale]


class TopKCodec(Codec):
    """Top-k sparsified payload over a mirror panel (CHOCO-style; see
    the module docstring). ``err`` carries the mirror x̂, seeded with a
    copy of the panel at init (:meth:`init_err` — one full-precision
    sync; from there only sparse innovations travel); encode transmits
    the k largest-magnitude entries of the innovation x - x̂ and returns
    the updated mirror as both the mixing view and the new carried
    state. ``delta_mix`` switches the panel engine to
    ``x <- x + (W - I) @ x̂`` mixing."""

    error_feedback = True   # the mirror IS the feedback state
    delta_mix = True
    needs_key = False       # values travel exact (f32) — nothing to dither
    VALUE_BYTES = 4

    # panels wider than this estimate the selection threshold from a
    # strided column subsample instead of an exact full-row top_k: the
    # exact k-th statistic is a full per-row sort (O(D log D) — ~48 s/row
    # panel at D=7.2M on CPU, and the same asymptotic pain on TPU), while
    # the subsampled quantile is O(sample log sample) and keeps ≈k
    # entries (the standard scalable approximate-top-k; the wire
    # accounting models exactly k). Tests exercise exact selection —
    # their panels sit far below the cutoff.
    THRESH_SAMPLE = 1 << 16

    def __init__(self, name: str = "topk", density: float = 0.125,
                 gamma: float = None, thresh_sample: int = THRESH_SAMPLE):
        if not 0.0 < density <= 1.0:
            raise ValueError(f"density must be in (0, 1], got {density}")
        self.name = name
        self.density = density
        self.thresh_sample = thresh_sample
        # CHOCO consensus step size: the delta mix x + gamma (W - I) x̂
        # must be damped in proportion to the compression — with gamma=1
        # each round injects the FULL mixing pull computed on mirrors
        # that the k-budget can only partially reconcile, and |x - x̂|
        # grows without bound (verified numerically: density 1/8,
        # gamma=1 diverges; gamma≈2*density contracts). The one-shot
        # global merge needs no damping: it is the full-bandwidth round
        # (see the engine's delta-merge path).
        self.gamma = min(1.0, 2.0 * density) if gamma is None else gamma

    def k_of(self, width: int) -> int:
        return max(1, int(width * self.density))

    def idx_bytes(self, width: int) -> int:
        """Bytes per packed index: the fewest whole bytes that address
        ``width`` columns (3 for panels up to 16M scalars)."""
        bits = max(1, math.ceil(math.log2(max(width, 2))))
        return (bits + 7) // 8

    def payload_bytes(self, rows: int, width: int, dtype) -> int:
        return rows * self.k_of(width) * self.VALUE_BYTES

    def total_bytes(self, rows: int, width: int, dtype) -> int:
        return (self.payload_bytes(rows, width, dtype)
                + rows * self.k_of(width) * self.idx_bytes(width))

    def residual(self, x, err):
        """The effective EF residual is the untransmitted innovation."""
        if err is None:
            return None
        return x.astype(jnp.float32) - err

    def init_err(self, x):
        # the mirror starts as a COPY of the panel (jnp.array copies —
        # an f32 aliasing view would break the segment driver's buffer
        # donation): one full-precision sync at init, sparse innovations
        # from then on. See Codec.init_err for why not zeros.
        return jnp.array(x, jnp.float32)

    def _threshold(self, innov):
        """Per-row selection threshold: the exact k-th largest |innov|
        up to ``thresh_sample`` columns, a strided-subsample quantile
        estimate beyond (see THRESH_SAMPLE)."""
        D = innov.shape[1]
        if D <= self.thresh_sample:
            return ref_mod.topk_threshold_ref(innov, self.k_of(D))
        stride = D // self.thresh_sample
        sub = jnp.abs(innov[:, ::stride].astype(jnp.float32))
        kk = max(1, int(sub.shape[1] * self.density))
        return jax.lax.top_k(sub, kk)[0][:, -1:]

    def encode(self, x, key=None, err=None, use_pallas: bool = False):
        _require_err(self, err)
        x32 = x.astype(jnp.float32)
        innov = x32 - err
        thresh = self._threshold(innov)
        if use_pallas:
            q = wire_quant.sparsify_topk_panel(innov, thresh)
        else:
            q = ref_mod.sparsify_topk_ref(innov, thresh)
        mirror = err + q
        return mirror, _storage_back(x.dtype), mirror

    def wire_payload(self, x, key=None, err=None):
        _require_err(self, err)  # the innovation is only defined
        # against the mirror — measuring top-k of the raw panel instead
        # would be a different (and wrong) payload
        x32 = x.astype(jnp.float32)
        innov = x32 - err
        D = x.shape[1]
        k = self.k_of(D)
        _, idx = jax.lax.top_k(jnp.abs(innov), k)
        vals = jnp.take_along_axis(innov, idx, axis=1)
        nb = self.idx_bytes(D)
        shifts = jnp.arange(nb, dtype=jnp.uint32) * 8
        packed_idx = ((idx.astype(jnp.uint32)[..., None] >> shifts)
                      & 0xFF).astype(jnp.uint8)
        return [vals.astype(jnp.float32)], [packed_idx]


CODECS = {
    "f32": F32Codec(),
    "bf16": DtypeCodec(jnp.bfloat16, "bf16"),
    "int8": Int8Codec("int8", stochastic=True, error_feedback=False),
    "int8_ef": Int8Codec("int8_ef", stochastic=True, error_feedback=True),
    "int4": Int4Codec("int4", stochastic=True, error_feedback=False),
    "int4_ef": Int4Codec("int4_ef", stochastic=True, error_feedback=True),
    "topk": TopKCodec("topk", density=0.125),
}


def get_codec(name):
    """Resolve a codec by registry name; codec instances pass through
    (lets tests build e.g. a deterministic-rounding Int8Codec)."""
    if not isinstance(name, str) and hasattr(name, "encode"):
        return name
    try:
        return CODECS[name]
    except KeyError:
        raise ValueError(
            f"unknown wire codec {name!r}; known: {sorted(CODECS)}"
        ) from None


def dtype_codec(wire_dtype):
    """Codec for the legacy ``wire_dtype=`` argument (None -> identity)."""
    if wire_dtype is None:
        return CODECS["f32"]
    wd = jnp.dtype(wire_dtype)
    if wd == jnp.dtype(jnp.bfloat16):
        return CODECS["bf16"]
    return DtypeCodec(wd, wd.name)
