"""Continuous-batching serving engine for the (merged) model.

The artifact decentralized training produces — after the paper's single
global merging — is ONE model; this module serves it maxtext/JetStream
style with a three-op split:

* **prefill(request)** — run the prompt at its exact length (one jit trace
  per distinct prompt length) against a cache row already sized for the
  full decode horizon (``max_len``);
* **insert(row, slot)** — splice that B=1 cache row into slot ``s`` of the
  engine's persistent slotted cache: every cache/state leaf is laid out
  ``(n_rep, max_concurrency, ...)`` and a slot is row ``s`` of axis 1
  across all layers' KV rings, recurrent states and cross-attention
  caches. The buffer is created once and DONATED through insert and step,
  so decode never reallocates it;
* **step()** — ONE jitted decode step over all slots at once, each at its
  own absolute position (per-slot position vectors), sampling one token
  per slot.

A host-side scheduler (:class:`ServingEngine`) admits queued requests into
free slots and retires slots on EOS / max-new, so heterogeneous-length
requests stream through a single compiled decode step — continuous
batching. At temperature 0 the engine is token-bit-identical to running
each request alone through :func:`generate` (pinned by tests): padded /
retired slots only ever contribute exact zeros to other rows' softmax
sums, and all per-row compute is batch-independent.

Sampling masks logits columns >= ``cfg.vocab_size`` to -inf first: the LM
head projects to ``cfg.padded_vocab`` (models/model.py) and the padding
columns carry random-init weights, so unmasked greedy/temperature sampling
can emit out-of-vocab ids.

**Matmul operands.** Where a float32 matmul at the precision in force is
one pass over bfloat16 operands (a TPU at the default precision,
:func:`f32_matmul_is_bf16_pass`), the engine holds the dense weight
matrices as those operands: when it takes the params it casts each leaf
``Model.operand_params`` names (the attention projections and dense MLP
matrices) to bfloat16 once, in one jitted program, and keeps every other
leaf as the caller's. ``models.layers.dense`` multiplies a float32
activation by such a weight with float32 accumulation, so no prefill or
decode step rounds the weights again; the products are the ones the
float32 weights give on the MXU. (A one-row matmul, the decode step of a
one-slot engine, the TPU compiler lowers to a float32 multiply-reduce:
there the float32 weights gave a finer product than the bf16 operands.)
Elsewhere (CPU, or ``highest`` precision) the engine serves the params
as given. ``stats`` (and :meth:`snapshot`)
report the cast leaves and their bytes, ``operand_leaves`` and
``operand_bytes``, both 0 where nothing was cast.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import annotate, histogram_set, scope


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def mask_oov(logits, vocab_size: Optional[int]):
    """Mask the padded-vocab tail: columns >= vocab_size go to -inf."""
    if vocab_size is None or vocab_size >= logits.shape[-1]:
        return logits
    oov = jnp.arange(logits.shape[-1]) >= vocab_size
    return jnp.where(oov, -jnp.inf, logits)


def sample_token(logits, rng, temperature: float = 0.0,
                 vocab_size: Optional[int] = None):
    """Greedy (temperature<=0) or categorical sample, never out-of-vocab.

    ``vocab_size`` is the REAL vocab; the head matmul is over
    ``padded_vocab`` whose tail columns are random-init — they must be
    masked before argmax/categorical or both can return ids outside the
    vocab."""
    logits = mask_oov(logits, vocab_size)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature, axis=-1).astype(
        jnp.int32)


# ---------------------------------------------------------------------------
# matmul operands
# ---------------------------------------------------------------------------

# values of jax_default_matmul_precision at which a TPU multiplies float32
# operands in one bfloat16 pass (None: not set)
_ONE_PASS_PRECISIONS = (None, "default", "bfloat16")


def f32_matmul_is_bf16_pass() -> bool:
    """Whether a float32 matmul here rounds both operands to bfloat16 and
    multiplies them in one pass with float32 accumulation: on a TPU, at
    the default matmul precision."""
    return (jax.default_backend() == "tpu"
            and jax.config.jax_default_matmul_precision
            in _ONE_PASS_PRECISIONS)


def operand_copy(model, params):
    """(params to serve, number of leaves cast, their bytes): the leaves
    ``model.operand_params`` names cast to bfloat16 in one jitted program
    where :func:`f32_matmul_is_bf16_pass`, every other leaf the caller's
    own; else ``params`` as given."""
    if not f32_matmul_is_bf16_pass():
        return params, 0, 0
    cast = jax.jit(model.operand_params, static_argnums=1)(params,
                                                          jnp.bfloat16)
    out = jax.tree.map(lambda c, p: c if c.dtype != p.dtype else p,
                       cast, params)
    done = [c for c, p in zip(jax.tree.leaves(out), jax.tree.leaves(params))
            if c is not p]
    return out, len(done), sum(c.nbytes for c in done)


# ---------------------------------------------------------------------------
# jitted ops
# ---------------------------------------------------------------------------


def make_prefill_fn(model, max_len: Optional[int] = None):
    def prefill(params, batch):
        with scope("serve.prefill"):
            return model.prefill(params, batch, max_len=max_len)
    return jax.jit(prefill)


def make_decode_fn(model):
    """Jitted decode step with the cache DONATED: the new cache aliases the
    input buffer in place instead of copying max_len of KV per token.
    Callers must not reuse the cache they passed in afterwards."""
    def decode(params, caches, tokens, index):
        with scope("serve.decode"):
            return model.decode_step(params, caches, tokens, index)
    return jax.jit(decode, donate_argnums=(1,))


def _tree_insert(caches, row, slot):
    """Splice a B=1 cache row (from prefill) into slot ``slot`` (axis 1 of
    every leaf) of the slotted cache. Leaves whose trailing dims are
    shorter than the engine's (cross-attention KV at the request's encoder
    length) are padded up — position leaves with -1 so the padding stays
    masked, everything else with zeros."""
    def put(path, big, r):
        r = r.astype(big.dtype)
        if r.shape[2:] != big.shape[2:]:
            cval = -1 if getattr(path[-1], "key", None) == "pos" else 0
            pads = [(0, 0), (0, 0)] + [(0, b - s) for b, s in
                                       zip(big.shape[2:], r.shape[2:])]
            r = jnp.pad(r, pads, constant_values=cval)
        return jax.lax.dynamic_update_slice_in_dim(big, r, slot, axis=1)
    with scope("serve.insert"):
        return jax.tree_util.tree_map_with_path(put, caches, row)


# ---------------------------------------------------------------------------
# one-shot generate (static batch)
# ---------------------------------------------------------------------------


def generate(model, params, batch, max_new: int, *, temperature: float = 0.0,
             rng=None, max_len: Optional[int] = None,
             eos_id: Optional[int] = None):
    """batch: model input dict with 'tokens' (B, S_prompt). Returns
    (B, max_new) generated tokens.

    The decode loop runs ON DEVICE inside one jit (lax.scan, or
    lax.while_loop with early exit when ``eos_id`` is set): tokens are
    collected in a device buffer and fetched ONCE at the end — no
    per-token host sync — and the prefill cache is donated into the loop.
    Rows that hit ``eos_id`` keep emitting ``eos_id`` and stop advancing
    their logits' influence; once every row is done the loop exits early
    so retired requests stop consuming decode steps."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    B, S = batch["tokens"].shape
    prefix = batch["patch_embeds"].shape[1] if "patch_embeds" in batch else 0
    S = S + prefix  # absolute positions include the multimodal prefix
    total = max_len or (S + max_new)
    V = model.cfg.vocab_size
    prefill = make_prefill_fn(model, max_len=total)
    logits, caches = prefill(params, batch)

    # params enter the jit as an argument: closed over, they would be
    # baked into the program as constants (at olmo-1b widths the TPU
    # compile of this loop took 89 s per call that way)
    @partial(jax.jit, donate_argnums=(2,))
    def loop(params, logits, caches, rng):
        def body(carry):
            i, caches, logits, rng, done, out = carry
            rng, k = jax.random.split(rng)
            tok = sample_token(logits, k, temperature, vocab_size=V)
            if eos_id is not None:
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
            out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, i))
            logits, caches = model.decode_step(params, caches, tok[:, None],
                                               jnp.asarray(S, jnp.int32) + i)
            return i + 1, caches, logits, rng, done, out

        out0 = jnp.full((B, max_new),
                        eos_id if eos_id is not None else 0, jnp.int32)
        carry = (jnp.asarray(0, jnp.int32), caches, logits, rng,
                 jnp.zeros((B,), bool), out0)
        if eos_id is None:
            carry, _ = jax.lax.scan(lambda c, _: (body(c), None), carry,
                                    None, length=max_new)
        else:
            carry = jax.lax.while_loop(
                lambda c: (c[0] < max_new) & ~jnp.all(c[4]), body, carry)
        # the cache is returned (and dropped by the caller) so the donated
        # input buffer has an output to alias — in-place for the whole loop
        return carry[1], carry[-1]

    _, out = loop(params, logits, caches, rng)
    return np.asarray(out)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


@dataclass
class Request:
    """One serving request: prompt ids + optional multimodal extras
    (``patch_embeds`` (P, d) / ``frame_embeds`` (S, d), unbatched).

    ``arrival`` is the ``time.perf_counter()`` time at which the request
    reached the server; ``submit()`` stamps it when left None. Queue wait
    and TTFT run from it, so a caller that submits late (after a step it
    was blocked on) passes the true arrival."""
    rid: Any
    tokens: np.ndarray
    max_new: int = 16
    extras: Dict[str, Any] = field(default_factory=dict)
    arrival: Optional[float] = None


# admitted requests whose record the engine keeps (oldest dropped first)
RECORD_CAP = 4096


class _Slot:
    __slots__ = ("req", "pos", "last", "out", "rec")

    def __init__(self, req, pos, first_token, rec):
        self.req = req
        self.pos = pos  # absolute position of the NEXT token to feed
        self.last = first_token
        self.out = [first_token]
        self.rec = rec  # the request's record (see ServingEngine)


class ServingEngine:
    """Slotted continuous-batching engine (see module docstring).

    ``max_len`` bounds prefix + prompt + max_new per request; the slotted
    cache holds ``max_concurrency`` such rows as one persistent donated
    device buffer. ``step()`` fetches exactly one (C,) token vector per
    tick — the scheduler needs the ids to retire slots — everything else
    stays on device.

    **Telemetry.** The engine keeps its own fixed-bucket latency
    histograms (:mod:`repro.telemetry.latency`): ``ttft_s`` (arrival →
    first token, covers queue + prefill), ``queue_wait_s`` (arrival →
    admission), ``decode_step_s`` (one jitted step incl. the (C,) token
    fetch) and ``per_token_s`` (a retired request's steady-state decode
    rate: time from its first token to retirement over tokens-1). It also
    keeps one record per admitted request (the newest ``RECORD_CAP``):
    ``rid``, ``arrival``, ``admit`` (admission start), ``first`` (first
    token on the host), ``retire`` (None while live) and ``tokens``, all
    ``perf_counter`` times. :meth:`snapshot` exports counters + occupancy
    + histogram summaries + the records; :meth:`reset` zeroes them
    WITHOUT touching live slots or queued work, so callers can discard
    warmup/compile ticks (serve_bench, the serve CLI). Passing ``events=``
    an :class:`repro.telemetry.EventLog` emits typed
    ``request_submit``/``request_admit``/``request_retire`` records.

    Host spans (:func:`repro.telemetry.annotate`) cover all of the
    engine's host time: ``serve.step`` a whole :meth:`step`, with
    ``serve.fetch`` its (C,) token fetch inside; ``serve.admit`` one
    request's whole admission, with ``serve.first_token`` (the host-side
    sample and its fetch) inside.
    """

    def __init__(self, model, params, *, max_concurrency: int = 4,
                 max_len: int = 128, eos_id: Optional[int] = None,
                 temperature: float = 0.0, rng=None, pad_id: int = 0,
                 events=None):
        self.model = model
        self.params, n_cast, cast_bytes = operand_copy(model, params)
        self.cfg = model.cfg
        self.C, self.max_len = int(max_concurrency), int(max_len)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.pad_id = int(pad_id)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.caches = model.init_cache(self.C, self.max_len,
                                       enc_len=self.max_len)
        self._empty_row = model.init_cache(1, self.max_len,
                                           enc_len=self.max_len)
        self._prefill = make_prefill_fn(model, max_len=self.max_len)
        self._insert_fn = jax.jit(_tree_insert, donate_argnums=(0,))
        V = self.cfg.vocab_size
        temp = self.temperature

        def step_fn(params, caches, tokens, index, rng):
            with scope("serve.decode"):
                logits, caches = model.decode_step(params, caches,
                                                   tokens[:, None], index)
            with scope("serve.sample"):
                tok = sample_token(logits, rng, temp, vocab_size=V)
            return caches, tok

        self._step_fn = jax.jit(step_fn, donate_argnums=(1,))
        self._slots: List[Optional[_Slot]] = [None] * self.C
        self.queue: collections.deque = collections.deque()
        self.results: Dict[Any, np.ndarray] = {}
        self.stats = {"capacity": self.C, "ticks": 0, "live_slot_ticks": 0,
                      "admitted": 0, "retired": 0, "prefill_tokens": 0,
                      "operand_leaves": n_cast, "operand_bytes": cast_bytes}
        self.hists = histogram_set(
            ("ttft_s", "queue_wait_s", "decode_step_s", "per_token_s"))
        self.records: collections.deque = collections.deque(
            maxlen=RECORD_CAP)
        self.events = events

    # ------------------------------------------------------------ telemetry
    def snapshot(self) -> Dict[str, Any]:
        """Stats snapshot: counters + occupancy + latency summaries (and
        the raw sparse histograms, for cross-engine aggregation) + a copy
        of the per-request records."""
        return {**self.stats, "occupancy": self.occupancy,
                "latency": {k: h.summary() for k, h in self.hists.items()},
                "histograms": {k: h.to_dict() for k, h in
                               self.hists.items()},
                "requests": [dict(r) for r in self.records]}

    def reset(self):
        """Zero counters and histograms and drop the request records;
        slots, queue and results are NOT touched — call after warmup so
        occupancy/latency cover only the measured window (the old dict was
        never resettable, so occupancy averaged over compile ticks)."""
        for k in ("ticks", "live_slot_ticks", "admitted", "retired",
                  "prefill_tokens"):
            self.stats[k] = 0
        for h in self.hists.values():
            h.reset()
        self.records.clear()

    # ----------------------------------------------------- slot primitives
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def live_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is not None]

    def insert(self, row_caches, slot: int):
        """Splice a B=1 cache row into ``slot`` (donates the old buffer)."""
        self.caches = self._insert_fn(self.caches, row_caches,
                                      jnp.asarray(slot, jnp.int32))

    def evict(self, slot: int):
        """Reset ``slot`` to the empty row (pos=-1 everywhere) and free it."""
        self.insert(self._empty_row, slot)
        self._slots[slot] = None

    # ------------------------------------------------------------ schedule
    def submit(self, req: Request):
        if req.arrival is None:  # stamp a copy: the caller's stays as given
            req = replace(req, arrival=time.perf_counter())
        self.queue.append(req)
        if self.events is not None:
            self.events.emit(
                "request_submit", rid=req.rid,
                prompt_len=int(np.asarray(req.tokens).size),
                max_new=int(req.max_new))

    def _sample_host(self, logits) -> int:
        with annotate("serve.first_token"):
            self._rng, k = jax.random.split(self._rng)
            return int(sample_token(logits, k, self.temperature,
                                    vocab_size=self.cfg.vocab_size)[0])

    def _retire_if_done(self, slot: int):
        s = self._slots[slot]
        if len(s.out) >= s.req.max_new or (
                self.eos_id is not None and s.last == self.eos_id):
            self.results[s.req.rid] = np.asarray(s.out, np.int32)
            self._slots[slot] = None
            self.stats["retired"] += 1
            s.rec["retire"] = time.perf_counter()
            s.rec["tokens"] = len(s.out)
            if len(s.out) > 1:
                self.hists["per_token_s"].record(
                    (s.rec["retire"] - s.rec["first"]) / (len(s.out) - 1))
            if self.events is not None:
                self.events.emit("request_retire", rid=s.req.rid,
                                 slot=slot, tick=self.stats["ticks"],
                                 tokens=len(s.out))

    def admit(self) -> int:
        """Prefill queued requests into free slots. Returns #admitted."""
        n = 0
        for slot in self.free_slots():
            if not self.queue:
                break
            with annotate("serve.admit"):
                self._admit_one(slot)
            n += 1
        return n

    def _admit_one(self, slot: int):
        """Admit the head of the queue into ``slot``: prefill, insert, the
        first token sampled on the host, and the request's record."""
        t_admit = time.perf_counter()
        req = self.queue.popleft()
        self.hists["queue_wait_s"].record(t_admit - req.arrival)
        prompt = np.asarray(req.tokens, np.int32).reshape(-1)
        batch = {"tokens": jnp.asarray(prompt[None])}
        for key, val in req.extras.items():
            batch[key] = jnp.asarray(val)[None]
        prefix = (batch["patch_embeds"].shape[1]
                  if "patch_embeds" in batch else 0)
        start = prefix + prompt.shape[0]
        if start + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid!r}: prefix+prompt+max_new = "
                f"{start + req.max_new} exceeds max_len={self.max_len}")
        logits, row = self._prefill(self.params, batch)
        self.insert(row, slot)
        first = self._sample_host(logits)
        t_first = time.perf_counter()
        self.hists["ttft_s"].record(t_first - req.arrival)
        rec = {"rid": req.rid, "arrival": req.arrival, "admit": t_admit,
               "first": t_first, "retire": None, "tokens": 1}
        self.records.append(rec)
        self._slots[slot] = _Slot(req, start, first, rec)
        self.stats["admitted"] += 1
        self.stats["prefill_tokens"] += int(start)
        if self.events is not None:
            self.events.emit("request_admit", rid=req.rid, slot=slot,
                             tick=self.stats["ticks"])
        self._retire_if_done(slot)  # max_new == 1 / instant EOS

    def step(self):
        """One decode step over ALL slots. Returns [(rid, token), ...] for
        the live slots (in slot order)."""
        with annotate("serve.step"):
            live = self.live_slots()
            tokens = np.full((self.C,), self.pad_id, np.int32)
            index = np.zeros((self.C,), np.int32)
            for i in live:
                tokens[i] = self._slots[i].last
                index[i] = self._slots[i].pos
            self._rng, k = jax.random.split(self._rng)
            t0 = time.perf_counter()
            self.caches, tok = self._step_fn(self.params, self.caches,
                                             jnp.asarray(tokens),
                                             jnp.asarray(index), k)
            with annotate("serve.fetch"):
                tok = np.asarray(tok)  # the ONE host fetch per tick: (C,)
            self.hists["decode_step_s"].record(time.perf_counter() - t0)
            self.stats["ticks"] += 1
            self.stats["live_slot_ticks"] += len(live)
            emitted = []
            for i in live:
                s = self._slots[i]
                s.pos += 1
                s.last = int(tok[i])
                s.out.append(s.last)
                emitted.append((s.req.rid, s.last))
                self._retire_if_done(i)
            return emitted

    @property
    def occupancy(self) -> float:
        """Live-slot-steps over capacity-steps across the run so far."""
        denom = self.stats["ticks"] * self.C
        return self.stats["live_slot_ticks"] / denom if denom else 0.0

    def serve(self, requests=None, *,
              stream: Optional[Callable[[Any, int], None]] = None):
        """Run until the queue and all slots drain. Returns {rid: tokens}
        (each (n,) int32, n <= max_new, ending at eos_id if hit)."""
        for r in requests or []:
            self.submit(r)
        while self.queue or self.live_slots():
            self.admit()
            if not self.live_slots():
                continue  # everything admitted retired instantly
            for rid, t in self.step():
                if stream is not None:
                    stream(rid, t)
        out, self.results = self.results, {}
        return out
