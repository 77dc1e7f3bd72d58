"""Direction-agnostic cut-layer compressor stack.

FedLite (§4.1) compresses only the *uplink* activations with grouped PQ;
PR 2's measured wire accounting showed the uncompressed cut-layer *gradient*
downlink then dominates bytes-on-the-wire. This module turns the implicit
"compression == uplink PQ" assumption into one explicit abstraction used by
core, federated, launch and benchmarks alike: a `CutCompressor` with
registered implementations

  * ``none``    — identity (dense wire payload; the SplitFed baseline).
  * ``pq``      — FedLite's grouped product quantizer (wraps
                  ``core/quantizer.py`` — behavior-preserving, including the
                  fused Pallas encode and the residual the corrected VJP
                  reuses).
  * ``topk``    — magnitude sparsification keeping a fraction ``k`` of
                  entries; optional error-feedback memory via the
                  `ErrorFeedback` wrapper (Konečný et al. 2016).
  * ``scalarq`` — uniform ``bits``-bit scalar quantization (stochastic
                  rounding when a PRNG key is supplied, nearest otherwise);
                  the quantize/dequantize hot loop has a Pallas kernel
                  (``repro.kernels.scalar_quant``) selected by the same
                  backend registry as the PQ encode.
  * ``chain``   — sequential composition: each stage compresses the dense
                  value *carrier* of the previous stage's payload, e.g.
                  ``chain:topk(k=0.1)+scalarq(bits=8)`` sends bit-packed
                  top-k indices plus 8-bit codes for the survivors.

Every compressor answers three questions:

  * math   — ``compress(z) -> Compressed`` (in-jit; recon + residual +
             payload arrays) and ``decompress``;
  * bits   — ``analytic_bits(n, d, phi)`` (the paper-style cost model,
             decomposed into ``overhead_bits`` + ``carrier_elems`` so chains
             account exactly);
  * wire   — ``wire_payload(comp) -> bytes`` via the versioned tagged codec
             in ``federated/wire.py`` (bit-exact round-trips, measured
             bytes validate the analytic model).

Direction hooks (``jax.custom_vjp``):

  * ``compress_with_correction(_stats)`` — the uplink: forward emits the
    reconstruction, backward adds FedLite's λ·(z − z̃) correction (eq. 5)
    using the residual fused with the forward compress.
  * ``compress_with_correction_carry`` — the state-carrying uplink: same
    correction, but a `CutState` threads cross-round carry through the
    round — PQ codebook warm-start (``compress_stateful`` /
    `core/quantizer.QuantizerState`) and per-client `ErrorFeedback` memory
    — returning ``(recon, distortion, new_state)``.
  * ``compress_downlink`` — the downlink: forward is the identity, backward
    passes the activation COTANGENT through the configured compressor
    before it reaches the client submodel. ``none`` reproduces the
    uncompressed backward pass bitwise (asserted in tests).
  * ``compress_downlink_keyed`` — same, with a per-step PRNG key threaded
    to the backward codec: ``scalarq`` downlinks round stochastically
    (unbiased) instead of to-nearest.

Spec strings (``ArchConfig.uplink_compressor`` / ``downlink_compressor``,
`FederatedTrainer` fields) are parsed by ``make_compressor``:
``"none"``, ``"pq"``, ``"topk(k=0.1)"``, ``"scalarq(bits=8)"``,
``"chain:topk(k=0.1)+scalarq(bits=8)"``.
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import math
import re
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import kmeans as _km
from repro.core.quantizer import (PQConfig, QuantizedBatch, QuantizerState,
                                  quantize, quantize_stateful)


# ---------------------------------------------------------------------------
# payloads (all-array NamedTuples: vmappable, jit-transparent)
# ---------------------------------------------------------------------------

class DensePayload(NamedTuple):
    values: jax.Array          # the tensor itself (identity compressor)


class SparsePayload(NamedTuple):
    indices: jax.Array         # (k,) int32 into the flattened tensor
    values: jax.Array          # (k,) surviving magnitudes (the carrier)


class ScalarPayload(NamedTuple):
    codes: jax.Array           # int32, input shape, values in [0, 2^bits)
    lo: jax.Array              # () f32 dequant offset
    scale: jax.Array           # () f32 dequant step


class Compressed(NamedTuple):
    """In-jit result of one compress: what the other side reconstructs,
    the residual the corrected VJP consumes, and the wire-able pieces."""
    recon: jax.Array           # decompressed tensor, input shape + dtype
    residual: jax.Array        # z − recon, input shape + dtype
    payload: Any               # DensePayload | QuantizedBatch | SparsePayload
    #                            | ScalarPayload | tuple of stage payloads


class CutState(NamedTuple):
    """Cross-round carry for one cut-layer direction.

    Both fields are optional pytrees; ``None`` means the corresponding
    mechanism is off and its trace never changes:

      * ``quantizer`` — `core/quantizer.QuantizerState`: the previous
        round's PQ codebooks (warm-started Lloyd; also the ``pq-delta``
        wire reference).
      * ``ef_memory`` — error-feedback memory, same shape as the cut
        tensor: the accumulated compression error re-added to the next
        round's input (`ErrorFeedback` semantics, exact telescoping).

    Passing a ``CutState`` (even one with both fields ``None``) to the
    state-aware hooks requests a new state back — the bootstrap round.
    """
    quantizer: Any = None
    ef_memory: Any = None


def index_bits(num_slots: int) -> int:
    """Packed index width for a flattened tensor of ``num_slots`` entries."""
    return max(math.ceil(math.log2(max(num_slots, 2))), 1)


# ---------------------------------------------------------------------------
# the compressor protocol
# ---------------------------------------------------------------------------

class CutCompressor:
    """Base class: a direction-agnostic cut-layer codec.

    Subclasses are frozen dataclasses (hashable → usable as jit statics and
    as fields of the frozen model dataclasses). The default ``analytic_bits``
    composes ``overhead_bits`` (structure the stage transmits itself) with
    ``carrier_elems`` (dense values left for a later stage — or for the wire
    at φ bits when the stage is terminal), which is what makes chained
    accounting exact.
    """
    name: str = "base"

    @property
    def spec(self) -> str:
        """Round-trippable spec string (parameters included) — what traces
        and benchmark rows record as the codec identity."""
        return self.name

    # ---- math (in-jit) ----------------------------------------------------
    def compress(self, z: jax.Array, *,
                 key: Optional[jax.Array] = None) -> Compressed:
        raise NotImplementedError

    def compress_stateful(self, z: jax.Array, state: Any = None, *,
                          key: Optional[jax.Array] = None
                          ) -> Tuple[Compressed, Any]:
        """Warm-start-aware compress: (Compressed, next-round codec state).

        The base implementation is stateless (returns ``None`` state);
        `PQCompressor` overrides it with the cross-round codebook
        warm-start (`core/quantizer.quantize_stateful`)."""
        del state
        return self.compress(z, key=key), None

    def decompress(self, comp: Compressed) -> jax.Array:
        return comp.recon

    def carrier(self, comp: Compressed) -> Optional[jax.Array]:
        """Dense value vector a downstream chain stage may compress further
        (None: the payload is terminal, e.g. pq codebooks+codes)."""
        return None

    def recompose(self, comp: Compressed, carrier_recon: jax.Array,
                  z: jax.Array) -> Compressed:
        """Rebuild ``comp`` after a downstream stage lossily reconstructed
        its carrier. ``z`` is the stage input (for the residual)."""
        raise NotImplementedError(f"{self.name} has no carrier to recompose")

    # ---- analytic accounting ---------------------------------------------
    def overhead_bits(self, n: int, d: int, phi_bits: int) -> int:
        """Bits of structure this stage transmits (indices, scales, ...)."""
        raise NotImplementedError

    def carrier_elems(self, n: int, d: int) -> int:
        """Dense float values this stage leaves for the next one."""
        raise NotImplementedError

    def analytic_bits(self, n: int, d: int, phi_bits: int = 32) -> int:
        """Message bits for an (n, d) batch when this stage is terminal."""
        return self.overhead_bits(n, d, phi_bits) \
            + self.carrier_elems(n, d) * phi_bits

    # ---- wire -------------------------------------------------------------
    def wire_payload(self, comp: Compressed,
                     value_dtype: str = "float16") -> bytes:
        """Serialize to the tagged wire format (``federated/wire.py``)."""
        from repro.federated import wire  # deferred: federated imports core
        return wire.encode_compressed(self, comp, value_dtype=value_dtype)


# ---------------------------------------------------------------------------
# implementations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NoneCompressor(CutCompressor):
    """Identity: dense payload, ``compress_downlink`` is a bitwise no-op."""
    name: str = dataclasses.field(default="none", init=False)

    def compress(self, z, *, key=None) -> Compressed:
        return Compressed(recon=z, residual=jnp.zeros_like(z),
                          payload=DensePayload(values=z))

    def carrier(self, comp):
        return comp.payload.values

    def recompose(self, comp, carrier_recon, z):
        recon = carrier_recon.reshape(z.shape).astype(z.dtype)
        return Compressed(recon=recon, residual=z - recon,
                          payload=DensePayload(values=recon))

    def overhead_bits(self, n, d, phi_bits):
        return 0

    def carrier_elems(self, n, d):
        return n * d


@dataclasses.dataclass(frozen=True)
class PQCompressor(CutCompressor):
    """FedLite's grouped PQ (§4.1) behind the compressor protocol.

    Delegates to ``core/quantizer.quantize`` — same fused backend encode,
    same ``QuantizedBatch`` (which doubles as the wire payload), so the
    pre-refactor uplink path is preserved exactly."""
    cfg: PQConfig
    name: str = dataclasses.field(default="pq", init=False)

    @property
    def spec(self) -> str:
        return (f"pq(q={self.cfg.num_subvectors},L={self.cfg.num_clusters},"
                f"R={self.cfg.num_groups})")

    def compress(self, z, *, key=None) -> Compressed:
        qb = quantize(z, self.cfg, key=key)
        return Compressed(recon=qb.dequantized, residual=qb.residual,
                          payload=qb)

    def compress_stateful(self, z, state: Optional[QuantizerState] = None, *,
                          key=None) -> Tuple[Compressed, QuantizerState]:
        """Cross-round warm-start: a prior `QuantizerState` makes Lloyd
        resume from last round's codebooks at ``cfg.effective_warm_iters``
        iterations; ``None`` runs the cold path and bootstraps the state."""
        qb, new_state = quantize_stateful(z, self.cfg, state, key)
        return Compressed(recon=qb.dequantized, residual=qb.residual,
                          payload=qb), new_state

    def overhead_bits(self, n, d, phi_bits):
        return self.cfg.message_bits(n, d, phi_bits=phi_bits)

    def carrier_elems(self, n, d):
        return 0


@dataclasses.dataclass(frozen=True)
class TopKCompressor(CutCompressor):
    """Magnitude sparsification: keep the largest-|z| fraction ``k``.

    The payload is (indices, values) over the flattened tensor; the values
    vector is the carrier a chained stage (e.g. ``scalarq``) compresses
    further. Error feedback is NOT applied here — wrap with `ErrorFeedback`
    where the caller owns the memory state."""
    k: float = 0.1
    name: str = dataclasses.field(default="topk", init=False)

    @property
    def spec(self) -> str:
        return f"topk(k={self.k})"

    def __post_init__(self):
        if not 0.0 < self.k <= 1.0:
            raise ValueError(f"topk fraction k={self.k} must be in (0, 1]")

    def k_count(self, num_elems: int) -> int:
        return max(int(round(self.k * num_elems)), 1)

    def compress(self, z, *, key=None) -> Compressed:
        flat = z.reshape(-1)
        kc = self.k_count(flat.shape[0])
        _, idx = jax.lax.top_k(jnp.abs(flat.astype(jnp.float32)), kc)
        idx = jnp.sort(idx).astype(jnp.int32)   # canonical order for the wire
        vals = flat[idx]
        recon = jnp.zeros_like(flat).at[idx].set(vals).reshape(z.shape)
        return Compressed(recon=recon, residual=z - recon,
                          payload=SparsePayload(indices=idx, values=vals))

    def carrier(self, comp):
        return comp.payload.values

    def recompose(self, comp, carrier_recon, z):
        flat = jnp.zeros(z.size, z.dtype).at[comp.payload.indices].set(
            carrier_recon.astype(z.dtype))
        recon = flat.reshape(z.shape)
        return Compressed(recon=recon, residual=z - recon,
                          payload=SparsePayload(indices=comp.payload.indices,
                                                values=carrier_recon))

    def overhead_bits(self, n, d, phi_bits):
        return self.k_count(n * d) * index_bits(n * d)

    def carrier_elems(self, n, d):
        return self.k_count(n * d)


@dataclasses.dataclass(frozen=True)
class ScalarQuantCompressor(CutCompressor):
    """Uniform b-bit scalar quantization over the tensor's [min, max] range.

    ``codes = round((z − lo)/scale)`` with ``scale = (hi − lo)/(2^b − 1)``;
    stochastic rounding (unbiased, Caldas et al. 2018) when a PRNG ``key``
    is passed to ``compress``, nearest rounding otherwise — the downlink
    VJP hook runs keyless, hence deterministic. The quantize/dequantize hot
    loop runs through the same backend registry as the PQ encode: the
    Pallas kernel (``repro.kernels.scalar_quant``) on "pallas"/"auto"-on-TPU,
    pure jnp elsewhere."""
    bits: int = 8
    backend: str = "auto"
    name: str = dataclasses.field(default="scalarq", init=False)

    @property
    def spec(self) -> str:
        return f"scalarq(bits={self.bits})"

    def __post_init__(self):
        if not 1 <= self.bits <= 16:
            raise ValueError(f"scalarq bits={self.bits} must be in [1, 16]")
        if self.backend not in _km.available_backends():
            raise ValueError(f"backend={self.backend!r} not one of "
                             f"{_km.available_backends()}")

    def compress(self, z, *, key=None) -> Compressed:
        zf = z.astype(jnp.float32)
        lo = jnp.min(zf)
        hi = jnp.max(zf)
        levels = (1 << self.bits) - 1
        scale = (hi - lo) / levels
        scale = jnp.where(scale > 0, scale, 1.0).astype(jnp.float32)
        t = (zf - lo) / scale
        if key is not None:   # stochastic rounding: E[codes·scale] = z − lo
            t = jnp.floor(t + jax.random.uniform(key, t.shape))
        use_kernel = key is None and \
            _km.resolve_backend(self.backend) == "pallas"
        if use_kernel:
            from repro.kernels import ops
            codes, recon = ops.scalar_quantize(
                zf.reshape(-1, z.shape[-1]) if z.ndim > 1 else zf.reshape(1, -1),
                lo, scale, self.bits)
            codes = codes.reshape(z.shape)
            recon = recon.reshape(z.shape).astype(z.dtype)
        else:
            codes = jnp.clip(jnp.round(t), 0, levels).astype(jnp.int32)
            recon = (lo + codes.astype(jnp.float32) * scale).astype(z.dtype)
        return Compressed(recon=recon, residual=z - recon,
                          payload=ScalarPayload(codes=codes, lo=lo,
                                                scale=scale))

    def overhead_bits(self, n, d, phi_bits):
        return 2 * 32 + n * d * self.bits   # lo + scale at f32, packed codes

    def carrier_elems(self, n, d):
        return 0


@dataclasses.dataclass(frozen=True)
class ChainCompressor(CutCompressor):
    """Sequential composition: stage i+1 compresses stage i's carrier.

    Only the first stage sees the (n, d) tensor; later stages see the dense
    value vector the previous payload still carries (e.g. top-k survivor
    values). A stage with no carrier (pq, scalarq) terminates the chain."""
    stages: Tuple[CutCompressor, ...]
    name: str = dataclasses.field(default="chain", init=False)

    def __post_init__(self):
        if len(self.stages) < 2:
            raise ValueError("chain needs at least two stages")
        for s in self.stages[:-1]:
            if s.carrier_elems(1, 1) == 0 and not isinstance(s, NoneCompressor):
                raise ValueError(
                    f"chain stage {s.name!r} is terminal (no carrier); "
                    f"only the last stage may be")

    @property
    def spec(self) -> str:
        return "chain:" + "+".join(s.spec for s in self.stages)

    def compress(self, z, *, key=None) -> Compressed:
        keys = [None] * len(self.stages) if key is None else \
            list(jax.random.split(key, len(self.stages)))
        comps = []
        inputs = []
        x = z
        for stage, k in zip(self.stages, keys):
            inputs.append(x)
            comp = stage.compress(x, key=k)
            comps.append(comp)
            x = stage.carrier(comp)
            if x is None:
                break
        # fold the last stage's lossy reconstruction back up the chain
        recon = comps[-1].recon
        executed = self.stages[:len(comps)]
        for stage, comp, x_in in zip(reversed(executed[:-1]),
                                     reversed(comps[:-1]),
                                     reversed(inputs[:-1])):
            comp = stage.recompose(comp, recon, x_in)
            recon = comp.recon
        return Compressed(recon=recon, residual=z - recon,
                          payload=tuple(c.payload for c in comps))

    def overhead_bits(self, n, d, phi_bits):
        total, elems = 0, n * d
        nn, dd = n, d
        for stage in self.stages:
            total += stage.overhead_bits(nn, dd, phi_bits)
            elems = stage.carrier_elems(nn, dd)
            if elems == 0:
                break
            nn, dd = elems, 1   # downstream stages see a flat carrier
        return total

    def carrier_elems(self, n, d):
        nn, dd = n, d
        for stage in self.stages:
            elems = stage.carrier_elems(nn, dd)
            if elems == 0:
                return 0
            nn, dd = elems, 1
        return nn * dd


# ---------------------------------------------------------------------------
# error feedback (memory owned by the caller — host loop or scan carry)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ErrorFeedback:
    """Error-feedback wrapper (Seide et al. 2014; Karimireddy et al. 2019):
    the compression error is remembered and re-added to the next input, so
    any contractive compressor transmits the full signal *eventually*.

        comp = c.compress(z + mem);   mem' = (z + mem) − comp.recon

    The memory is explicit state: callers thread it through rounds (it is a
    per-client tensor in a real deployment). ``init_memory`` gives the
    zero state."""
    compressor: CutCompressor

    def init_memory(self, z: jax.Array) -> jax.Array:
        return jnp.zeros_like(z)

    def step(self, z: jax.Array, memory: jax.Array, *,
             key: Optional[jax.Array] = None
             ) -> Tuple[Compressed, jax.Array]:
        corrected = z + memory
        comp = self.compressor.compress(corrected, key=key)
        return comp, corrected - comp.recon


# ---------------------------------------------------------------------------
# registry + spec parsing
# ---------------------------------------------------------------------------

_FACTORIES: Dict[str, Callable[..., CutCompressor]] = {}


def register_compressor(name: str,
                        factory: Callable[..., CutCompressor]) -> None:
    """Register (or replace) a named compressor factory."""
    _FACTORIES[name] = factory


register_compressor("none", lambda **kw: NoneCompressor(**kw))
register_compressor("pq", lambda **kw: PQCompressor(**kw))
register_compressor("topk", lambda **kw: TopKCompressor(**kw))
register_compressor("scalarq", lambda **kw: ScalarQuantCompressor(**kw))


def available_compressors() -> Tuple[str, ...]:
    return tuple(sorted(_FACTORIES)) + ("chain",)


_CALL_RE = re.compile(r"^(?P<name>[a-zA-Z_][\w]*)(?:\((?P<args>.*)\))?$")


def _parse_one(spec: str, pq: Optional[PQConfig]) -> CutCompressor:
    m = _CALL_RE.match(spec.strip())
    if not m:
        raise ValueError(f"malformed compressor spec {spec!r}")
    name, args = m.group("name"), m.group("args")
    if name not in _FACTORIES:
        raise ValueError(f"unknown compressor {name!r}; registered: "
                         f"{available_compressors()}")
    kwargs: Dict[str, Any] = {}
    for part in (args or "").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"compressor arg {part!r} must be key=value")
        k, v = part.split("=", 1)
        try:
            kwargs[k.strip()] = ast.literal_eval(v.strip())
        except (ValueError, SyntaxError):
            kwargs[k.strip()] = v.strip()   # bare strings, e.g. backend=jnp
    if name == "pq" and "cfg" not in kwargs:
        if pq is None:
            raise ValueError(
                "spec 'pq' needs a PQConfig: pass make_compressor(..., pq=...)")
        kwargs["cfg"] = pq
    return _FACTORIES[name](**kwargs)


def make_compressor(spec, *, pq: Optional[PQConfig] = None
                    ) -> Optional[CutCompressor]:
    """Build a compressor from a spec string (see module docstring).

    Accepts an already-built `CutCompressor` (returned as-is) and ``None``
    (returns None, meaning "direction not configured"). ``pq`` supplies the
    PQConfig a bare ``"pq"`` spec wraps."""
    if spec is None or isinstance(spec, CutCompressor):
        return spec
    spec = spec.strip()
    if spec.startswith("chain:"):
        stages = tuple(_parse_one(s, pq) for s in spec[len("chain:"):]
                       .split("+"))
        return ChainCompressor(stages=stages)
    return _parse_one(spec, pq)


# ---------------------------------------------------------------------------
# direction hooks (custom VJPs)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def compress_with_correction(z: jax.Array, lam,
                             compressor: CutCompressor) -> jax.Array:
    """Uplink hook: forward emits the compressed reconstruction, backward
    adds FedLite's λ·(z − z̃) correction (eq. 5) using the residual the
    forward compress already produced. Generalizes
    ``core/correction.quantize_with_correction`` to any registered codec."""
    return compressor.compress(z).recon


def _cwc_fwd(z, lam, compressor):
    comp = compressor.compress(z)
    return comp.recon, (comp.residual, jnp.asarray(lam, jnp.float32))


def _cwc_bwd(compressor, res, g):
    residual, lam = res
    return (g + lam.astype(g.dtype) * residual.astype(g.dtype),
            jnp.zeros_like(lam))


compress_with_correction.defvjp(_cwc_fwd, _cwc_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def compress_with_correction_stats(z: jax.Array, lam,
                                   compressor: CutCompressor):
    """Like ``compress_with_correction`` but also returns the mean ‖z − z̃‖²
    per vector as a second, non-differentiable output."""
    comp = compressor.compress(z)
    return comp.recon, _distortion(comp.residual)


def _distortion(residual: jax.Array) -> jax.Array:
    r = residual.astype(jnp.float32)
    n = max(int(residual.size // residual.shape[-1]), 1)
    return jnp.sum(r * r) / n


def _cwcs_fwd(z, lam, compressor):
    comp = compressor.compress(z)
    return ((comp.recon, _distortion(comp.residual)),
            (comp.residual, jnp.asarray(lam, jnp.float32)))


def _cwcs_bwd(compressor, res, g):
    gz, _ = g   # the distortion output is a metric: its cotangent is dropped
    residual, lam = res
    return (gz + lam.astype(gz.dtype) * residual.astype(gz.dtype),
            jnp.zeros_like(lam))


compress_with_correction_stats.defvjp(_cwcs_fwd, _cwcs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def compress_downlink(z: jax.Array, compressor: CutCompressor) -> jax.Array:
    """Downlink hook: identity forward; the backward pass sends the
    activation COTANGENT through ``compressor`` before it reaches the
    client submodel — the server→client gradient message becomes a
    compressed payload. With `NoneCompressor` the backward pass returns the
    cotangent unchanged, bitwise-reproducing the uncompressed path
    (asserted in tests/test_compressors.py)."""
    return z


def _dl_fwd(z, compressor):
    return z, None


def _dl_bwd(compressor, _, g):
    if isinstance(compressor, NoneCompressor):
        return (g,)
    # scoped here, in the backward rule, so the scope lands on the codec's
    # backward-pass operations
    with jax.named_scope("fl_downlink_codec"):
        return (compressor.compress(g).recon.astype(g.dtype),)


compress_downlink.defvjp(_dl_fwd, _dl_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def compress_downlink_keyed(z: jax.Array, key: jax.Array,
                            compressor: CutCompressor) -> jax.Array:
    """``compress_downlink`` with a per-step PRNG key threaded into the
    backward codec: ``scalarq`` (standalone or as a chain stage) then uses
    *stochastic* rounding on the gradient cotangent — unbiased,
    E[recon] = g (Caldas et al. 2018) — instead of round-to-nearest.

    ``key`` is a raw uint32 PRNG key (``jax.random.PRNGKey`` /
    ``fold_in``); its cotangent is the symbolic float0 zero. The keyless
    ``compress_downlink`` remains the deterministic path and is
    bitwise-unchanged."""
    return z


def _dlk_fwd(z, key, compressor):
    return z, key


def _dlk_bwd(compressor, key, g):
    if isinstance(compressor, NoneCompressor):
        gz = g
    else:
        with jax.named_scope("fl_downlink_codec"):
            gz = compressor.compress(g, key=key).recon.astype(g.dtype)
    # integer-dtype primals take float0 cotangents
    return (gz, np.zeros(key.shape, jax.dtypes.float0))


compress_downlink_keyed.defvjp(_dlk_fwd, _dlk_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def compress_downlink_stateful(z: jax.Array, state: Any,
                               compressor: CutCompressor) -> jax.Array:
    """``compress_downlink`` with cross-round codec state threaded IN.

    ``state`` (e.g. a `core/quantizer.QuantizerState` from the previous
    round, or ``None`` for a cold round) reaches the backward codec via
    ``compressor.compress_stateful``: a ``pq`` downlink then warm-starts
    Lloyd on the gradient cotangent from last round's gradient codebooks —
    ``cfg.effective_warm_iters`` iterations instead of a cold
    ``kmeans_iters`` recluster — exactly mirroring the uplink's
    ``compress_with_correction_carry`` warm start. It is also what the
    ``pq-delta`` wire kind diffs against, so the downlink codebook message
    shrinks to b-bit deltas versus the acked reference
    (``FederatedTrainer.codebook_delta_bits`` measures it;
    ``bench_comm.py`` asserts the reduction).

    The state is an auxiliary INPUT only — a VJP's backward pass cannot
    emit new primal state, so the refreshed reference lineage is owned by
    the measurement/trainer layer (the same split the uplink uses: warm
    math in-jit, acked wire references host-side). ``state`` receives a
    zero cotangent; ``None`` state runs the cold path, bitwise-identical
    to ``compress_downlink``.
    """
    return z


def _dls_fwd(z, state, compressor):
    return z, state


def _dls_bwd(compressor, state, g):
    if isinstance(compressor, NoneCompressor):
        gz = g
    else:
        with jax.named_scope("fl_downlink_codec"):
            comp, _ = compressor.compress_stateful(g, state)
            gz = comp.recon.astype(g.dtype)
    return (gz, _zero_state_cotangent(state))


compress_downlink_stateful.defvjp(_dls_fwd, _dls_bwd)


# ---------------------------------------------------------------------------
# the state-carrying uplink hook (warm-start + error feedback)
# ---------------------------------------------------------------------------

def _zero_state_cotangent(state):
    """Cotangent pytree for a `CutState` primal: zeros for float leaves,
    float0 for integer leaves (the round counter). The state is auxiliary
    carry — no gradient may flow into last round's codebooks or memory."""
    return jax.tree.map(
        lambda x: np.zeros(jnp.shape(x), jax.dtypes.float0)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.integer)
        else jnp.zeros_like(x), state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def compress_with_correction_carry(z: jax.Array, lam, state: CutState,
                                   compressor: CutCompressor):
    """State-carrying uplink hook: like ``compress_with_correction_stats``
    but threading a `CutState` across rounds. Returns
    ``(recon, distortion, new_state)``.

    Forward:
      1. error feedback (iff ``state.ef_memory`` is not None):
         ``z_in = z + memory`` — the accumulated compression error is
         re-added before compressing (`ErrorFeedback` semantics); the new
         memory is ``z_in − recon`` (== the compress residual), so the
         telescoped sum of transmissions recovers the full signal.
      2. warm-started compress: ``compressor.compress_stateful`` resumes
         from ``state.quantizer`` (PQ codebook warm-start; stateless
         codecs ignore it and return ``None``).

    Backward: FedLite's eq.-5 correction ``g + λ·(z_in − recon)`` on the
    activation cotangent, reusing the residual fused with the forward
    compress; ``lam`` and the state get zero cotangents (the state is
    auxiliary carry, not a differentiable input).
    """
    recon, dist, new_state, _ = _cwcarry(z, state, compressor)
    return recon, dist, new_state


def _cwcarry(z, state, compressor):
    z_in = z if state.ef_memory is None \
        else z + state.ef_memory.astype(z.dtype)
    comp, new_q = compressor.compress_stateful(z_in, state.quantizer)
    new_ef = None if state.ef_memory is None else comp.residual
    new_state = CutState(quantizer=new_q, ef_memory=new_ef)
    return comp.recon, _distortion(comp.residual), new_state, comp.residual


def _cwcarry_fwd(z, lam, state, compressor):
    recon, dist, new_state, residual = _cwcarry(z, state, compressor)
    return ((recon, dist, new_state),
            (residual, jnp.asarray(lam, jnp.float32), state))


def _cwcarry_bwd(compressor, res, g):
    gz = g[0]   # distortion and state outputs are carry/metrics: dropped
    residual, lam, state = res
    return (gz + lam.astype(gz.dtype) * residual.astype(gz.dtype),
            jnp.zeros_like(lam), _zero_state_cotangent(state))


compress_with_correction_carry.defvjp(_cwcarry_fwd, _cwcarry_bwd)
