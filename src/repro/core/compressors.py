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

The cut boundary (one custom VJP per direction):

  * ``uplink`` — forward emits the reconstruction, backward adds FedLite's
    λ·(z − z̃) correction (eq. 5) using the residual fused with the forward
    compress. A `CutState` threads the cross-round carry: PQ codebook warm
    start (``compress_stateful`` / `core/quantizer.QuantizerState`) and
    error-feedback memory. Returns ``(recon, distortion, new_state)``.
  * ``downlink`` — forward is the identity, backward passes the activation
    COTANGENT through the codec before it reaches the client submodel,
    with an optional PRNG key (stochastic ``scalarq`` rounding) and codec
    state. ``none`` reproduces the uncompressed backward pass bitwise.

``apply_cut`` applies both per client to client-major activations
``(C, ..., d)``; every model calls it at its cut.

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

from repro.core import kmeans as _km
from repro.core.quantizer import (PQConfig, QuantizedBatch, QuantizerState,
                                  quantize, quantize_stateful)
from repro.core.split import dtype_bits


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

    The ``uplink`` hook returns the next round's state; from an empty
    ``CutState()`` that is the bootstrap round.
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
    """Identity: dense payload; the ``downlink`` hook passes it bitwise."""
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
    is passed to ``compress``, nearest rounding otherwise (the ``downlink``
    hook passes the step's key when it has one). The quantize/dequantize hot
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
# the cut boundary: one hook per direction, one per-client cut
# ---------------------------------------------------------------------------

def _distortion(residual: jax.Array) -> jax.Array:
    """Mean ‖z − z̃‖² per vector, from the residual."""
    r = residual.astype(jnp.float32)
    n = max(int(residual.size // residual.shape[-1]), 1)
    return jnp.sum(r * r) / n


def _uplink(z, state: CutState, compressor: CutCompressor):
    z_in = z if state.ef_memory is None \
        else z + state.ef_memory.astype(z.dtype)
    comp, new_q = compressor.compress_stateful(z_in, state.quantizer)
    # PQ's distortion is accumulated in float32 before the residual's cast
    dist = comp.payload.distortion \
        if isinstance(comp.payload, QuantizedBatch) \
        else _distortion(comp.residual)
    new_ef = None if state.ef_memory is None else comp.residual
    return comp.recon, dist, CutState(new_q, new_ef), comp.residual


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def uplink(z: jax.Array, lam, state: CutState, compressor: CutCompressor):
    """The uplink hook: FedLite's gradient-corrected compression (§4.2).

    Returns ``(recon, distortion, new_state)``. The codec is not
    differentiable, and the server returns ∂h/∂z̃, the gradient at the
    *compressed* activation. FedLite approximates the true ∂h/∂z with a
    first-order correction that replaces the Hessian with λ·I:

        g̃_z  =  ∂h/∂z̃  +  λ·(z − z̃)                               (eq. 5)

    which, per Appendix A, is exactly the gradient of the surrogate loss
    ‖z − ẑ‖² + (λ/2)‖z − z̃‖²: λ adds a regularizer that pulls the client
    model toward activations with a low compression error. λ = 0 is the
    straight-through estimator the paper ablates against. ``lam`` may be a
    traced scalar, so a scheduled λ needs no recompile.

    Forward: with ``state.ef_memory`` set, error feedback re-adds the
    accumulated compression error first (``z_in = z + memory``; the new
    memory is the residual ``z_in − recon``, `ErrorFeedback` semantics);
    then ``compressor.compress_stateful`` resumes from ``state.quantizer``
    (PQ codebook warm start; stateless codecs return ``None``). An empty
    ``CutState()`` is the no-carry case, and its ``new_state`` bootstraps
    the carry. ``distortion`` is the mean ‖z_in − z̃‖² per vector.

    Backward: eq. 5 with the residual the forward compress produced (the
    PQ encode emits it fused), so K-means runs once per forward and
    backward. ``lam`` and ``state`` get zero cotangents: the state is
    carry, not a differentiable input.
    """
    recon, dist, new_state, _ = _uplink(z, state, compressor)
    return recon, dist, new_state


def _uplink_fwd(z, lam, state, compressor):
    recon, dist, new_state, residual = _uplink(z, state, compressor)
    return (recon, dist, new_state), (residual, jnp.asarray(lam, jnp.float32))


def _uplink_bwd(compressor, res, g):
    gz = g[0]   # the distortion and state outputs are metric and carry
    residual, lam = res
    return (gz + lam.astype(gz.dtype) * residual.astype(gz.dtype),
            None, None)


uplink.defvjp(_uplink_fwd, _uplink_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def downlink(z: jax.Array, key: Optional[jax.Array], state: Any,
             compressor: CutCompressor) -> jax.Array:
    """The downlink hook: identity forward; the backward pass sends the
    activation COTANGENT through ``compressor`` before it reaches the
    client submodel, so the server→client gradient message is a compressed
    payload. With `NoneCompressor` the cotangent passes untouched, bitwise.

    ``key`` (a raw uint32 PRNG key, or ``None``) reaches the codec:
    ``scalarq``, alone or as a chain stage, then rounds stochastically
    (unbiased, E[recon] = g; Caldas et al. 2018) instead of to nearest.
    ``state`` (codec state such as a previous round's
    `core/quantizer.QuantizerState`, or ``None`` for a cold round) reaches
    ``compressor.compress_stateful``: a ``pq`` downlink then warm-starts
    Lloyd on the gradient from last round's gradient codebooks. A backward
    pass cannot emit new primal state, so the refreshed lineage stays with
    the caller. Both get zero cotangents.
    """
    return z


def _downlink_fwd(z, key, state, compressor):
    return z, (key, state)


def _downlink_bwd(compressor, res, g):
    key, state = res
    if not isinstance(compressor, NoneCompressor):
        # scoped here, in the backward rule, so the scope lands on the
        # codec's backward-pass operations
        with jax.named_scope("fl_downlink_codec"):
            comp, _ = compressor.compress_stateful(g, state, key=key)
            g = comp.recon.astype(g.dtype)
    return g, None, None


downlink.defvjp(_downlink_fwd, _downlink_bwd)


def _accounting_bits(compressor: CutCompressor, dtype) -> int:
    """Float width φ a codec's message is counted at: PQ's own
    ``phi_bits`` (the paper's fixed-width cost model), else the
    activation dtype."""
    if isinstance(compressor, PQCompressor):
        return compressor.cfg.phi_bits
    return dtype_bits(dtype)


def apply_cut(z: jax.Array, up: Optional[CutCompressor],
              down: Optional[CutCompressor], lam, *,
              key: Optional[jax.Array] = None,
              state: Optional[CutState] = None
              ) -> Tuple[jax.Array, Dict[str, Any]]:
    """The cut boundary, per client: ``z`` is client-major ``(C, ..., d)``.

    Each client's slice goes through the `uplink` hook (``up``; ``None``
    skips it) and then the `downlink` hook (``down``; ``None`` or
    ``none`` skips it), vmapped over clients, so every client clusters
    with its own codebooks. ``key`` is split into one key per client for
    the downlink codec. ``state`` (a `CutState` whose leaves carry the
    leading client axis) threads the uplink carry; the new state comes
    back under ``stats["cut_state"]``.

    Returns ``(z̃, stats)``. The stats count one client's message as the
    wire carries it: ``pq_distortion`` (mean over clients),
    ``pq_compression_ratio`` (dense bits over the uplink message's, per
    client, at the codec's accounting width) and ``downlink_message_bits``
    (all C messages). Each stat is one more output of the jitted step.
    """
    c, d = z.shape[0], z.shape[-1]
    n = math.prod(z.shape[1:-1])   # vectors per client
    stats: Dict[str, Any] = {}
    if up is not None:
        carry = CutState() if state is None else state
        z, dist, new_state = jax.vmap(
            lambda zi, si: uplink(zi, lam, si, up))(z, carry)
        phi = _accounting_bits(up, z.dtype)
        bits = up.analytic_bits(n, d, phi_bits=phi)
        stats.update(pq_distortion=jnp.mean(dist),
                     pq_compression_ratio=phi * n * d / max(bits, 1))
        if state is not None:
            stats["cut_state"] = new_state
    if down is not None and not isinstance(down, NoneCompressor):
        keys = None if key is None else jax.random.split(key, c)
        z = jax.vmap(lambda zi, ki: downlink(zi, ki, None, down))(z, keys)
        stats["downlink_message_bits"] = float(
            c * down.analytic_bits(n, d, _accounting_bits(down, z.dtype)))
    return z, stats
