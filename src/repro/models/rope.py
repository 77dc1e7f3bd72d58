"""Rotary position embeddings: standard RoPE (half-split and interleaved
pairs) and Qwen2-VL style M-RoPE.

M-RoPE (multimodal RoPE, arXiv:2409.12191) splits the head_dim/2 frequency
bands into (temporal, height, width) sections; each section rotates by the
corresponding component of a 3-vector position id. Text tokens carry
(t, t, t) so M-RoPE degenerates to RoPE on text.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp


def _angles(positions: jnp.ndarray, head_dim: int, theta: float) -> jnp.ndarray:
    """positions: (..., S) -> angles (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # explicit rank lift: (..., S, 1) * (1, ..., 1, half) — rank promotion is
    # an error under test
    return positions[..., None].astype(jnp.float32) \
        * freqs.reshape((1,) * positions.ndim + (half,))


def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float,
                mrope_sections: Optional[Tuple[int, int, int]] = None) -> jnp.ndarray:
    """Build rotation angles (B, S, head_dim//2).

    positions: (B, S) for RoPE, (3, B, S) for M-RoPE.
    """
    if mrope_sections is None:
        return _angles(positions, head_dim, theta)
    assert positions.ndim == 3 and positions.shape[0] == 3, "M-RoPE needs (3,B,S) ids"
    ang = _angles(positions, head_dim, theta)  # (3, B, S, half)
    half = ang.shape[-1]
    sections = jnp.asarray(mrope_sections)
    # frequency band b belongs to section: first section whose cumsum exceeds b
    band_section = jnp.searchsorted(jnp.cumsum(sections), jnp.arange(half),
                                    side="right")                    # (half,)
    onehot = (band_section[None, :] == jnp.arange(3)[:, None])       # (3, half)
    return jnp.sum(ang * onehot[:, None, None, :], axis=0)           # (B, S, half)


def apply_rope(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """Rotate. x: (B, S, H, head_dim); angles: (B, S, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def apply_rope_interleaved(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """Rotate the interleaved pairs (x[2i], x[2i+1]) by angles[..., i], as
    DeepSeek-V3 rotates its decoupled q_pe / k_pe. x: (B, S, H, d);
    angles: (B, S, d//2)."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)
