"""Pure-jnp oracles for every Pallas kernel in this package.

These define the *semantics*; each Pallas kernel must match its oracle
bit-for-bit (integer outputs) or to float tolerance (float outputs) across
the shape/dtype sweep in tests/test_kernels_*.py.

Layout conventions
------------------
Video payloads are channel-planar for kernel work: ``(T, C, H, W)`` for
frame sequences and ``(C, H, W)`` for single frames. ``ops.py`` converts
from the user-facing interleaved ``(T, H, W, C)`` uint8 layout.

The TVC codec (closed-loop DPCM):
  iframe  = frames[0]
  recon_0 = iframe
  r_t     = frames[t] - recon_{t-1}
  rq_t    = clip(round(r_t / q), lo, hi)            # quantized residual
  recon_t = clip(recon_{t-1} + rq_t * q, vmin, vmax)
Decoding replays the recon chain — this is exactly the look-back
dependency (I-frame = independent frame A, P-frames = dependent Δ−A) that
drives the paper's look-back cost c_l.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# delta codec (closed-loop DPCM over T)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("q", "lo", "hi", "vmin", "vmax"))
def delta_encode(
    frames: jnp.ndarray,  # (T, C, H, W) float32
    *,
    q: float,
    lo: int,
    hi: int,
    vmin: float,
    vmax: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (iframe (C,H,W) f32, residuals (T-1,C,H,W) int32).

    Module-level ``jit``: the scan would otherwise retrace (and XLA
    recompile) on EVERY call — the closure is new each time — costing
    tens of milliseconds of fixed overhead per GOP.  Jitted here, the
    compile happens once per (shape, q-params) and the read/write paths
    pay only the kernel itself."""
    frames = frames.astype(jnp.float32)
    iframe = frames[0]

    def step(recon, frame):
        r = frame - recon
        rq = jnp.clip(jnp.round(r / q), lo, hi)
        recon = jnp.clip(recon + rq * q, vmin, vmax)
        return recon, rq.astype(jnp.int32)

    _, residuals = jax.lax.scan(step, iframe, frames[1:])
    return iframe, residuals


@functools.partial(jax.jit, static_argnames=("q", "vmin", "vmax"))
def delta_decode(
    iframe: jnp.ndarray,  # (C, H, W) f32
    residuals: jnp.ndarray,  # (T-1, C, H, W) int
    *,
    q: float,
    vmin: float,
    vmax: float,
) -> jnp.ndarray:
    """Returns frames (T, C, H, W) f32 (recon chain; frame 0 == iframe).

    Jitted at module level for the same reason as `delta_encode`: a
    per-call scan closure retraces and recompiles every decode."""
    iframe = iframe.astype(jnp.float32)

    def step(recon, rq):
        recon = jnp.clip(recon + rq.astype(jnp.float32) * q, vmin, vmax)
        return recon, recon

    _, rest = jax.lax.scan(step, iframe, residuals)
    return jnp.concatenate([iframe[None], rest], axis=0)


# --------------------------------------------------------------------------
# fused transcode: decode(q_in) -> box-downsample(factor) -> encode(q_out)
# --------------------------------------------------------------------------

def box_downsample(x: jnp.ndarray, factor: int) -> jnp.ndarray:
    """Mean-pool the last two axes by `factor` (must divide H and W)."""
    if factor == 1:
        return x
    *lead, h, w = x.shape
    x = x.reshape(*lead, h // factor, factor, w // factor, factor)
    return x.mean(axis=(-3, -1))


def transcode(
    iframe: jnp.ndarray,
    residuals: jnp.ndarray,
    *,
    q_in: float,
    q_out: float,
    factor: int,
    lo: int,
    hi: int,
    vmin: float,
    vmax: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused transcode oracle. Returns (iframe_out, residuals_out)."""
    frames = delta_decode(iframe, residuals, q=q_in, vmin=vmin, vmax=vmax)
    small = box_downsample(frames, factor)
    return delta_encode(small, q=q_out, lo=lo, hi=hi, vmin=vmin, vmax=vmax)


# --------------------------------------------------------------------------
# homography warp (bilinear, zero fill outside)
# --------------------------------------------------------------------------

def warp(
    img: jnp.ndarray,  # (C, H, W) f32
    hmat_inv: jnp.ndarray,  # (3, 3) f32: maps dst (x,y,1) -> src coords
    out_shape: Tuple[int, int] | None = None,
) -> jnp.ndarray:
    """out[c, y, x] = bilinear(img[c], H^-1 @ [x, y, 1]).

    Convention: `hmat_inv` maps *destination* pixel coordinates (x=col,
    y=row, homogeneous) into *source* coordinates. `warp(img, inv(H))`
    therefore applies the forward homography H to the image.
    """
    c, h, w = img.shape
    oh, ow = out_shape if out_shape is not None else (h, w)
    ys, xs = jnp.mgrid[0:oh, 0:ow]
    xs = xs.reshape(-1).astype(jnp.float32)
    ys = ys.reshape(-1).astype(jnp.float32)
    # elementwise, not a matmul: a TPU runs an f32 matmul at bf16
    # precision by default, which would move 1080p coordinates by pixels
    m = hmat_inv.astype(jnp.float32).reshape(9)
    den = m[6] * xs + m[7] * ys + m[8]
    sx = (m[0] * xs + m[1] * ys + m[2]) / den
    sy = (m[3] * xs + m[4] * ys + m[5]) / den

    x0 = jnp.floor(sx)
    y0 = jnp.floor(sy)
    fx = sx - x0
    fy = sy - y0
    x0i = x0.astype(jnp.int32)
    y0i = y0.astype(jnp.int32)

    def gather(yi, xi):
        valid = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        yc = jnp.clip(yi, 0, h - 1)
        xc = jnp.clip(xi, 0, w - 1)
        vals = img[:, yc, xc]  # (C, N)
        return jnp.where(valid[None, :], vals, 0.0), valid

    v00, m00 = gather(y0i, x0i)
    v01, m01 = gather(y0i, x0i + 1)
    v10, m10 = gather(y0i + 1, x0i)
    v11, m11 = gather(y0i + 1, x0i + 1)
    w00 = (1 - fy) * (1 - fx)
    w01 = (1 - fy) * fx
    w10 = fy * (1 - fx)
    w11 = fy * fx
    out = v00 * w00 + v01 * w01 + v10 * w10 + v11 * w11
    return out.reshape(c, oh, ow)


# --------------------------------------------------------------------------
# per-channel histogram fingerprints
# --------------------------------------------------------------------------

def histogram(
    frames: jnp.ndarray,  # (N, C, H, W), values in [0, vmax]
    *,
    bins: int,
    vmax: float = 255.0,
) -> jnp.ndarray:
    """Returns (N, C, bins) int32 per-channel histograms."""
    x = frames.astype(jnp.float32)
    idx = jnp.clip((x * (bins / (vmax + 1.0))).astype(jnp.int32), 0, bins - 1)
    onehot = jax.nn.one_hot(idx, bins, dtype=jnp.int32)  # (N,C,H,W,B)
    return onehot.sum(axis=(2, 3))


# --------------------------------------------------------------------------
# fused per-frame MSE (sum of squared error; mean taken by caller)
# --------------------------------------------------------------------------

def mse_sum(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a, b: (N, H, W) -> (N,) f32 sums of squared differences."""
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return (d * d).sum(axis=(1, 2))


# --------------------------------------------------------------------------
# paged decode attention (GOP-paged KV) — serving hot-spot
# --------------------------------------------------------------------------

def paged_decode_attention(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pages: jnp.ndarray,  # (P, page, Hkv, D)
    v_pages: jnp.ndarray,  # (P, page, Hkv, D)
    block_table: jnp.ndarray,  # (B, max_pages) int32, -1 = absent
    seq_lens: jnp.ndarray,  # (B,) int32 — valid KV length per sequence
    *,
    scale: float | None = None,
) -> jnp.ndarray:
    """Single-token decode attention over block-table-paged KV.

    Returns (B, Hq, D). Hq must be a multiple of Hkv (GQA).
    """
    b, hq, d = q.shape
    p, page, hkv, _ = k_pages.shape
    groups = hq // hkv
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    max_pages = block_table.shape[1]

    # Gather each sequence's KV: (B, max_pages*page, Hkv, D)
    safe_table = jnp.maximum(block_table, 0)
    k = k_pages[safe_table].reshape(b, max_pages * page, hkv, d)
    v = v_pages[safe_table].reshape(b, max_pages * page, hkv, d)
    pos = jnp.arange(max_pages * page)[None, :]  # (1, L)
    valid = (pos < seq_lens[:, None]) & (
        jnp.repeat(block_table >= 0, page, axis=1)
    )

    qg = q.reshape(b, hkv, groups, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgd,blhd->bhgl", qg, kf) * scale
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgl,blhd->bhgd", probs, vf)
    return out.reshape(b, hq, d).astype(q.dtype)
