"""Pallas TPU kernels for the TVC closed-loop DPCM (delta) codec.

The GOP chain (I-frame + quantized P-frame residuals) is sequential in T,
so each kernel invocation owns a spatial VMEM tile for *all* T frames and
walks the chain with a ``fori_loop`` while the tile stays resident. The
grid covers (channel, H-tiles, W-tiles); T is small (GOP size, ≤64) so a
(T, 1, bh, bw) f32 tile of 64x8x128x4B = 256KiB fits VMEM comfortably and
the W tile is lane-aligned (128) / H tile sublane-aligned (8).

Semantics are defined by :mod:`repro.kernels.ref` (``delta_encode`` /
``delta_decode``); tests sweep shapes and dtypes against those oracles.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BH = 8
DEFAULT_BW = 128


def _encode_kernel(frames_ref, iframe_ref, resid_ref, *, q, lo, hi, vmin, vmax):
    t_total = frames_ref.shape[0]
    iframe = frames_ref[0].astype(jnp.float32)
    iframe_ref[...] = iframe

    def body(t, recon):
        frame = frames_ref[t].astype(jnp.float32)
        r = frame - recon
        rq = jnp.clip(jnp.round(r * (1.0 / q)), lo, hi)
        recon = jnp.clip(recon + rq * q, vmin, vmax)
        resid_ref[t - 1] = rq.astype(jnp.int32)
        return recon

    jax.lax.fori_loop(1, t_total, body, iframe)


def _decode_kernel(iframe_ref, resid_ref, frames_ref, *, q, vmin, vmax):
    t_resid = resid_ref.shape[0]
    recon = iframe_ref[...].astype(jnp.float32)
    frames_ref[0] = recon

    def body(t, recon):
        rq = resid_ref[t].astype(jnp.float32)
        recon = jnp.clip(recon + rq * q, vmin, vmax)
        frames_ref[t + 1] = recon
        return recon

    jax.lax.fori_loop(0, t_resid, body, recon)


@functools.partial(
    jax.jit,
    static_argnames=("q", "lo", "hi", "vmin", "vmax", "bh", "bw", "interpret"),
)
def delta_encode_pallas(
    frames: jnp.ndarray,  # (T, C, H, W) f32; H % bh == 0, W % bw == 0
    *,
    q: float,
    lo: int,
    hi: int,
    vmin: float,
    vmax: float,
    bh: int = DEFAULT_BH,
    bw: int = DEFAULT_BW,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    t, c, h, w = frames.shape
    grid = (c, h // bh, w // bw)
    kernel = functools.partial(
        _encode_kernel, q=q, lo=lo, hi=hi, vmin=vmin, vmax=vmax
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((t, 1, bh, bw), lambda ci, i, j: (0, ci, i, j)),
        ],
        out_specs=(
            pl.BlockSpec((1, bh, bw), lambda ci, i, j: (ci, i, j)),
            pl.BlockSpec((t - 1, 1, bh, bw), lambda ci, i, j: (0, ci, i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((c, h, w), jnp.float32),
            jax.ShapeDtypeStruct((t - 1, c, h, w), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(frames.astype(jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("q", "vmin", "vmax", "bh", "bw", "interpret"),
)
def delta_decode_pallas(
    iframe: jnp.ndarray,  # (C, H, W) f32
    residuals: jnp.ndarray,  # (T-1, C, H, W) int32
    *,
    q: float,
    vmin: float,
    vmax: float,
    bh: int = DEFAULT_BH,
    bw: int = DEFAULT_BW,
    interpret: bool = False,
) -> jnp.ndarray:
    c, h, w = iframe.shape
    tm1 = residuals.shape[0]
    grid = (c, h // bh, w // bw)
    kernel = functools.partial(_decode_kernel, q=q, vmin=vmin, vmax=vmax)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, bw), lambda ci, i, j: (ci, i, j)),
            pl.BlockSpec((tm1, 1, bh, bw), lambda ci, i, j: (0, ci, i, j)),
        ],
        out_specs=pl.BlockSpec((tm1 + 1, 1, bh, bw), lambda ci, i, j: (0, ci, i, j)),
        out_shape=jax.ShapeDtypeStruct((tm1 + 1, c, h, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(iframe.astype(jnp.float32), residuals.astype(jnp.int32))
