"""Pallas per-channel histogram kernel (joint-compression fingerprints, §5.1.3).

Grid = (N, C, H-tiles, W-tiles); the (1, C, bins_padded) int32 output
block holds every channel of one frame (a block's last two dims must be
(8, 128)-aligned or full), so it is revisited across the channel and
spatial axes ("arbitrary" semantics) and accumulated in place — the
canonical TPU reduction-across-grid pattern.
Bin counting is B masked VPU reductions (one compare+sum per bin), which
beats a scatter on TPU since there is no atomic HBM scatter-add.

Padded spatial rows/cols (to reach lane/sublane alignment) are masked out
via the statically-known valid extents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_BH = 8
DEFAULT_BW = 128


def _hist_kernel(frames_ref, out_ref, *, bins, vmax, h_valid, w_valid, bh, bw):
    ci = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when((ci == 0) & (i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = frames_ref[0, 0].astype(jnp.float32)  # (bh, bw)
    idx = jnp.clip((x * (bins / (vmax + 1.0))).astype(jnp.int32), 0, bins - 1)

    rows = i * bh + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)
    cols = j * bw + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)
    valid = (rows < h_valid) & (cols < w_valid)

    # one masked full reduction per bin, scattered into its lane
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, out_ref.shape[2]), 1)
    counts = jnp.zeros((1, out_ref.shape[2]), jnp.int32)
    for b in range(bins):
        n = jnp.sum(((idx == b) & valid).astype(jnp.int32))
        counts = counts + jnp.where(lanes == b, n, 0)
    out_ref[0, pl.ds(ci, 1), :] += counts


@functools.partial(
    jax.jit,
    static_argnames=("bins", "vmax", "h_valid", "w_valid", "bh", "bw", "interpret"),
)
def histogram_pallas(
    frames: jnp.ndarray,  # (N, C, H, W) f32/int — H, W already tile-padded
    *,
    bins: int,
    vmax: float = 255.0,
    h_valid: int | None = None,
    w_valid: int | None = None,
    bh: int = DEFAULT_BH,
    bw: int = DEFAULT_BW,
    interpret: bool = False,
) -> jnp.ndarray:
    n, c, h, w = frames.shape
    h_valid = h if h_valid is None else h_valid
    w_valid = w if w_valid is None else w_valid
    bins_padded = max(LANE, ((bins + LANE - 1) // LANE) * LANE)
    grid = (n, c, h // bh, w // bw)
    kernel = functools.partial(
        _hist_kernel,
        bins=bins, vmax=vmax, h_valid=h_valid, w_valid=w_valid, bh=bh, bw=bw,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bh, bw), lambda ni, ci, i, j: (ni, ci, i, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, c, bins_padded), lambda ni, ci, i, j: (ni, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((n, c, bins_padded), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(frames.astype(jnp.float32))
    return out[:, :, :bins]
