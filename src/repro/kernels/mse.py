"""Pallas fused per-frame sum-of-squared-error kernel (quality model §3.2).

Grid = (N, H-tiles, W-tiles); a (1, bh, bw) f32 block per frame is
accumulated elementwise across spatial tiles (a (1, 1) scalar block per
frame would break the rule that a block's last two dims are (8, 128)-
aligned or full), and the wrapper sums the partial block. Differences are
squared in f32 while both tiles are VMEM-resident, so a quality check
costs a single read of each operand. Only the tests call it today; the
store's quality checks run in numpy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BH = 8
DEFAULT_BW = 128


def _mse_kernel(a_ref, b_ref, out_ref, *, h_valid, w_valid, bh, bw):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    rows = i * bh + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)
    cols = j * bw + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)
    valid = (rows < h_valid) & (cols < w_valid)
    d = jnp.where(valid, a - b, 0.0)
    out_ref[0] += d * d


@functools.partial(
    jax.jit, static_argnames=("h_valid", "w_valid", "bh", "bw", "interpret")
)
def mse_sum_pallas(
    a: jnp.ndarray,  # (N, H, W) — H, W tile-padded
    b: jnp.ndarray,
    *,
    h_valid: int | None = None,
    w_valid: int | None = None,
    bh: int = DEFAULT_BH,
    bw: int = DEFAULT_BW,
    interpret: bool = False,
) -> jnp.ndarray:
    n, h, w = a.shape
    h_valid = h if h_valid is None else h_valid
    w_valid = w if w_valid is None else w_valid
    grid = (n, h // bh, w // bw)
    kernel = functools.partial(
        _mse_kernel, h_valid=h_valid, w_valid=w_valid, bh=bh, bw=bw
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, bw), lambda ni, i, j: (ni, i, j)),
            pl.BlockSpec((1, bh, bw), lambda ni, i, j: (ni, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bh, bw), lambda ni, i, j: (ni, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, bh, bw), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
    return out.sum(axis=(1, 2))
