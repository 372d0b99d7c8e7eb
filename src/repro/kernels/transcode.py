"""Fused transcode Pallas kernel: decode(q_in) → box-downsample → encode(q_out).

This is the paper's per-pixel transcode hot-spot (cost model §3.1), fused
into a single HBM→VMEM pass instead of the paper's discrete
decode/rescale/encode pipeline stages (FFmpeg/NVENC). For every *output*
spatial tile we stream the corresponding (factor·bh, factor·bw) input
tile, run both recon chains (input-resolution and output-resolution) in
VMEM, and emit the re-quantized residuals — the intermediate full-rate
frames never touch HBM.

Beyond-paper optimization; the unfused path (delta_decode → downsample →
delta_encode) is kept as the paper-faithful baseline in ops.py.
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


def _pool(x: jnp.ndarray, factor: int) -> jnp.ndarray:
    """Box-mean ``(f*bh, f*bw) -> (bh, bw)`` as two 0/1-weighted matmuls.

    Mosaic cannot lower the reshape a strided mean needs, so the rows and
    then the columns are pooled through pooling matrices on the MXU. At
    HIGHEST precision every product and partial sum is exact for the
    codec's integer-valued planes, so the result equals the oracle's
    ``reshape(...).mean(...)`` bit for bit.
    """
    if factor == 1:
        return x
    h, w = x.shape
    bh, bw = h // factor, w // factor
    r = jax.lax.broadcasted_iota(jnp.int32, (bh, h), 0)
    k = jax.lax.broadcasted_iota(jnp.int32, (bh, h), 1)
    rows = jnp.where((k >= r * factor) & (k < r * factor + factor),
                     1.0 / factor, 0.0).astype(jnp.float32)
    k = jax.lax.broadcasted_iota(jnp.int32, (w, bw), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (w, bw), 1)
    cols = jnp.where((k >= c * factor) & (k < c * factor + factor),
                     1.0 / factor, 0.0).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    y = jnp.dot(rows, x, precision=hi, preferred_element_type=jnp.float32)
    return jnp.dot(y, cols, precision=hi, preferred_element_type=jnp.float32)


def _transcode_kernel(
    iframe_ref,  # (1, f*bh, f*bw)
    resid_ref,  # (T-1, 1, f*bh, f*bw)
    iframe_out_ref,  # (1, bh, bw)
    resid_out_ref,  # (T-1, 1, bh, bw)
    *,
    q_in,
    q_out,
    factor,
    lo,
    hi,
    vmin,
    vmax,
):
    t_resid = resid_ref.shape[0]
    recon_in = iframe_ref[0].astype(jnp.float32)
    recon_out = _pool(recon_in, factor)
    iframe_out_ref[0] = recon_out

    def body(t, carry):
        recon_in, recon_out = carry
        rq = resid_ref[t, 0].astype(jnp.float32)
        recon_in = jnp.clip(recon_in + rq * q_in, vmin, vmax)
        target = _pool(recon_in, factor)
        r = target - recon_out
        rq_out = jnp.clip(jnp.round(r * (1.0 / q_out)), lo, hi)
        recon_out = jnp.clip(recon_out + rq_out * q_out, vmin, vmax)
        resid_out_ref[t, 0] = rq_out.astype(jnp.int32)
        return recon_in, recon_out

    jax.lax.fori_loop(0, t_resid, body, (recon_in, recon_out))


@functools.partial(
    jax.jit,
    static_argnames=(
        "q_in", "q_out", "factor", "lo", "hi", "vmin", "vmax", "bh", "bw",
        "interpret",
    ),
)
def transcode_pallas(
    iframe: jnp.ndarray,  # (C, H, W) f32
    residuals: jnp.ndarray,  # (T-1, C, H, W) int32
    *,
    q_in: float,
    q_out: float,
    factor: int,
    lo: int,
    hi: int,
    vmin: float,
    vmax: float,
    bh: int = DEFAULT_BH,
    bw: int = DEFAULT_BW,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    c, h, w = iframe.shape
    tm1 = residuals.shape[0]
    oh, ow = h // factor, w // factor
    if oh % bh or ow % bw:
        raise ValueError(f"output ({oh},{ow}) not tileable by ({bh},{bw})")
    grid = (c, oh // bh, ow // bw)
    kernel = functools.partial(
        _transcode_kernel,
        q_in=q_in, q_out=q_out, factor=factor,
        lo=lo, hi=hi, vmin=vmin, vmax=vmax,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, factor * bh, factor * bw), lambda ci, i, j: (ci, i, j)),
            pl.BlockSpec(
                (tm1, 1, factor * bh, factor * bw), lambda ci, i, j: (0, ci, i, j)
            ),
        ],
        out_specs=(
            pl.BlockSpec((1, bh, bw), lambda ci, i, j: (ci, i, j)),
            pl.BlockSpec((tm1, 1, bh, bw), lambda ci, i, j: (0, ci, i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((c, oh, ow), jnp.float32),
            jax.ShapeDtypeStruct((tm1, c, oh, ow), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(iframe.astype(jnp.float32), residuals.astype(jnp.int32))
