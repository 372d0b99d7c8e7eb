"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * layout conversion: user-facing video is interleaved (T, H, W, C)
    uint8; kernels are channel-planar (T, C, H, W) f32,
  * padding H→multiple of 8 and W→multiple of 128 (TPU sublane/lane
    tiles) and unpadding the results,
  * dispatch: the compiled Pallas kernel on TPU, interpret-mode Pallas
    or the jnp oracle elsewhere (``use_pallas=False`` forces the oracle,
    the paper-faithful baseline used in differential tests).

Every call counts the path it took in the process registry as
``vss_kernel_dispatch_total{op=..., path=pallas|interpret|oracle|xla}``,
so a run can prove which implementation served it. The homography warp
has one implementation on every platform: a jitted XLA program (a
bilinear warp is a data-dependent 2-D gather, which Mosaic cannot
lower), counted as ``path="xla"``.

Every function here has a matching oracle in :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import utils
from repro.kernels import delta_codec as _dc
from repro.kernels import histogram as _hist
from repro.kernels import mse as _mse
from repro.kernels import ref
from repro.kernels import transcode as _tc
from repro.obs.registry import LabelledCounter

SUBLANE = 8
LANE = 128

DISPATCH_METRIC = "vss_kernel_dispatch_total"

_dispatches = LabelledCounter(
    DISPATCH_METRIC,
    "codec/joint kernel calls by the implementation that ran",
    ("op", "path"),
)


def _count(op: str, path: str) -> None:
    """Count one call of ``op`` served by ``path`` (default registry)."""
    _dispatches.inc(op, path)


def dispatch_counts() -> dict:
    """``{(op, path): calls}`` so far in this process (default registry)."""
    return _dispatches.counts()


def _resolve(op: str, use_pallas, interpret):
    """Pick and count the path of one call; returns ``interpret`` or
    None for the oracle.

    ``use_pallas=None`` means Pallas on TPU (or with
    REPRO_FORCE_PALLAS=1) and the jit-compiled jnp oracle elsewhere:
    interpret-mode Pallas is a correctness tool, not a fast path.
    """
    if use_pallas is None:
        use_pallas = (os.environ.get("REPRO_FORCE_PALLAS") == "1"
                      or jax.default_backend() == "tpu")
    if not use_pallas:
        _count(op, "oracle")
        return None
    interpret = utils.interpret_default() if interpret is None else interpret
    _count(op, "interpret" if interpret else "pallas")
    return interpret


_warp_xla = jax.jit(ref.warp, static_argnames=("out_shape",))


def to_planar(frames: jnp.ndarray) -> jnp.ndarray:
    """(T, H, W, C) -> (T, C, H, W) f32."""
    return jnp.moveaxis(frames, -1, 1).astype(jnp.float32)


def from_planar(frames: jnp.ndarray, dtype=None) -> jnp.ndarray:
    """(T, C, H, W) -> (T, H, W, C)."""
    out = jnp.moveaxis(frames, 1, -1)
    return out.astype(dtype) if dtype is not None else out


def _pad_hw(x: jnp.ndarray):
    """Pad the trailing two axes to (8, 128) multiples; return valid extents."""
    h, w = x.shape[-2], x.shape[-1]
    x = utils.pad_to_multiple(x, -2, SUBLANE)
    x = utils.pad_to_multiple(x, -1, LANE)
    return x, h, w


def delta_encode(
    frames: jnp.ndarray,  # (T, C, H, W)
    *,
    q: float,
    lo: int,
    hi: int,
    vmin: float,
    vmax: float,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    interpret = _resolve("delta_encode", use_pallas, interpret)
    if interpret is None:
        return ref.delta_encode(frames, q=q, lo=lo, hi=hi, vmin=vmin, vmax=vmax)
    padded, h, w = _pad_hw(frames)
    iframe, resid = _dc.delta_encode_pallas(
        padded, q=q, lo=lo, hi=hi, vmin=vmin, vmax=vmax, interpret=interpret
    )
    return iframe[:, :h, :w], resid[:, :, :h, :w]


def delta_decode(
    iframe: jnp.ndarray,  # (C, H, W)
    residuals: jnp.ndarray,  # (T-1, C, H, W)
    *,
    q: float,
    vmin: float,
    vmax: float,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = _resolve("delta_decode", use_pallas, interpret)
    if interpret is None:
        return ref.delta_decode(iframe, residuals, q=q, vmin=vmin, vmax=vmax)
    ipad, h, w = _pad_hw(iframe)
    rpad, _, _ = _pad_hw(residuals)
    frames = _dc.delta_decode_pallas(
        ipad, rpad, q=q, vmin=vmin, vmax=vmax, interpret=interpret
    )
    return frames[:, :, :h, :w]


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
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused decode→downsample→encode. Requires factor | H and factor | W."""
    interpret = _resolve("transcode", use_pallas, interpret)
    if interpret is None:
        return ref.transcode(
            iframe, residuals, q_in=q_in, q_out=q_out, factor=factor,
            lo=lo, hi=hi, vmin=vmin, vmax=vmax,
        )
    c, h, w = iframe.shape
    # output tiles must be (8,128)-aligned => input padded to factor*(8,128)
    ipad = utils.pad_to_multiple(
        utils.pad_to_multiple(iframe, -2, factor * SUBLANE), -1, factor * LANE
    )
    rpad = utils.pad_to_multiple(
        utils.pad_to_multiple(residuals, -2, factor * SUBLANE), -1, factor * LANE
    )
    oh, ow = h // factor, w // factor
    io, ro = _tc.transcode_pallas(
        ipad, rpad, q_in=q_in, q_out=q_out, factor=factor,
        lo=lo, hi=hi, vmin=vmin, vmax=vmax, interpret=interpret,
    )
    return io[:, :oh, :ow], ro[:, :, :oh, :ow]


def warp(
    img: jnp.ndarray,  # (C, H, W)
    hmat_inv: jnp.ndarray,  # (3, 3)
    *,
    out_shape: Tuple[int, int] | None = None,
) -> jnp.ndarray:
    """Bilinear homography warp (semantics of ``ref.warp``), jitted."""
    _count("warp", "xla")
    oh, ow = out_shape if out_shape is not None else img.shape[1:]
    return _warp_xla(img, hmat_inv, out_shape=(int(oh), int(ow)))


def histogram(
    frames: jnp.ndarray,  # (N, C, H, W)
    *,
    bins: int = 16,
    vmax: float = 255.0,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = _resolve("histogram", use_pallas, interpret)
    if interpret is None:
        return ref.histogram(frames, bins=bins, vmax=vmax)
    padded, h, w = _pad_hw(frames)
    return _hist.histogram_pallas(
        padded, bins=bins, vmax=vmax, h_valid=h, w_valid=w, interpret=interpret
    )


def mse_sum(
    a: jnp.ndarray,  # (N, H, W)
    b: jnp.ndarray,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = _resolve("mse_sum", use_pallas, interpret)
    if interpret is None:
        return ref.mse_sum(a, b)
    apad, h, w = _pad_hw(a)
    bpad, _, _ = _pad_hw(b)
    return _mse.mse_sum_pallas(
        apad, bpad, h_valid=h, w_valid=w, interpret=interpret
    )


def mse(a: jnp.ndarray, b: jnp.ndarray, **kw) -> jnp.ndarray:
    """Per-frame mean squared error for (N, H, W) planes."""
    n = a.shape[-2] * a.shape[-1]
    return mse_sum(a, b, **kw) / n


def psnr_from_mse(mse_val, peak: float = 255.0):
    m = jnp.maximum(jnp.asarray(mse_val, jnp.float32), 1e-12)
    return 10.0 * jnp.log10((peak * peak) / m)


def psnr(a: jnp.ndarray, b: jnp.ndarray, peak: float = 255.0, **kw) -> jnp.ndarray:
    """Per-frame PSNR for (N, H, W) planes (∞ capped at ~480 dB)."""
    return psnr_from_mse(mse(a, b, **kw), peak=peak)


def paged_decode_attention(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pages: jnp.ndarray,  # (P, page, Hkv, D)
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # (B, maxp) int32
    seq_lens: jnp.ndarray,  # (B,) int32
    *,
    scale: float | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    from repro.kernels.paged_attention import paged_decode_attention_pallas

    interpret = _resolve("paged_decode_attention", use_pallas, interpret)
    if interpret is None:
        return ref.paged_decode_attention(
            q, k_pages, v_pages, block_table, seq_lens, scale=scale
        )
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, block_table, seq_lens,
        scale=scale, interpret=interpret,
    )
