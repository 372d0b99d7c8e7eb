"""Least HBM bytes of the codec's device work, from shapes alone.

Whatever the implementation, a decode of one GOP of ``t`` frames of
``h`` x ``w`` x ``c`` must read the uint8 I-frame and the ``t - 1``
residual planes at their stored width and write ``t`` uint8 frames. An
encode reads the ``t`` uint8 frames and writes the I-frame and the
residuals; a fused transcode reads one tier's planes and writes
another's, at the size its box pool leaves, and no frames. Widening,
padding and layout passes are not counted, so a kernel that fuses them
away reads closer to its roofline.
"""
from __future__ import annotations


def decode_bytes(t: int, h: int, w: int, c: int = 3,
                 resid_bytes: int = 1) -> int:
    plane = h * w * c
    return plane + (t - 1) * plane * resid_bytes + t * plane


def encode_bytes(t: int, h: int, w: int, c: int = 3,
                 resid_bytes: int = 1) -> int:
    plane = h * w * c
    return t * plane + plane + (t - 1) * plane * resid_bytes


def transcode_bytes(t: int, h: int, w: int, c: int = 3, factor: int = 1,
                    resid_in: int = 1, resid_out: int = 1) -> int:
    plane, out = h * w * c, (h // factor) * (w // factor) * c
    return plane + (t - 1) * plane * resid_in + out + (t - 1) * out * resid_out


def least_seconds(nbytes: float, device_kind: str, peaks: dict) -> float:
    """``nbytes`` over the device's HBM bandwidth; an unknown device is
    an error, never a default."""
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return nbytes / peaks["devices"][device_kind]["hbm_bytes_per_s"]
