"""Small shared helpers (shape padding, tree math, byte formatting)."""
from __future__ import annotations

import math
import os
from pathlib import Path
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_axis_to(x: jnp.ndarray, axis: int, target: int, value=0):
    """Pad axis of `x` up to `target` with `value`; no-op if already there."""
    cur = x.shape[axis]
    if cur == target:
        return x
    if cur > target:
        raise ValueError(f"axis {axis} size {cur} > target {target}")
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - cur)
    return jnp.pad(x, pads, constant_values=value)


def pad_to_multiple(x: jnp.ndarray, axis: int, mult: int, value=0):
    return pad_axis_to(x, axis, round_up(x.shape[axis], mult), value)


def tree_size_bytes(tree) -> int:
    return sum(
        np.prod(l.shape) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree)
        if hasattr(l, "shape")
    )


def tree_num_params(tree) -> int:
    return sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(tree)
        if hasattr(l, "shape")
    )


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0 or unit == "PiB":
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def interpret_default() -> bool:
    """Pallas kernels are compiled by Mosaic on a TPU and run in
    interpret mode on any other backend (the CPU test suite)."""
    return jax.default_backend() != "tpu"


# the checkout root (src/repro/utils.py -> parents[2])
REPO_ROOT = Path(__file__).resolve().parents[2]
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points only.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there
    and no other directory is set here. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (git-ignored), never a temp, pid- or
    time-derived path, so later runs of the same checkout find it. Every
    compile is cached: the codec kernels compile in well under JAX's
    default one-second threshold. Returns the cache directory.
    """
    path = os.environ.get(COMPILE_CACHE_ENV)
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def prod(xs: Iterable[int]) -> int:
    return int(math.prod(xs))


def stack_trees(trees: Sequence):
    """Stack a list of identical pytrees along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *trees)
