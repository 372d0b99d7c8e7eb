"""Real-size compiles of the codec and joint-compression kernels for a
TPU v5e chip that is described, not attached.

Each case lowers the ``ops`` wrapper (padding included) at the Visual
Road 2K size, 1920x1080 with a 30-frame GOP, for one chip of a
``v5e:2x2`` topology and compiles it with the chip's compiler, which
refuses what interpret mode accepts: misaligned blocks, unsupported
vector layouts, gathers Mosaic cannot lower. Nothing runs. The topology
is described inside a fixture, so only the test worker that runs this
file loads the TPU compiler.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

T, C, H, W = 30, 3, 1080, 1920
H4K, W4K = 2160, 3840
TIER_Q = {"int8": 8.0, "int16": 1.0}  # tvc-med and tvc-ll residuals


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without
    # one, so keep these compiles out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _has_kernel(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


def test_delta_encode_compiles(one_chip):
    hlo = _compile(
        lambda x: ops.delta_encode(
            x, q=8.0, lo=-128, hi=127, vmin=0.0, vmax=255.0,
            use_pallas=True, interpret=False,
        ),
        one_chip, ((T, C, H, W), jnp.float32),
    )
    assert _has_kernel(hlo)


@pytest.mark.parametrize("resid", ["int8", "int16"])
def test_delta_decode_compiles(one_chip, resid):
    hlo = _compile(
        lambda i, r: ops.delta_decode(
            i, r.astype(jnp.int32), q=TIER_Q[resid], vmin=0.0, vmax=255.0,
            use_pallas=True, interpret=False,
        ),
        one_chip, ((C, H, W), jnp.float32), ((T - 1, C, H, W), resid),
    )
    assert _has_kernel(hlo)


def test_delta_decode_4k_compiles(one_chip):
    hlo = _compile(
        lambda i, r: ops.delta_decode(
            i, r.astype(jnp.int32), q=8.0, vmin=0.0, vmax=255.0,
            use_pallas=True, interpret=False,
        ),
        one_chip, ((C, H4K, W4K), jnp.float32),
        ((T - 1, C, H4K, W4K), jnp.int8),
    )
    assert _has_kernel(hlo)


@pytest.mark.parametrize("factor", [1, 2])
def test_transcode_compiles(one_chip, factor):
    hlo = _compile(
        lambda i, r: ops.transcode(
            i, r, q_in=8.0, q_out=2.0, factor=factor, lo=-128, hi=127,
            vmin=0.0, vmax=255.0, use_pallas=True, interpret=False,
        ),
        one_chip, ((C, H, W), jnp.float32), ((T - 1, C, H, W), jnp.int32),
    )
    assert _has_kernel(hlo)


@pytest.mark.parametrize("h,w", [(H, W), (H4K, W4K)])
def test_warp_compiles(one_chip, h, w):
    # the warp is one XLA program on every platform: no kernel to find,
    # and no size above which it is swapped for something else
    hlo = _compile(
        lambda x, m: ops.warp(x, m, out_shape=(h, w)),
        one_chip, ((C, h, w), jnp.float32), ((3, 3), jnp.float32),
    )
    assert "ENTRY" in hlo


def test_histogram_compiles(one_chip):
    hlo = _compile(
        lambda x: ops.histogram(x, bins=16, use_pallas=True, interpret=False),
        one_chip, ((1, C, H, W), jnp.float32),
    )
    assert _has_kernel(hlo)


def test_mse_compiles(one_chip):
    hlo = _compile(
        lambda a, b: ops.mse_sum(a, b, use_pallas=True, interpret=False),
        one_chip, ((T, H, W), jnp.float32), ((T, H, W), jnp.float32),
    )
    assert _has_kernel(hlo)
