"""Per-kernel differential tests: Pallas (interpret mode) vs jnp oracle,
swept over shapes/dtypes (the kernel contract in kernels/ref.py). The
warp has a single jitted XLA implementation, checked against a numpy
bilinear reference."""
import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def frames(t, c, h, w):
    return jnp.asarray(RNG.integers(0, 256, (t, c, h, w)).astype(np.float32))


@pytest.mark.parametrize("t,h,w", [
    (2, 8, 128), (6, 24, 200), (3, 17, 130), (5, 64, 256),
])
@pytest.mark.parametrize("q,lo,hi", [(2.0, -128, 127), (8.0, -128, 127),
                                     (1.0, -32768, 32767)])
def test_delta_codec_matches_oracle(t, h, w, q, lo, hi):
    x = frames(t, 3, h, w)
    ip, rp = ops.delta_encode(x, q=q, lo=lo, hi=hi, vmin=0, vmax=255,
                              use_pallas=True)
    ir, rr = ref.delta_encode(x, q=q, lo=lo, hi=hi, vmin=0, vmax=255)
    np.testing.assert_allclose(ip, ir, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(rp), np.asarray(rr))
    dp = ops.delta_decode(ip, rp.astype(jnp.int32), q=q, vmin=0, vmax=255,
                          use_pallas=True)
    dr = ref.delta_decode(ir, rr.astype(jnp.int32), q=q, vmin=0, vmax=255)
    np.testing.assert_allclose(dp, dr, atol=1e-4)


@pytest.mark.parametrize("factor", [1, 2, 4])
@pytest.mark.parametrize("q_in,q_out", [(2.0, 8.0), (8.0, 2.0)])
def test_transcode_fused_matches_oracle(factor, q_in, q_out):
    x = frames(4, 3, 32, 256)
    ifr, res = ref.delta_encode(x, q=q_in, lo=-128, hi=127, vmin=0, vmax=255)
    res = res.astype(jnp.int32)
    io_p, ro_p = ops.transcode(
        ifr, res, q_in=q_in, q_out=q_out, factor=factor, lo=-128, hi=127,
        vmin=0, vmax=255, use_pallas=True,
    )
    io_r, ro_r = ref.transcode(
        ifr, res, q_in=q_in, q_out=q_out, factor=factor, lo=-128, hi=127,
        vmin=0, vmax=255,
    )
    np.testing.assert_allclose(io_p, io_r, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(ro_p), np.asarray(ro_r))


def _np_warp(img, hmat_inv, oh, ow):
    """Plain numpy bilinear warp with zero fill outside the source."""
    c, h, w = img.shape
    ys, xs = np.mgrid[0:oh, 0:ow].astype(np.float32).reshape(2, -1)
    m = hmat_inv.astype(np.float32).reshape(9)
    den = m[6] * xs + m[7] * ys + m[8]
    sx = (m[0] * xs + m[1] * ys + m[2]) / den
    sy = (m[3] * xs + m[4] * ys + m[5]) / den
    x0, y0 = np.floor(sx), np.floor(sy)
    fx, fy = sx - x0, sy - y0
    x0, y0 = x0.astype(np.int64), y0.astype(np.int64)
    out = np.zeros((c, oh * ow), np.float32)
    for dy, dx, wt in ((0, 0, (1 - fy) * (1 - fx)), (0, 1, (1 - fy) * fx),
                       (1, 0, fy * (1 - fx)), (1, 1, fy * fx)):
        yi, xi = y0 + dy, x0 + dx
        ok = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        vals = img[:, np.clip(yi, 0, h - 1), np.clip(xi, 0, w - 1)]
        out += np.where(ok, vals, 0.0) * wt
    return out.reshape(c, oh, ow)


@pytest.mark.parametrize("h,w,oh,ow", [
    (32, 160, 32, 160), (24, 136, 16, 128), (64, 256, 40, 200),
])
def test_warp_matches_oracle(h, w, oh, ow):
    img = frames(1, 3, h, w)[0]
    hmat = np.array(
        [[1.02, 0.03, 2.0], [0.01, 0.99, -1.5], [2e-5, 1e-5, 1.0]],
        np.float32,
    )
    wp = ops.warp(img, jnp.asarray(hmat), out_shape=(oh, ow))
    wr = _np_warp(np.asarray(img), hmat, oh, ow)
    np.testing.assert_allclose(wp, wr, atol=5e-2)


@pytest.mark.parametrize("n,c,h,w,bins", [
    (1, 3, 16, 130, 16), (4, 3, 33, 128, 32), (2, 1, 8, 256, 8),
])
def test_histogram_matches_oracle(n, c, h, w, bins):
    x = frames(n, c, h, w)
    hp = ops.histogram(x, bins=bins, use_pallas=True)
    hr = ref.histogram(x, bins=bins)
    np.testing.assert_array_equal(np.asarray(hp), np.asarray(hr))
    assert int(hp.sum()) == n * c * h * w  # histograms partition pixels


@pytest.mark.parametrize("n,h,w", [(1, 8, 128), (4, 20, 150), (2, 64, 512)])
def test_mse_matches_oracle(n, h, w):
    a = frames(n, 1, h, w)[:, 0]
    b = a + jnp.asarray(RNG.normal(0, 5, a.shape).astype(np.float32))
    np.testing.assert_allclose(
        ops.mse_sum(a, b, use_pallas=True), ref.mse_sum(a, b), rtol=1e-5
    )


@pytest.mark.parametrize("b,hq,hkv,d,p,page", [
    (2, 4, 2, 64, 8, 16), (1, 8, 8, 128, 4, 8), (3, 8, 2, 128, 16, 32),
])
def test_paged_attention_matches_oracle(b, hq, hkv, d, p, page):
    q = jnp.asarray(RNG.standard_normal((b, hq, d)).astype(np.float32))
    kp = jnp.asarray(RNG.standard_normal((p, page, hkv, d)).astype(np.float32))
    vp = jnp.asarray(RNG.standard_normal((p, page, hkv, d)).astype(np.float32))
    maxp = p // 2
    bt = jnp.asarray(RNG.integers(0, p, (b, maxp)).astype(np.int32))
    sl = jnp.asarray(RNG.integers(1, maxp * page, (b,)).astype(np.int32))
    op = ops.paged_decode_attention(q, kp, vp, bt, sl, use_pallas=True)
    orf = ref.paged_decode_attention(q, kp, vp, bt, sl)
    np.testing.assert_allclose(op, orf, atol=1e-4)


def test_dispatch_counts_record_the_path():
    """Every ops call counts the implementation that served it."""
    x = frames(3, 3, 8, 128)
    kw = dict(q=8.0, lo=-128, hi=127, vmin=0, vmax=255)
    before = ops.dispatch_counts()
    ops.delta_encode(x, use_pallas=False, **kw)
    ops.delta_encode(x, use_pallas=True, **kw)  # interpret mode off-TPU
    ops.warp(x[0], jnp.eye(3))
    after = ops.dispatch_counts()
    delta = {k: v - before.get(k, 0) for k, v in after.items()}
    assert delta[("delta_encode", "oracle")] == 1
    assert delta[("delta_encode", "interpret")] == 1
    assert delta[("warp", "xla")] == 1
    assert delta.get(("delta_encode", "pallas"), 0) == 0


_CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from repro.utils import REPO_ROOT, init_compile_cache
path = init_compile_cache()
if {compile}:
    jax.jit(lambda v: jnp.sin(v) * 3)(jnp.ones(7)).block_until_ready()
print(json.dumps([path, jax.config.jax_compilation_cache_dir,
                  jax.config.jax_persistent_cache_min_compile_time_secs,
                  str(REPO_ROOT)]))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins; unset, the cache sits at the
    checkout's fixed, git-ignored .jax_cache."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=from_env)],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    )
    path, configured, min_secs, root = json.loads(out.stdout.splitlines()[-1])
    assert configured == path and min_secs == 0
    if from_env:
        assert path == str(tmp_path / "cache")
        assert os.listdir(path), "nothing was cached where the env said"
    else:
        assert path == os.path.join(root, ".jax_cache")
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


def test_codec_roundtrip_through_gop_layer():
    """encode→serialize→deserialize→decode at the codec layer."""
    from repro import codec

    clip = RNG.integers(0, 256, (8, 24, 40, 3)).astype(np.uint8)
    for tier, tol in (("tvc-ll", 0), ("tvc-hi", 2), ("tvc-med", 6)):
        enc = codec.encode_gop(clip, tier)
        data = codec.serialize_gop(enc)
        dec = codec.decode_gop(codec.deserialize_gop(data))
        err = np.abs(dec.astype(int) - clip.astype(int)).max()
        assert err <= tol, f"{tier}: max err {err}"
