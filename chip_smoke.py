"""Bring-up smoke: VSS's ingest -> store -> read -> serve path on one TPU.

    python chip_smoke.py [--seed N]
    python chip_smoke.py --cpu-rehearsal   # same phases, tiny, on the CPU

One process drives the public API at the Visual Road 2K size of the
paper's Table 1 (1920x1080 at 30 fps, 30-frame GOPs):

  (a) live ingest: 4 cameras x 10 s through pipelined ``VSSWriter``s in
      codec ``h264`` (tier tvc-med), frames generated from ``--seed``
      one GOP at a time;
  (b) one ``VSS.read_batch``: a whole-video scan, a sub-GOP trim (the
      ranged prefix decode), an ROI crop at half the frame rate, a
      960x540 conversion and a ``codec="hevc"`` re-encoded read;
  (c) ``cost.calibrate`` (the fused transcode) and one
      ``transcode_gop(scale_factor=2)`` of a stored 1080p GOP;
  (d) joint compression of an overlapping 1080p pair (overlap 0.30, as
      ``vroad-2k-30`` in configs/vss_paper.py), then reads of both views;
  (e) an in-process ``VSSService`` answering HTTP reads whose segment
      bytes are fetched and decoded (its server is a thread of this
      process, so no other process ever needs the chip).

Checks: every read equals a plain reference, the ``kernels/ref.py``
decode of the same stored GOP bytes followed by a numpy crop, frame
stride and box resample (re-encoded reads also through ``ref.py``'s
encode). The one exception is the warped part of a joint view, which is
allowed 1 level: the warp's f32 division and bilinear blend on the
device may round a .5 differently from numpy's. Reads must also reach
the tier's quality against the source (40 dB for tvc-med) and joint
views the ``tau_db`` passed in. The ``vss_kernel_dispatch_total``
counters must show every codec call on the compiled Pallas kernels (and
the warp on its XLA program): no oracle and no interpret-mode call.

Before any phase it exits non-zero unless JAX's default backend is a
TPU. The times it prints are host wall seconds of a smoke, not
benchmark results. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
``--cpu-rehearsal`` never prints it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OVERRIDES = ("VSS_USE_PALLAS", "REPRO_FORCE_PALLAS")

# Visual Road 2K (paper Table 1) and the same phases at a size the CPU's
# Pallas interpreter gets through
FULL = dict(width=1920, height=1080, fps=30, gop=30, cameras=4, seconds=10)
REHEARSAL = dict(width=256, height=144, fps=30, gop=30, cameras=2, seconds=2)
JOINT_OVERLAP = 0.30  # configs/vss_paper.py "vroad-2k-30"
JOINT_TAU_DB = 24.0  # the store's default joint abort threshold (§5.1.2)
MED_MIN_DB = 40.0  # tvc-med ("h264") tier quality against the source
PATH_OPS = ("delta_encode", "delta_decode", "transcode", "histogram")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def psnr(sse: float, n: int) -> float:
    return float("inf") if sse == 0 else 10 * np.log10(255.0**2 * n / sse)


def sq_err(a: np.ndarray, b: np.ndarray) -> int:
    d = np.subtract(a, b, dtype=np.int16)
    return int(np.square(d, dtype=np.int32).sum(dtype=np.int64))


# -- plain references ----------------------------------------------------

def to_u8(planar) -> np.ndarray:
    """(T, C, H, W) float -> (T, H, W, C) uint8, rounded half to even,
    converted on the device and downloaded once."""
    import jax.numpy as jnp

    x = jnp.moveaxis(planar, 1, -1)
    return np.asarray(jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8))


def ref_decode(enc) -> np.ndarray:
    """``ref.delta_decode`` of a stored TVC GOP's planes."""
    import jax.numpy as jnp
    from repro import codec
    from repro.kernels import ref

    tier = codec.TIERS[enc.codec]
    iframe, resid = codec.gop_planes(enc)
    planar = ref.delta_decode(
        jnp.asarray(iframe).astype(jnp.float32),
        jnp.asarray(resid).astype(jnp.int32),
        q=tier.q, vmin=0.0, vmax=255.0,
    )
    return to_u8(planar)


def ref_roundtrip(frames: np.ndarray, codec_name: str) -> np.ndarray:
    """``ref.delta_encode`` then ``ref.delta_decode`` of one GOP."""
    import jax.numpy as jnp
    from repro import codec
    from repro.kernels import ref

    tier = codec.TIERS[codec.canonical_codec(codec_name)]
    planar = jnp.moveaxis(jnp.asarray(frames), -1, 1).astype(jnp.float32)
    iframe, resid = ref.delta_encode(
        planar, q=tier.q, lo=tier.lo, hi=tier.hi, vmin=0.0, vmax=255.0
    )
    return to_u8(ref.delta_decode(iframe, resid, q=tier.q, vmin=0.0,
                                  vmax=255.0))


def box_pool(frames: np.ndarray, out_wh) -> np.ndarray:
    w, h = out_wh
    t, ih, iw, c = frames.shape
    f = ih // h
    check(ih == f * h and iw == f * w, f"no integer pool {iw}x{ih}->{w}x{h}")
    x = frames.astype(np.float32).reshape(t, h, f, w, f, c).mean((2, 4))
    return np.clip(np.rint(x), 0, 255).astype(np.uint8)


def view(frames, box=None, out_wh=None) -> np.ndarray:
    if box is not None:
        x0, y0, x1, y1 = box
        frames = frames[:, y0:y1, x0:x1]
    if out_wh is not None:
        frames = box_pool(frames, out_wh)
    return frames


def np_warp_u8(frames: np.ndarray, hinv: np.ndarray, out_hw) -> np.ndarray:
    """Bilinear warp of (T, H, W, C) uint8 through ``hinv`` (dst -> src),
    zero outside the source, in numpy float32."""
    t, h, w, c = frames.shape
    oh, ow = out_hw
    ys, xs = np.mgrid[0:oh, 0:ow].astype(np.float32).reshape(2, -1)
    m = hinv.astype(np.float32).reshape(9)
    den = m[6] * xs + m[7] * ys + m[8]
    sx = (m[0] * xs + m[1] * ys + m[2]) / den
    sy = (m[3] * xs + m[4] * ys + m[5]) / den
    x0, y0 = np.floor(sx), np.floor(sy)
    fx, fy = sx - x0, sy - y0
    x0, y0 = x0.astype(np.int64), y0.astype(np.int64)
    taps = []
    for dy, dx, wt in ((0, 0, (1 - fy) * (1 - fx)), (0, 1, (1 - fy) * fx),
                       (1, 0, fy * (1 - fx)), (1, 1, fy * fx)):
        yi, xi = y0 + dy, x0 + dx
        ok = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        idx = np.clip(yi, 0, h - 1) * w + np.clip(xi, 0, w - 1)
        taps.append((idx, np.where(ok, wt, 0.0).astype(np.float32)[:, None]))
    out = np.empty((t, oh, ow, c), np.uint8)
    for i in range(t):
        img = frames[i].reshape(h * w, c).astype(np.float32)
        acc = sum(img[idx] * wt for idx, wt in taps)
        out[i] = np.clip(np.rint(acc), 0, 255).astype(np.uint8).reshape(
            oh, ow, c)
    return out


def stored_gops(vss, name):
    """[(GopMeta, EncodedGOP)] of a video's original, in frame order."""
    from repro import codec
    from repro.core.deferred import is_wrapped, unwrap_bytes

    pid = vss.catalog.get_original_id(name)
    out = []
    for g in sorted(vss.catalog.gops_for(pid), key=lambda g: g.start_frame):
        data = vss.backend.get(g.path)
        if is_wrapped(data):
            data = unwrap_bytes(data)
        out.append((g, codec.deserialize_gop(data)))
    return out


class Smoke:
    def __init__(self, size: dict, seed: int, expect_path: str):
        self.size = size
        self.seed = seed
        self.expect_path = expect_path
        self.w, self.h = size["width"], size["height"]
        self.fps, self.gop = size["fps"], size["gop"]
        self.frames = size["fps"] * size["seconds"]
        self._compile_s = 0.0
        self._lock = threading.Lock()

    # -- bookkeeping -----------------------------------------------------
    def on_event(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self._compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax
        from repro.kernels import ops

        print(f"phase {name}: start", flush=True)
        c0, d0 = self._compile_s, ops.dispatch_counts()
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", "not reported")
        calls = {
            f"{op}/{path}": int(n - d0.get((op, path), 0))
            for (op, path), n in sorted(ops.dispatch_counts().items())
            if n - d0.get((op, path), 0)
        }
        print(f"phase {name}: passed; smoke host wall {wall:.3f} s, "
              f"of which compile {self._compile_s - c0:.3f} s; "
              f"peak_bytes_in_use {peak}; kernel calls {calls}",
              flush=True)

    def source_gops(self, cam: int):
        from repro.data.video import road_gops

        return road_gops(self.frames, self.gop, self.w, self.h,
                         seed=self.seed + cam)

    def frame_range(self, t0: float, t1: float):
        return int(round(t0 * self.fps)), int(round(t1 * self.fps))

    def check_read(self, label, frames, vss, name, cam, t, *, step=1,
                   box=None, out_wh=None):
        """Kernel frames == ref decode + numpy view, GOP by GOP; and the
        tier's quality against the regenerated source."""
        t0 = time.perf_counter()
        f0, f1 = self.frame_range(*t)
        off, sse, n = 0, 0, 0
        for (g, enc), src in zip(stored_gops(vss, name),
                                 self.source_gops(cam)):
            gs = g.start_frame
            sel = [f - gs for f in range(max(gs, f0), min(gs + g.num_frames,
                                                          f1))
                   if (f - f0) % step == 0]
            if not sel:
                continue
            want = view(ref_decode(enc)[sel], box, out_wh)
            got = frames[off:off + len(want)]
            check(got.shape == want.shape,
                  f"{label}: shape {got.shape} != reference {want.shape}")
            check(np.array_equal(got, want),
                  f"{label}: frames differ from the ref.py decode "
                  f"(max |diff| {np.abs(got.astype(int) - want).max()})")
            sse += sq_err(got, view(src[sel], box, out_wh))
            n += got.size
            off += len(want)
        check(off == len(frames) and off > 0,
              f"{label}: {len(frames)} frames read, {off} expected")
        q = psnr(sse, n)
        check(q >= MED_MIN_DB, f"{label}: {q:.2f} dB < {MED_MIN_DB} dB")
        print(f"  {label}: {frames.shape} equals the reference; "
              f"{q:.2f} dB against the source (checked in "
              f"{time.perf_counter() - t0:.3f} s)", flush=True)

    def check_reencoded(self, label, encs, decoded_ref, codec_name):
        """Each re-encoded GOP decodes (ref.py) to the ref.py encode ->
        decode of the reference frames it was made from."""
        off = 0
        for enc in encs:
            t = enc.shape[0]
            want = ref_roundtrip(decoded_ref[off:off + t], codec_name)
            got = ref_decode(enc)
            check(np.array_equal(got, want),
                  f"{label}: re-encoded GOP at frame {off} differs from the "
                  f"ref.py encode (max |diff| "
                  f"{np.abs(got.astype(int) - want).max()})")
            off += t
        check(off == len(decoded_ref),
              f"{label}: {off} frames re-encoded, {len(decoded_ref)} read")

    def ref_view_frames(self, vss, name, t, box=None, out_wh=None):
        f0, f1 = self.frame_range(*t)
        parts = []
        for g, enc in stored_gops(vss, name):
            gs = g.start_frame
            lo, hi = max(gs, f0) - gs, min(gs + g.num_frames, f1) - gs
            if lo < hi:
                parts.append(view(ref_decode(enc)[lo:hi], box, out_wh))
        return np.concatenate(parts)

    def source_view(self, cam, t, box=None, out_wh=None):
        f0, f1 = self.frame_range(*t)
        parts, start = [], 0
        for src in self.source_gops(cam):
            lo, hi = max(start, f0) - start, min(start + len(src), f1) - start
            if lo < hi:
                parts.append(view(src[lo:hi], box, out_wh))
            start += len(src)
        return np.concatenate(parts)

    # -- phases ------------------------------------------------------------
    def ingest(self, vss):
        from repro.core.spec import WriteSpec

        cams = self.size["cameras"]
        writers = [
            vss.writer_spec(WriteSpec(name=f"cam{i}", fps=float(self.fps),
                                      codec="h264", gop_frames=self.gop))
            for i in range(cams)
        ]
        sources = [self.source_gops(i) for i in range(cams)]
        for _ in range(self.frames // self.gop):  # live: GOP by GOP
            for w, src in zip(writers, sources):
                w.append(next(src))
        for w in writers:
            w.close()
        st = vss.ingest.stats()
        check(st.windows_published >= cams * self.frames // self.gop,
              f"only {st.windows_published} windows went through the "
              f"pipelined ingest")
        for i in range(cams):
            gops = stored_gops(vss, f"cam{i}")
            check(len(gops) == self.frames // self.gop
                  and sum(g.num_frames for g, _ in gops) == self.frames,
                  f"cam{i}: {len(gops)} GOPs stored")
            check(all(e.codec == "tvc-med" and e.shape[1:] ==
                      (self.h, self.w, 3) for _, e in gops),
                  f"cam{i}: unexpected stored codec or shape")
        print(f"  {cams} cameras x {self.frames} frames stored; "
              f"{st.windows_published} publish windows", flush=True)

    def read_batch(self, vss):
        from repro.core.spec import ReadSpec

        s, w, h, gop, fps = (self.size["seconds"], self.w, self.h, self.gop,
                             self.fps)
        box = (w // 4, h // 4, 3 * w // 4, 3 * h // 4)
        half = (w // 2, h // 2)
        trim = (gop / fps, (gop + gop // 2) / fps)  # frames [gop, 1.5 gop)
        conv = (0.4 * s, 0.6 * s)
        hevc = (0.6 * s, 0.8 * s)
        specs = [
            ReadSpec(name="cam0", cache=False),
            ReadSpec(name="cam1", t=trim, cache=False),
            ReadSpec(name=f"cam{2 % self.size['cameras']}", t=(0.0, s),
                     roi=box, fps=fps / 2, cache=False),
            ReadSpec(name=f"cam{3 % self.size['cameras']}", t=conv,
                     resolution=half, cache=False),
            ReadSpec(name="cam0", t=hevc, codec="hevc", cache=False),
        ]
        ranged = "vss_read_ranged_fetches_total"
        r0 = vss.registry.value(ranged)
        t0 = time.perf_counter()
        scan, trimmed, roi, conv_r, hevc_r = vss.read_batch(specs)
        print(f"  read_batch of {len(specs)} specs returned in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        check(vss.registry.value(ranged) > r0,
              "the sub-GOP trim did not take the ranged prefix path")
        self.check_read("scan", scan.frames, vss, "cam0", 0, (0.0, s))
        self.check_read("trim", trimmed.frames, vss, "cam1", 1, trim)
        c = 2 % self.size["cameras"]
        self.check_read("roi", roi.frames, vss, f"cam{c}", c, (0.0, s),
                        step=2, box=box)
        c = 3 % self.size["cameras"]
        self.check_read("960x540" if half == (960, 540) else "half-res",
                        conv_r.frames, vss, f"cam{c}", c, conv, out_wh=half)
        check(hevc_r.encoded and all(e.codec == "tvc-hi"
                                     for e in hevc_r.encoded),
              "hevc read did not return tvc-hi GOPs")
        med = self.ref_view_frames(vss, "cam0", hevc)
        self.check_reencoded("hevc", hevc_r.encoded, med, "hevc")
        got = hevc_r.frames
        check(np.array_equal(got, np.concatenate(
            [ref_decode(e) for e in hevc_r.encoded])),
              "hevc: kernel decode differs from the ref.py decode")
        q = psnr(sq_err(got, self.source_view(0, hevc)), got.size)
        check(q >= MED_MIN_DB, f"hevc: {q:.2f} dB < {MED_MIN_DB} dB")
        print(f"  hevc: {len(hevc_r.encoded)} GOPs {got.shape} equal the "
              f"reference; {q:.2f} dB against the source", flush=True)

    def calibrate(self, vss):
        import jax.numpy as jnp
        from repro import codec
        from repro.core.cost import calibrate
        from repro.kernels import ref

        model = calibrate()
        costs = [c for v in model.table.values() for _, c in v]
        check(costs and all(np.isfinite(c) and c > 0 for c in costs),
              "calibrate produced a non-finite or empty table")
        print(f"  calibrate: {len(model.table)} conversions timed", flush=True)
        g, enc = stored_gops(vss, "cam0")[0]
        out = codec.transcode_gop(enc, "hevc", scale_factor=2)
        check(out.shape == (self.gop, self.h // 2, self.w // 2, 3),
              f"transcode shape {out.shape}")
        tin, tout = codec.TIERS[enc.codec], codec.TIERS["tvc-hi"]
        iframe, resid = codec.gop_planes(enc)
        io, ro = ref.transcode(
            jnp.asarray(iframe, jnp.float32), jnp.asarray(resid, jnp.int32),
            q_in=tin.q, q_out=tout.q, factor=2, lo=tout.lo, hi=tout.hi,
            vmin=0.0, vmax=255.0,
        )
        got_i, got_r = codec.gop_planes(out)
        check(np.array_equal(got_i, np.asarray(io).astype(np.uint8))
              and np.array_equal(got_r, np.asarray(ro).astype(np.int8)),
              "transcode x2: planes differ from ref.transcode")
        src = next(iter(self.source_gops(0)))
        dec = ref_decode(out)
        q = psnr(sq_err(dec, box_pool(src, (self.w // 2, self.h // 2))),
                 dec.size)
        check(q >= MED_MIN_DB, f"transcode x2: {q:.2f} dB < {MED_MIN_DB} dB")
        print(f"  transcode x2: {out.shape} planes equal ref.transcode; "
              f"{q:.2f} dB against the pooled source", flush=True)

    def joint(self, vss):
        from repro.core.spec import ReadSpec, WriteSpec
        from repro.data.video import synthesize_overlapping_pair

        left, right, _ = synthesize_overlapping_pair(
            self.gop, self.w, self.h, overlap=JOINT_OVERLAP,
            seed=self.seed + 100,
        )
        for name, frames in (("road_left", left), ("road_right", right)):
            vss.write_spec(WriteSpec(name=name, fps=float(self.fps),
                                     codec="h264", gop_frames=self.gop),
                           frames)
        t0 = time.perf_counter()
        jids = vss.apply_joint_compression(
            ["road_left", "road_right"], merge="unprojected",
            tau_db=JOINT_TAU_DB,
        )
        check(len(jids) == 1, f"joint compression made {len(jids)} pairs")
        print(f"  apply_joint_compression took "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        for name, src in (("road_left", left), ("road_right", right)):
            got = vss.read_spec(ReadSpec(name=name, cache=False)).frames
            want, warped_cols = self.ref_joint_view(vss, name)
            check(got.shape == want.shape == src.shape,
                  f"{name}: read {got.shape}, reference {want.shape}")
            diff = np.abs(got.astype(int) - want)
            check(diff[:, :, warped_cols:].max(initial=0) == 0,
                  f"{name}: decoded columns differ from the reference")
            check(diff.max(initial=0) <= 1,
                  f"{name}: warped columns differ by {diff.max()} > 1 level")
            q = psnr(sq_err(got, src), got.size)
            check(q >= JOINT_TAU_DB,
                  f"{name}: {q:.2f} dB < tau {JOINT_TAU_DB} dB")
            print(f"  {name}: {got.shape} matches the reference "
                  f"({int((diff > 0).sum())} values off by 1 in the warp); "
                  f"{q:.2f} dB against the source", flush=True)

    def ref_joint_view(self, vss, name):
        """A joint view rebuilt from its stored slices with ref.py decodes
        and a numpy warp; also returns how many leading columns were
        warped."""
        from repro import codec

        cat = vss.catalog
        pieces, warped = [], 0
        for g in cat.gops_for(cat.get_original_id(name)):
            rec = cat.get_joint(g.joint_ref)
            check(not rec["duplicate"] and rec["g_scale"] == 1.0,
                  f"{name}: unexpected duplicate/rescaled joint record")
            side_a = rec["gop_a"] == g.gop_id
            for seg in rec["segments"]:
                dec = {
                    part: ref_decode(codec.deserialize_gop(
                        vss.backend.get(key)))
                    for part, key in seg["paths"].items()
                }
                f_comp = np.concatenate([dec["left"], dec["overlap"]], axis=2)
                if side_a:
                    pieces.append(f_comp)
                    continue
                h = np.asarray(seg["h"], np.float64).reshape(3, 3)
                warped = seg["x_g"]
                g_over = np_warp_u8(f_comp, h.astype(np.float32),
                                    (f_comp.shape[1], warped))
                pieces.append(np.concatenate([g_over, dec["right"]], axis=2))
        return np.concatenate(pieces), warped

    def serve(self, vss):
        from repro import codec
        from repro.serving.config import ServiceConfig
        from repro.serving.service import VSSService

        gop, fps, w, h = self.gop, self.fps, self.w, self.h
        conv_t = (0.0, gop / fps)
        reqs = {
            "pass-through": {"name": "cam1", "t": [0.0, 2 * gop / fps],
                             "codec": "h264", "cache": False},
            "converted": {"name": "cam0", "t": list(conv_t),
                          "codec": "hevc", "resolution": [w // 2, h // 2],
                          "cache": False},
            "joint": {"name": "road_left", "codec": "h264", "cache": False},
        }
        svc = VSSService(vss, config=ServiceConfig(window_s=0.05))
        try:
            manifests, errors = {}, {}

            def post(label, body):
                try:
                    req = urllib.request.Request(
                        svc.url + "/v1/read", data=json.dumps(body).encode(),
                        method="POST")
                    with urllib.request.urlopen(req, timeout=600) as r:
                        manifests[label] = json.loads(r.read())
                except Exception as e:  # noqa: BLE001 - reported below
                    errors[label] = e

            threads = [threading.Thread(target=post, args=kv)
                       for kv in reqs.items()]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
                check(not t.is_alive(), "an HTTP read hung")
            check(not errors, f"HTTP reads failed: {errors}")
            blobs = {}
            for label, man in manifests.items():
                blobs[label] = []
                for seg in man["segments"]:
                    with urllib.request.urlopen(svc.url + seg["url"],
                                                timeout=600) as r:
                        data = r.read()
                    check(len(data) == seg["nbytes"],
                          f"{label}: short segment")
                    blobs[label].append(codec.deserialize_gop(data))
        finally:
            svc.close()
        stored = [e for _, e in stored_gops(vss, "cam1")[:2]]
        check([e.payload for e in blobs["pass-through"]]
              == [e.payload for e in stored],
              "pass-through: served bytes differ from the stored GOPs")
        for label, encs in blobs.items():
            got = np.concatenate([codec.decode_gop(e) for e in encs])
            check(np.array_equal(got, np.concatenate(
                [ref_decode(e) for e in encs])),
                  f"{label}: kernel decode of served bytes differs from "
                  f"ref.py")
            print(f"  served {label}: {len(encs)} segments, {got.shape} "
                  f"decoded equal to the reference", flush=True)
        self.check_reencoded(
            "served converted", blobs["converted"],
            self.ref_view_frames(vss, "cam0", conv_t, out_wh=(w // 2, h // 2)),
            "hevc")
        joint_view, _ = self.ref_joint_view(vss, "road_left")
        self.check_reencoded("served joint", blobs["joint"], joint_view,
                             "h264")

    def check_dispatch(self):
        from repro.kernels import ops

        counts = ops.dispatch_counts()
        wrong = {f"{op}/{path}": int(n) for (op, path), n in counts.items()
                 if n and op != "warp" and path != self.expect_path}
        check(not wrong, f"calls off the {self.expect_path} kernels: {wrong}")
        missing = [op for op in PATH_OPS
                   if not counts.get((op, self.expect_path))]
        if not counts.get(("warp", "xla")):
            missing.append("warp")
        check(not missing, f"no {self.expect_path} calls of {missing}")
        print(f"kernel dispatch: {dict((f'{o}/{p}', int(n)) for (o, p), n in sorted(counts.items()))}",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, interpret-mode kernels; "
                         "never reports ok")
    args = ap.parse_args(argv)

    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX's default backend is {backend!r}, not "
              f"'tpu'; nothing was run", file=sys.stderr)
        return 2
    overridden = [k for k in OVERRIDES if k in os.environ]
    if overridden:
        print(f"chip_smoke: {overridden} set; the smoke must run the "
              f"default kernel selection", file=sys.stderr)
        return 2
    if args.cpu_rehearsal:
        os.environ["REPRO_FORCE_PALLAS"] = "1"  # interpret-mode kernels
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.store import VSS
    from repro.utils import init_compile_cache

    cache = init_compile_cache()
    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}; compile cache {cache}", flush=True)
    size = REHEARSAL if args.cpu_rehearsal else FULL
    smoke = Smoke(size, args.seed,
                  "interpret" if args.cpu_rehearsal else "pallas")
    print(f"size: {size}; times below are host wall seconds of a smoke, "
          f"not benchmark results", flush=True)
    jax.monitoring.register_event_duration_secs_listener(smoke.on_event)

    with tempfile.TemporaryDirectory(prefix="vss-chip-smoke-") as tmp:
        vss = VSS(os.path.join(tmp, "store"))
        try:
            with smoke.phase("a-ingest"):
                smoke.ingest(vss)
            with smoke.phase("b-read-batch"):
                smoke.read_batch(vss)
            with smoke.phase("c-calibrate-transcode"):
                smoke.calibrate(vss)
            with smoke.phase("d-joint"):
                smoke.joint(vss)
            with smoke.phase("e-serve"):
                smoke.serve(vss)
        finally:
            vss.close()
    smoke.check_dispatch()
    if args.cpu_rehearsal:
        print("cpu rehearsal passed; it reports no result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
