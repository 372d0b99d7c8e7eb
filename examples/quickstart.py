"""VSS quickstart — the declarative spec API end-to-end.

    PYTHONPATH=src python examples/quickstart.py

Writes a synthetic traffic video, reads it back through `ReadSpec`s
with different spatial/temporal/physical parameters, issues a batch of
overlapping requests through the joint planner (`read_batch`), shows
the cache evolving, and jointly compresses two overlapping cameras.
The classic keyword form (``vss.read(name, t=..., codec=...)``) still
works — it builds the same spec under the hood (see docs/api.md).
"""
import tempfile
import time

from repro.core.spec import ReadSpec, WriteSpec
from repro.core.store import VSS
from repro.core.quality import exact_psnr
from repro.data.video import synthesize_overlapping_pair, synthesize_road


def main():
    root = tempfile.mkdtemp(prefix="vss_quickstart_")
    vss = VSS(root)
    print(f"VSS root: {root}")

    # -- write (T=4s @30fps, S=192x108, P=h264) -----------------------------
    clip = synthesize_road(120, width=192, height=108, seed=0)
    vss.write_spec(WriteSpec(name="traffic", fps=30.0, codec="h264"), clip)
    print(f"wrote traffic: {vss.stats('traffic')}")

    # -- declarative reads: say WHAT view you want --------------------------
    r = vss.read_spec(ReadSpec(name="traffic", t=(1.0, 3.0), codec="rgb"))
    print(f"read rgb [1,3): {r.frames.shape}")
    r = vss.read_spec(ReadSpec(name="traffic", resolution=(96, 54)))
    print(f"read 96x54 thumbnail: {r.frames.shape}")
    r = vss.read_spec(
        ReadSpec(name="traffic", roi=(48, 27, 144, 81), codec="hevc")
    )
    print(f"read ROI as hevc: {len(r.encoded)} GOPs, {r.nbytes} bytes")
    print(f"cache now: {vss.stats('traffic')}")

    # -- batched reads: N overlapping requests, ONE joint plan --------------
    # (a VDBMS fanning analysis windows over the same camera; the joint
    # planner shares fragments, dedupes GOP fetches into a single
    # batch_get, and decodes each GOP once)
    specs = [
        ReadSpec(name="traffic", t=(0.5 * i, 0.5 * i + 1.5), cache=False)
        for i in range(5)
    ]
    t0 = time.perf_counter()
    for s in specs:
        vss.read_spec(s).frames
    t_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = vss.read_batch(specs)
    for r in results:
        r.frames
    t_batch = time.perf_counter() - t0
    shared = results[0].plan.problem.demands
    print(f"read_batch: {len(specs)} overlapping reads "
          f"{t_seq:.3f}s sequential -> {t_batch:.3f}s batched "
          f"({t_seq / max(t_batch, 1e-9):.1f}x), "
          f"max segment demand {max(shared) if shared else 1}")

    # -- multi-stream ingest: N cameras through the shared pipeline ---------
    # (each writer encodes on the ingest thread while the store's
    # bounded publish queue + worker pool issue the batched puts and
    # windowed catalog commits; close() is a durability barrier)
    cams = [f"ingest_cam{i}" for i in range(3)]
    writers = [
        vss.writer_spec(
            WriteSpec(name=name, fps=30.0, codec="hevc", gop_frames=15),
            batch_gops=2,
        )
        for name in cams
    ]
    t0 = time.perf_counter()
    for off in range(0, clip.shape[0], 30):
        for w in writers:
            w.append(clip[off: off + 30])  # round-robin live chunks
    for w in writers:
        w.close()  # everything durable AND indexed from here on
    dt = time.perf_counter() - t0
    st = vss.ingest.stats()
    print(f"multi-stream ingest: {len(cams)} cameras, "
          f"{len(cams) * clip.shape[0] / dt:.0f} frames/s, "
          f"{st.windows_published} publish windows, "
          f"queue high-water {st.max_queued_gops} GOPs")

    # -- second read of the same region: served from cached views -----------
    t0 = time.perf_counter()
    vss.read_spec(ReadSpec(name="traffic", t=(1.0, 3.0), cache=False))
    print(f"cached re-read took {time.perf_counter()-t0:.3f}s "
          f"(plan: pass-through / cached fragments)")

    # -- joint compression of two overlapping cameras ------------------------
    left, right, _ = synthesize_overlapping_pair(
        12, width=192, height=108, overlap=0.6, seed=1
    )
    vss.write_spec(
        WriteSpec(name="cam_left", fps=30.0, codec="hevc", gop_frames=6),
        left,
    )
    vss.write_spec(
        WriteSpec(name="cam_right", fps=30.0, codec="hevc", gop_frames=6),
        right,
    )
    before = (vss.catalog.total_bytes("cam_left")
              + vss.catalog.total_bytes("cam_right"))
    jids = vss.apply_joint_compression(["cam_left", "cam_right"],
                                       merge="mean", tau_db=24.0)
    after = (vss.catalog.total_bytes("cam_left")
             + vss.catalog.total_bytes("cam_right"))
    print(f"joint compression: {len(jids)} GOP pairs, "
          f"{before} → {after} bytes ({100*(1-after/max(before,1)):.1f}% saved)")
    rl = vss.read_spec(ReadSpec(name="cam_left", cache=False)).frames
    rr = vss.read_spec(ReadSpec(name="cam_right", cache=False)).frames
    print(f"recovered quality: left {exact_psnr(rl, left):.1f} dB, "
          f"right {exact_psnr(rr, right):.1f} dB")
    vss.close()
    print("OK")


if __name__ == "__main__":
    from repro.utils import init_compile_cache

    init_compile_cache()
    main()
