"""The paper's §2 application: monitor an intersection for vehicles.

    PYTHONPATH=src python examples/video_analytics_app.py

Three phases over two overlapping cameras stored in VSS:
  1. *index*  — read low-res frames (cached as views), detect vehicles,
  2. *search* — given an alert color, re-scan the cached low-res views,
  3. *retrieve* — export h264 clips around each match for a phone.
Joint compression deduplicates the overlapping cameras on disk.
"""
import tempfile
import time

import numpy as np

from repro.core.store import VSS
from repro.data.video import CAR_COLORS, synthesize_overlapping_pair


def detect_cars(frames: np.ndarray):
    """Color-histogram detector: (frame, color) hits."""
    hits = []
    for name, rgb in CAR_COLORS.items():
        ref = np.array(rgb, np.float32)
        d = np.abs(frames.astype(np.float32) - ref).sum(-1)  # (T, H, W)
        mask = (d < 40).sum(axis=(1, 2)) > 15
        hits.extend((int(i), name) for i in np.nonzero(mask)[0])
    return sorted(hits)


def main():
    root = tempfile.mkdtemp(prefix="vss_app_")
    vss = VSS(root)
    left, right, _ = synthesize_overlapping_pair(
        150, width=256, height=144, overlap=0.5, seed=4, n_cars=8
    )
    for name, frames in (("cam_a", left), ("cam_b", right)):
        vss.write(name, frames, fps=30.0, codec="h264", gop_frames=15)
    print("ingested 2 cameras:", vss.stats("cam_a"), vss.stats("cam_b"))

    # joint compression of the overlapping pair
    jids = vss.apply_joint_compression(["cam_a", "cam_b"], merge="mean",
                                       tau_db=24.0)
    total = vss.catalog.total_bytes("cam_a") + vss.catalog.total_bytes("cam_b")
    print(f"joint compression: {len(jids)} pairs, {total} bytes on disk")

    # phase 1: index — low-res reads (VSS caches the views)
    t0 = time.perf_counter()
    index = {}
    for cam in ("cam_a", "cam_b"):
        r = vss.read(cam, resolution=(64, 36), codec="rgb",
                     quality_eps_db=18.0)
        index[cam] = detect_cars(r.frames)
    t_index = time.perf_counter() - t0
    print(f"index: {sum(len(v) for v in index.values())} detections "
          f"in {t_index:.2f}s")

    # phase 2: search for the alert color (red) — cached views serve this
    t0 = time.perf_counter()
    matches = {
        cam: [f for f, c in hits if c == "red"]
        for cam, hits in index.items()
    }
    for cam in matches:
        r = vss.read(cam, resolution=(64, 36), codec="rgb",
                     quality_eps_db=18.0)  # hits the cached view
        detect_cars(r.frames)
    t_search = time.perf_counter() - t0
    n_red = sum(len(v) for v in matches.values())
    print(f"search: {n_red} red-vehicle frames in {t_search:.2f}s")

    # phase 3: retrieve clips for the first responder's phone (h264)
    t0 = time.perf_counter()
    clips = 0
    for cam, frames_hit in matches.items():
        for f in frames_hit[:3]:
            s = max(0.0, f / 30.0 - 0.25)
            r = vss.read(cam, t=(s, min(5.0, s + 0.5)), codec="h264",
                         quality_eps_db=24.0)
            clips += 1
    t_retr = time.perf_counter() - t0
    print(f"retrieve: {clips} clips in {t_retr:.2f}s")
    print("final store state:", vss.stats("cam_a"), vss.stats("cam_b"))
    vss.close()
    print("OK")


if __name__ == "__main__":
    from repro.utils import init_compile_cache

    init_compile_cache()
    main()
