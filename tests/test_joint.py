"""Joint compression (§5.1): Algorithm 1, recovery quality, candidates."""
import numpy as np

from repro.core.quality import exact_psnr
from repro.data.video import synthesize_overlapping_pair


def _write_pair(vss, left, right, gop=6):
    vss.write("cam_l", left, fps=30.0, codec="tvc-hi", gop_frames=gop)
    vss.write("cam_r", right, fps=30.0, codec="tvc-hi", gop_frames=gop)


def test_joint_compression_saves_storage_and_recovers(vss, overlap_pair):
    left, right, _ = overlap_pair
    _write_pair(vss, left, right)
    before = (vss.catalog.total_bytes("cam_l")
              + vss.catalog.total_bytes("cam_r"))
    jids = vss.apply_joint_compression(
        ["cam_l", "cam_r"], merge="mean", tau_db=24.0
    )
    assert jids, "no pair was jointly compressed"
    after = (vss.catalog.total_bytes("cam_l")
             + vss.catalog.total_bytes("cam_r"))
    assert after < before
    rl = vss.read("cam_l", codec="rgb", cache=False).frames
    rr = vss.read("cam_r", codec="rgb", cache=False).frames
    assert exact_psnr(rl, left) >= 24.0
    assert exact_psnr(rr, right) >= 24.0


def test_unprojected_merge_keeps_left_lossless(vss, overlap_pair):
    left, right, _ = overlap_pair
    _write_pair(vss, left, right)
    jids = vss.apply_joint_compression(
        ["cam_l", "cam_r"], merge="unprojected", tau_db=24.0
    )
    assert jids
    rl = vss.read("cam_l", codec="rgb", cache=False).frames
    rr = vss.read("cam_r", codec="rgb", cache=False).frames
    # Table 2: unprojected merge favors the left view
    assert exact_psnr(rl, left) >= exact_psnr(rr, right) - 1.0
    assert exact_psnr(rl, left) >= 30.0


def test_duplicate_frames_become_pointer(vss, clip):
    """§5.1.1: ‖H−I‖ ≤ ε → the redundant GOP is a pointer, not re-encoded."""
    vss.write("cam_a", clip[:12], fps=30.0, codec="tvc-hi", gop_frames=6)
    vss.write("cam_b", clip[:12].copy(), fps=30.0, codec="tvc-hi",
              gop_frames=6)
    jids = vss.apply_joint_compression(["cam_a", "cam_b"], merge="mean")
    assert jids
    rec = vss.catalog.get_joint(jids[0])
    assert rec["duplicate"]
    rb = vss.read("cam_b", codec="rgb", cache=False).frames
    assert exact_psnr(rb, clip[:12]) >= 40.0


def test_disjoint_videos_not_joined(vss):
    a = synthesize_overlapping_pair(6, width=96, height=64, seed=3)[0]
    b = synthesize_overlapping_pair(6, width=96, height=64, seed=99)[0]
    vss.write("cam_a", a, fps=30.0, codec="tvc-hi", gop_frames=6)
    vss.write("cam_b", b, fps=30.0, codec="tvc-hi", gop_frames=6)
    jids = vss.apply_joint_compression(["cam_a", "cam_b"], merge="mean",
                                       tau_db=24.0)
    # different worlds: either no candidates, or quality-verified abort
    for j in jids:
        rec = vss.catalog.get_joint(j)
        assert rec is not None  # any accepted pair must have verified ≥ τ
    ra = vss.read("cam_a", codec="rgb", cache=False).frames
    assert exact_psnr(ra, a) >= 24.0


def test_homography_estimation_accuracy(overlap_pair):
    from repro.core import features

    left, right, h_true = overlap_pair
    h_est = features.estimate_homography(left[0], right[0])
    assert h_est is not None
    # compare action on sample points rather than matrix entries
    pts = np.array([[10, 10, 1], [80, 40, 1], [30, 70, 1]], np.float32).T
    p_true = h_true @ pts
    p_est = h_est @ pts
    p_true /= p_true[2]
    p_est /= p_est[2]
    assert np.abs(p_true - p_est).max() < 3.0  # within 3 px


def test_joint_video_reads_in_its_stored_codec(vss, overlap_pair):
    """A read in the stored codec re-encodes jointly-compressed GOPs:
    their original objects are gone, so there are no bytes to pass
    through verbatim."""
    left, right, _ = overlap_pair
    _write_pair(vss, left, right)
    assert vss.apply_joint_compression(
        ["cam_l", "cam_r"], merge="unprojected", tau_db=24.0
    )
    for name, src in (("cam_l", left), ("cam_r", right)):
        out = vss.read(name, codec="tvc-hi", cache=False)
        assert out.encoded and all(e.codec == "tvc-hi" for e in out.encoded)
        rgb = vss.read(name, codec="rgb", cache=False).frames
        assert out.frames.shape == rgb.shape == src.shape
        assert exact_psnr(out.frames, rgb) >= 45.0
