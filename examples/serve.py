"""VSS-as-a-service walkthrough — the HTTP serving tier end to end.

    PYTHONPATH=src python examples/serve.py

Starts a `VSSService` over a fresh store, then plays a typical
video-analytics front end against it:

1. eight concurrent clients POST overlapping declarative reads and the
   intake-window coalescer executes them as a couple of joint plans
   (watch `batches` stay far below the request count);
2. each response is a manifest of HMAC-signed segment URLs — the
   example fetches the bytes, decodes them, and checks them against an
   in-process read;
3. a low-rate tenant gets shed with 503 + Retry-After once its token
   bucket drains, and a request whose `deadline_ms` is already spent
   is refused instead of queued;
4. the stored-layout manifest and `/metrics` close the loop.

Everything here is stdlib HTTP — any language with an HTTP client can
be a VSS client.
"""
import json
import tempfile
import threading
import urllib.error
import urllib.request

import numpy as np

from repro import codec
from repro.core.config import VSSConfig
from repro.core.store import VSS
from repro.data.video import synthesize_road
from repro.obs import MetricsRegistry
from repro.serving import AdmissionController, VSSService
from repro.serving.config import ServiceConfig


def post_read(base, body, tenant="demo"):
    req = urllib.request.Request(
        base + "/v1/read", data=json.dumps(body).encode(),
        headers={"X-VSS-Tenant": tenant}, method="POST",
    )
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def fetch_frames(base, manifest):
    """Walk the signed segment URLs and decode the GOPs they serve."""
    gops = []
    for seg in manifest["segments"]:
        with urllib.request.urlopen(base + seg["url"]) as r:
            gops.append(codec.deserialize_gop(r.read()))
    return np.concatenate([codec.decode_gop(g) for g in gops], axis=0)


def main():
    root = tempfile.mkdtemp(prefix="vss_serve_")
    reg = MetricsRegistry(enabled=True)
    vss = VSS(root, config=VSSConfig(registry=reg))
    clip = synthesize_road(120, width=192, height=108, seed=0)
    vss.write("traffic", clip, fps=30.0, codec="tvc-med", gop_frames=15)

    service = VSSService(vss, config=ServiceConfig(window_s=0.02),
                         registry=reg)
    base = service.url
    print(f"serving {root} at {base}")

    # -- 1+2: concurrent overlapping reads, coalesced into joint plans ----
    views = [
        {"t": [0.0, 2.0], "codec": "tvc-lo"},
        {"t": [0.0, 2.0], "codec": "tvc-lo"},      # exact duplicate
        {"t": [1.0, 3.0], "codec": "tvc-lo"},
        {"t": [0.0, 2.0], "codec": "tvc-hi"},
    ]
    results = [None] * 8
    barrier = threading.Barrier(len(results))

    def client(i):
        body = dict(views[i % len(views)], name="traffic", cache=False)
        barrier.wait()
        results[i] = post_read(base, body)

    threads = [
        threading.Thread(target=client, args=(i,))
        for i in range(len(results))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(status == 200 for status, _, _ in results)
    batches = reg.value("vss_serve_batches_total")
    print(f"coalescing: {len(results)} concurrent requests ran as "
          f"{batches:.0f} joint read_batch plan(s)")

    frames = fetch_frames(base, results[0][1])
    ref = vss.read("traffic", t=(0.0, 2.0), codec="tvc-lo",
                   cache=False).frames
    assert np.array_equal(frames, ref)
    print(f"signed segments: {len(results[0][1]['segments'])} GOPs "
          f"fetched over HTTP, bit-exact vs in-process read "
          f"{frames.shape}")

    # -- 3: QoS — tenant rate shed and deadline shed ----------------------
    strict = VSSService(
        vss, config=ServiceConfig(window_s=0.02),
        registry=MetricsRegistry(enabled=True),
        admission=AdmissionController(tenant_rate=1.0, tenant_burst=2),
    )
    try:
        body = {"name": "traffic", "t": [0.0, 1.0], "codec": "tvc-med",
                "cache": False}
        codes = [post_read(strict.url, body, tenant="greedy")[0]
                 for _ in range(4)]
        shed = next(h for s, _, h in
                    [post_read(strict.url, body, tenant="greedy")]
                    if s == 503)
        print(f"tenant rate limit: statuses {codes} -> shed with "
              f"X-VSS-Shed-Reason={shed['X-VSS-Shed-Reason']!r}, "
              f"Retry-After={shed['Retry-After']}s")
        status, _, headers = post_read(
            strict.url, dict(body, deadline_ms=0), tenant="patient"
        )
        print(f"expired deadline: {status} "
              f"(reason {headers['X-VSS-Shed-Reason']!r}) — refused "
              f"up front, not queued into uselessness")
    finally:
        strict.close()

    # -- 4: stored layout + metrics ---------------------------------------
    with urllib.request.urlopen(base + "/v1/manifest/traffic") as r:
        layout = json.loads(r.read())
    ngops = sum(len(p["gops"]) for p in layout["physicals"])
    print(f"stored manifest: {len(layout['physicals'])} physical(s), "
          f"{ngops} signed GOP URLs")
    with urllib.request.urlopen(base + "/metrics") as r:
        families = sum(1 for line in r.read().decode().splitlines()
                       if line.startswith("# TYPE vss_serve_"))
    print(f"/metrics exposes {families} serving families")

    service.close()
    vss.close()
    print("OK")


if __name__ == "__main__":
    from repro.utils import init_compile_cache

    init_compile_cache()
    main()
