"""Batched LM serving with paged KV on VSS-style pages.

    PYTHONPATH=src python examples/serve_lm.py

Continuous batching over a paged KV pool: requests sharing a prompt
prefix dedup their pages (the §5.1 joint-compression analogue); the
decode step runs the paged-attention kernel for the whole batch at once.
"""
import time

import jax
import numpy as np

from repro.configs import smoke_config
from repro.models import model as M
from repro.serving.engine import ServingEngine


def main():
    cfg = smoke_config("phi3-mini-3.8b")
    params = M.init_model(jax.random.key(0), cfg)
    eng = ServingEngine(cfg, params, page_size=16, num_pages=256,
                        max_batch=8)

    system_prompt = list(range(100, 164))  # 64 shared tokens (4 pages)
    rng = np.random.default_rng(0)
    rids = []
    for i in range(12):
        user = list(rng.integers(0, cfg.vocab_size, 16))
        rids.append(eng.submit(system_prompt + user, max_new=12))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done.values())
    print(f"served {len(done)} requests, {toks} tokens in {wall:.2f}s "
          f"({toks/wall:.1f} tok/s on CPU)")
    print(f"metrics: {eng.metrics}")
    dd = [r.dedup_pages for r in done.values()]
    print(f"dedup pages per request: {dd}")
    print(f"pages in use: {eng.pool.pages_in_use}/{eng.pool.cfg.num_pages}")
    assert sum(dd) > 0, "prefix dedup never hit"
    print("OK")


if __name__ == "__main__":
    from repro.utils import init_compile_cache

    init_compile_cache()
    main()
