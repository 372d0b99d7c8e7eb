"""End-to-end LM training through the framework.

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--preset small]

The full production path at host scale: a token corpus written into VSS,
the deterministic double-buffered TokenPipeline reading through the
store, microbatched AdamW train steps with remat, async multi-
representation checkpoints on VSS, a mid-run injected failure, and a
restart that resumes bit-exactly.

Presets: ``small`` (~5M params, runs in minutes on CPU) and ``100m``
(~100M params — the assigned driver scale; same code path, use real
hardware). The dry-run (repro.launch.dryrun) covers the 3.8B–104B
configs on the production mesh.
"""
import argparse
import dataclasses
import os
import tempfile

import numpy as np

from repro.configs import smoke_config
from repro.core.store import VSS
from repro.data.tokens import TokenPipeline, write_token_corpus
from repro.launch.steps import TrainHyper
from repro.train.checkpoint import CheckpointManager
from repro.train.runner import SimulatedFailure, Trainer, TrainerConfig

PRESETS = {
    "small": dict(num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
                  d_ff=1024, vocab_size=8192, head_dim=32),
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 d_ff=3072, vocab_size=32064, head_dim=64),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--preset", default="small", choices=list(PRESETS))
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash at this step, then auto-restart")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        smoke_config("phi3-mini-3.8b"),
        name=f"phi3-{args.preset}", **PRESETS[args.preset],
    )
    root = tempfile.mkdtemp(prefix="train_lm_")
    print(f"run root: {root}; config: {cfg.name} "
          f"({cfg.num_layers}L d{cfg.d_model})")

    # corpus into VSS — synthetic Zipfian tokens
    vss = VSS(os.path.join(root, "data"))
    rng = np.random.default_rng(0)
    zipf = np.clip(rng.zipf(1.3, 2_000_000), 0, cfg.vocab_size - 1)
    n = write_token_corpus(vss, "corpus", zipf.astype(np.int32))
    print(f"corpus: {n} tokens via VSS")

    hyper = TrainHyper(num_microbatches=2, total_steps=args.steps,
                       warmup_steps=10)
    pipe = TokenPipeline(vss, "corpus", n, batch=args.batch, seq=args.seq)
    ckpt = CheckpointManager(os.path.join(root, "ckpt"), keep_last=3,
                             derived_reprs=("bf16",))
    trainer = Trainer(
        cfg, hyper, pipe, ckpt,
        tcfg=TrainerConfig(checkpoint_every=max(args.steps // 4, 10),
                           fail_at_step=args.fail_at, log_every=10),
    )
    trainer.init_or_resume()
    try:
        res = trainer.train(args.steps)
    except SimulatedFailure as e:
        print(f"!! {e} — restarting from the newest checkpoint")
        trainer.ckpt.wait()
        pipe2 = TokenPipeline(vss, "corpus", n, batch=args.batch,
                              seq=args.seq)
        trainer = Trainer(cfg, hyper, pipe2, ckpt,
                          tcfg=TrainerConfig(
                              checkpoint_every=max(args.steps // 4, 10)))
        assert trainer.resume(), "no checkpoint to resume from"
        print(f"resumed at step {trainer.step}")
        res = trainer.train(args.steps)

    print(f"trained {res['steps']} steps in {res['wall_s']:.1f}s; "
          f"loss {res['log'][0]['loss']:.3f} → {res['final_loss']:.3f}")
    print(f"pipeline: {pipe.stats}")
    print(f"checkpoints: { {s: i.nbytes for s, i in ckpt.stats().items()} }")
    ckpt.close()
    vss.close()
    assert res["final_loss"] < res["log"][0]["loss"], "loss did not improve"
    print("OK")


if __name__ == "__main__":
    from repro.utils import init_compile_cache

    init_compile_cache()
    main()
