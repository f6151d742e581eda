"""End-to-end LM training driver (deliverable b): trains a ~100M-param dense
model for a few hundred steps on the planted-structure pipeline with the
full production stack — sharding rules, AdamW, checkpointing, and the
fault-tolerant loop.

    PYTHONPATH=src python examples/train_lm.py --steps 300

On this CPU container it uses a single-device mesh; the identical step
function lowers onto the 16x16 / 2x16x16 production meshes (see
``repro.launch.dryrun``).
"""
import argparse
import dataclasses

import jax

from repro import enable_compile_cache
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import TokenPipeline
from repro.launch.mesh import mesh_for_devices
from repro.optim import AdamWConfig
from repro.runtime import FaultTolerantLoop, StragglerMonitor
from repro.sharding import make_rules
from repro.train import build_train_step, init_train_state
from repro.models import param_count


def hundred_m_config():
    """~100M params: a scaled-down olmo-family config."""
    base = get_config("olmo_1b")
    return dataclasses.replace(
        base, name="olmo_100m", n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=8, head_dim=64, d_ff=2048, vocab_size=50304)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    cfg = hundred_m_config()
    rules = make_rules(mesh_for_devices())
    opt = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps,
                      weight_decay=0.01)
    state = init_train_state(cfg, jax.random.PRNGKey(0), opt_cfg=opt)
    print(f"model: {cfg.name}, {param_count(state.params) / 1e6:.1f}M params")

    step_fn = jax.jit(build_train_step(cfg, rules, opt))
    pipe = TokenPipeline(cfg, args.batch, args.seq, seed=0)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    loop = FaultTolerantLoop(
        step_fn,
        lambda s: {k: jax.numpy.asarray(v) for k, v in pipe.batch_at(s).items()},
        ckpt, ckpt_every=100,
        straggler=StragglerMonitor(),
        install_sigterm=True,
    )

    # auto-resume from the latest checkpoint (restart-safe driver)
    restored = ckpt.restore(state)
    start = 0
    if restored is not None:
        state, start = restored
        print(f"resumed from checkpoint at step {start}")

    state, end, hist = loop.run(state, start, args.steps - start,
                                log_every=25)
    first = sum(h["loss"] for h in hist[:10]) / max(len(hist[:10]), 1)
    last = sum(h["loss"] for h in hist[-10:]) / max(len(hist[-10:]), 1)
    print(f"loss {first:.3f} -> {last:.3f} over {len(hist)} steps "
          f"({loop.straggler.stragglers} straggler steps)")
    ckpt.save(end, state)


if __name__ == "__main__":
    main()
