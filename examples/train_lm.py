"""Train an assigned-architecture LM (reduced config) on the synthetic
affine-sequence task: loss drops from ~ln(V) toward the structure floor,
with checkpointing + simulated preemption restart along the way.

  PYTHONPATH=src python examples/train_lm.py --arch gemma3-12b --steps 120
"""
import argparse
import tempfile

import numpy as np
import jax

from repro.runtime import use_compile_cache
from repro.checkpoint import CheckpointManager
from repro.distributed import FailureInjector, run_with_restarts
from repro.launch import train as T


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--fail-at", type=int, default=60,
                    help="inject a simulated node failure at this step")
    args = ap.parse_args()

    model, cfg, mesh, rules, p_shard, jitted, data = T.build(
        args.arch, smoke=True, batch=args.batch, seq=args.seq)
    print(f"arch={cfg.name} params={cfg.param_count():,}")

    run0 = T.init_state(model, mesh, rules, p_shard)
    ckdir = tempfile.mkdtemp(prefix="ck_")
    mgr = CheckpointManager(ckdir)
    like = jax.tree.map(np.asarray, {"params": run0.params,
                                     "opt": run0.opt_state})
    mgr.save(0, like)
    injector = FailureInjector(at_steps=(args.fail_at,))
    losses = []

    def restore():
        tree, step = mgr.restore(like)
        if step:
            print(f"[restart] restored checkpoint step {step}")
        return T.TrainRun(tree["params"], tree["opt"], step)

    def train(state):
        out, ls, wd = T.train_loop(state, jitted, data, mesh, rules,
                                   args.steps, ckpt=mgr, ckpt_every=20,
                                   injector=injector, log_every=20)
        losses.extend(ls)
        return out

    final, restarts = run_with_restarts(train, restore)
    print(f"finished at step {final.step} after {restarts} restart(s); "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "training did not reduce the loss"


if __name__ == "__main__":
    main()
