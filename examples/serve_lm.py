"""Serve a small model with batched requests: prefill the prompt batch, then
batched single-token decode steps against the KV caches. Exercises every
cache kind via --arch (full KV, sliding-window ring, recurrent state).

  PYTHONPATH=src python examples/serve_lm.py --arch recurrentgemma-9b
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.runtime import use_compile_cache
from repro.configs import registry
from repro.launch.serve import generate
from repro.models import Model


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()

    cfg = registry.smoke(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # batched "requests": affine progressions the model could learn; here we
    # serve from random weights, so we check throughput + shape/finite only
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, jnp.int32)
    t0 = time.perf_counter()
    toks = generate(model, params, prompts, args.max_new,
                    max_len=args.prompt_len + args.max_new)
    dt = time.perf_counter() - t0
    toks = np.asarray(toks)
    assert toks.shape == (args.batch, args.max_new)
    n = toks.size
    print(f"arch={cfg.name}: {n} tokens in {dt:.1f}s "
          f"({n/dt:.1f} tok/s incl. compile on CPU)")
    for b in range(args.batch):
        print(f"  req{b}: {toks[b][:12].tolist()} ...")


if __name__ == "__main__":
    main()
