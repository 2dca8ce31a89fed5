#!/usr/bin/env python3
"""Runnable demo: incremental (KV-cache) generation on a dp x tp mesh —
the inference half of the model family. Every tensor-parallel partial
sum in the decode step reduces through the framework's own ring
schedule, exactly as in training; the compiled step is position-generic
(static shapes), so one program serves the whole generation.

Usage:
  python examples/generate.py --steps 16            # greedy
  python examples/generate.py --steps 16 --temp 0.8 # sampled
"""

import argparse
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=16,
                    help="tokens to generate after the prompt")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--temp", type=float, default=0.0,
                    help="0 = greedy, else softmax temperature")
    ap.add_argument("--cpu-devices", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from accl_tpu.utils.compile_cache import enable_compile_cache

    # the CPU backend's device count; a TPU host ignores it
    jax.config.update("jax_num_cpu_devices", args.cpu_devices)
    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from accl_tpu.models import (
        TransformerConfig,
        init_kv_cache,
        init_params,
        make_decode_step,
    )
    from accl_tpu.models.transformer import shard_params
    from accl_tpu.parallel import make_mesh

    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    mesh = make_mesh({"dp": n // tp, "sp": 1, "tp": tp},
                     devices=jax.devices())
    cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128)
    params = shard_params(init_params(cfg, jax.random.key(args.seed)),
                          cfg, mesh)

    dp = dict(mesh.shape)["dp"]
    B = -(-max(args.batch, 1) // dp) * dp  # round up to a dp multiple
    total = args.prompt_len + args.steps
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg.vocab, (B, args.prompt_len)) \
        .astype(np.int32)

    step = make_decode_step(cfg, mesh)
    cache = init_kv_cache(cfg, mesh, B, max_len=total)
    key = jax.random.key(args.seed + 1)

    toks = prompt
    logits = None
    # prefill token-by-token: the SAME compiled step serves prefill and
    # generation (a fused prefill would be one make_forward call; decode
    # from scratch keeps the demo single-program)
    for t in range(total - 1):
        cur = toks[:, t:t + 1]
        logits, cache = step(params, cache, cur,
                             jnp.array([t], jnp.int32))
        if t >= args.prompt_len - 1:
            if args.temp > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits[:, 0] / args.temp)
                nxt = np.asarray(nxt, np.int32)[:, None]
            else:
                nxt = np.asarray(jnp.argmax(logits[:, 0], -1),
                                 np.int32)[:, None]
            toks = np.concatenate([toks, nxt], axis=1)

    print(f"mesh={dict(mesh.shape)} prompt_len={args.prompt_len} "
          f"generated={toks.shape[1] - args.prompt_len}")
    for b in range(min(B, 2)):
        print(f"  seq[{b}]: {toks[b].tolist()}")


if __name__ == "__main__":
    main()
