#!/usr/bin/env python3
"""Runnable demo: train a model with its parallelism routed entirely
through accl-tpu schedules, with checkpoint/resume.

Two model families: the dense dp x sp x tp transformer (default) and the
expert-parallel MoE (--model moe, dp x ep with dispatch/combine through
the framework alltoall). Checkpointing is a TPU-first extension past the
reference (which, as a collectives library, has none — SURVEY.md §5):
parameters save/restore via orbax so an interrupted run resumes exactly.

Usage:
  python examples/train_lm.py --steps 20 --ckpt /tmp/accl_ckpt
  python examples/train_lm.py --steps 20 --ckpt /tmp/accl_ckpt  # resumes
  python examples/train_lm.py --model moe --steps 20
"""

import argparse
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt", default=None, help="checkpoint directory")
    ap.add_argument("--cpu-devices", type=int, default=8)
    ap.add_argument("--model", choices=("dense", "moe"), default="dense")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages for the dense model (layers "
                         "shard over a pp mesh axis, GPipe microbatching)")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize each block in the backward pass "
                         "(jax.checkpoint): O(1) activation memory")
    ap.add_argument("--top-k", type=int, default=1,
                    help="experts per token for --model moe")
    args = ap.parse_args()
    # model-specific flags fail loudly on the wrong path instead of
    # silently measuring the plain step
    if args.model == "moe" and (args.pp > 1 or args.remat):
        raise SystemExit("--pp/--remat apply to --model dense only")
    if args.model == "dense" and args.top_k != 1:
        raise SystemExit("--top-k applies to --model moe only")

    import jax

    from accl_tpu.utils.compile_cache import enable_compile_cache

    # the CPU backend's device count; a TPU host ignores it
    jax.config.update("jax_num_cpu_devices", args.cpu_devices)
    enable_compile_cache()

    import numpy as np

    from accl_tpu.parallel import factorize_devices, make_mesh

    n_dev = len(jax.devices())
    if args.model == "moe":
        from accl_tpu.models import (MoEConfig, init_moe_params,
                                     make_moe_train_step)
        from accl_tpu.models.moe import place_moe_params

        ep = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
        dp = n_dev // ep
        axes = {"dp": dp, "ep": ep}
        mesh = make_mesh(axes)
        cfg = MoEConfig(d_model=64, d_ff=128, n_experts=ep,
                        experts_per_rank=1, vocab=128, seq=32,
                        top_k=args.top_k)
        print(f"mesh {axes}; MoE with {cfg.n_experts} experts, "
              f"top-{cfg.top_k} routing")
        params = init_moe_params(cfg, jax.random.key(0))

        def place(p):
            return place_moe_params(p, cfg, mesh)

        def make_batch():
            rng = np.random.default_rng(0)
            b = 2 * n_dev
            tokens = rng.integers(0, cfg.vocab, (b, cfg.seq)).astype(np.int32)
            return tokens, np.roll(tokens, -1, 1)

        step = make_moe_train_step(cfg, mesh, lr=3e-2)
    else:
        from accl_tpu.models import (TransformerConfig, init_params,
                                     make_train_step)
        from accl_tpu.models.transformer import demo_batch, shard_params

        pp = max(1, args.pp)
        if pp > 1:
            if n_dev % pp:
                raise SystemExit(f"--pp {pp} does not divide {n_dev} devices")
            rest = n_dev // pp
            tp = 2 if rest % 2 == 0 else 1
            axes = {"dp": rest // tp, "sp": 1, "tp": tp, "pp": pp}
        else:
            axes = factorize_devices(n_dev)
        mesh = make_mesh(axes)
        heads = max(4, axes["tp"] * 2)
        # grouped-query shape when it divides cleanly: half the kv heads,
        # still a multiple of tp (kv heads shard over tp too)
        kv = heads // 2 if (heads // 2) % axes["tp"] == 0 else heads
        cfg = TransformerConfig(vocab=128, d_model=heads * 8, n_heads=heads,
                                n_kv_heads=kv, n_layers=max(2, pp),
                                d_ff=heads * 16)
        print(f"mesh {axes}; model d={cfg.d_model} heads={cfg.n_heads} "
              f"kv={cfg.kv_heads} layers={cfg.n_layers}"
              + (" remat" if args.remat else ""))
        params = init_params(cfg, jax.random.key(0))

        def place(p):
            return shard_params(p, cfg, mesh)

        def make_batch():
            # B_local = batch/dp must divide by the pp microbatch count
            batch = max(2, axes["dp"]) * max(pp, 2)
            return demo_batch(cfg, mesh, batch=batch,
                              seq=max(32, axes["sp"] * 16))

        step = make_train_step(cfg, mesh, lr=3e-2, remat=args.remat)

    start_step = 0

    ckptr = None
    if args.ckpt:
        import orbax.checkpoint as ocp

        path = pathlib.Path(args.ckpt).absolute()
        ckptr = ocp.StandardCheckpointer()
        latest = sorted(
            d for d in path.glob("step_*")
            if d.name.split("_")[1].isdigit()  # skip orbax tmp dirs from
        ) if path.exists() else []             # interrupted saves
        if latest:
            start_step = int(latest[-1].name.split("_")[1])
            params = ckptr.restore(latest[-1], params)
            print(f"resumed from {latest[-1]}")

    params = place(params)
    tokens, targets = make_batch()

    for s in range(start_step, start_step + args.steps):
        params, loss = step(params, tokens, targets)
        if s % 5 == 0 or s == start_step + args.steps - 1:
            print(f"step {s:4d}  loss {float(loss):.4f}")

    if ckptr is not None:
        target = pathlib.Path(args.ckpt).absolute() / \
            f"step_{start_step + args.steps:06d}"
        host_params = jax.tree.map(lambda x: np.asarray(x), params)
        if args.model == "dense" and args.pp > 1:
            # checkpoints stay in the mesh-independent per-layer list form,
            # so a run can resume onto a different pp width WHEN the model
            # depth matches (n_layers here is max(2, pp): pp<=2 widths
            # interchange; deeper pipelines need the same --pp to resume)
            from accl_tpu.models.transformer import unstack_layer_params

            host_params = unstack_layer_params(host_params, cfg.n_layers)
        ckptr.save(target, host_params, force=True)
        ckptr.wait_until_finished()
        print(f"saved {target}")


if __name__ == "__main__":
    main()
