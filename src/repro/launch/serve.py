"""Batched serving driver: prefill a batch of prompts, then decode.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
        --preset smoke --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_config, reduced_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import inputs as minputs
from repro.models.transformer import init_params
from repro.train import steps as steps_mod


def run(argv=None) -> np.ndarray:
    """Serve as the command line says; returns the generated tokens,
    ``(batch, gen)`` int32 (the prefill's token, then one per decode)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=sorted(ARCHS))
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = (reduced_config(args.arch) if args.preset == "smoke"
           else get_config(args.arch))
    mesh = make_host_mesh(args.model_parallel)
    rules = shd.make_rules(cfg, mesh)
    max_len = args.prompt_len + args.gen

    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    batch = minputs.make_train_batch(rng, cfg, args.batch, args.prompt_len)
    batch.pop("labels")

    prefill = jax.jit(steps_mod.make_prefill_step(cfg, cache_len=max_len))
    decode = jax.jit(steps_mod.make_decode_step(cfg), donate_argnums=2)

    with mesh, shd.use_rules(mesh, rules):
        t0 = time.perf_counter()
        tok, cache = prefill(params, batch)
        tok.block_until_ready()
        t_prefill = time.perf_counter() - t0
        outs = [tok]
        t0 = time.perf_counter()
        for i in range(args.gen - 1):
            tok, cache = decode(params, tok, cache,
                                jnp.asarray(args.prompt_len + i, jnp.int32))
            outs.append(tok)
        tok.block_until_ready()
        t_decode = time.perf_counter() - t0

    gen = np.asarray(jnp.concatenate(outs, axis=1))
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"[serve] arch={args.arch} batch={args.batch} "
          f"prefill={t_prefill*1e3:.1f}ms "
          f"decode={t_decode*1e3:.1f}ms ({tps:.1f} tok/s)", flush=True)
    print(f"[serve] sample tokens: {gen[0][:16]}", flush=True)
    return gen


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
