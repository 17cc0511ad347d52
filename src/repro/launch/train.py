"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --preset smoke --steps 100 --ckpt-dir /tmp/ckpt

``--preset smoke`` trains the family-preserving reduced config (CPU-sized);
``--preset full`` uses the assigned architecture verbatim (TPU-sized).  The
loop runs under the fault-tolerance manager: auto-resume, async atomic
checkpoints, straggler monitoring; ``--fail-at N`` injects a failure at
step N to demonstrate recovery.

State and batches are placed on a (data, model) mesh over every device of
the host: parameters split on the model axis, optimizer moments also on
the data axis (ZeRO-1), batches on the data axis
(:func:`repro.launch.cells.state_shardings`).  The step is compiled once.
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.configs.registry import ARCHS, get_config, reduced_config
from repro.data.pipeline import DataConfig, ShardedTokenPipeline
from repro.dist import sharding as shd
from repro.ft.manager import FaultTolerantRunner, RunReport
from repro.launch.cells import state_shardings
from repro.launch.mesh import make_host_mesh
from repro.train import steps as steps_mod


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "block", "full"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "int8_ef"])
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def run(argv=None, *,
        on_step: Optional[Callable[[int, Dict[str, float]], None]] = None
        ) -> RunReport:
    """Train as the command line says and return the runner's report.

    ``on_step(step, metrics)`` is called after every completed step, with
    the state of that step still on the devices."""
    args = parse_args(argv)
    cfg = (reduced_config(args.arch) if args.preset == "smoke"
           else get_config(args.arch))
    tc = TrainConfig(total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
                     microbatches=args.microbatches, remat=args.remat,
                     grad_compression=args.grad_compression)
    mesh = make_host_mesh(args.model_parallel)
    print(f"[train] arch={args.arch} preset={args.preset} "
          f"params={cfg.param_count()/1e6:.1f}M mesh={dict(mesh.shape)}",
          flush=True)

    data = ShardedTokenPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch))

    def host_batch(step: int):
        b = data.batch_at(step)
        out = {"tokens": b["tokens"], "labels": b["labels"]}
        if cfg.family == "encdec":
            Se = max(1, args.seq_len // cfg.enc_len_ratio)
            out["enc_embeds"] = jnp.zeros((args.batch, Se, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm" and cfg.n_prefix_embeds_ratio:
            St = args.seq_len - args.seq_len // cfg.n_prefix_embeds_ratio
            out["tokens"] = out["tokens"][:, :St]
            out["prefix_embeds"] = jnp.zeros(
                (args.batch, args.seq_len - St, cfg.d_model), jnp.bfloat16)
        return out

    rules = shd.make_rules(cfg, mesh)
    rng = jax.random.PRNGKey(0)

    def init(r):
        return steps_mod.init_train_state(r, cfg)

    state_sh = state_shardings(cfg, mesh, jax.eval_shape(init, rng),
                               zero1=tc.zero1)
    batch_sh = shd.batch_input_shardings(mesh, host_batch(0), rules)
    step_fn = jax.jit(steps_mod.make_train_step(cfg, tc),
                      in_shardings=(state_sh, batch_sh),
                      out_shardings=(state_sh, NamedSharding(mesh, P())),
                      donate_argnums=0)
    with mesh, shd.use_rules(mesh, rules):
        state = jax.jit(init, out_shardings=state_sh)(rng)

    def run_step(state, batch):
        with mesh, shd.use_rules(mesh, rules):
            return step_fn(state, batch)

    def batch_at(step: int):
        return jax.device_put(host_batch(step), batch_sh)

    runner = FaultTolerantRunner(args.ckpt_dir, save_every=args.save_every)
    t0 = time.perf_counter()
    _, report = runner.run(state, args.steps, run_step, batch_at,
                           log_every=args.log_every, fail_at=args.fail_at,
                           on_step=on_step)
    dt = time.perf_counter() - t0
    print(f"[train] done in {dt:.1f}s: steps={report.steps_run} "
          f"resumed_from={report.resumed_from} "
          f"recoveries={report.failures_recovered} "
          f"final={report.final_metrics} straggler={report.straggler}",
          flush=True)
    return report


def main(argv=None) -> int:
    """Exit status 0 unless the runner recovered from a failure that was
    not injected with ``--fail-at``."""
    injected = int(parse_args(argv).fail_at is not None)
    return 0 if run(argv).failures_recovered <= injected else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
