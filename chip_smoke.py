#!/usr/bin/env python3
"""Bring-up run of the system's main paths on one TPU chip, in one process.

    python chip_smoke.py              # one chip: phases 0-5
    python chip_smoke.py --chips 4    # four chips: the sharded train path only

Phases (each prints its wall time and its compile time on its own line):

  0. device      — a TPU must be the default device; anything else exits
                   non-zero here, before any other work.
  1. cim sweep   — the fig14 grid (9 Table-IV workloads x 3 cache
                   geometries) through ``DSEEngine(executor="thread")``
                   under the jax analysis backend, equal field for field
                   to the same grid under numpy; compiled Pallas placement,
                   no jax -> numpy fallback.
  2. large trace — the first 2^20 accesses of the ``KM@256`` stream
                   replayed for the three fig14 geometries in one device
                   launch, equal to ``CacheHierarchy.replay`` column for
                   column.
  3. daemon      — three overlapping ``/v1/sweep`` requests to an in-process
                   DSE daemon, equal to phase 1; a repeat compiles nothing.
  4. tpu mode    — the ``fig_tpu_dse`` sweep through ``TpuBackend``, and
                   whether each arch's HLO numbers lowered for the TPU equal
                   the ones lowered for the CPU.
  5. model       — ``repro.launch.train`` and ``repro.launch.serve`` at the
                   full ``qwen1.5-0.5b`` widths: step-0 loss against the same
                   forward pass on the CPU, no runner recovery, no
                   compilation after the first step, every token decoded.

``--chips 4`` runs the train entry with ``--model-parallel 4`` and on a
4x1 data mesh, each step-0 loss against the loss of the same model on one
chip, and prints each chip's ``bytes_in_use`` while the state is live.

The last line of standard output is one JSON object, printed only when
every check passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import threading
import time

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
LARGE = "KM@256"                 # 7.2M instructions, 3.1M memory accesses
# Phase 2 replays the first 2^20 accesses of LARGE.  The replay scan takes
# one serial device step per access (about 160 us on a v5e), so the whole
# stream (2^22 padded steps, about 11 minutes) would leave too little of
# the run's time budget; a 2^20 prefix keeps it at a few minutes and still
# covers more than 10^6 accesses.
PREFIX = 1 << 20
TRAIN = ["--arch", ARCH, "--preset", "full", "--steps", "3", "--batch", "8",
         "--seq-len", "512", "--save-every", "0", "--log-every", "1"]
SERVE = ["--arch", ARCH, "--preset", "full", "--batch", "4",
         "--prompt-len", "128", "--gen", "32"]
# |TPU - reference| <= LOSS_RTOL * |reference| for the step-0 loss.  The
# model computes in bfloat16; the TPU accumulates its bf16 matmuls in a
# different order than the CPU and rounds f32 matmul inputs to bf16 at
# its default precision.  Each of those perturbs a logit by about 2^-8 of
# its size; at init the loss is about ln(vocab) ~ 12 and such errors
# average out over 4096 tokens, so 1% leaves an order of magnitude of
# room while a wrong mask, a dropped layer or a bad shard moves it more.
LOSS_RTOL = 1e-2


class Failed(Exception):
    """A check of this run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


class CompileWatch:
    """Counts compilations (backend compiles and persistent-cache loads)
    and sums their trace + lower + compile seconds, process-wide."""
    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._EVENTS:
            with self._lock:
                self.seconds += secs
                self.count += event == self._EVENTS[-1]

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.count += 1


@contextlib.contextmanager
def phase(name: str, watch: CompileWatch):
    t0, c0, s0 = time.perf_counter(), watch.count, watch.seconds
    print(f"[{name}] start", flush=True)
    yield
    print(f"[{name}] wall {time.perf_counter() - t0:.3f} s, compile "
          f"{watch.seconds - s0:.3f} s ({watch.count - c0} compilations)",
          flush=True)


def device_or_exit(chips: int):
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"[phase 0] the default jax device is {d.platform!r}, not a "
              f"TPU: this run measures nothing off the chip", flush=True)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"[phase 0] {chips} chips asked for, {len(devices)} present",
              flush=True)
        raise SystemExit(2)
    print(f"[phase 0] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    return devices


# ---------------------------------------------------------------- phase 1
def _rows(records):
    return [repr(dataclasses.astuple(r)) for r in records]


def cim_sweep():
    from benchmarks.common import SWEEP_BENCHES
    from benchmarks.fig14_cache_cfg import CFG_NAMES
    from repro.core import accel
    from repro.core.accel import pallas_ops, place
    from repro.dse import DSEEngine, SweepSpace

    space = SweepSpace(workloads=SWEEP_BENCHES, caches=CFG_NAMES)
    with accel.use_backend("numpy"):
        ref = DSEEngine(executor="thread").run(space)
    compiles = accel.jit_compiles()
    with accel.use_backend("jax"):
        check(place._use_pallas() and not pallas_ops._interpret(),
              "placement must use compiled Pallas kernels on the TPU")
        got = DSEEngine(executor="thread").run(space)
    print(f"[phase 1] {len(got)} points: numpy {ref.elapsed_s:.3f} s, "
          f"jax {got.elapsed_s:.3f} s; jit specializations "
          f"+{accel.jit_compiles() - compiles}, replay batches "
          f"{got.stats.get('replay_batches')}, fallbacks "
          f"{accel.fallbacks()}", flush=True)
    check(len(got) == len(SWEEP_BENCHES) * len(CFG_NAMES) == 27,
          "the fig14 grid has 27 points")
    check(_rows(got.records) == _rows(ref.records),
          "jax sweep records differ from numpy")
    check(accel.jit_compiles() > compiles, "the jax path compiled nothing")
    check(accel.fallbacks() == 0, "a jax -> numpy fallback happened")
    return ref


# ---------------------------------------------------------------- phase 2
def large_trace():
    from benchmarks.fig14_cache_cfg import CFG_NAMES
    from repro.core.accel import replay
    from repro.core.cache import CacheHierarchy
    from repro.core.isa import OP_STORE
    from repro.core.sampling.pipeline import build_workload
    from repro.core.trace import TraceLimits, trace_structural
    from repro.dse.space import CacheOption

    t0 = time.perf_counter()
    fn, args = build_workload(LARGE)
    st = trace_structural(fn, *args, limits=TraceLimits(1 << 62))
    ct = st.columns
    mem = np.flatnonzero(ct.mem_mask)
    print(f"[phase 2] {LARGE}: {st.n_instructions} instructions, "
          f"{len(mem)} accesses, traced in "
          f"{time.perf_counter() - t0:.3f} s; replaying the first {PREFIX}",
          flush=True)
    check(len(mem) >= PREFIX, f"{LARGE} has too few accesses")
    mem = mem[:PREFIX]
    addrs, writes = ct.addr[mem], ct.op[mem] == OP_STORE
    geos = [CacheOption.of(n).levels for n in CFG_NAMES]

    for idxs, kernel, kargs in replay._launches(addrs, writes, geos):
        mem_an = kernel.lower(*kargs).compile().memory_analysis()
        print(f"[phase 2] replay batch of {len(idxs)} geometries over "
              f"{len(kargs[4])} padded steps: {mem_an}", flush=True)
    t0 = time.perf_counter()
    got = replay.replay_columns_batch(addrs, writes, geos)
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    for gi, levels in enumerate(geos):
        hier = CacheHierarchy(levels)
        ref = hier.replay(addrs, writes)
        lvl, hit, bank, mshr, counters = got[gi]
        for name, a, b in zip(("level", "hit", "bank", "mshr"), ref,
                              (lvl, hit, bank, mshr)):
            check(a.dtype == b.dtype and np.array_equal(a, b),
                  f"{LARGE} {CFG_NAMES[gi]} column {name} differs")
        check(counters == hier.counters(),
              f"{LARGE} {CFG_NAMES[gi]} counters differ")
    print(f"[phase 2] device replay {t_dev:.3f} s (first call, compile "
          f"included); numpy oracle {time.perf_counter() - t0:.3f} s for "
          f"{len(geos)} geometries; columns and counters equal", flush=True)


# ---------------------------------------------------------------- phase 3
def _point_key(doc):
    return (doc["workload"], doc["cache"], doc["cim_levels"], doc["tech"],
            doc["cim_set"], doc["host"])


def daemon(ref):
    from benchmarks.common import SWEEP_BENCHES
    from benchmarks.fig14_cache_cfg import CFG_NAMES
    from repro.core import accel
    from repro.dse.service import ServiceClient, running_server
    from repro.dse.service.codec import records_json

    def strip(doc):
        return {k: v for k, v in doc.items() if k not in ("index", "round")}

    want = {_point_key(d): strip(d) for d in records_json(ref.records)}
    requests = [SWEEP_BENCHES[:6], SWEEP_BENCHES[3:], SWEEP_BENCHES]
    with accel.use_backend("jax"), \
            running_server(max_workers=4) as (url, _service):
        client = ServiceClient(url)
        replies = [None] * len(requests)

        def ask(i):
            replies[i] = client.sweep(requests[i], caches=list(CFG_NAMES))
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
            check(not t.is_alive(), "a daemon request did not finish")
        m1 = client.metrics()
        again = client.sweep(SWEEP_BENCHES, caches=list(CFG_NAMES))
        m2 = client.metrics()
    for wl, reply in zip(requests, replies + [again]):
        check(reply is not None and len(reply.records)
              == len(wl) * len(CFG_NAMES), "a daemon reply is incomplete")
    for reply in replies + [again]:
        for doc in reply.records:
            check(strip(doc) == want[_point_key(doc)],
                  f"daemon record differs from phase 1: {_point_key(doc)}")
    pts = m2["service"]["points"]
    print(f"[phase 3] {len(requests)} concurrent requests + 1 repeat: "
          f"requested {pts['requested']}, evaluated {pts['evaluated']}, "
          f"coalesced {pts['coalesced']}, memo hits {pts['memo_hits']}; "
          f"jit_compiles {m1['accel']['jit_compiles']} -> "
          f"{m2['accel']['jit_compiles']}, fallbacks "
          f"{m2['accel']['fallbacks']}", flush=True)
    check(m2["accel"]["jit_compiles"] == m1["accel"]["jit_compiles"],
          "a repeated daemon request compiled")
    check(m2["accel"]["fallbacks"] == 0, "the daemon fell back to numpy")


# ---------------------------------------------------------------- phase 4
def tpu_mode():
    from benchmarks import fig_tpu_dse
    from repro.dse import TpuBackend

    rows, results = fig_tpu_dse.run()
    check(len(results) == len(fig_tpu_dse.WORKLOADS) * len(
        fig_tpu_dse.CHIPS) * len(fig_tpu_dse.THRESHOLDS),
        "fig_tpu_dse grid incomplete")
    check(all(np.isfinite([r["energy_improvement"], r["speedup"]]).all()
              for r in rows), "fig_tpu_dse produced non-finite records")
    backend = TpuBackend()
    cpu = jax.devices("cpu")[0]
    same = True
    for w in fig_tpu_dse.WORKLOADS:
        on_tpu = backend._analyze(w)
        with jax.default_device(cpu):
            on_cpu = backend._analyze(w)
        fields = ("flops", "hlo_bytes", "total_bytes", "collective_bytes")
        diff = {f: (getattr(on_tpu, f), getattr(on_cpu, f)) for f in fields
                if getattr(on_tpu, f) != getattr(on_cpu, f)}
        same &= not diff
        print(f"[phase 4] {w}: lowered for tpu vs cpu "
              f"{'equal' if not diff else diff}", flush=True)
    print(f"[phase 4] {len(results)} points in {results.elapsed_s:.3f} s; "
          f"per-arch HLO numbers {'equal' if same else 'differ'} across "
          f"platforms", flush=True)


# ---------------------------------------------------------------- phase 5
def reference_loss(argv, device):
    """Step-0 loss of the train entry's model, batch and seed, computed
    unsharded on ``device``."""
    from repro.configs.registry import get_config, reduced_config
    from repro.data.pipeline import DataConfig, ShardedTokenPipeline
    from repro.launch.train import parse_args
    from repro.models.transformer import init_params
    from repro.train import steps as steps_mod

    args = parse_args(argv)
    cfg = (get_config if args.preset == "full" else reduced_config)(args.arch)
    batch = ShardedTokenPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch)).batch_at(0)
    with jax.default_device(device):
        params = jax.jit(lambda r: init_params(r, cfg))(jax.random.PRNGKey(0))
        loss = jax.jit(lambda p, b: steps_mod.loss_fn(p, cfg, b)[1][0])(
            params, {"tokens": batch["tokens"], "labels": batch["labels"]})
        return float(loss)


def train_step0(argv, watch: CompileWatch, on_step0=None):
    """Run the train entry; returns (step-0 loss, compilations after the
    first step).  Fails on any runner recovery."""
    from repro.launch import train

    seen = {}

    def on_step(step, metrics):
        if not seen:
            seen["loss"] = metrics["loss"]
            seen["compiles"] = watch.count
            if on_step0 is not None:
                on_step0()
        seen["last"] = watch.count

    with tempfile.TemporaryDirectory(prefix="chip-smoke-ckpt-") as ckpt:
        report = train.run(argv + ["--ckpt-dir", ckpt], on_step=on_step)
    check(report.failures_recovered == 0,
          f"the runner recovered {report.failures_recovered} times")
    check(report.steps_run == int(argv[argv.index("--steps") + 1]),
          "train did not run every step")
    return seen["loss"], seen["last"] - seen["compiles"]


def model(watch: CompileWatch):
    from repro.configs.registry import get_config
    from repro.launch import serve

    cpu_loss = reference_loss(TRAIN, jax.devices("cpu")[0])
    loss, late = train_step0(TRAIN, watch)
    print(f"[phase 5] train step-0 loss tpu {loss!r} cpu {cpu_loss!r} "
          f"(rel diff {abs(loss - cpu_loss) / abs(cpu_loss):.3e}, limit "
          f"{LOSS_RTOL}); compilations after step 0: {late}", flush=True)
    check(abs(loss - cpu_loss) <= LOSS_RTOL * abs(cpu_loss),
          "step-0 loss off the CPU reference")
    check(late == 0, "train compiled after its first step")

    args = dict(zip(SERVE[::2], SERVE[1::2]))
    tokens = serve.run(SERVE)
    want = (int(args["--batch"]), int(args["--gen"]))
    check(tokens.shape == want, f"served {tokens.shape}, asked {want}")
    check(bool(((tokens >= 0) & (tokens < get_config(ARCH).padded_vocab))
               .all()), "served token ids out of the vocabulary")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[phase 5] served {tokens.shape[0]}x{tokens.shape[1]} tokens; "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)


def sharded_train(watch: CompileWatch, devices):
    ref = reference_loss(TRAIN, devices[0])
    print(f"[4 chips] one-chip step-0 loss {ref!r}", flush=True)

    def show_memory():
        used = [d.memory_stats()["bytes_in_use"] for d in devices]
        print(f"[4 chips] bytes_in_use per chip at step 0: {used}",
              flush=True)
        check(min(used) * 2 > max(used), "train state sits on one chip")

    for name, extra in (("model-parallel 4", ["--model-parallel", "4"]),
                        ("data 4x1", ["--model-parallel", "1"])):
        with phase(f"4 chips: {name}", watch):
            loss, late = train_step0(TRAIN + extra, watch, show_memory)
            print(f"[4 chips] {name}: step-0 loss {loss!r} (rel diff "
                  f"{abs(loss - ref) / abs(ref):.3e}, limit {LOSS_RTOL}); "
                  f"compilations after step 0: {late}", flush=True)
            check(abs(loss - ref) <= LOSS_RTOL * abs(ref),
                  f"{name} step-0 loss off the one-chip loss")
            check(late == 0, f"{name} compiled after its first step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded train path, on four chips")
    args = ap.parse_args(argv)

    watch = CompileWatch()
    with phase("phase 0: device", watch):
        devices = device_or_exit(args.chips)
        sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
        from repro.compile_cache import enable_compile_cache
        print(f"[phase 0] compile cache {enable_compile_cache()}",
              flush=True)
    try:
        if args.chips == 4:
            sharded_train(watch, devices)
        else:
            with phase("phase 1: cim sweep", watch):
                ref = cim_sweep()
            with phase("phase 2: large trace", watch):
                large_trace()
            with phase("phase 3: daemon", watch):
                daemon(ref)
            with phase("phase 4: tpu mode", watch):
                tpu_mode()
            with phase("phase 5: model", watch):
                model(watch)
    except Failed as e:
        print(f"[chip_smoke] FAILED: {e}", flush=True)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
