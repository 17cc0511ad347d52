"""Training steps of a model through the repo's training entry point: the
kind of a configuration with ``"kind": "train"``.

The run drives ``repro.launch.train.run(argv, on_step=...)``, the same
compiled step, state, feed and fault-tolerant loop a user runs, with the
command line the configuration (``entry``) and the traffic mix (``batch``,
``seq_len``, ``steps``) give.  What the benchmark makes, it hands the
entry for the length of the run:

* the weights: the plain reference's published initialisation drawn from
  ``--seed``, rounded to the type they are served in, made on the device
  in the entry's own jitted init (the entry's init key is the seed's);
* the batches: uniform token ids drawn from ``--seed`` and the step
  (:class:`Feed`), in place of the entry's synthetic stream;
* a look at the state after each step, which reads the leaf norms the
  comparison needs after the compared steps.

Set-up runs the first ``warmup_steps`` steps: the compile (or the load
from the persistent cache) and the ``COMPARED_STEPS`` steps that the
reference follows.  The window opens when the last warm-up step is done
and closes with the first step done ``--seconds`` later.

After the window, with the program's state freed, the plain reference
runs the compared steps from the same weights on the same batches, in
float32 at ``HIGHEST`` precision, a block of sequences at a time.  The
numbers compared, each against the configuration's limit:

``loss_gap``         the largest relative gap of a compared step's loss;
``grad_gap``         over leaves, the gap between the norms of the first
                     step's gradient as the optimizer gets it (the
                     program's worked out from its first moment, which
                     after one step is ``(1 - beta1)`` times it), over the
                     larger of the reference's norm of that leaf and of
                     the median leaf;
``update_gap``       the same of the parameters' change over the compared
                     steps, as the parameter store keeps it, over the
                     leaves whose reference gradient is at least a
                     thousandth of the median leaf's (a key bias's is
                     nought under softmax, and such a leaf moves under
                     Adam by round-off alone);
``nonfinite_steps``  steps of the run whose loss is not finite (limit 0);
``window_compiles``  compilations inside the window (limit 0).

A leaf is one layer's slice of a stacked parameter, or a whole other one.

The reference.  The configuration's ``reference`` names a module under
``bench/`` (:func:`reference_of`), the one source of the cell's sizes,
parameters and FLOPs; the harness reaches the architecture through it
alone.  It imports nothing of the program, takes what every architecture
shares from :mod:`bench.model_common`, and provides:

``dims_of(config)``      the sizes the configuration file states, as a
                         dict with at least ``V`` (the vocabulary) and
                         ``L`` (the layers);
``shapes(dims)``         each parameter's name and shape;
``init(key, dims)``      the published initialisation, float32;
``store(x, offset, dtype)``  a value as the parameter store keeps it;
``leaf_norms(tree)``     each parameter's norm, a vector over layers for
                         one that the reference stacks over layers on its
                         leading axis (its own rule, not the harness's);
``flat_leaves(norms)``   those norms on the host as ``{leaf: norm}``;
``Trainer``              :class:`bench.model_common.Trainer` around the
                         reference's loss: ``Trainer(dims, hp, storage,
                         block, mode=, causal=, skip_layer=)`` and
                         ``.steps(make_params, batches, total_steps,
                         lr_scale=)``;
``ROUNDING``             the controls' roundings by ``mode``;
``train_flops_per_token(dims, seq_len)``  model FLOPs of a training step
                         a token, forward and backward, no recomputation:
                         what ``mfu`` counts.

The configuration's ``layout`` maps each program leaf (``a/b/c``) to a
reference parameter (reshaped) and the offset the program holds it from;
``scopes`` (named scopes of the step, ``jax.named_scope``) and ``kernels``
(the names that a kernel's custom calls start with) say what a traced
run reads from the device trace for the per-layer readers
(:class:`Measurements`); both may be left out.
"""
from __future__ import annotations

import contextlib
import gc
import math
import resource
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple
from unittest import mock

import numpy as np

from bench.measure import DeviceReadings

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "nonfinite_steps",
           "window_compiles")
END_TO_END = ("tokens_per_s", "peak_rss_gb", "setup_s")
# the benchmark's own spans around the entry's calls into the feed, the
# step and the loop; the trace labels idle gaps by them
SPAN_NAMES = ("train.feed", "train.step", "train.loop")
COMPARED_STEPS = 3
# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone and is not held to its change
STILL_LEAF = 1e-3


class WindowClosed(Exception):
    """Raised from ``on_step`` to end the entry's loop at the deadline."""


def seed_key(seed: int):
    """The init key of ``--seed``, all of its bits."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def argv_of(config: Dict, traffic: Dict, ckpt_dir) -> List[str]:
    """The entry's command line for a cell."""
    e = config["entry"]
    return ["--arch", e["arch"], "--preset", e["preset"],
            "--remat", e["remat"], "--steps", str(traffic["steps"]),
            "--batch", str(traffic["batch"]),
            "--seq-len", str(traffic["seq_len"]),
            "--save-every", "0", "--log-every", "0",
            "--ckpt-dir", str(ckpt_dir)]


def tokens_per_step(traffic: Dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


def hyper(config: Dict, traffic: Dict) -> Dict:
    """The optimizer's constants, with the warm-up the entry derives from
    its step count."""
    hp = dict(config["optimizer"])
    hp["warmup"] = max(traffic["steps"] // hp.pop("warmup_divisor"), 1)
    return hp


class Feed:
    """Batches of uniform token ids drawn from the seed and the step;
    each row's labels are its tokens shifted by one."""

    def __init__(self, seed: int, vocab: int, batch: int, seq_len: int,
                 annotate=None):
        self.seed, self.vocab = seed % 2 ** 64, vocab
        self.batch, self.seq_len = batch, seq_len
        self.annotate = annotate

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        if self.annotate is not None:
            self.annotate("train.feed")
        rng = np.random.default_rng([self.seed, step])
        toks = rng.integers(0, self.vocab, (self.batch, self.seq_len + 1),
                            dtype=np.int32)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if self.annotate is not None:
            self.annotate("train.step")
        return out

    def pairs(self, steps: int):
        return [(b["tokens"], b["labels"])
                for b in map(self.batch_at, range(steps))]


class Layout:
    """The program's parameter tree against the reference's names: each
    program leaf (``a/b/c``) is a reference parameter reshaped, held as
    its offset from ``offset`` (norm gains the program keeps as
    ``gain - 1``), as the configuration's ``layout`` says.  ``ref`` is
    the reference module, ``dims`` its sizes."""

    def __init__(self, layout: Dict[str, list], ref, dims: Dict):
        self.ref = ref
        self.shapes = ref.shapes(dims)
        self.of = {path: (name, float(off))
                   for path, (name, off) in layout.items()}
        missing = set(self.shapes) - {n for n, _ in self.of.values()}
        if missing:
            raise ValueError(f"layout leaves out {sorted(missing)}")
        self.offset = {n: o for n, o in self.of.values()}

    @staticmethod
    def path(keys) -> str:
        return "/".join(str(getattr(k, "key", k)) for k in keys)

    def to_program(self, ref: Dict, template, exact: bool = False):
        """Program leaves from the reference's, in the template's shapes
        and types; ``exact``: their values in float32."""
        import jax
        from bench.model_common import rounded

        def leaf(keys, t):
            p = self.path(keys)
            if p not in self.of:
                raise KeyError(f"program leaf {p} is not in the layout")
            name, off = self.of[p]
            x = rounded((ref[name] - off).reshape(t.shape), t.dtype)
            return x if exact else x.astype(t.dtype)

        return jax.tree_util.tree_map_with_path(leaf, template)

    def norms(self, tree) -> Dict:
        """The reference's leaf norms of a program tree, each leaf in its
        reference parameter's shape, keyed by reference name."""
        import jax

        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        named = {}
        for keys, v in flat:
            name = self.of[self.path(keys)][0]
            named[name] = v.reshape(self.shapes[name])
        return self.ref.leaf_norms(named)


class Watcher:
    """The entry's ``on_step`` and the look at its state after each step:
    the window's clock, the compared steps' losses, the step's other
    metrics over the window's steps (``counters``), the state's leaf
    norms after the first and the last compared step, and (traced) the
    benchmark's spans on the profiler's clock."""

    def __init__(self, seconds: float, warmup: int, watch, observe,
                 profile=None):
        self.seconds, self.warmup, self.watch = seconds, warmup, watch
        self.observe = observe
        self.profile = profile
        self.losses: List[float] = []
        self.nonfinite = 0
        self.t_open = self.t_close = None
        self.open_compiles = self.close_compiles = None
        self.last = None
        self.readings: Dict = {}
        # the host clock at the end of each step from the window's open,
        # and the process's CPU use across the window (for the log)
        self.ends: List[float] = []
        self.usage = []
        self.counters: Dict[str, List[float]] = {}
        self._span = None

    def annotate(self, name: Optional[str]) -> None:
        """Closes the open span and opens ``name`` (None: none)."""
        import jax
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if name is not None and self.profile is not None:
            self._span = jax.profiler.TraceAnnotation(name)
            self._span.__enter__()

    def on_step(self, step: int, metrics: Dict[str, float]) -> None:
        now = time.perf_counter()
        self.annotate("train.loop")
        loss = metrics["loss"]
        if not math.isfinite(loss):
            self.nonfinite += 1
        if step < COMPARED_STEPS:
            self.losses.append(loss)
        if step >= self.warmup - 1:
            self.ends.append(now)
        if step >= self.warmup:
            for k, v in metrics.items():
                if k != "loss":
                    self.counters.setdefault(k, []).append(v)
        if step == self.warmup - 1:
            self.t_open, self.open_compiles = now, self.watch.count
            self.usage.append(resource.getrusage(resource.RUSAGE_SELF))
            if self.profile is not None:
                self.profile.start()
        elif step >= self.warmup and now - self.t_open >= self.seconds:
            self.t_close, self.close_compiles = now, self.watch.count
            self.usage.append(resource.getrusage(resource.RUSAGE_SELF))
            self.last = step
            self.annotate(None)
            raise WindowClosed(step)

    def step_times(self) -> np.ndarray:
        """The host-clock seconds of each window step."""
        return np.diff(self.ends)

    def step_log(self) -> str:
        """The window's step times and the process's CPU use across it:
        what tells a slow window's stalls from a slower chip."""
        if len(self.ends) < 2 or len(self.usage) < 2:
            return "no window steps"
        dt = self.step_times()
        med = float(np.median(dt))
        slow = dt > 1.2 * med
        a, b = self.usage
        return (f"window step times: median {med:.4f} s, min {dt.min():.4f}, "
                f"max {dt.max():.4f}; {int(slow.sum())} over 1.2x the median "
                f"({float((dt[slow] - med).sum()):.3f} s beyond it, at "
                f"window steps {np.flatnonzero(slow)[:8].tolist()}); "
                f"CPU user {b.ru_utime - a.ru_utime:.2f} s, system "
                f"{b.ru_stime - a.ru_stime:.2f} s")

    def after_step(self, step: int, state) -> None:
        """Called with the state after ``step``, before the next step."""
        if step == 0 or step == COMPARED_STEPS - 1:
            self.readings.update(self.observe(step, state))


class Measurements(DeviceReadings):
    """What a traced train run measured, for the per-layer readers: the
    window's model FLOPs, seconds and step times on the host clock, the
    mean of each step metric over the window's steps, and the profiled
    part's device trace (None where the profile caught no device) with
    the time under each of the configuration's ``scopes`` and the ops of
    each of its ``kernels``."""

    def __init__(self, device: Optional[Dict], model_flops: float,
                 host_window_s: float, peaks: Dict[str, float],
                 step_s: Sequence[float] = (),
                 counters: Optional[Dict[str, List[float]]] = None):
        self.device = device
        self.model_flops = model_flops
        self.host_window_s = host_window_s
        self.peaks = peaks
        self.step_s = list(step_s)
        self.counters = counters or {}

    def mfu(self) -> Optional[float]:
        """Model FLOPs of the window's steps over the window and the
        chip's bf16 peak, in %."""
        if self.host_window_s <= 0:
            return None
        return (100.0 * self.model_flops
                / (self.host_window_s * self.peaks["flops_per_s"]))

    def step_median_s(self) -> Optional[float]:
        """The median of the window's step times, in seconds."""
        return statistics.median(self.step_s) if self.step_s else None

    def counter(self, name: str) -> Optional[float]:
        """The mean of the step metric ``name`` over the window's steps."""
        seen = self.counters.get(name)
        return statistics.fmean(seen) if seen else None

    def scope_s(self, scope: str) -> Optional[float]:
        """Device seconds under the named scope ``scope`` over the
        profiled part: the union of its ops' intervals, averaged over the
        chips."""
        if self.device is None:
            return None
        return self.device["scopes"].get(scope)

    def scope_s_per_step(self, scope: str) -> Optional[float]:
        """:meth:`scope_s` a step: over the profiled part's length in
        window median steps."""
        secs, med = self.scope_s(scope), self.step_median_s()
        if secs is None or med is None or not self.window_s:
            return None
        return secs * med / self.window_s

    def ops(self, prefix: str) -> List[Tuple[float, str]]:
        """``(device seconds, HLO head)`` of each whole custom-call op in
        the profiled part whose name starts with ``prefix``, one of the
        configuration's ``kernels``."""
        if self.device is None:
            return []
        return [tuple(o) for o in self.device["ops"].get(prefix, [])]


def reference_of(config: Dict):
    """The configuration's reference module, ``bench.<reference>``: the
    harness's one way to the architecture (the contract is in this
    module's docstring)."""
    import importlib
    return importlib.import_module(f"bench.{config['reference']}")


def storage_of(config: Dict, layout: Layout) -> Dict:
    """Each reference parameter's store: ``(offset, dtype)``."""
    import jax.numpy as jnp
    dtype = jnp.dtype(config["param_dtype"])
    return {n: (o, dtype) for n, o in layout.offset.items()}


def drive(config: Dict, traffic: Dict, seed: int, seconds: float, watch,
          work, log, profile=None) -> Watcher:
    """Runs the entry from the seed's weights and batches until the window
    has closed; the state is freed when this returns."""
    import jax
    from repro.launch import train as entry
    from repro.train import steps as steps_mod

    ref = reference_of(config)
    dims = ref.dims_of(config)
    layout = Layout(config["layout"], ref, dims)
    b1 = config["optimizer"]["beta1"]
    real_init = steps_mod.init_train_state
    key = seed_key(seed)

    def program_params(rng, template, exact=False):
        return layout.to_program(ref.init(rng, dims), template, exact)

    def seeded_init(rng, cfg):
        state = real_init(rng, cfg)
        return dict(state, params=program_params(rng, state["params"]))

    @jax.jit
    def grad_norms(m):
        return {k: v / (1.0 - b1) for k, v in layout.norms(m).items()}

    @jax.jit
    def change_norms(params, rng):
        first = program_params(rng, params, exact=True)
        return layout.norms(jax.tree_util.tree_map(
            lambda a, b: a.astype("float32") - b, params, first))

    def observe(step, state):
        if step == 0:
            return {"grad": ref.flat_leaves(
                jax.device_get(grad_norms(state["opt"]["m"])))}
        return {"change": ref.flat_leaves(
            jax.device_get(change_norms(state["params"], key)))}

    watcher = Watcher(seconds, traffic["warmup_steps"], watch, observe,
                      profile)

    class Observed(entry.FaultTolerantRunner):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            save = self.ckpt.maybe_save

            def maybe_save(step, state, **kw):
                watcher.after_step(step, state)
                return save(step, state, **kw)

            self.ckpt.maybe_save = maybe_save

    def feed(data_cfg):
        return Feed(seed, data_cfg.vocab_size, data_cfg.global_batch,
                    data_cfg.seq_len, watcher.annotate)

    ckpt = work / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    argv = argv_of(config, traffic, ckpt)
    log("entry: repro.launch.train " + " ".join(argv))
    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            jax.random, "PRNGKey", lambda _: key))
        stack.enter_context(mock.patch.object(
            steps_mod, "init_train_state", seeded_init))
        stack.enter_context(mock.patch.object(
            entry, "ShardedTokenPipeline", feed))
        stack.enter_context(mock.patch.object(
            entry, "FaultTolerantRunner", Observed))
        stack.enter_context(contextlib.redirect_stdout(sys.stderr))
        try:
            entry.run(argv, on_step=watcher.on_step)
        except WindowClosed:
            pass
        else:
            raise RuntimeError(
                f"the entry's {traffic['steps']} steps ended before the "
                f"window closed: the mix's steps are too few")
        finally:
            watcher.annotate(None)
            shutil.rmtree(ckpt, ignore_errors=True)
    if profile is not None:
        profile.join()
    gc.collect()
    return watcher


def reference_readings(config: Dict, traffic: Dict, seed: int,
                       mode: str = "f32", fault: Optional[str] = None,
                       ) -> Dict:
    """The plain reference's readings of the compared steps from the
    seed's weights and batches; ``mode`` (a control's rounding,
    the reference's ``ROUNDING``) and ``fault`` put a control or
    a planted fault in its place (``half_batch``, ``double_update``,
    ``skip_layer``, ``no_causal_mask``)."""
    import jax
    ref = reference_of(config)
    dims = ref.dims_of(config)
    layout = Layout(config["layout"], ref, dims)
    storage = storage_of(config, layout)
    key = seed_key(seed)
    make = jax.jit(lambda k: {n: ref.store(x, *storage[n])
                              for n, x in ref.init(k, dims).items()})
    batches = Feed(seed, dims["V"], traffic["batch"],
                   traffic["seq_len"]).pairs(COMPARED_STEPS)
    block = traffic["reference_block"]
    if fault == "half_batch":
        batches = [(t[: len(t) // 2], l[: len(l) // 2]) for t, l in batches]
        block = min(block, len(batches[0][0]))
    trainer = ref.Trainer(
        dims, hyper(config, traffic), storage, block, mode=mode,
        causal=fault != "no_causal_mask",
        skip_layer=dims["L"] // 2 if fault == "skip_layer" else None)
    with jax.default_matmul_precision("highest"):
        return trainer.steps(lambda: make(key), batches, traffic["steps"],
                             lr_scale=2.0 if fault == "double_update"
                             else 1.0)


def _gap(a: float, b: float, scale: float) -> float:
    g = abs(a - b) / scale
    return g if math.isfinite(g) else math.inf


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``loss_gap``, ``grad_gap`` and ``update_gap`` of a program's (or a
    stand-in's) readings against the reference's; a non-finite reading
    gives an infinite gap."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("the program's leaves are not the reference's")
    loss_gap = max(_gap(p, r, abs(r)) for p, r in zip(prog["loss"],
                                                       ref["loss"]))
    g_ref = ref["grad"]
    g_med = statistics.median(g_ref.values())
    grad_gap = max(_gap(prog["grad"][k], g, max(g, g_med))
                   for k, g in g_ref.items())
    moving = [k for k, g in g_ref.items() if g >= STILL_LEAF * g_med]
    c_ref = ref["change"]
    c_med = statistics.median(c_ref[k] for k in moving)
    update_gap = max(_gap(prog["change"][k], c_ref[k], max(c_ref[k], c_med))
                     for k in moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}


def device_peak(stats: Dict) -> Optional[int]:
    """The chip's peak memory from JAX's ``memory_stats``: the buffers in
    use and the scratch the TPU runtime reserves for the step apart from
    them (``peak_bytes_reserved``), which ``peak_bytes_in_use`` leaves
    out."""
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def _json_number(x: float) -> float:
    """A gap as JSON can hold it: an infinite one as 1e308."""
    return x if math.isfinite(x) else 1e308


def run(ctx):
    """One run of a train cell."""
    from bench import cell as cells
    from bench.kinds import Outcome
    from bench.peaks import peak

    args, config, traffic, log = ctx.args, ctx.config, ctx.traffic, ctx.log
    profile = None
    if args.trace:
        prof_dir = cells.WORK / "profile"
        shutil.rmtree(prof_dir, ignore_errors=True)
        profile = ctx.Profile(prof_dir, **traffic["profile"])
    w = drive(config, traffic, args.seed, args.seconds, ctx.watch,
              cells.WORK, log, profile)
    setup_s = w.t_open - ctx.t0
    window_s = w.t_close - w.t_open
    window_steps = w.last - (traffic["warmup_steps"] - 1)
    tokens = window_steps * tokens_per_step(traffic)
    window_compiles = w.close_compiles - w.open_compiles
    rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    memory_peak = device_peak(ctx.devices[0].memory_stats() or {})
    log(f"set-up {setup_s:.3f} s; window {window_s:.3f} s, {window_steps} "
        f"steps, {tokens} tokens; compilations {ctx.watch.count} "
        f"({ctx.watch.seconds:.3f} s), {window_compiles} in the window; "
        f"losses {w.losses}")
    log(w.step_log())

    t_ref = time.perf_counter()
    ref = reference_readings(config, traffic, args.seed)
    log(f"reference {time.perf_counter() - t_ref:.3f} s; losses "
        f"{ref['loss']}")
    prog = {"loss": w.losses, **w.readings}
    numbers = {k: _json_number(v) for k, v in compare(prog, ref).items()}
    numbers["nonfinite_steps"] = w.nonfinite
    numbers["window_compiles"] = window_compiles

    m = None
    if args.trace:
        ref_mod = reference_of(config)
        flops = ref_mod.train_flops_per_token(ref_mod.dims_of(config),
                                              traffic["seq_len"])
        device = ctx.device_trace(profile, SPAN_NAMES, {},
                                  config.get("scopes", ()),
                                  config.get("kernels", ()))
        m = Measurements(device, tokens * flops, window_s,
                         peak(ctx.devices[0].device_kind),
                         w.step_times(), w.counters)
    end_to_end = {
        "tokens_per_s": {"value": tokens / window_s, "unit": "tokens/s"},
        "peak_rss_gb": {"value": rss_gb, "unit": "GB"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    return Outcome(attempted=w.last + 1, failed=w.nonfinite,
                   end_to_end=end_to_end, measurements=m, numbers=numbers,
                   memory_peak_bytes=memory_peak,
                   window_compiles=window_compiles)
