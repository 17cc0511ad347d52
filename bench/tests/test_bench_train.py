"""The train kind: a run drives ``repro.launch.train`` end to end on the CPU
(the look for a chip is skipped) at the smoke preset, ``correct`` holds
for a sound run and fails for each fault the cell can have and for the
controls (the reference in int8, float8 e4m3 or float8 e5m2); a second
model configuration needs only
files of its own.

The cells run in a benchmark root of their own: the qwen1.5-0.5b
configuration at the smoke preset's sizes (2 layers, width 64, 4 heads of
16, vocabulary 512) and yi-34b's smoke preset (untied head, no biases),
4 x 64 tokens a step.  Their limits hold the smoke sizes' readings on the
CPU (a sound run reads ``loss_gap`` under 2e-5, ``grad_gap`` under 4e-3
and ``update_gap`` under 0.07; the int8 control ``grad_gap`` 0.09 and
more), not the cell's.
"""
from __future__ import annotations

import functools
import json
import pathlib
import types

import numpy as np
import pytest

from bench import model_flops, model_reference, run
from bench.kinds import train

ROOT = pathlib.Path(__file__).resolve().parents[2]
LIMITS = {"loss_gap": 1e-4, "grad_gap": 0.02, "update_gap": 0.12,
          "nonfinite_steps": 0, "window_compiles": 0}
SMOKE = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 512, "num_hidden_layers": 2}


def _yi(qwen: dict) -> dict:
    """yi-34b's smoke preset in the same form: a llama decoder."""
    cfg = dict(qwen, name="yi-smoke", qkv_bias=False,
               tie_word_embeddings=False, rope_theta=5e6,
               entry=dict(qwen["entry"], arch="yi-34b"))
    layout = {k: v for k, v in qwen["layout"].items()
              if not k.startswith("layers/attn/b")}
    layout["lm_head"] = ["lm_head", 0]
    cfg["layout"] = layout
    return cfg


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    """A benchmark root holding two smoke-sized train cells."""
    root = tmp_path_factory.mktemp("train_root")
    qwen = json.loads(
        (ROOT / "bench/configs/qwen1.5-0.5b-train.json").read_text())
    qwen.update(SMOKE, name="qwen-smoke", limits=LIMITS,
                entry=dict(qwen["entry"], preset="smoke"))
    traffic = json.loads((ROOT / "bench/traffic/train-s2048.json").read_text())
    traffic.update(batch=4, seq_len=64, reference_block=2,
                   profile={"lead_s": 0.0, "span_s": 0.2})
    (root / "bench/configs").mkdir(parents=True)
    (root / "bench/traffic").mkdir(parents=True)
    for cfg in (qwen, _yi(qwen)):
        (root / f"bench/configs/{cfg['name']}.json").write_text(
            json.dumps(cfg))
    (root / "bench/traffic/smoke.json").write_text(json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in ("qwen-smoke", "yi-smoke")],
        "workloads": [{"name": f"{n}.train", "config": f"{n}-smoke",
                       "traffic": "smoke", "chips": 1}
                      for n in ("qwen", "yi")],
        "per_layer": [dict(m, workloads=["qwen.train", "yi.train"])
                      for m in json.loads((ROOT / "BENCHMARK.json")
                                          .read_text())["per_layer"]
                      if m["moves"] == "tokens_per_s"]}))
    return root


@pytest.fixture
def drive(smoke_root, monkeypatch):
    """Runs a cell once and hands back its result; the run's process
    settings (compile cache) are restored afterwards."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_compilation_cache_max_size")}

    def go(cell="qwen.train", seed=5, trace=0):
        args = types.SimpleNamespace(workload=cell, seed=seed, seconds=0.0,
                                     trace=trace)
        return run.run(args, require_tpu=False, root=smoke_root)

    yield go
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _values(out):
    return {k: v["value"] for k, v in out["checks"].items()}


def _planted(monkeypatch, broken):
    """The entry's step with ``broken(step)`` in its place."""
    from repro.train import steps
    real = steps.make_train_step
    monkeypatch.setattr(steps, "make_train_step",
                        lambda cfg, tc: broken(real(cfg, tc)))


def test_sound_run_is_correct(drive):
    out = drive()
    assert out["correct"], out["checks"]
    assert list(out["checks"]) == list(train.NUMBERS)
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["window_compiles"] == 0
    # four warm-up steps (the three compared among them), one in the window
    assert out["attempted"] == 5
    assert sorted(out["metrics"]) == sorted(train.END_TO_END)
    assert out["metrics"]["tokens_per_s"]["unit"] == "tokens/s"


def test_traced_run_reads_the_step_and_no_device_off_the_chip(
        drive, monkeypatch):
    """A traced run opens its spans under the profiler and reads ``mfu``;
    the CPU's profile holds no device plane, so the device's numbers are
    left out (the CPU is given the v5e's peak only to reach the reader)."""
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    out = drive(trace=1)
    assert out["correct"], out["checks"]
    assert list(out["metrics"]) == ["mfu"]
    assert 0 < out["metrics"]["mfu"]["value"] < 100
    assert out["device"]["busy_s"] is None and "breakdown" not in out


def test_a_second_model_needs_only_files_of_its_own(drive):
    """yi-34b's cell is a configuration, a traffic mix and a
    BENCHMARK.json entry: no code of the harness names it."""
    out = drive("yi.train")
    assert out["correct"], out["checks"]


def test_state_left_unchanged_fails(drive, monkeypatch):
    def broken(step):
        def f(state, batch):
            return state, step(state, batch)[1]
        return f

    _planted(monkeypatch, broken)
    out = drive()
    assert not out["correct"]
    assert _values(out)["update_gap"] == pytest.approx(1.0)


def test_half_the_batch_left_out_fails(drive, monkeypatch):
    def broken(step):
        def f(state, batch):
            return step(state, {k: v[: v.shape[0] // 2]
                                for k, v in batch.items()})
        return f

    _planted(monkeypatch, broken)
    out = drive()
    assert not out["correct"]
    assert _values(out)["grad_gap"] > LIMITS["grad_gap"]


def test_loss_altered_where_it_is_produced_fails(drive, monkeypatch):
    def broken(step):
        def f(state, batch):
            new, metrics = step(state, batch)
            return new, dict(metrics, loss=metrics["loss"] * 1.01)
        return f

    _planted(monkeypatch, broken)
    out = drive()
    assert not out["correct"]
    assert _values(out)["loss_gap"] == pytest.approx(0.01, rel=0.01)


def test_update_scaled_fails(drive, monkeypatch):
    import jax
    from repro.optim import adamw
    real = adamw.adamw_update

    def doubled(params, grads, moments, step, tc):
        new, opt, om = real(params, grads, moments, step, tc)
        new = jax.tree_util.tree_map(
            lambda n, p: (2 * n.astype("float32") - p.astype("float32")
                          ).astype(p.dtype), new, params)
        return new, opt, om

    monkeypatch.setattr(adamw, "adamw_update", doubled)
    out = drive()
    assert not out["correct"]
    assert _values(out)["update_gap"] > LIMITS["update_gap"]


def test_reference_layer_skipped_fails(drive, monkeypatch):
    monkeypatch.setattr(train, "reference_readings", functools.partial(
        train.reference_readings, fault="skip_layer"))
    out = drive()
    assert not out["correct"]
    assert _values(out)["grad_gap"] > LIMITS["grad_gap"]


def test_control_int8_reference_fails(smoke_root):
    """The reference with every contraction in int8, one precision below
    the bfloat16 the configuration states, put in the program's place,
    does not pass."""
    from bench import cell
    _, config, traffic = cell.load_cell("qwen.train", smoke_root)
    ref = train.reference_readings(config, traffic, 5)
    control = train.reference_readings(config, traffic, 5, mode="int8")
    numbers = train.compare(control, ref)
    assert any(numbers[k] > LIMITS[k] for k in numbers)
    assert train.compare(ref, ref) == {"loss_gap": 0.0, "grad_gap": 0.0,
                                       "update_gap": 0.0}


@pytest.mark.parametrize("mode", ["e4m3", "e5m2"])
def test_control_fp8_reference_fails(smoke_root, mode):
    """The reference with every contraction in a float8 format, the other
    step below bfloat16, put in the program's place, does not pass."""
    from bench import cell
    _, config, traffic = cell.load_cell("qwen.train", smoke_root)
    ref = train.reference_readings(config, traffic, 5)
    control = train.reference_readings(config, traffic, 5, mode=mode)
    numbers = train.compare(control, ref)
    assert any(numbers[k] > LIMITS[k] for k in numbers)


def test_fp8_rounding_matches_the_formats():
    """The float8 controls round as a cast to the format would, with one
    scale per tensor and the formats' subnormals."""
    import jax
    import jax.numpy as jnp
    x = np.concatenate([
        np.random.default_rng(3).standard_normal(4096).astype(np.float32),
        np.float32([2.0 ** -12, -3 * 2.0 ** -14, 0.0])])
    for mode, dtype in (("e4m3", jnp.float8_e4m3fn), ("e5m2", jnp.float8_e5m2)):
        got = np.asarray(jax.jit(model_reference.ROUNDING[mode])(x))
        s = np.float32(np.abs(x).max() / float(jnp.finfo(dtype).max))
        want = (x / s).astype(dtype).astype(np.float32) * s
        assert np.array_equal(got, want), mode


def test_feed_is_drawn_from_the_seed():
    a = train.Feed(2 ** 33 + 5, 512, 4, 64)
    b = train.Feed(2 ** 33 + 5, 512, 4, 64)
    c = train.Feed(5, 512, 4, 64)
    x, y, z = a.batch_at(3), b.batch_at(3), c.batch_at(3)
    assert np.array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(x["tokens"], z["tokens"])
    assert not np.array_equal(x["tokens"], a.batch_at(4)["tokens"])
    assert np.array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert len({r.tobytes() for r in x["tokens"]}) == 4


def test_seed_key_keeps_every_bit():
    keys = {tuple(np.asarray(train.seed_key(s)).tolist())
            for s in (7, 2 ** 32 + 7, 2 ** 33 + 7)}
    assert len(keys) == 3


def test_model_flops_of_the_cell():
    cfg = json.loads(
        (ROOT / "bench/configs/qwen1.5-0.5b-train.json").read_text())
    dims = model_reference.dims_of(cfg)
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert model_flops.layer_weights(dims) == per_layer
    assert model_flops.train_flops_per_token(dims, 2048) == (
        6 * 24 * per_layer + 6 * 1024 * 151936 + 12 * 24 * 1024 * 2048)


def test_device_peak_counts_the_reserved_scratch():
    """A TPU's ``memory_stats`` keep the step's scratch apart from the
    buffers in use; the chip's peak is the two together."""
    assert train.device_peak({}) is None
    assert train.device_peak({"peak_bytes_in_use": 7}) == 7
    assert train.device_peak({"peak_bytes_in_use": 4669190144,
                              "peak_bytes_reserved": 8447754240}) == (
        13116944384)


def test_train_readers():
    readers = run.metric_readers(("mfu", "device_idle_share.train"))
    m = train.Measurements(None, 197e12, 2.0, {"flops_per_s": 197e12})
    assert readers["mfu"](m) == pytest.approx(50.0)
    assert readers["device_idle_share.train"](m) is None
    m.device = {"busy_s": 0.75, "window_s": 1.0, "device_ops": [],
                "idle_gaps": []}
    assert readers["device_idle_share.train"](m) == pytest.approx(25.0)
    assert m.breakdown == {"device_ops": [], "idle_gaps": []}
