"""Plain reference of a Qwen1.5 decoder's training step, in float32.

It follows the published architecture (``Qwen2ForCausalLM`` of Hugging
Face ``transformers``, which Qwen1.5 checkpoints use) and imports nothing
of the program:

* token embedding; per layer, pre-norm attention and pre-norm SwiGLU MLP
  with residuals; a final RMSNorm; logits from the embedding (tied) or
  from a head of its own;
* RMSNorm: ``x / sqrt(mean(x^2) + eps) * g``;
* attention: q, k and v projections (with bias where the configuration's
  ``qkv_bias`` says, as Qwen1.5's always have), rotary embedding (θ from
  the configuration, rotate-half form), causal softmax over
  ``q k^T / sqrt(head_dim)``, grouped heads where there are fewer key and
  value heads, an output projection without bias;
* MLP: ``down(silu(gate(x)) * up(x))``;
* loss: mean next-token cross-entropy over every position of the batch,
  whose labels are given;
* optimizer: the gradient clipped to a global norm, then one AdamW step
  (bias-corrected moments in float32, decoupled weight decay on the
  matrices only), the learning rate linear warm-up then cosine decay.

Every contraction goes through :func:`contract` at ``HIGHEST`` precision,
so a TPU computes it in float32.  The controls, one precision below the
bfloat16 a configuration states, round both operands of every
contraction, forward and backward, with one scale per tensor: to int8
(``mode="int8"``), or to the float8 formats ``e4m3`` (``float8_e4m3fn``:
3 mantissa bits, to 448) and ``e5m2`` (2 mantissa bits, to 57344), with
their subnormals (``mode="e4m3"``, ``"e5m2"``).

Parameters are held by name, layers stacked on a leading axis:

``embed`` (V, d); ``norm`` (d,); ``lm_head`` (d, V) where the embedding
is not tied; ``layers.input_norm``, ``layers.post_norm`` (L, d);
``layers.q`` (L, d, H*dh); ``layers.k``, ``layers.v`` (L, d, Hkv*dh);
``layers.q_bias`` (L, H*dh), ``layers.k_bias``, ``layers.v_bias``
(L, Hkv*dh) where there are biases; ``layers.o`` (L, H*dh, d); ``layers.gate``, ``layers.up``
(L, d, F); ``layers.down`` (L, F, d).

A *leaf* of the comparison is one layer's slice of a stacked parameter
(``layers.q[3]``) or a whole unstacked one.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------- sizes
def dims_of(config: Dict) -> Dict:
    """The sizes a configuration file states (Hugging Face key names)."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "d": d, "H": h,
            "Hkv": config["num_key_value_heads"],
            "dh": config.get("head_dim", d // h),
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "tied": bool(config["tie_word_embeddings"]),
            "bias": bool(config["qkv_bias"]),
            "init_std": float(config["initializer_range"])}


def shapes(dims: Dict) -> Dict[str, Tuple[int, ...]]:
    L, d, F, V = dims["L"], dims["d"], dims["F"], dims["V"]
    q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    out = {"embed": (V, d), "norm": (d,),
           "layers.input_norm": (L, d), "layers.post_norm": (L, d),
           "layers.q": (L, d, q), "layers.k": (L, d, kv),
           "layers.v": (L, d, kv), "layers.o": (L, q, d),
           "layers.gate": (L, d, F), "layers.up": (L, d, F),
           "layers.down": (L, F, d)}
    if dims["bias"]:
        out.update({"layers.q_bias": (L, q), "layers.k_bias": (L, kv),
                    "layers.v_bias": (L, kv)})
    if not dims["tied"]:
        out["lm_head"] = (d, V)
    return out


def is_gain(name: str) -> bool:
    return name.endswith("norm")


def is_matrix(name: str, shape: Sequence[int]) -> bool:
    """Matrices take weight decay; biases and norm gains do not."""
    per = shape[1:] if name.startswith("layers.") else shape
    return len(per) >= 2


def init(key, dims: Dict) -> Dict[str, jnp.ndarray]:
    """The published initialisation: every matrix and the embedding
    normal with the configuration's ``initializer_range``, biases zero,
    norm gains one."""
    out = {}
    names = sorted(shapes(dims))
    keys = jax.random.split(key, len(names))
    for k, name in zip(keys, names):
        shape = shapes(dims)[name]
        if is_gain(name):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_bias"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = dims["init_std"] * jax.random.normal(
                k, shape, jnp.float32)
    return out


def leaf_norms(tree: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """L2 norm of each leaf: a vector over layers for a stacked
    parameter, a scalar otherwise."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        axes = tuple(range(1 if name.startswith("layers.") else 0, x.ndim))
        out[name] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


def flat_leaves(norms: Dict[str, np.ndarray]) -> Dict[str, float]:
    """``{leaf: norm}`` from :func:`leaf_norms`' output on the host."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v)
    return out


# ---------------------------------------------------------- contractions
def _q8(x):
    """``x`` rounded to int8 with one scale for the tensor."""
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _qfloat(x, dtype):
    """``x`` rounded to the float8 ``dtype``, subnormals kept, with one
    scale for the tensor that maps its largest magnitude to the format's
    largest.  The rounding is done in float32 arithmetic, so no pair of
    casts is left for the compiler to drop."""
    info = jnp.finfo(dtype)
    top = float(info.max)
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    # the binade of |y|, no lower than the smallest normal's
    e = jnp.maximum(jnp.frexp(y)[1] - 1, info.minexp)
    q = jnp.ldexp(jnp.float32(1.0), e - info.nmant)
    return jnp.clip(jnp.round(y / q) * q, -top, top) * s


ROUNDING = {"int8": _q8,
            "e4m3": lambda x: _qfloat(x, jnp.float8_e4m3fn),
            "e5m2": lambda x: _qfloat(x, jnp.float8_e5m2)}


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rounded_einsum(spec: str, q: Callable):
    ins, out = spec.split("->")
    sa, sb = ins.split(",")

    @jax.custom_vjp
    def f(a, b):
        return _einsum(spec, q(a), q(b))

    def fwd(a, b):
        qa, qb = q(a), q(b)
        return _einsum(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        qg = q(g)
        return (_einsum(f"{out},{sb}->{sa}", qg, qb),
                _einsum(f"{sa},{out}->{sb}", qa, qg))

    f.defvjp(fwd, bwd)
    return f


def contract(spec: str, a, b, mode: str = "f32"):
    """``einsum(spec, a, b)`` at ``HIGHEST`` precision; with a ``mode`` of
    :data:`ROUNDING` on operands (and, backward, cotangents) rounded so.
    Every index of ``spec``'s output appears in an operand."""
    if mode == "f32":
        return _einsum(spec, a, b)
    if mode in ROUNDING:
        return _rounded_einsum(spec, ROUNDING[mode])(a, b)
    raise ValueError(f"unknown contraction mode {mode!r}")


# --------------------------------------------------------------- forward
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """Rotary embedding of ``x`` (b, S, heads, dh), positions 0..S-1."""
    dh, S = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def layer(x, p, dims, mode="f32", causal=True):
    """One decoder layer on ``x`` (b, S, d); ``p`` holds its slices."""
    b, S, _ = x.shape
    H, Hkv, dh = dims["H"], dims["Hkv"], dims["dh"]
    h = rms_norm(x, p["layers.input_norm"], dims["eps"])
    q = contract("bsd,dk->bsk", h, p["layers.q"], mode)
    k = contract("bsd,dk->bsk", h, p["layers.k"], mode)
    v = contract("bsd,dk->bsk", h, p["layers.v"], mode)
    if dims["bias"]:
        q = q + p["layers.q_bias"]
        k = k + p["layers.k_bias"]
        v = v + p["layers.v_bias"]
    q = rope(q.reshape(b, S, H, dh), dims["theta"])
    k = rope(k.reshape(b, S, Hkv, dh), dims["theta"])
    v = v.reshape(b, S, Hkv, dh)
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = contract("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(dh)
    if causal:
        pos = jnp.arange(S)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    a = contract("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)
    x = x + contract("bsk,kd->bsd", a.reshape(b, S, H * dh),
                     p["layers.o"], mode)
    h = rms_norm(x, p["layers.post_norm"], dims["eps"])
    g = jax.nn.silu(contract("bsd,df->bsf", h, p["layers.gate"], mode))
    u = contract("bsd,df->bsf", h, p["layers.up"], mode)
    return x + contract("bsf,fd->bsd", g * u, p["layers.down"], mode)


def loss(params, tokens, labels, dims, mode="f32", causal=True,
         skip_layer: Optional[int] = None):
    """Mean cross-entropy of ``labels`` given ``tokens`` (b, S).  Each
    layer is recomputed in the backward pass (``jax.checkpoint``), so a
    block of sequences holds one layer's activations at a time."""
    x = params["embed"][tokens]
    stacked = {k: v for k, v in params.items() if k.startswith("layers.")}

    def body(x, p_i):
        p, i = p_i
        y = layer(x, p, dims, mode, causal)
        if skip_layer is not None:
            y = jnp.where(i == skip_layer, x, y)
        return y, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                        (stacked, jnp.arange(dims["L"])))
    x = rms_norm(x, params["norm"], dims["eps"])
    head = params["embed"].T if dims["tied"] else params["lm_head"]
    logits = contract("bsd,dv->bsv", x, head, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ------------------------------------------------------------- optimizer
def lr_at(step: int, hp: Dict, total_steps: int) -> float:
    """Linear warm-up over ``warmup`` steps to ``learning_rate``, then
    cosine decay to 0 at ``total_steps``."""
    w = hp["warmup"]
    if step < w:
        return hp["learning_rate"] * (step + 1) / w
    prog = min(max((step - w) / max(total_steps - w, 1), 0.0), 1.0)
    return 0.5 * hp["learning_rate"] * (1.0 + math.cos(math.pi * prog))


def rounded(x, dtype):
    """``x`` (float32) rounded to the precision of ``dtype``, kept in
    float32.  ``reduce_precision`` and not a cast there and back: XLA on
    the TPU drops a float32 -> bfloat16 -> float32 pair of casts as
    excess precision, and the rounding with it."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def store(x, offset: float, dtype):
    """``x`` as a parameter store of ``dtype`` keeps it, the value held as
    its offset from ``offset``."""
    return rounded(x - offset, dtype) + offset


class Trainer:
    """Steps of the plain reference: gradients over blocks of ``block``
    sequences, clipping, AdamW, and the parameter store's rounding.

    ``storage`` maps a parameter to ``(offset, dtype)``: the configuration's
    parameter store, which rounds each updated value (float32 keeps it).
    ``mode``, ``causal`` and ``skip_layer`` change the arithmetic for the
    control and for faults planted in the reference."""

    def __init__(self, dims: Dict, hp: Dict, storage: Dict, block: int,
                 mode: str = "f32", causal: bool = True,
                 skip_layer: Optional[int] = None):
        self.dims, self.hp, self.block = dims, hp, block

        def block_grad(params, acc, tokens, labels):
            l, g = jax.value_and_grad(loss)(params, tokens, labels, dims,
                                            mode, causal, skip_layer)
            return l, {k: acc[k] + g[k] for k in acc}

        def update(params, m, v, g_sum, n, t, lr):
            g = {k: x / n for k, x in g_sum.items()}
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
            scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gn, 1e-9))
            g = {k: x * scale for k, x in g.items()}
            b1, b2 = hp["beta1"], hp["beta2"]
            bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
            new_p, new_m, new_v = {}, {}, {}
            for k, p in params.items():
                new_m[k] = b1 * m[k] + (1 - b1) * g[k]
                new_v[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
                delta = (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2)
                                            + hp["eps"])
                if is_matrix(k, p.shape):
                    delta = delta + hp["weight_decay"] * p
                offset, dtype = storage[k]
                new_p[k] = store(p - lr * delta, offset, dtype)
            return new_p, new_m, new_v, leaf_norms(g)

        self._grad = jax.jit(block_grad, donate_argnums=1)
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))
        self._change = jax.jit(lambda a, b: leaf_norms(
            {k: a[k] - b[k] for k in a}))

    def grad(self, params, tokens, labels):
        """Mean loss and summed gradient over a batch, ``block`` rows at a
        time, and the number of blocks: rows are of equal length, so the
        batch's mean is the mean of the blocks' means."""
        n = tokens.shape[0] // self.block
        total_l = 0.0
        acc = {k: jnp.zeros_like(x) for k, x in params.items()}
        for i in range(n):
            rows = slice(i * self.block, (i + 1) * self.block)
            l, acc = self._grad(params, acc, jnp.asarray(tokens[rows]),
                                jnp.asarray(labels[rows]))
            total_l += float(l)
        return total_l / n, acc, n

    def steps(self, make_params: Callable[[], Dict],
              batches: Sequence[Tuple[np.ndarray, np.ndarray]],
              total_steps: int, lr_scale: float = 1.0) -> Dict:
        """Runs one step per batch from ``make_params()`` and returns the
        readings: each step's loss, the first step's gradient as the
        optimizer gets it (clipped), and the parameters' change over the
        steps as the store keeps it, each a norm per leaf."""
        p = make_params()
        m = {k: jnp.zeros_like(x) for k, x in p.items()}
        v = {k: jnp.zeros_like(x) for k, x in p.items()}
        losses, grad_norms = [], None
        for t, (tokens, labels) in enumerate(batches):
            l, g, n = self.grad(p, tokens, labels)
            losses.append(l)
            lr = lr_scale * lr_at(t, self.hp, total_steps)
            p, m, v, gn = self._update(p, m, v, g, jnp.float32(n),
                                       jnp.float32(t + 1), jnp.float32(lr))
            del g
            if grad_norms is None:
                grad_norms = flat_leaves(jax.device_get(gn))
        del m, v
        change = self._change(p, make_params())
        return {"loss": losses, "grad": grad_norms,
                "change": flat_leaves(jax.device_get(change))}
