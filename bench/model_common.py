"""What every plain model reference shares, whatever its architecture.

A reference module (``bench/<reference>.py``, named by a train
configuration's ``reference``; the contract is in :mod:`bench.kinds.train`)
imports these and keeps only what its architecture decides: its sizes, its
parameters and which of them are stacked over layers, its forward pass and
loss, and its FLOP count.

* :func:`rounded`, :func:`store`: a parameter store's rounding;
* :func:`leaf_norms` (given the reference's rule for stacked parameters),
  :func:`flat_leaves`: the comparison's leaves and their norms;
* :func:`contract` and :data:`ROUNDING`: every contraction at ``HIGHEST``
  precision, or, for the controls one precision below the bfloat16 a
  configuration states, with both operands (and, backward, the
  cotangent) rounded with one scale per tensor: to int8 (``"int8"``), or
  to the float8 formats ``e4m3`` (``float8_e4m3fn``: 3 mantissa bits, to
  448) and ``e5m2`` (2 mantissa bits, to 57344), with their subnormals;
* :func:`rms_norm`, :func:`rope`: the norm and the rotary embedding;
* :class:`Trainer`: gradients over blocks of sequences, clipping, AdamW
  and the store's rounding, around a reference's loss.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- leaves
def leaf_norms(tree: Dict[str, jnp.ndarray],
               stacked: Callable[[str], bool]) -> Dict[str, jnp.ndarray]:
    """L2 norm of each leaf: a vector over layers for a parameter that
    ``stacked`` says is stacked over layers on its leading axis, a scalar
    otherwise."""
    out = {}
    for name, x in tree.items():
        x = x.reshape(x.shape[0], -1) if stacked(name) else x.reshape(-1)
        x = x.astype(jnp.float32)
        out[name] = jnp.sqrt(jnp.sum(x * x, axis=-1))
    return out


def flat_leaves(norms: Dict[str, np.ndarray]) -> Dict[str, float]:
    """``{leaf: norm}`` from a reference's ``leaf_norms`` on the host."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v)
    return out


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


# ----------------------------------------------------------- layer parts
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """Rotary embedding of ``x`` (b, S, heads, dh), positions 0..S-1,
    rotate-half form."""
    dh, S = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


# ------------------------------------------------------------- optimizer
def lr_at(step: int, hp: Dict, total_steps: int) -> float:
    """Linear warm-up over ``warmup`` steps to ``learning_rate``, then
    cosine decay to 0 at ``total_steps``."""
    w = hp["warmup"]
    if step < w:
        return hp["learning_rate"] * (step + 1) / w
    prog = min(max((step - w) / max(total_steps - w, 1), 0.0), 1.0)
    return 0.5 * hp["learning_rate"] * (1.0 + math.cos(math.pi * prog))


class Trainer:
    """Steps of a plain reference: gradients over blocks of ``block``
    sequences, the gradient clipped to a global norm, then one AdamW step
    (bias-corrected moments in float32, decoupled weight decay where
    ``decays``) and the parameter store's rounding.

    A reference subclasses it and sets, as static methods, ``loss(params,
    tokens, labels, dims, mode, causal, skip_layer)`` (the mean
    cross-entropy of a block), ``decays(name, shape)`` (whether weight
    decay applies) and ``leaf_norms(tree)``.

    ``storage`` maps a parameter to ``(offset, dtype)``: the configuration's
    parameter store, which rounds each updated value (float32 keeps it).
    ``mode``, ``causal`` and ``skip_layer`` change the arithmetic for the
    controls and for faults planted in the reference."""

    loss: Callable
    decays: Callable
    leaf_norms: Callable

    def __init__(self, dims: Dict, hp: Dict, storage: Dict, block: int,
                 mode: str = "f32", causal: bool = True,
                 skip_layer: Optional[int] = None):
        self.dims, self.hp, self.block = dims, hp, block
        loss, decays, norms = self.loss, self.decays, self.leaf_norms

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
                if decays(k, p.shape):
                    delta = delta + hp["weight_decay"] * p
                offset, dtype = storage[k]
                new_p[k] = store(p - lr * delta, offset, dtype)
            return new_p, new_m, new_v, norms(g)

        self._grad = jax.jit(block_grad, donate_argnums=1)
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))
        self._change = jax.jit(lambda a, b: norms(
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
