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
* optimizer (:class:`bench.model_common.Trainer`): the gradient clipped to
  a global norm, then one AdamW step, weight decay on the matrices only,
  the learning rate linear warm-up then cosine decay.

Every contraction goes through :func:`bench.model_common.contract`, at
``HIGHEST`` precision or rounded as a control's ``mode`` says.

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
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench import model_common
from bench.model_common import (ROUNDING, contract, flat_leaves, rms_norm,
                                rope, store)

__all__ = ("dims_of", "shapes", "init", "store", "leaf_norms",
           "flat_leaves", "Trainer", "ROUNDING", "train_flops_per_token")


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


def stacked(name: str) -> bool:
    """Whether a parameter is stacked over layers on its leading axis."""
    return name.startswith("layers.")


def is_matrix(name: str, shape: Sequence[int]) -> bool:
    """Matrices take weight decay; biases and norm gains do not."""
    per = shape[1:] if stacked(name) else shape
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
    return model_common.leaf_norms(tree, stacked)


# --------------------------------------------------------------- forward
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
    layers = {k: v for k, v in params.items() if stacked(k)}

    def body(x, p_i):
        p, i = p_i
        y = layer(x, p, dims, mode, causal)
        if skip_layer is not None:
            y = jnp.where(i == skip_layer, x, y)
        return y, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                        (layers, jnp.arange(dims["L"])))
    x = rms_norm(x, params["norm"], dims["eps"])
    head = params["embed"].T if dims["tied"] else params["lm_head"]
    logits = contract("bsd,dv->bsv", x, head, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)




class Trainer(model_common.Trainer):
    """Steps of this reference (:class:`bench.model_common.Trainer`)."""
    loss = staticmethod(loss)
    decays = staticmethod(is_matrix)
    leaf_norms = staticmethod(leaf_norms)


# ------------------------------------------------------------------ FLOPs
def layer_weights(dims: Dict) -> int:
    """Weights of one layer's matrix multiplications."""
    d, F = dims["d"], dims["F"]
    q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    return d * q + 2 * d * kv + q * d + 3 * d * F


def train_flops_per_token(dims: Dict, seq_len: int) -> float:
    """Model FLOPs of a training step per token, forward and backward
    (PaLM's convention, Chowdhery et al. 2022, appendix B): ``6 N`` for
    the ``N`` weights of the layers' matrix multiplications, ``6 d V`` for
    the output head (tied or not; the embedding lookup is no
    multiplication), and ``12 L d S`` for the attention scores and their
    weighting of the values over a sequence of ``S``.  Biases, norms,
    softmax and the optimizer are left out, and so is any recomputation
    (rematerialisation), so the share of the peak that this count gives
    cannot pass what the chip did."""
    L, d = dims["L"], dims["d"]
    return (6.0 * L * layer_weights(dims) + 6.0 * d * dims["V"]
            + 12.0 * L * d * seq_len)
