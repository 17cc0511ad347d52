"""A second plain reference, for the tests: a Llama decoder (Yi's
``LlamaForCausalLM``: no biases, a head of its own) under parameter names
of its own (GGUF's), with its own rule for what is stacked over layers and
its own FLOP count.  A train cell names it as ``"reference":
"tests.toy_reference"``; nothing of the harness names it.

Parameters: ``token_embd`` (V, d); ``output_norm`` (d,); ``output``
(d, V); stacked over layers on a leading axis, ``blk.attn_norm``,
``blk.ffn_norm`` (L, d); ``blk.attn_q`` (L, d, H*dh); ``blk.attn_k``,
``blk.attn_v`` (L, d, Hkv*dh); ``blk.attn_output`` (L, H*dh, d);
``blk.ffn_gate``, ``blk.ffn_up`` (L, d, F); ``blk.ffn_down`` (L, F, d).
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


def dims_of(config: Dict) -> Dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "d": d, "H": h,
            "Hkv": config["num_key_value_heads"],
            "dh": config.get("head_dim", d // h),
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "init_std": float(config["initializer_range"])}


def shapes(dims: Dict) -> Dict[str, Tuple[int, ...]]:
    L, d, F, V = dims["L"], dims["d"], dims["F"], dims["V"]
    q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    return {"token_embd": (V, d), "output_norm": (d,), "output": (d, V),
            "blk.attn_norm": (L, d), "blk.ffn_norm": (L, d),
            "blk.attn_q": (L, d, q), "blk.attn_k": (L, d, kv),
            "blk.attn_v": (L, d, kv), "blk.attn_output": (L, q, d),
            "blk.ffn_gate": (L, d, F), "blk.ffn_up": (L, d, F),
            "blk.ffn_down": (L, F, d)}


def stacked(name: str) -> bool:
    return name.startswith("blk.")


def decays(name: str, shape: Sequence[int]) -> bool:
    return not name.endswith("norm")


def init(key, dims: Dict) -> Dict[str, jnp.ndarray]:
    """Matrices and the embedding normal with ``initializer_range``,
    norm gains one."""
    sizes = shapes(dims)
    keys = jax.random.split(key, len(sizes))
    return {name: (jnp.ones(sizes[name], jnp.float32)
                   if name.endswith("norm") else dims["init_std"]
                   * jax.random.normal(k, sizes[name], jnp.float32))
            for k, name in zip(keys, sorted(sizes))}


def leaf_norms(tree: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    return model_common.leaf_norms(tree, stacked)


def _block(x, p, dims, mode, causal):
    b, S, _ = x.shape
    H, Hkv, dh = dims["H"], dims["Hkv"], dims["dh"]
    h = rms_norm(x, p["blk.attn_norm"], dims["eps"])
    q = rope(contract("bsd,dk->bsk", h, p["blk.attn_q"], mode)
             .reshape(b, S, H, dh), dims["theta"])
    k = rope(contract("bsd,dk->bsk", h, p["blk.attn_k"], mode)
             .reshape(b, S, Hkv, dh), dims["theta"])
    v = contract("bsd,dk->bsk", h, p["blk.attn_v"], mode).reshape(
        b, S, Hkv, dh)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = contract("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(dh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = contract("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)
    x = x + contract("bsk,kd->bsd", a.reshape(b, S, H * dh),
                     p["blk.attn_output"], mode)
    h = rms_norm(x, p["blk.ffn_norm"], dims["eps"])
    g = jax.nn.silu(contract("bsd,df->bsf", h, p["blk.ffn_gate"], mode))
    u = contract("bsd,df->bsf", h, p["blk.ffn_up"], mode)
    return x + contract("bsf,fd->bsd", g * u, p["blk.ffn_down"], mode)


def loss(params, tokens, labels, dims, mode="f32", causal=True,
         skip_layer: Optional[int] = None):
    x = params["token_embd"][tokens]
    blocks = {k: v for k, v in params.items() if stacked(k)}

    def body(x, p_i):
        p, i = p_i
        y = _block(x, p, dims, mode, causal)
        if skip_layer is not None:
            y = jnp.where(i == skip_layer, x, y)
        return y, None

    x, _ = jax.lax.scan(body, x, (blocks, jnp.arange(dims["L"])))
    x = rms_norm(x, params["output_norm"], dims["eps"])
    logits = contract("bsd,dv->bsv", x, params["output"], mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


class Trainer(model_common.Trainer):
    loss = staticmethod(loss)
    decays = staticmethod(decays)
    leaf_norms = staticmethod(leaf_norms)


def train_flops_per_token(dims: Dict, seq_len: int) -> float:
    """``6 N + 6 d V`` and the causal half of the attention's scores and
    values, ``6 L (H dh) S``."""
    L, d, F = dims["L"], dims["d"], dims["F"]
    q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    weights = 2 * d * q + 2 * d * kv + 3 * d * F
    return (6.0 * L * weights + 6.0 * d * dims["V"]
            + 6.0 * L * q * seq_len)
