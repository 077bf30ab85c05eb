"""Dense pre-norm decoder (the Llama layout that deepseek-llm-7b uses).

The plain reference of the family: RMSNorm, rotary embeddings on the two
halves of each head, causal softmax attention with grouped K/V heads, a
SwiGLU MLP, a final RMSNorm and an untied output head (arXiv:2401.02954
§2, which follows Llama).  It is written from that description in
``jax.numpy`` with every contraction at an explicit precision, and
imports nothing of the system under test.

Beside the reference: where each weight sits in the parameter tree the
program is handed, how it is drawn, and the operations and bytes of one
decode launch and of one token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.precision import einsum

def dims(sizes: dict) -> dict:
    d, h, kv, hd = (sizes["hidden_size"], sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    return {"d": d, "h": h, "kv": kv, "hd": hd, "qd": h * hd,
            "kvd": kv * hd, "f": sizes["intermediate_size"],
            "v": sizes["vocab_size"], "layers": sizes["num_hidden_layers"]}


def program_fields(sizes: dict) -> dict:
    """The program's ``ModelConfig`` fields these sizes must match."""
    return {"family": "dense", "d_model": sizes["hidden_size"],
            "n_heads": sizes["num_attention_heads"],
            "n_kv_heads": sizes["num_key_value_heads"],
            "hd": sizes["head_dim"], "d_ff": sizes["intermediate_size"],
            "vocab": sizes["vocab_size"],
            "n_layers": sizes["num_hidden_layers"],
            "norm_eps": sizes["rms_norm_eps"],
            "rope_theta": sizes["rope_theta"], "tie_embeddings": False,
            "qkv_bias": False, "activation": "silu"}


def weight_spec(sizes: dict) -> dict:
    """``path -> (shape, draw)`` for every weight, in the layout of the
    parameter tree the program binds.  ``draw`` is ``("normal", std)`` or
    ``("one_plus_normal", std)``."""
    m = dims(sizes)
    d, n = m["d"], m["layers"]
    return {
        "embed": ((m["v"], d), ("normal", 0.02)),
        "lm_head": ((d, m["v"]), ("normal", d ** -0.5)),
        "final_ln": ((d,), ("one_plus_normal", 0.1)),
        "blocks/attn/ln": ((n, 1, d), ("one_plus_normal", 0.1)),
        "blocks/attn/wq": ((n, 1, d, m["qd"]), ("normal", d ** -0.5)),
        "blocks/attn/wk": ((n, 1, d, m["kvd"]), ("normal", d ** -0.5)),
        "blocks/attn/wv": ((n, 1, d, m["kvd"]), ("normal", d ** -0.5)),
        "blocks/attn/wo": ((n, 1, m["qd"], d), ("normal", m["qd"] ** -0.5)),
        "blocks/mlp/ln": ((n, 1, d), ("one_plus_normal", 0.1)),
        "blocks/mlp/wi": ((n, 1, d, 2, m["f"]), ("normal", d ** -0.5)),
        "blocks/mlp/wo": ((n, 1, m["f"], d), ("normal", m["f"] ** -0.5)),
    }


# ---------------------------------------------------------------- reference

def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def hidden(w: dict, sizes: dict, tokens, precision):
    """Final-normed hidden states ``(B, L, d)`` of a teacher-forced pass
    over ``tokens (B, L)`` at positions ``0..L-1``."""
    m = dims(sizes)
    eps = sizes["rms_norm_eps"]
    b, L = tokens.shape
    mm = lambda eq, a, c: einsum(eq, a, c, precision)
    h = w["embed"][tokens]
    half = m["hd"] // 2
    inv = sizes["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32)
                                  / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv     # (L, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rot(x):  # (B, L, heads, hd): rotate the two halves of each head
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((L, L), bool))
    g = m["h"] // m["kv"]
    a, f = w["blocks"]["attn"], w["blocks"]["mlp"]
    for i in range(m["layers"]):
        x = _rmsnorm(h, a["ln"][i, 0], eps)
        q = rot(mm("bld,de->ble", x, a["wq"][i, 0]).reshape(
            b, L, m["h"], m["hd"]))
        k = rot(mm("bld,de->ble", x, a["wk"][i, 0]).reshape(
            b, L, m["kv"], m["hd"]))
        v = mm("bld,de->ble", x, a["wv"][i, 0]).reshape(
            b, L, m["kv"], m["hd"])
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(m["hd"])
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, axis=-1), v)
        h = h + mm("ble,ed->bld", o.reshape(b, L, m["qd"]), a["wo"][i, 0])
        x = _rmsnorm(h, f["ln"][i, 0], eps)
        gu = mm("bld,dgf->blgf", x, f["wi"][i, 0])
        h = h + mm("blf,fd->bld", jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :],
                   f["wo"][i, 0])
    return _rmsnorm(h, w["final_ln"], eps)


def head(w: dict) -> jax.Array:
    return w["lm_head"]


# ------------------------------------------------------- operations, bytes

WEIGHT_BYTES = 2     # weights are bf16 values: what a deployment reads
STATE_BYTES = 4      # the K/V cache and the logits are f32


def matmul_params(sizes: dict) -> int:
    """Weights a token multiplies with: every layer's and the head."""
    m = dims(sizes)
    layer = (m["d"] * (m["qd"] + 2 * m["kvd"]) + m["qd"] * m["d"]
             + 3 * m["d"] * m["f"])
    return m["layers"] * layer + m["d"] * m["v"]


def vector_params(sizes: dict) -> int:
    m = dims(sizes)
    return m["layers"] * 2 * m["d"] + m["d"]


def token_flops(sizes: dict, position: int) -> int:
    """Model operations of one token at ``position`` (it attends to
    ``position + 1`` keys): two per weight multiply-add, plus QK^T and
    PV over the live keys."""
    m = dims(sizes)
    attn = 4 * (position + 1) * m["h"] * m["hd"]
    return 2 * matmul_params(sizes) + m["layers"] * attn


def decode_launch(sizes: dict, positions) -> tuple:
    """``(flops, bytes)`` the algorithm needs for one decode launch that
    feeds one token to each active slot at ``positions``: every weight
    but the embedding table read once at 2 B, the embedding rows
    gathered, K/V read up to each live length and the new K/V written
    at 4 B, the logits written at 4 B."""
    m = dims(sizes)
    b = len(positions)
    flops = sum(token_flops(sizes, int(p)) for p in positions)
    live = sum(int(p) + 1 for p in positions)
    weights = (matmul_params(sizes) + vector_params(sizes)) * WEIGHT_BYTES
    embed = b * m["d"] * WEIGHT_BYTES
    kv = m["layers"] * 2 * m["kvd"] * STATE_BYTES * (live + b)
    logits = b * m["v"] * STATE_BYTES
    return flops, weights + embed + kv + logits
