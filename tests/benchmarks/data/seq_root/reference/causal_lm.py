"""Plain reference: a pre-LayerNorm causal transformer language model in
``jax.numpy`` (token embedding + learned positions; per block LayerNorm,
multi-head softmax attention under a causal mask, LayerNorm, a
gelu(tanh) MLP; final LayerNorm and an untied head with a bias).
Imports nothing of ``msrflute_tpu``; the tree's names are the ones the
system's module gives its parameters, so that the harness can hand the
program these weights.

Its loss is the benchmark's plain next-token loss: the model reads
``x[:, :-1]`` and is scored on ``x[:, 1:]`` at the real positions.

Init: normal(0.02) embeddings and positions, lecun-normal kernels
(untruncated), zero biases, unit LayerNorm scales.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import fedround


def _sizes(model_config: dict) -> tuple:
    return tuple(int(model_config[k]) for k in (
        "vocab_size", "embed_dim", "num_heads", "head_dim", "mlp_dim",
        "num_layers", "seq_len"))


def init(rng: np.random.Generator, model_config: dict) -> dict:
    vocab, embed, heads, head_dim, mlp, layers, seq_len = _sizes(model_config)

    def normal(shape, std):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    def dense(fan_in, fan_out, bias=True):
        layer = {"kernel": normal((fan_in, fan_out), np.sqrt(1.0 / fan_in))}
        if bias:
            layer["bias"] = np.zeros((fan_out,), np.float32)
        return layer

    def norm():
        return {"scale": np.ones((embed,), np.float32),
                "bias": np.zeros((embed,), np.float32)}

    params = {"Embed_0": {"embedding": normal((vocab, embed), 0.02)},
              "pos": normal((seq_len - 1, embed), 0.02),
              "LayerNorm_0": norm(), "Dense_0": dense(embed, vocab)}
    for i in range(layers):
        params[f"block_{i}"] = {
            "LayerNorm_0": norm(), "LayerNorm_1": norm(),
            "_MHA_0": {
                "Dense_0": dense(embed, 3 * heads * head_dim, bias=False),
                "Dense_1": dense(heads * head_dim, embed, bias=False)},
            "Dense_0": dense(embed, mlp), "Dense_1": dense(mlp, embed)}
    return params


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def _attention(x, p, heads, head_dim):
    batch, length, _ = x.shape
    qkv = (x @ p["Dense_0"]["kernel"]).reshape(
        batch, length, 3 * heads, head_dim)
    q, k, v = jnp.split(qkv, 3, axis=2)
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k) / np.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(batch, length, heads * head_dim) @ \
        p["Dense_1"]["kernel"]


def forward(params: dict, x, model_config: dict):
    """Logits ``[B, L, vocab]`` for ids ``[B, L]``, float32 throughout."""
    _, _, heads, head_dim, _, layers, _ = _sizes(model_config)
    h = params["Embed_0"]["embedding"][x] + params["pos"][:x.shape[1]]
    for i in range(layers):
        p = params[f"block_{i}"]
        h = h + _attention(_layer_norm(h, p["LayerNorm_0"]), p["_MHA_0"],
                           heads, head_dim)
        m = _layer_norm(h, p["LayerNorm_1"])
        m = jax.nn.gelu(m @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"],
                        approximate=True)
        h = h + m @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]
    h = _layer_norm(h, params["LayerNorm_0"])
    return h @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]


def loss(params: dict, batch: dict, model_config: dict):
    return fedround.next_token_loss(forward, params, batch, model_config)
