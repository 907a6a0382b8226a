"""Plain GPT-2 (Radford et al. 2019): forward and loss in float32 jax.numpy.

No kernels, no cache, no batching tricks; every matmul at
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise computed in bfloat16 passes).  Independent of ``tpu_dist``: it is
fed the program's parameter tree by name and knows nothing else of it.

Pre-LN blocks, learned positions, fused qkv projection split [q | k | v],
``n_head`` heads, a 4x MLP, a final LayerNorm.  It follows the PROGRAM, not
the paper, on what the configuration files list as ``departures``:

- the LM head is its own matrix with a bias (GPT-2 ties it to the token
  embedding and has no bias);
- GELU is the exact erf form (GPT-2's ``gelu_new`` is the tanh form);
- no dropout (the published config trains with 0.1).

The blocks run under ``lax.scan`` over stacked parameters: the mathematics
is that of a loop, and the program compiles in seconds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# (module path inside a block, tensor name) of the program's block parameters
_BLOCK = [("ln1", "weight"), ("ln1", "bias"),
          ("attn", "qkv_weight"), ("attn", "qkv_bias"),
          ("attn", "out_weight"), ("attn", "out_bias"),
          ("ln2", "weight"), ("ln2", "bias"),
          ("mlp.0", "weight"), ("mlp.0", "bias"),
          ("mlp.2", "weight"), ("mlp.2", "bias")]


def stack_params(config: dict, params: dict) -> dict:
    """The program's ``{path: {name: array}}`` tree -> embeddings, head, and
    per-block tensors stacked along a leading layer axis (dtypes kept; the
    forward casts to float32 where it computes)."""
    blocks = {f"{path}.{leaf}": jnp.stack(
                  [params[f"block{i}.{path}"][leaf]
                   for i in range(config["n_layer"])])
              for path, leaf in _BLOCK}
    return {"wte": params["tok"]["weight"], "wpe": params["pos"]["weight"],
            "blocks": blocks,
            "ln_f.weight": params["ln_f"]["weight"],
            "ln_f.bias": params["ln_f"]["bias"],
            "head.weight": params["head"]["weight"],
            "head.bias": params["head"]["bias"]}


def _layer_norm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def forward(config: dict, stacked: dict, tokens):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    f32 = lambda a: a.astype(jnp.float32)
    n_head, eps = config["n_head"], config["layer_norm_epsilon"]
    b, t = tokens.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, p):
        p = {k: f32(v) for k, v in p.items()}
        h = _layer_norm(x, p["ln1.weight"], p["ln1.bias"], eps)
        qkv = h @ p["attn.qkv_weight"] + p["attn.qkv_bias"]
        q, k, v = (a.reshape(b, t, n_head, -1) for a in
                   jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        scores = jnp.where(causal, scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + att.reshape(b, t, -1) @ p["attn.out_weight"] + p["attn.out_bias"]
        h = _layer_norm(x, p["ln2.weight"], p["ln2.bias"], eps)
        h = jax.nn.gelu(h @ p["mlp.0.weight"] + p["mlp.0.bias"],
                        approximate=False)
        return x + h @ p["mlp.2.weight"] + p["mlp.2.bias"], None

    with jax.default_matmul_precision("highest"):
        x = f32(stacked["wte"])[tokens] + f32(stacked["wpe"])[:t]
        x, _ = jax.lax.scan(block, x, stacked["blocks"])
        x = _layer_norm(x, f32(stacked["ln_f.weight"]),
                        f32(stacked["ln_f.bias"]), eps)
        return x @ f32(stacked["head.weight"]) + f32(stacked["head.bias"])


def loss(config: dict, stacked: dict, tokens, labels):
    """Mean next-token cross-entropy of ``labels`` (B, T) under the model."""
    logp = jax.nn.log_softmax(forward(config, stacked, tokens), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
