"""Plain OLMoE (Muennighoff et al. 2024; HF ``modeling_olmoe.py``): forward in
float32 jax.numpy.

No kernels, no cache, no capacity, no batching tricks; every matmul at
``jax.default_matmul_precision("highest")``.  Independent of ``tpu_dist``: it
is fed the program's parameter tree by name and knows nothing else of it.

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  RMSNorm;  head
    Attn: q = RMSNorm_d(x Wq), k = RMSNorm_d(x Wk), v = x Wv, each with its
          own weight and over the WHOLE projection, before the head split;
          heads of d / n_head; rotate-half rope (theta) on q and k; causal
          softmax(q k^T / sqrt(head)) v; Wo.  No biases.
    MoE:  p = softmax_float32(x Wr) over all experts; the top-k values and
          indices; weights used as they are (``norm_topk_prob`` false);
          out = sum_j p_j * Wdown_j (silu(x Wgate_j) * (x Wup_j)).  Every
          expert is computed densely over every token and combined under
          the top-k mask: no routing machinery to get wrong.

It follows the PROGRAM, not the publication, on what the configuration file
lists as ``departures``:

- the LM head carries a bias (OLMoE's has none); it is initialised to zero;
- the q, k and v projections are one fused matrix split [q | k | v].

The served bfloat16 parameters of the full configuration are ~9.8 GiB and
stay on the device while the verifier runs, so nothing here copies them:
``stack_params`` regroups references, the layers are a Python loop, and the
experts a ``lax.scan`` over the parameters' own leading axis that upcasts one
expert's three matrices at a time (a bfloat16 value upcasts exactly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def stack_params(config: dict, params: dict) -> dict:
    """The program's ``{path: {name: array}}`` tree regrouped by layer; the
    arrays themselves, no copies (the name is gpt2.py's, for the driver)."""
    def block(i):
        at, mlp = params[f"block{i}.attn"], params[f"block{i}.mlp"]
        return {"ln1": params[f"block{i}.ln1"]["weight"],
                "ln2": params[f"block{i}.ln2"]["weight"],
                "qkv": at["qkv_weight"], "out": at["out_weight"],
                "q_norm": at["q_norm_weight"], "k_norm": at["k_norm_weight"],
                "router": mlp["router"], "gate": mlp["w1"], "up": mlp["w3"],
                "down": mlp["w2"]}
    return {"wte": params["tok"]["weight"],
            "blocks": [block(i) for i in range(config["num_hidden_layers"])],
            "ln_f": params["ln_f"]["weight"],
            "head.weight": params["head"]["weight"],
            "head.bias": params["head"]["bias"]}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, T, H, D), positions 0..T-1, rotate-half convention."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv        # (T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def moe(config: dict, p: dict, h):
    """The expert layer on h (N, d) float32 -> (N, d)."""
    f32 = lambda a: a.astype(jnp.float32)
    probs = jax.nn.softmax(h @ f32(p["router"]), axis=-1)        # (N, E)
    vals, idx = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        vals = vals / vals.sum(-1, keepdims=True)
    # (N, E): a token's weight for each expert, zero outside its top-k
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(vals)

    def one(acc, ex):
        gate, up, down, w = ex
        out = (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)
        return acc + w[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["gate"], p["up"], p["down"], weight.T))
    return acc


def block(config: dict, p: dict, x):
    """One layer on x (B, T, d) float32, positions 0..T-1."""
    f32 = lambda a: a.astype(jnp.float32)
    n_head, eps = config["num_attention_heads"], config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    b, t, _ = x.shape
    h = _rms_norm(x, f32(p["ln1"]), eps)
    q, k, v = jnp.split(h @ f32(p["qkv"]), 3, axis=-1)
    q = _rms_norm(q, f32(p["q_norm"]), eps)
    k = _rms_norm(k, f32(p["k_norm"]), eps)
    q, k, v = (a.reshape(b, t, n_head, -1) for a in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + att.reshape(b, t, -1) @ f32(p["out"])
    h = _rms_norm(x, f32(p["ln2"]), eps)
    return x + moe(config, p, h.reshape(b * t, -1)).reshape(x.shape)


def forward(config: dict, stacked: dict, tokens):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(stacked["wte"])[tokens]
        for p in stacked["blocks"]:
            x = block(config, p, x)
        x = _rms_norm(x, f32(stacked["ln_f"]), config["rms_norm_eps"])
        return x @ f32(stacked["head.weight"]) + f32(stacked["head.bias"])
