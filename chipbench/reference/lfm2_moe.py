"""Plain LFM2-MoE (LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type:
lfm2_moe``; the published ``modeling_lfm2_moe.py``): forward in float32
jax.numpy.

No kernels, no cache, no batching of picks, no routing machinery; every
matmul at ``jax.default_matmul_precision("highest")``.  Independent of
``tpu_dist``: it is fed the program's parameter tree by name and knows nothing
else of it.

    h = x + Op_i(N(x));  y = h + FFN_i(N(h));  final N (the published
    ``embedding_norm``);  untied head
    N(x) = x * rsqrt(mean(x^2) + norm_eps) * w
    Op_i, ``layer_types[i] == "conv"``: [B | C | u] = x W_in (d -> 3 d, no
        bias); s_t = B_t * u_t; POSITION BY POSITION (a ``lax.scan`` over
        time that carries the last ``conv_L_cache - 1`` values of s, zeros
        before the first position):
            c_t = sum_j w[:, j] * s_{t - (K - 1) + j}
        depthwise, causal, ``conv_bias`` false, NO activation;
        out = (C_t * c_t) W_out.
    Op_i, ``"full_attention"``: q, k, v by bias-free projections to
        ``num_attention_heads`` / ``num_key_value_heads`` heads of d /
        num_attention_heads; N over each query head and each key head (a
        weight of the head's size, read as 1 + the program's zero-centred
        leaf; norm_eps) BEFORE the rotation; rope by
        halves over the whole head, ``rope_parameters.rope_theta``; causal
        softmax(q k^T / sqrt(head)) v, K/V head j serving the query heads
        [j G, (j + 1) G); bias-free W_o.
    FFN_i, i < ``num_dense_layers``: W_2 (silu(x W_1) * (x W_3)),
        ``intermediate_size`` wide.  Else the expert layer: z = x W_r; p =
        sigmoid(z); the ``num_experts_per_tok`` experts of largest p + b
        (``use_expert_bias``; b = ``router_bias``, the published
        ``expert_bias``); weights p of those, WITHOUT b, over (their sum +
        1e-6) (``norm_topk_prob``), times ``routed_scaling_factor``; each
        expert the same SwiGLU ``moe_intermediate_size`` wide; no shared
        expert.  Every expert is computed densely over every token and
        combined under the top-k mask.

``stack_params`` regroups references and copies nothing; the layers are a
Python loop and the experts a ``lax.scan`` over the parameters' own leading
axis that upcasts one expert's three matrices at a time, so that the float32
copies never stand beside the program's parameters all at once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = lambda a: a.astype(jnp.float32)

# By name, so that a control (chipbench/tests/fixture/*_control_lfm2moe) or a
# test can plant ONE change here: how a matrix is read for a matmul, the
# convolution's activation (none), its two gates, the order of its taps, where
# the head norms stand (and ``_head_norm``, below), which layers are dense,
# and what the picks' weights are made of.
_mat = f32
_conv_activation = lambda c: c
_gate_in = lambda b, u: b * u
_gate_out = lambda c, mixed: c * mixed
_taps = lambda w: w
_norm_then_rope = True
_is_moe = lambda config, i: i >= config["num_dense_layers"]
_pick_weights = lambda scores, bias: scores
_normalized = lambda vals: vals / (vals.sum(-1, keepdims=True) + 1e-6)


def stack_params(config: dict, params: dict) -> dict:
    """The program's ``{path: {name: array}}`` tree regrouped by layer; the
    arrays themselves, no copies.  A layer holds ``mlp`` (the dense SwiGLU's
    three matrices) or ``moe`` (the expert layer's leaves), whichever the
    program has."""
    def block(i):
        out = {"ln1": params[f"block{i}.ln1"]["weight"],
               "ln2": params[f"block{i}.ln2"]["weight"],
               "mixer": dict(params[f"block{i}.attn"])}
        if f"block{i}.mlp" in params:
            out["moe"] = dict(params[f"block{i}.mlp"])
        else:
            out["mlp"] = {name: params[f"block{i}.mlp.{name}"]["weight"]
                          for name in ("gate", "up", "down")}
        return out
    return {"wte": params["tok"]["weight"],
            "blocks": [block(i) for i in range(config["num_hidden_layers"])],
            "ln_f": params["ln_f"]["weight"],
            "head": params["head"]["weight"]}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


# the norm over each query and key head, by name like the hooks above
_head_norm = _norm


def _rope(x, theta: float):
    """x (B, T, H, D), positions 0..T-1, rotate-half over all D."""
    t, d = x.shape[1], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def short_conv(config: dict, p: dict, a):
    """The gated short convolution on a (B, T, d) float32, the convolution
    position by position."""
    b_gate, c_gate, u = jnp.split(a @ _mat(p["in_weight"]), 3, axis=-1)
    s = _gate_in(b_gate, u)                                  # (B, T, d)
    w = _taps(f32(p["conv_weight"]))                         # (d, K)
    taps = config["conv_L_cache"]
    if w.shape[1] != taps:
        raise ValueError(f"conv_weight holds {w.shape[1]} taps, "
                         f"conv_L_cache says {taps}")
    if config["conv_bias"]:
        raise NotImplementedError("conv_bias true is not the published "
                                  "model")

    def position(tail, s_t):
        # tail (B, K - 1, d), oldest first: s_{t-K+1} .. s_{t-1}
        window = jnp.concatenate([tail, s_t[:, None]], axis=1)
        return window[:, 1:], jnp.einsum("bkd,dk->bd", window, w)

    zeros = jnp.zeros((a.shape[0], taps - 1, a.shape[-1]), jnp.float32)
    _, mixed = jax.lax.scan(position, zeros, jnp.moveaxis(s, 1, 0))
    mixed = _conv_activation(jnp.moveaxis(mixed, 0, 1))
    return _gate_out(c_gate, mixed) @ _mat(p["out_weight"])


def attention(config: dict, p: dict, a):
    """Grouped-query attention with head norms on a (B, T, d) float32."""
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // n_q
    theta, eps = config["rope_parameters"]["rope_theta"], config["norm_eps"]
    b, t, _ = a.shape
    qkv = a @ _mat(p["qkv_weight"])                     # [q | k | v]
    q, k, v = jnp.split(qkv, [n_q * hd, (n_q + n_kv) * hd], axis=-1)
    q, k = q.reshape(b, t, n_q, hd), k.reshape(b, t, n_kv, hd)
    # the program holds a head norm's weight zero-centred (the published w
    # is 1 + the leaf: the configuration's ``departures``)
    qn = lambda x: _head_norm(x, 1.0 + f32(p["q_norm_weight"]), eps)
    kn = lambda x: _head_norm(x, 1.0 + f32(p["k_norm_weight"]), eps)
    if _norm_then_rope:
        q, k = _rope(qn(q), theta), _rope(kn(k), theta)
    else:
        q, k = qn(_rope(q, theta)), kn(_rope(k, theta))
    v = v.reshape(b, t, n_kv, hd)
    k, v = (jnp.repeat(x, n_q // n_kv, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(b, t, n_q * hd) @ _mat(p["out_weight"])


def gated_mlp(gate, up, down, h):
    return (jax.nn.silu(h @ _mat(gate)) * (h @ _mat(up))) @ _mat(down)


def moe(config: dict, p: dict, h):
    """The expert layer on h (N, d) float32: every expert under the top-k
    mask."""
    scores = jax.nn.sigmoid(h @ _mat(p["router"]))              # (N, E)
    bias = f32(p["router_bias"]) if config["use_expert_bias"] else 0.0
    _, idx = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])
    vals = jnp.take_along_axis(_pick_weights(scores, bias), idx, axis=-1)
    if config["norm_topk_prob"]:
        vals = _normalized(vals)
    vals = vals * config["routed_scaling_factor"]
    # (N, E): a token's weight for each expert, zero outside its top-k
    weight = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], idx].set(vals)

    def one(acc, ex):
        gate, up, down, w = ex
        return acc + w[:, None] * gated_mlp(gate, up, down, h), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w1"], p["w3"], p["w2"], weight.T))
    return acc


def block(config: dict, i: int, p: dict, x):
    """Layer ``i`` on x (B, T, d) float32, positions 0..T-1."""
    eps, kind = config["norm_eps"], config["layer_types"][i]
    if kind not in ("conv", "full_attention"):
        raise ValueError(f"layer_types[{i}] is {kind!r}")
    mixer = short_conv if kind == "conv" else attention
    x = x + mixer(config, p["mixer"], _norm(x, f32(p["ln1"]), eps))
    h = _norm(x, f32(p["ln2"]), eps).reshape(-1, x.shape[-1])
    if _is_moe(config, i):
        out = moe(config, p["moe"], h)
    else:
        out = gated_mlp(p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"],
                        h)
    return x + out.reshape(x.shape)


def forward(config: dict, stacked: dict, tokens):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        x = _mat(stacked["wte"][tokens])
        for i, p in enumerate(stacked["blocks"]):
            x = block(config, i, p, x)
        x = _norm(x, f32(stacked["ln_f"]), config["norm_eps"])
        return x @ _mat(stacked["head"])
