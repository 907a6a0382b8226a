"""Plain Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``; HF
``modeling_qwen3_next.py``): forward in float32 jax.numpy.

No kernels, no cache, no chunks, no routing machinery; every matmul at
``jax.default_matmul_precision("highest")``.  Independent of ``tpu_dist``: it
is fed the program's parameter tree by name and knows nothing else of it.

    h = x + Mixer_i(N(x));  y = h + MoE(N(h));  final N;  untied head
    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)            (zero-centred)
    Mixer_i: full attention where (i + 1) % full_attention_interval == 0,
             else Gated DeltaNet.
    Full attention: [q | gate | k | v] = x Wqkv; q, k through N over each
        head's dims (one weight of head_dim each, shared by the heads);
        rotate-half rope (theta) on the first head_dim * partial_rotary_factor
        dims of each head; causal softmax(q k^T / sqrt(head_dim)) v, K/V head
        j serving query heads [j G, (j + 1) G); out = (attn * sigmoid(gate)) Wo.
    Gated DeltaNet: [q | k | v | z] = x Wqkvz, [b | a] = x Wba; [q | k | v]
        through a causal depthwise convolution of width K (no bias) and SiLU;
        q, k L2-normalised per head, q scaled by Dk^-1/2, key head j serving
        value heads [j R, (j + 1) R); beta = sigmoid(b),
        g = -exp(A_log) softplus(a + dt_bias).  Per value head, TOKEN BY
        TOKEN (a ``lax.scan`` over time), state S (Dk x Dv) from zero:
            S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T
            o_t = S^T q_t
        y = rmsnorm(o; w) * silu(z) per head (plain weight), out = y Wout.
    MoE: p = softmax(x Wr) over ALL the router's experts; the top-k values
        and indices, divided by their sum (``norm_topk_prob``);
        routed = sum_j p_j Wdown_j (silu(x Wgate_j) * (x Wup_j)) over the
        picks that fall on the experts it is GIVEN (the parameters hold
        ``num_experts`` of the router's ``router_num_experts``, from
        ``expert_offset``): what the absent experts would add is left out,
        as the program leaves it out; every given expert is computed densely
        over every token and combined under the top-k mask.
        shared = sigmoid(x w_s) * Wdown_s (silu(x Wgate_s) * (x Wup_s)).

It follows the PROGRAM, not the publication, on what the configuration file
lists as ``departures``:

- the attention projections are one fused matrix split [q | gate | k | v]
  with q and gate each head-major (HF interleaves q and gate per head in
  ``q_proj``); DeltaNet's are [q | k | v | z] and [b | a] (HF groups them per
  key head): the same matmuls over relabelled columns;
- the multi-token-prediction module is not built.

``stack_params`` regroups references and copies nothing; the layers are a
Python loop and the experts a ``lax.scan`` over the parameters' own leading
axis that upcasts one expert's three matrices at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_full(config: dict, i: int) -> bool:
    return (i + 1) % config["full_attention_interval"] == 0


def stack_params(config: dict, params: dict) -> dict:
    """The program's ``{path: {name: array}}`` tree regrouped by layer; the
    arrays themselves, no copies."""
    def block(i):
        mix, mlp = params[f"block{i}.attn"], params[f"block{i}.mlp"]
        return {"ln1": params[f"block{i}.ln1"]["weight"],
                "ln2": params[f"block{i}.ln2"]["weight"],
                "mixer": dict(mix), "mlp": dict(mlp)}
    return {"wte": params["tok"]["weight"],
            "blocks": [block(i) for i in range(config["num_hidden_layers"])],
            "ln_f": params["ln_f"]["weight"],
            "head": params["head"]["weight"]}


f32 = lambda a: a.astype(jnp.float32)


def _norm(x, w, eps):
    """Zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * (
        1.0 + w)


def _rope(x, theta, rotary):
    """x (B, T, H, D), positions 0..T-1, rotate-half over the first
    ``rotary`` dims of each head."""
    t = x.shape[1]
    rot, rest = x[..., :rotary], x[..., rotary:]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv     # (T, rotary/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = rot[..., :rotary // 2], rot[..., rotary // 2:]
    return jnp.concatenate(
        [rot * cos + jnp.concatenate([-x2, x1], -1) * sin, rest], -1)


def attention(config: dict, p: dict, h):
    """Gated full attention on h (B, T, d) float32."""
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    b, t, _ = h.shape
    q, gate, k, v = jnp.split(h @ f32(p["qkv_weight"]), [
        n_q * hd, 2 * n_q * hd, 2 * n_q * hd + n_kv * hd], axis=-1)
    q = _norm(q.reshape(b, t, n_q, hd), f32(p["q_norm_weight"]), eps)
    k = _norm(k.reshape(b, t, n_kv, hd), f32(p["k_norm_weight"]), eps)
    v = v.reshape(b, t, n_kv, hd)
    rotary = int(hd * config["partial_rotary_factor"])
    theta = float(config["rope_theta"])
    q, k = _rope(q, theta, rotary), _rope(k, theta, rotary)
    k, v = (jnp.repeat(a, n_q // n_kv, axis=2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return (att.reshape(b, t, -1) * jax.nn.sigmoid(gate)) @ f32(
        p["out_weight"])


def delta_net(config: dict, p: dict, h):
    """Gated DeltaNet on h (B, T, d) float32, the recurrence token by
    token."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    width = config["linear_conv_kernel_dim"]
    b, t, _ = h.shape
    key_dim, value_dim = hk * dk, hv * dv
    mixed, z = jnp.split(h @ f32(p["qkvz_weight"]), [2 * key_dim + value_dim],
                         axis=-1)
    beta_in, a = jnp.split(h @ f32(p["ba_weight"]), 2, axis=-1)
    # causal depthwise convolution: tap ``width - 1`` is the current position
    w = f32(p["conv_weight"])                                  # (C, width)
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + t] * w[:, j]
                            for j in range(width)))
    q, k, v = jnp.split(mixed, [key_dim, 2 * key_dim], axis=-1)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True)
                                     + 1e-6)
    q = l2(q.reshape(b, t, hk, dk)) * dk ** -0.5
    k = l2(k.reshape(b, t, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    v = v.reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(beta_in)                              # (B, T, Hv)
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(a + f32(p["dt_bias"]))

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x          # (B, Hv, .) each
        s = s * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + k_t[..., :, None] * (beta_t[..., None] * r)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    time_first = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((b, hv, dk, dv), jnp.float32),
                        tuple(map(time_first, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1)                                   # (B, T, Hv, Dv)
    o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                          + config["rms_norm_eps"]) * f32(p["norm_weight"])
    y = o * jax.nn.silu(z.reshape(b, t, hv, dv))
    return y.reshape(b, t, value_dim) @ f32(p["out_weight"])


def moe_routed(config: dict, p: dict, h):
    """The routed experts' part on h (N, d) float32: the experts given
    (``p["w1"]``'s leading axis, numbered from ``expert_offset``) under the
    top-k mask over all the router's experts."""
    probs = jax.nn.softmax(h @ f32(p["router"]), axis=-1)       # (N, E_all)
    vals, idx = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        vals = vals / vals.sum(-1, keepdims=True)
    # (N, E_all): a token's weight for each expert, zero outside its top-k
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(vals)
    first = config.get("expert_offset", 0)
    given = weight[:, first:first + p["w1"].shape[0]]

    def one(acc, ex):
        gate, up, down, w = ex
        out = (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)
        return acc + w[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w1"], p["w3"], p["w2"], given.T))
    return acc


def moe_shared(p: dict, h):
    """The shared expert under its sigmoid gate, on h (N, d) float32."""
    out = (jax.nn.silu(h @ f32(p["shared_w1"])) * (h @ f32(p["shared_w3"]))
           ) @ f32(p["shared_w2"])
    return jax.nn.sigmoid(h @ f32(p["shared_gate"])) * out


def block(config: dict, i: int, p: dict, x):
    """Layer ``i`` on x (B, T, d) float32, positions 0..T-1."""
    eps = config["rms_norm_eps"]
    mixer = attention if _is_full(config, i) else delta_net
    x = x + mixer(config, p["mixer"], _norm(x, f32(p["ln1"]), eps))
    h = _norm(x, f32(p["ln2"]), eps).reshape(-1, x.shape[-1])
    return x + (moe_routed(config, p["mlp"], h)
                + moe_shared(p["mlp"], h)).reshape(x.shape)


def forward(config: dict, stacked: dict, tokens):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        x = f32(stacked["wte"])[tokens]
        for i, p in enumerate(stacked["blocks"]):
            x = block(config, i, p, x)
        x = _norm(x, f32(stacked["ln_f"]), config["rms_norm_eps"])
        return x @ f32(stacked["head"])
