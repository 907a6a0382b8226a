"""Plain Xing4.0 (XingChen-AGI/Xing4.0-29B-A4B ``config.json``, ``model_type:
xing4_0``): the DeepSeek-V3 block inside a residual of ``hc_mult`` streams
mixed by manifold-constrained hyper-connections (Xie et al.,
arXiv:2512.24880, on Zhu et al., arXiv:2409.19606); forward in float32
jax.numpy.

No kernels, no cache, no absorbed projections, no routing machinery; every
matmul at ``jax.default_matmul_precision("highest")``.  Independent of
``tpu_dist`` and of the other references: it is fed the program's parameter
tree by name and knows nothing else of it.

    The residual, for n = hc_mult and C = hidden_size, a token's X in R^(n x C):
    open:   X_0[i] = e for i < n                  (the embedding, n copies)
    a sublayer F (attention or FFN, each behind the block's own C-wide
    RMSNorm N) with its own parameters:
      r      = RMSNorm_w(vec X)        over all n C numbers, eps = rms_norm_eps
      Hpre~  = a_pre  (r P_pre)  + b_pre        in R^n
      Hpost~ = a_post (r P_post) + b_post       in R^n
      Hres~  = a_res  mat(r P_res) + b_res      in R^(n x n)
      Hpre = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
      M_0 = exp(clip(Hres~, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
      M_t = rows(cols(M_(t-1))), t = 1..hc_sinkhorn_iters;
            cols(M) = M / (column sums + hc_eps), rows likewise;  Hres = M_last
      u  = sum_i Hpre[i] X[i];   y = F(N(u))
      X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y
    close:  x = sum_i X_L[i];  logits = head(N_f(x))

    N(x) = x * rsqrt(mean(x^2) + eps) * w
    FFN_i: dense SwiGLU, down(silu(gate x) * up x), for
        i < first_k_dense_replace or i off the moe_layer_freq grid; else
        the expert layer.
    Attn (latent, EXPANDED form only): c_q = N(x W_qa); per head
        [q_nope | q_pe] = c_q W_qb; [c_kv | k_pe] = x W_kva, k_pe one head
        shared by all; c_kv <- N(c_kv); rope on q_pe and k_pe by YaRN's
        blended frequencies (below); per head [k_nope | v] = c_kv W_kvb;
        causal softmax(s (q_nope . k_nope + q_pe . k_pe)) v with
        s = (nope + rope)^-1/2 * (0.1 mscale_all_dim ln(factor) + 1)^2;
        out = concat_heads W_o.  Computed over blocks of 512 queries, so
        the float32 scores of 32 heads x 4,096 x 4,096 never exist at once.
    YaRN (``DeepseekV3YarnRotaryEmbedding``): pair i of rope/2 keeps
        theta^(-2i/rope) where ramp_i = 0 and takes 1/factor of it where
        ramp_i = 1, ramp = clip((i - low) / (high - low), 0, 1),
        low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
        c(n) = rope ln(original_max / (2 pi n)) / (2 ln theta).  At this
        model's scalars (rope 64, theta 10,000, original 4,096, betas 32 and
        1) c(32) = 10.47 and c(1) = 22.5: pairs 0-10 keep their frequency,
        23-31 take a 64th, 11-22 the linear blend.
    Expert layer: sc = sigmoid(x W_r) over ALL the router's experts
        (``router_num_experts``); the ``num_experts_per_tok`` experts with
        the largest sc + b (b = ``router_bias``, the published
        ``e_score_correction_bias``; one group); weights
        sc_j / (sum_j sc_j + 1e-20) * routed_scaling_factor, WITHOUT b;
        routed = sum_j w_j down_j(silu(gate_j x) * up_j x) over the picks
        that fall on the experts it is GIVEN (all of them in this model's
        cell: ``n_routed_experts`` = ``router_num_experts``, ``expert_offset``
        0); every given expert is computed densely over every token and
        combined under the top-k mask.  shared = down_s(silu(gate_s x) *
        up_s x), no gate.  out = routed + shared.

It follows the PROGRAM, not the publication, on what the configuration file
lists as ``assumed`` (the streams opened as copies and closed as a sum;
columns before rows, ``hc_eps`` in both denominators; the clamp before the
exponential) and as ``departures``:

- the multi-token-prediction layer (``num_nextn_predict_layers`` 1) is not
  built;
- q_pe and k_pe are roped by halves as they come out of the projections (HF
  de-interleaves each head's pairs first): with weights drawn from a seed, a
  relabelling of W_qb's and W_kva's rope columns;
- the guard of the weights' sum is left to the publication's 1e-20 here and
  is max(sum, 1e-9) in the program: the same float32 quotient wherever four
  sigmoids sum to more than 1e-9.

``stack_params`` regroups references and copies nothing; the layers are a
Python loop and the experts a ``lax.scan`` over the parameters' own leading
axis that upcasts one expert's three matrices at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _is_moe(config: dict, i: int) -> bool:
    return (i >= config["first_k_dense_replace"]
            and i % config["moe_layer_freq"] == 0)


def stack_params(config: dict, params: dict) -> dict:
    """The program's ``{path: {name: array}}`` tree regrouped by layer; the
    arrays themselves, no copies."""
    def block(i):
        if _is_moe(config, i):
            mlp = dict(params[f"block{i}.mlp"])
        else:
            mlp = {name: params[f"block{i}.mlp.{name}"]["weight"]
                   for name in ("gate", "up", "down")}
        return {"ln1": params[f"block{i}.ln1"]["weight"],
                "ln2": params[f"block{i}.ln2"]["weight"],
                "attn": dict(params[f"block{i}.attn"]), "mlp": mlp,
                "hc_attn": dict(params[f"block{i}.hc_attn"]),
                "hc_mlp": dict(params[f"block{i}.hc_mlp"])}
    return {"wte": params["tok"]["weight"],
            "blocks": [block(i) for i in range(config["num_hidden_layers"])],
            "ln_f": params["ln_f"]["weight"],
            "head": params["head"]["weight"]}


f32 = lambda a: a.astype(jnp.float32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def yarn_inv_freq(config: dict):
    """(rope / 2,) float32 frequencies, as the published code blends
    them."""
    sc = config["rope_scaling"]
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])

    def correction_dim(rotations):
        return (dim * math.log(sc["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001       # yarn_linear_ramp_mask: prevent singularity
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = 1.0 / base ** (2.0 * pair / dim)
    inter = extra / sc["factor"]
    mask = 1.0 - jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    return inter * (1.0 - mask) + extra * mask


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rope(config: dict, x):
    """x (B, T, H, rope), positions 0..T-1, rotate-half over the halves."""
    t, d = x.shape[1], x.shape[-1]
    sc = config["rope_scaling"]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_inv_freq(config)
    m = (_mscale(sc["factor"], sc["mscale"])
         / _mscale(sc["factor"], sc["mscale_all_dim"]))
    cos = m * jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = m * jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(config: dict, p: dict, h):
    """Latent attention on h (B, T, d) float32, keys and values rebuilt for
    every position, the scores a block of queries at a time."""
    n_head, eps = config["num_attention_heads"], config["rms_norm_eps"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    r, v_dim = config["kv_lora_rank"], config["v_head_dim"]
    sc = config["rope_scaling"]
    b, t, _ = h.shape
    c_q = _norm(h @ f32(p["q_a_weight"]), f32(p["q_a_norm_weight"]), eps)
    q = (c_q @ f32(p["q_b_weight"])).reshape(b, t, n_head, nope + rope)
    kv_a = h @ f32(p["kv_a_weight"])
    c_kv = _norm(kv_a[..., :r], f32(p["kv_a_norm_weight"]), eps)
    k_pe = _rope(config, kv_a[..., None, r:])               # (B, T, 1, rope)
    kv = (c_kv @ f32(p["kv_b_weight"])).reshape(b, t, n_head, nope + v_dim)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, t, n_head, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(config, q[..., nope:])], -1)
    scale = (nope + rope) ** -0.5 * _mscale(sc["factor"],
                                            sc["mscale_all_dim"]) ** 2
    kpos = jnp.arange(t)
    out = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        qpos = lo + jnp.arange(qb.shape[1])
        scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, -1), v))
    att = jnp.concatenate(out, axis=1)
    return att.reshape(b, t, n_head * v_dim) @ f32(p["out_weight"])


def gated_mlp(gate, up, down, h):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def moe_routed(config: dict, p: dict, h):
    """The routed experts' part on h (N, d) float32: the experts given
    (``p["w1"]``'s leading axis, numbered from ``expert_offset``) under the
    top-k mask over all the router's experts."""
    scores = jax.nn.sigmoid(h @ f32(p["router"]))              # (N, E_all)
    _, idx = jax.lax.top_k(scores + f32(p["router_bias"]),
                           config["num_experts_per_tok"])
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if config["norm_topk_prob"]:
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
    vals = vals * config["routed_scaling_factor"]
    # (N, E_all): a token's weight for each expert, zero outside its top-k
    weight = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], idx].set(vals)
    first = config.get("expert_offset", 0)
    given = weight[:, first:first + p["w1"].shape[0]]

    def one(acc, ex):
        gate, up, down, w = ex
        return acc + w[:, None] * gated_mlp(gate, up, down, h), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w1"], p["w3"], p["w2"], given.T))
    return acc


def hyper_coefficients(config: dict, p: dict, xs):
    """One sublayer's ``(Hpre, Hpost, Hres)`` from the streams xs
    (..., n, C) float32: (..., n), (..., n) and (..., n, n)."""
    n, c = xs.shape[-2:]
    r = _norm(xs.reshape(xs.shape[:-2] + (n * c,)), f32(p["norm_weight"]),
              config["rms_norm_eps"])
    pre = f32(p["pre_scale"]) * (r @ f32(p["pre_weight"])) + f32(p["pre_bias"])
    post = (f32(p["post_scale"]) * (r @ f32(p["post_weight"]))
            + f32(p["post_bias"]))
    res = (f32(p["res_scale"])
           * (r @ f32(p["res_weight"])).reshape(r.shape[:-1] + (n, n))
           + f32(p["res_bias"]))
    m = jnp.exp(jnp.clip(res, config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"]))
    for _ in range(config["hc_sinkhorn_iters"]):
        m = m / (m.sum(-2, keepdims=True) + config["hc_eps"])   # columns
        m = m / (m.sum(-1, keepdims=True) + config["hc_eps"])   # rows
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


def hyper_connected(config: dict, p: dict, xs, sublayer):
    """xs (B, T, n, C) -> the streams after ``sublayer`` (a function of
    (B, T, C)) read their mix and wrote back into all of them."""
    pre, post, res = hyper_coefficients(config, p, xs)
    y = sublayer(jnp.einsum("...i,...ic->...c", pre, xs))
    return (jnp.einsum("...ij,...jc->...ic", res, xs)
            + post[..., None] * y[..., None, :])


def ffn(config: dict, i: int, mlp: dict, h):
    """Layer ``i``'s FFN on h (B, T, d) float32."""
    flat = h.reshape(-1, h.shape[-1])
    if _is_moe(config, i):
        out = moe_routed(config, mlp, flat) + gated_mlp(
            mlp["shared_w1"], mlp["shared_w3"], mlp["shared_w2"], flat)
    else:
        out = gated_mlp(mlp["gate"], mlp["up"], mlp["down"], flat)
    return out.reshape(h.shape)


def block(config: dict, i: int, p: dict, xs):
    """Layer ``i`` on the streams xs (B, T, n, C) float32, positions
    0..T-1."""
    eps = config["rms_norm_eps"]
    xs = hyper_connected(
        config, p["hc_attn"], xs,
        lambda u: attention(config, p["attn"], _norm(u, f32(p["ln1"]), eps)))
    return hyper_connected(
        config, p["hc_mlp"], xs,
        lambda u: ffn(config, i, p["mlp"], _norm(u, f32(p["ln2"]), eps)))


def forward(config: dict, stacked: dict, tokens):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        e = f32(stacked["wte"])[tokens]
        xs = jnp.broadcast_to(e[..., None, :], e.shape[:-1]
                              + (config["hc_mult"], e.shape[-1]))
        for i, p in enumerate(stacked["blocks"]):
            xs = block(config, i, p, xs)
        x = _norm(xs.sum(-2), f32(stacked["ln_f"]), config["rms_norm_eps"])
        return x @ f32(stacked["head"])
