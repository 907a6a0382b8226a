"""Plain Kimi Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``,
``model_type: kimi_linear``; the Kimi Linear report, arXiv:2510.26692; the
published ``modeling_kimi.py``): forward in float32 jax.numpy.

No kernels, no cache, no chunks, no absorbed projections, no routing
machinery; every matmul at ``jax.default_matmul_precision("highest")``.
Independent of ``tpu_dist``: it is fed the program's parameter tree by name
and knows nothing else of it.

    h = x + Mixer_i(N(x));  y = h + FFN_i(N(h));  final N;  untied head
    N(x) = x * rsqrt(mean(x^2) + eps) * w
    Mixer_i: Kimi Delta Attention where i + 1 is in
        ``linear_attn_config.kda_layers``, latent attention where it is in
        ``linear_attn_config.full_attn_layers`` (1-based, the published
        lists; a file of fewer layers reads the entries it has layers for).
    FFN_i: dense SwiGLU, down(silu(gate x) * up x), for
        i < first_k_dense_replace or i off the moe_layer_freq grid; else the
        expert layer.
    KDA (H heads of D = ``linear_attn_config.head_dim``, keys and values
        alike): q, k, v = x W_q, x W_k, x W_v, EACH through a causal
        depthwise convolution of its own of width ``short_conv_kernel_size``
        (no bias) and SiLU; q, k L2-normalised per head, q scaled by D^-1/2;
        beta = sigmoid(x W_b), one number a head;
        g = -exp(A_log_h) * softplus((x W_fa) W_fb + dt_bias), D numbers a
        head, <= 0.  Per head, TOKEN BY TOKEN (a ``lax.scan`` over time),
        state S (D x D) from zero:
            S <- Diag(exp(g_t)) S;  r = v_t - S^T k_t;
            S <- S + k_t (beta_t r)^T;  o_t = S^T q_t
        y = rmsnorm(o; w) * sigmoid((x W_ga) W_gb) per head, out = y W_o.
    Latent attention (EXPANDED form only; no query rank, NO rotation:
        ``q_lora_rank: null``, ``mla_use_nope: true``): per head
        [q_nope | q_pe] = x W_q; [c_kv | k_pe] = x W_kva, k_pe one head
        shared by all; c_kv <- N(c_kv); per head [k_nope | v] = c_kv W_kvb;
        causal softmax((nope + rope)^-1/2 (q_nope . k_nope + q_pe . k_pe)) v;
        out = concat_heads W_o.
    Expert layer: sc = sigmoid(x W_r) over ALL the router's experts
        (``router_num_experts``); the ``num_experts_per_token`` experts with
        the largest sc + b (b = ``router_bias``, the published
        ``e_score_correction_bias``; one group); weights
        sc_j / (sum_j sc_j + 1e-20) * routed_scaling_factor, WITHOUT b
        (``moe_renormalize``); routed = sum_j w_j down_j(silu(gate_j x) *
        up_j x) over the picks that fall on the experts it is GIVEN (the
        parameters hold ``num_experts`` of the router's, from
        ``expert_offset``): what the absent experts would add is left out,
        as the program leaves it out; every given expert is computed densely
        over every token and combined under the top-k mask.  shared =
        down_s(silu(gate_s x) * up_s x), no gate.  out = routed + shared.

It follows the PROGRAM, not the publication, on what the configuration file
lists as ``departures``:

- the output gate's second projection has no bias (ISSUE 40's equations;
  flash-linear-attention's layer gives ``g_b_proj`` one);
- the guard of the weights' sum is left to the publication's 1e-20 here and
  is max(sum, 1e-9) in the program: the same float32 quotient wherever
  eight sigmoids sum to more than 1e-9.

``stack_params`` regroups references and copies nothing; the layers are a
Python loop and the experts a ``lax.scan`` over the parameters' own leading
axis that upcasts one expert's three matrices at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_moe(config: dict, i: int) -> bool:
    return (i >= config["first_k_dense_replace"]
            and i % config["moe_layer_freq"] == 0)


def _is_kda(config: dict, i: int) -> bool:
    """Layer ``i`` (0-based) by the two published lists (1-based)."""
    lists = config["linear_attn_config"]
    kda, full = i + 1 in lists["kda_layers"], i + 1 in lists["full_attn_layers"]
    if kda == full:
        raise ValueError(f"layer {i + 1} is in {'both' if kda else 'neither'}"
                         f" of kda_layers and full_attn_layers")
    return kda


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
                "mixer": dict(params[f"block{i}.attn"]), "mlp": mlp}
    return {"wte": params["tok"]["weight"],
            "blocks": [block(i) for i in range(config["num_hidden_layers"])],
            "ln_f": params["ln_f"]["weight"],
            "head": params["head"]["weight"]}


f32 = lambda a: a.astype(jnp.float32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _conv_silu(x, w):
    """Causal depthwise convolution of x (B, T, C) by w (C, width), tap
    ``width - 1`` the current position's, then SiLU."""
    width, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + t] * w[:, j]
                           for j in range(width)))


# the output gate's activation, and what a latent layer does to q_pe and k_pe
# (nothing: ``mla_use_nope``), by name, so that a control
# (chipbench/tests/fixture/fault_control_kimilinear) can plant a fault here
_output_gate = jax.nn.sigmoid
_positions = lambda config, x: x


def kda_gates(config: dict, p: dict, h):
    """The forget gate's log decay (B, T, H, D), <= 0, and beta (B, T, H)
    of h (B, T, d) float32."""
    lin = config["linear_attn_config"]
    n_head, hd = lin["num_heads"], lin["head_dim"]
    b, t, _ = h.shape
    f = ((h @ f32(p["f_a_weight"])) @ f32(p["f_b_weight"])
         + f32(p["dt_bias"])).reshape(b, t, n_head, hd)
    g = -jnp.exp(f32(p["A_log"]))[:, None] * jax.nn.softplus(f)
    return g, jax.nn.sigmoid(h @ f32(p["b_weight"]))


def kda(config: dict, p: dict, h):
    """Kimi Delta Attention on h (B, T, d) float32, the recurrence token by
    token."""
    lin = config["linear_attn_config"]
    n_head, hd = lin["num_heads"], lin["head_dim"]
    b, t, _ = h.shape
    heads = lambda x: x.reshape(b, t, n_head, hd)
    q, k, v = (heads(_conv_silu(h @ f32(p[f"{c}_weight"]),
                                f32(p[f"{c}_conv_weight"])))
               for c in ("q", "k", "v"))
    l2 = lambda x: x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True)
                                     + 1e-6)
    q, k = l2(q) * hd ** -0.5, l2(k)
    g, beta = kda_gates(config, p, h)

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x      # (B, H, D) but beta_t (B, H)
        s = s * jnp.exp(g_t)[..., None]     # a decay a ROW of the state
        r = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + k_t[..., :, None] * (beta_t[..., None] * r)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    time_first = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((b, n_head, hd, hd), jnp.float32),
                        tuple(map(time_first, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1)                               # (B, T, H, D)
    o = _norm(o, f32(p["norm_weight"]), config["rms_norm_eps"])
    gate = _output_gate((h @ f32(p["g_a_weight"])) @ f32(p["g_b_weight"]))
    return (o * heads(gate)).reshape(b, t, n_head * hd) @ f32(
        p["out_weight"])


def attention(config: dict, p: dict, h):
    """Latent attention without positions on h (B, T, d) float32, keys and
    values rebuilt for every position."""
    n_head, eps = config["num_attention_heads"], config["rms_norm_eps"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    r, v_dim = config["kv_lora_rank"], config["v_head_dim"]
    if config["q_lora_rank"] is not None or not config["mla_use_nope"]:
        raise NotImplementedError("this reference is the published form: "
                                  "q_lora_rank null, mla_use_nope true")
    b, t, _ = h.shape
    q = (h @ f32(p["q_weight"])).reshape(b, t, n_head, nope + rope)
    q = jnp.concatenate([q[..., :nope], _positions(config, q[..., nope:])],
                        -1)
    kv_a = h @ f32(p["kv_a_weight"])
    c_kv = _norm(kv_a[..., :r], f32(p["kv_a_norm_weight"]), eps)
    k_pe = _positions(config, kv_a[..., None, r:])          # (B, T, 1, rope)
    kv = (c_kv @ f32(p["kv_b_weight"])).reshape(b, t, n_head, nope + v_dim)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, t, n_head, rope))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (nope + rope) ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     kv[..., nope:])
    return att.reshape(b, t, n_head * v_dim) @ f32(p["out_weight"])


def gated_mlp(gate, up, down, h):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def moe_routed(config: dict, p: dict, h):
    """The routed experts' part on h (N, d) float32: the experts given
    (``p["w1"]``'s leading axis, numbered from ``expert_offset``) under the
    top-k mask over all the router's experts."""
    scores = jax.nn.sigmoid(h @ f32(p["router"]))              # (N, E_all)
    _, idx = jax.lax.top_k(scores + f32(p["router_bias"]),
                           config["num_experts_per_token"])
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if config["moe_renormalize"]:
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


def moe_shared(p: dict, h):
    """The shared expert, no gate, on h (N, d) float32."""
    return gated_mlp(p["shared_w1"], p["shared_w3"], p["shared_w2"], h)


def block(config: dict, i: int, p: dict, x):
    """Layer ``i`` on x (B, T, d) float32, positions 0..T-1."""
    eps = config["rms_norm_eps"]
    mixer = kda if _is_kda(config, i) else attention
    x = x + mixer(config, p["mixer"], _norm(x, f32(p["ln1"]), eps))
    h = _norm(x, f32(p["ln2"]), eps).reshape(-1, x.shape[-1])
    mlp = p["mlp"]
    if _is_moe(config, i):
        out = moe_routed(config, mlp, h) + moe_shared(mlp, h)
    else:
        out = gated_mlp(mlp["gate"], mlp["up"], mlp["down"], h)
    return x + out.reshape(x.shape)


def forward(config: dict, stacked: dict, tokens):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        x = f32(stacked["wte"])[tokens]
        for i, p in enumerate(stacked["blocks"]):
            x = block(config, i, p, x)
        x = _norm(x, f32(stacked["ln_f"]), config["rms_norm_eps"])
        return x @ f32(stacked["head"])
