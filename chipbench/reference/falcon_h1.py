"""Plain Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct ``config.json``,
``model_type: falcon_h1``; the Falcon-H1 report; the published
``modeling_falcon_h1.py``): forward in float32 jax.numpy.

No kernels, no cache, no chunks; every matmul at
``jax.default_matmul_precision("highest")``.  Independent of ``tpu_dist``: it
is fed the program's parameter tree by name and knows nothing else of it; the
multipliers are read from the configuration and applied HERE, where the
published code applies them (the program's seeded matrices are not folded
with them).

    x0     = E[tokens] * embedding_multiplier
    u      = N_in(x)
    h      = x + attention_out_multiplier * Attn(u * attention_in_multiplier)
               + ssm_out_multiplier * SSM(u)
    y      = h + MLP(N_ff(h))
    logits = (N_f(x_L) W_head) * lm_head_multiplier
    N(x)   = x * rsqrt(mean(x^2) + rms_norm_eps) * w

    Attn(a): q = a W_q (num_attention_heads of head_dim), k = (a W_k) *
        key_multiplier, v = a W_v (num_key_value_heads each); rope by halves
        over the whole head, rope_theta, on q and k; causal softmax(q k^T /
        sqrt(head_dim)) v, K/V head j serving the query heads [j G, (j + 1)
        G); W_o; no bias.
    MLP(a): down(up(a) * silu(gate(a) * mlp_multipliers[0])) *
        mlp_multipliers[1].
    SSM(a): p = ((a * ssm_in_multiplier) W_in) * m, W_in's columns split
        [z | x | B | C | dt] = [d_ssm | d_ssm | G N | G N | H] and m the five
        ssm_multipliers spread over those segments;
        [x | B | C] = silu(conv([x | B | C]) + b_conv), causal, depthwise,
        width mamba_d_conv; dt = softplus(dt + dt_bias), one a head;
        A = -exp(A_log), one a head; x in H heads of P = mamba_d_head; B, C
        in G groups of N = mamba_d_state, group g serving the heads [g H / G,
        (g + 1) H / G).  Per head, TOKEN BY TOKEN (a ``lax.scan`` over time),
        state S (P x N) from zero:
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
            y_t = S_t C_t + D x_t
        o = Ng(y * silu(z)), an RMSNorm over each of the G groups of d_ssm /
        G numbers (``mamba_rms_norm``, ``mamba_norm_before_gate`` false),
        one weight of d_ssm; SSM = o W_out; no bias.

``mamba_expand``, ``mlp_expansion_factor``, ``attn_layer_indices`` (null) and
``num_logits_to_keep`` size or select nothing here: ``mamba_d_ssm`` and
``intermediate_size`` are given, and every layer has both mixers.

``stack_params`` regroups references and copies nothing; the layers are a
Python loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

f32 = lambda a: a.astype(jnp.float32)

# By name, so that a control (chipbench/tests/fixture/*_control_falconh1) or
# a test can plant ONE change here: how a matrix is read for a matmul, the
# step's activation, the groups the gated norm is over, and which group's B
# and C a head reads.
_mat = f32
_dt_activation = jax.nn.softplus
_norm_groups = lambda config: config["mamba_n_groups"]
_group_of_head = lambda config: (
    np.arange(config["mamba_n_heads"])
    // (config["mamba_n_heads"] // config["mamba_n_groups"]))


def stack_params(config: dict, params: dict) -> dict:
    """The program's ``{path: {name: array}}`` tree regrouped by layer; the
    arrays themselves, no copies."""
    def block(i):
        return {"ln1": params[f"block{i}.ln1"]["weight"],
                "ln2": params[f"block{i}.ln2"]["weight"],
                "attention": dict(params[f"block{i}.attn.attention"]),
                "ssm": dict(params[f"block{i}.attn.ssm"]),
                "mlp": {name: params[f"block{i}.mlp.{name}"]["weight"]
                        for name in ("gate", "up", "down")}}
    return {"wte": params["tok"]["weight"],
            "blocks": [block(i) for i in range(config["num_hidden_layers"])],
            "ln_f": params["ln_f"]["weight"],
            "head": params["head"]["weight"]}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x (B, T, H, D), positions 0..T-1, rotate-half over all D."""
    t, d = x.shape[1], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(config: dict, p: dict, a):
    """Grouped-query attention on a (B, T, d) float32."""
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    b, t, _ = a.shape
    qkv = a @ _mat(p["qkv_weight"])                     # [q | k | v]
    q, k, v = jnp.split(qkv, [n_q * hd, (n_q + n_kv) * hd], axis=-1)
    k = k * config["key_multiplier"]
    q = _rope(q.reshape(b, t, n_q, hd), config["rope_theta"])
    k = _rope(k.reshape(b, t, n_kv, hd), config["rope_theta"])
    v = v.reshape(b, t, n_kv, hd)
    k, v = (jnp.repeat(x, n_q // n_kv, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return att.reshape(b, t, n_q * hd) @ _mat(p["out_weight"])


def _conv_silu(x, w, bias):
    """Causal depthwise convolution of x (B, T, C) by w (C, width), tap
    ``width - 1`` the current position's, plus ``bias``, then SiLU."""
    width, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + t] * w[:, j]
                           for j in range(width)) + bias)


def ssm(config: dict, p: dict, a):
    """The Mamba-2 mixer on a (B, T, d) float32, the recurrence token by
    token."""
    n_head, hd = config["mamba_n_heads"], config["mamba_d_head"]
    n_group, n_state = config["mamba_n_groups"], config["mamba_d_state"]
    d_ssm = config["mamba_d_ssm"]
    if d_ssm != n_head * hd:
        raise ValueError(f"mamba_d_ssm {d_ssm} is not mamba_n_heads x "
                         f"mamba_d_head = {n_head * hd}")
    b, t, _ = a.shape
    segments = [d_ssm, d_ssm, n_group * n_state, n_group * n_state, n_head]
    mup = jnp.asarray(np.repeat(np.float32(config["ssm_multipliers"]),
                                segments))
    proj = ((a * config["ssm_in_multiplier"]) @ _mat(p["in_weight"])) * mup
    z, xbc, dt = jnp.split(proj, [d_ssm, sum(segments[:4])], axis=-1)
    xbc = _conv_silu(xbc, f32(p["conv_weight"]), f32(p["conv_bias"]))
    x, bm, cm = jnp.split(xbc, [d_ssm, d_ssm + n_group * n_state], axis=-1)
    x = x.reshape(b, t, n_head, hd)
    by_head = lambda m: m.reshape(b, t, n_group, n_state)[
        :, :, _group_of_head(config)]                      # (B, T, H, N)
    dt = _dt_activation(dt + f32(p["dt_bias"]))            # (B, T, H)
    decay_rate, skip = -jnp.exp(f32(p["A_log"])), f32(p["D"])

    def token(s, inputs):
        x_t, b_t, c_t, dt_t = inputs        # (B, H, P), (B, H, N) x 2, (B, H)
        s = (s * jnp.exp(dt_t * decay_rate)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) + skip[:, None] * x_t

    time_first = lambda m: jnp.moveaxis(m, 1, 0)
    _, y = jax.lax.scan(
        token, jnp.zeros((b, n_head, hd, n_state), jnp.float32),
        tuple(map(time_first, (x, by_head(bm), by_head(cm), dt))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, d_ssm) * jax.nn.silu(z)
    groups = _norm_groups(config)
    y = _norm(y.reshape(b, t, groups, d_ssm // groups), 1.0,
              config["rms_norm_eps"]).reshape(b, t, d_ssm)
    return (y * f32(p["norm_weight"])) @ _mat(p["out_weight"])


def mlp(config: dict, p: dict, a):
    gate_m, down_m = config["mlp_multipliers"]
    return ((a @ _mat(p["up"])) * jax.nn.silu((a @ _mat(p["gate"])) * gate_m)
            ) @ _mat(p["down"]) * down_m


def block(config: dict, p: dict, x):
    """One layer on x (B, T, d) float32, positions 0..T-1."""
    eps = config["rms_norm_eps"]
    u = _norm(x, f32(p["ln1"]), eps)
    x = (x + config["attention_out_multiplier"] * attention(
            config, p["attention"], u * config["attention_in_multiplier"])
         + config["ssm_out_multiplier"] * ssm(config, p["ssm"], u))
    return x + mlp(config, p["mlp"], _norm(x, f32(p["ln2"]), eps))


def forward(config: dict, stacked: dict, tokens):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        x = _mat(stacked["wte"][tokens]) * config["embedding_multiplier"]
        for p in stacked["blocks"]:
            x = block(config, p, x)
        x = _norm(x, f32(stacked["ln_f"]), config["rms_norm_eps"])
        return (x @ _mat(stacked["head"])) * config["lm_head_multiplier"]
