"""Plain float32 reference of granite-4.0-h (HF ``GraniteMoeHybrid`` with no
experts), for the tests: ``jax.numpy`` under ``default_matmul_precision
("highest")``, with no cache, no chunking and no kernels.

Every layer is ``x += r * mixer(rmsnorm(x))`` then ``x += r * swiglu(rmsnorm(x))``
with ``r`` the residual multiplier. The mixer is

* Mamba2 (one group): ``in_proj`` to (z, x, B, C), ``dt_proj`` to dt (one
  matrix in the published checkpoint, two in the program); a causal depthwise
  conv of width W with bias over (x, B, C), then SiLU; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the sequential recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t (x_t ⊗ B_t)``, ``y_t = C_t · h_t + D x_t``
  from a zero state; the gated norm ``rmsnorm(y * silu(z))``; ``out_proj``;
* or GQA attention over every earlier position, scores times the attention
  multiplier, with no position embedding (NoPE).

Embeddings are multiplied by the embedding multiplier; the logits, from the
tied embedding after a final RMSNorm, are divided by the logits scaling.

Departures from the published model: the weights are the program's tree
(``mamba_layers``/``attn_layers`` stacks, read in the order of
``cfg.layer_types``); the vocabulary is padded to a multiple of 256 and the
logits cover the padded rows; the MLP's gate and up projections are two
matrices where the published checkpoint holds them as one.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision="highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def mamba(p, x, cfg):
    """Mamba2 mixer over x (B, S, d), one position at a time."""
    B, S, _ = x.shape
    di, N, H, P, W = (cfg.d_inner_ssm, cfg.ssm_state, cfg.n_ssm_heads,
                      cfg.ssm_head_dim, cfg.ssm_conv_width)
    z, xBC = jnp.split(_mm(x, p["in_proj"]["w"]), [di], axis=-1)
    dt = _mm(x, p["dt_proj"]["w"])
    padded = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = sum(padded[:, i:i + S] * w[i] for i in range(W)) + p["conv_b"].astype(F32)
    xs, Bm, Cm = jnp.split(jax.nn.silu(conv), [di, di + N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))          # (B, S, H)
    A = -jnp.exp(p["A_log"].astype(F32))

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp                                # (B,H,P) (B,N) (B,N) (B,H)
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.einsum("bn,bhpn->bhp", C_t, h, precision="highest")

    seq = (xs.transpose(1, 0, 2, 3), Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2),
           dt.transpose(1, 0, 2))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32), seq)
    y = y.transpose(1, 0, 2, 3) + p["D"].astype(F32)[:, None] * xs
    y = rmsnorm(y.reshape(B, S, di) * jax.nn.silu(z), p["gnorm"]["scale"], cfg.norm_eps)
    return _mm(y, p["out_proj"]["w"])


def attention(p, x, cfg):
    """Causal GQA over x (B, S, d), no position embedding."""
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = _mm(x, p["wq"]["w"]).reshape(B, S, H, hd)
    k = jnp.repeat(_mm(x, p["wk"]["w"]).reshape(B, S, K, hd), H // K, axis=2)
    v = jnp.repeat(_mm(x, p["wv"]["w"]).reshape(B, S, K, hd), H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * cfg.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision="highest")
    return _mm(o.reshape(B, S, H * hd), p["wo"]["w"])


def swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"]["w"])) * _mm(x, p["up"]["w"]), p["down"]["w"])


def logits(params, tokens, cfg):
    """(B, S, padded vocab) logits of the whole sequence ``tokens`` (B, S)."""
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["w"].astype(F32)
        x = table[tokens] * cfg.embedding_multiplier
        r = cfg.residual_multiplier
        n = {"mamba": 0, "attention": 0}
        for kind in cfg.layer_types:
            stack = params["mamba_layers" if kind == "mamba" else "attn_layers"]
            lp = jax.tree.map(lambda a: a[n[kind]], stack)
            n[kind] += 1
            xn = rmsnorm(x, lp["ln1"]["scale"], cfg.norm_eps)
            mix = mamba(lp["mamba"], xn, cfg) if kind == "mamba" else attention(lp["attn"], xn, cfg)
            x = x + r * mix
            x = x + r * swiglu(lp["mlp"], rmsnorm(x, lp["ln2"]["scale"], cfg.norm_eps))
        h = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        return jnp.einsum("bsd,vd->bsv", h, table, precision="highest") / cfg.logits_scaling


def ssd_recurrence(x, dt, A, B_in, C_in):
    """The SSD scan one step at a time: x (B,S,H,P), dt (B,S,H), A (H,),
    B_in/C_in (B,S,N) -> y (B,S,H,P), final state (B,H,P,N)."""
    Bsz, S, H, P = x.shape
    N = B_in.shape[-1]

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.einsum("bn,bhpn->bhp", C_t, h, precision="highest")

    seq = tuple(a.astype(F32).swapaxes(0, 1) for a in (x, B_in, C_in, dt))
    hT, y = jax.lax.scan(step, jnp.zeros((Bsz, H, P, N), F32), seq)
    return y.swapaxes(0, 1), hT
