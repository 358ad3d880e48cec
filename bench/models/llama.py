"""The Llama-style decoder (``LlamaForCausalLM``): RMSNorm, rotate-half
RoPE, grouped-query causal attention, SwiGLU, a tied head where the
configuration ties it.  A configuration names it with ``"arch": "llama"``
(``Spec.arch``).

What an architecture module gives the benchmark:

- ``shapes(cfg)``: path -> shape of every trained leaf;
- ``STACKS``: the top-level collections whose leaves carry the layers on
  a leading axis, in the order backward completes them;
- ``loss_sum(params, tokens, labels, cfg, einsum)``: the plain float32
  forward pass and summed loss, every matrix product through ``einsum``;
- ``train_flops_per_token(cfg, seq)``: model FLOPs per trained token;
- ``arch_config(cfg)``: the program's ``ArchConfig`` keyword arguments
  but its ``plan``.

Leaves are stored (in, out), the layers stacked on a leading axis:

    embed/table (V, d)            final_norm/scale (d,)
    blocks/attn/{wq,wk,wv,wo}/w   blocks/{ln1,ln2}/scale (L, d)
    blocks/mlp/{gate,up,down}/w   unembed/table (V, d), untied only
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STACKS = ("blocks",)
LAYER_NAMES = ("attn/wk/w", "attn/wo/w", "attn/wq/w", "attn/wv/w",
               "ln1/scale", "ln2/scale", "mlp/down/w", "mlp/gate/w",
               "mlp/up/w")
LOGIT_CHUNK = 512


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Path -> shape of every trained leaf."""
    L, d, ff, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    out = {
        "embed/table": (V, d),
        "final_norm/scale": (d,),
        "blocks/attn/wq/w": (L, d, q),
        "blocks/attn/wk/w": (L, d, kv),
        "blocks/attn/wv/w": (L, d, kv),
        "blocks/attn/wo/w": (L, q, d),
        "blocks/ln1/scale": (L, d),
        "blocks/ln2/scale": (L, d),
        "blocks/mlp/gate/w": (L, d, ff),
        "blocks/mlp/up/w": (L, d, ff),
        "blocks/mlp/down/w": (L, ff, d),
    }
    if not cfg["tie_embeddings"]:
        out["unembed/table"] = (V, d)
    return out


def arch_config(cfg: dict) -> dict:
    """The program's ``ArchConfig`` keyword arguments, ``plan`` aside."""
    keys = ("name", "family", "n_layers", "d_model", "n_heads",
            "n_kv_heads", "d_ff", "vocab", "head_dim", "rope", "rope_theta",
            "tie_embeddings", "norm_eps")
    return {k: cfg[k] for k in keys}


# ---------------------------------------------------------------- model
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over (B, S, heads, hd) at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, es, x, p):
    b, s, _ = x.shape
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    h = _rmsnorm(x, p["ln1/scale"], cfg["norm_eps"])
    q = es("bsd,de->bse", h, p["attn/wq/w"]).reshape(b, s, H, hd)
    k = es("bsd,de->bse", h, p["attn/wk/w"]).reshape(b, s, KV, hd)
    v = es("bsd,de->bse", h, p["attn/wv/w"]).reshape(b, s, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    q = q.reshape(b, s, KV, H // KV, hd)
    sc = es("bqkgd,bskd->bkgqs", q, k) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = es("bkgqs,bskd->bqkgd", pr, v).reshape(b, s, H * hd)
    x = x + es("bse,ed->bsd", o, p["attn/wo/w"])
    h = _rmsnorm(x, p["ln2/scale"], cfg["norm_eps"])
    g = es("bsd,df->bsf", h, p["mlp/gate/w"])
    u = es("bsd,df->bsf", h, p["mlp/up/w"])
    return x + es("bsf,fd->bsd", jax.nn.silu(g) * u, p["mlp/down/w"])


def loss_sum(params: dict, tokens, labels, cfg: dict, einsum):
    """Summed next-token cross-entropy over the batch (labels < 0 masked).
    Layers run under ``jax.checkpoint`` and the head's logits in chunks,
    so activations stay small."""
    es = einsum
    x = params["embed/table"][tokens]
    stacked = {n: params["blocks/" + n] for n in LAYER_NAMES}
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda c, p: (_layer(cfg, es, c, p), None)),
        x, stacked)
    x = _rmsnorm(x, params["final_norm/scale"], cfg["norm_eps"])
    head = params["embed/table" if cfg["tie_embeddings"] else
                   "unembed/table"]
    b, s, d = x.shape
    c = min(LOGIT_CHUNK, s)
    xs = x.reshape(b, s // c, c, d).swapaxes(0, 1)
    ls = labels.reshape(b, s // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(xc, lc):
        logits = es("bcd,vd->bcv", xc, head)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                   -1)[..., 0]
        return jnp.sum((lse - gold) * (lc >= 0))

    tot, _ = jax.lax.scan(lambda t, xl: (t + chunk(*xl), None),
                          jnp.float32(0), (xs, ls))
    return tot


# ---------------------------------------------------------- work counts
def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication, per token:
    every layer's projections and the LM head (the tied embedding counts
    once, as the head; the embedding lookup is a gather)."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q = cfg["n_heads"] * hd
    kv = cfg["n_kv_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * cfg["d_ff"]
    return cfg["n_layers"] * per_layer + cfg["vocab"] * d


def param_count(cfg: dict) -> int:
    """Every trained parameter: the matmul parameters, two RMSNorm scales
    per layer and the final norm (plus an untied head where there is one)."""
    n = matmul_params(cfg) + (2 * cfg["n_layers"] + 1) * cfg["d_model"]
    if not cfg["tie_embeddings"]:
        n += cfg["vocab"] * cfg["d_model"]
    return n


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul parameters (forward 2,
    backward 4) plus causal attention, 6 x layers x seq x heads x head_dim
    (QK^T and PV, forward and backward, half of the square kept by the
    causal mask).  Recomputation under remat is not counted."""
    attn = 6 * cfg["n_layers"] * seq * cfg["n_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + attn
