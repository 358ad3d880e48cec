"""The DeepSeek-V3 decoder (``model_type`` ``deepseek_v3``, as Moonlight
uses it): latent attention (MLA), ``first_k_dense_replace`` dense SwiGLU
layers, then sparse-expert layers with sigmoid routing and shared experts;
an untied head.  A configuration names it with ``"arch": "deepseek_v3"``
(``Spec.arch``); ``bench/models/llama.py`` says what such a module gives.

The configuration file keeps the published ``config.json`` keys.  Where a
chip holds a share of each expert layer, ``n_routed_experts`` counts the
experts held here, ``router_experts`` the experts the router scores (the
published count) and ``first_held`` the first held; ``vocab_size`` is the
vocabulary slice held here.

The plain float32 forward pass, per layer:

    a   = MLA(RMSNorm(x)):  q = h Wq (heads of nope + rope);
          [c, k_pe] = h Wkv_a;  [k_nope, v] = RMSNorm(c) Wkv_b;
          RoPE on the rope dimensions of q and k_pe (pairs (2i, 2i+1)
          rotated by theta^(-2i/rope), as the Hugging Face code's
          permutation gives it); k = [k_nope, k_pe]; causal softmax at
          (nope + rope)^-0.5; out Wo
    x  += a
    x  += SwiGLU(RMSNorm(x))                                (dense layers)
    x  += shared(h) + sum_held w_e(h) E_e(h)                (expert layers)
          h = RMSNorm(x); scores = sigmoid(h Wr) over router_experts;
          the top num_experts_per_tok of scores + bias chosen (the bias
          stays zero: its balancing update is not in config.json), w the
          chosen scores over their sum times routed_scaling_factor;
          w_e = 0 where expert e was not chosen

Attention runs in query blocks and each held expert over every token,
weighted by its routing weight (zero where not routed), so that the
activations fit one chip; every layer runs under ``jax.checkpoint``.

Leaves are stored (in, out), the layers stacked on a leading axis, the
held experts on the next:

    embed/table, unembed/table (V, d)         final_norm/scale (d,)
    {dense,moe}_blocks/attn/{wq,wkv_a,wkv_b,wo}/w
    {dense,moe}_blocks/attn/kv_norm/scale     {dense,moe}_blocks/ln{1,2}/scale
    dense_blocks/mlp/{gate,up,down}/w         moe_blocks/moe/router (L, d, E)
    moe_blocks/moe/experts/{gate,up,down} (L, held, d|f, f|d)
    moe_blocks/moe/shared/{gate,up,down}/w
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STACKS = ("moe_blocks", "dense_blocks")
ATTN_NAMES = ("attn/kv_norm/scale", "attn/wkv_a/w", "attn/wkv_b/w",
              "attn/wo/w", "attn/wq/w", "ln1/scale", "ln2/scale")
DENSE_NAMES = ATTN_NAMES + ("mlp/down/w", "mlp/gate/w", "mlp/up/w")
MOE_NAMES = ATTN_NAMES + ("moe/experts/down", "moe/experts/gate",
                          "moe/experts/up", "moe/router",
                          "moe/shared/down/w", "moe/shared/gate/w",
                          "moe/shared/up/w")
LOGIT_CHUNK = 512
Q_BLOCK = 256


def _sizes(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], H=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                vd=cfg["v_head_dim"], r=cfg["kv_lora_rank"],
                ff=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
                E=cfg["router_experts"], held=cfg["n_routed_experts"],
                k=cfg["num_experts_per_tok"], ns=cfg["n_shared_experts"],
                V=cfg["vocab_size"], Ld=cfg["first_k_dense_replace"],
                Lm=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Path -> shape of every trained leaf."""
    z = _sizes(cfg)
    d, H, r = z["d"], z["H"], z["r"]
    out = {"embed/table": (z["V"], d), "unembed/table": (z["V"], d),
           "final_norm/scale": (d,)}
    for stack, L in (("dense_blocks", z["Ld"]), ("moe_blocks", z["Lm"])):
        out.update({
            f"{stack}/attn/wq/w": (L, d, H * (z["nope"] + z["rope"])),
            f"{stack}/attn/wkv_a/w": (L, d, r + z["rope"]),
            f"{stack}/attn/kv_norm/scale": (L, r),
            f"{stack}/attn/wkv_b/w": (L, r, H * (z["nope"] + z["vd"])),
            f"{stack}/attn/wo/w": (L, H * z["vd"], d),
            f"{stack}/ln1/scale": (L, d),
            f"{stack}/ln2/scale": (L, d)})
    L, f, sf = z["Ld"], z["ff"], z["f"] * z["ns"]
    out.update({"dense_blocks/mlp/gate/w": (L, d, f),
                "dense_blocks/mlp/up/w": (L, d, f),
                "dense_blocks/mlp/down/w": (L, f, d)})
    L, held, f = z["Lm"], z["held"], z["f"]
    out.update({"moe_blocks/moe/router": (L, d, z["E"]),
                "moe_blocks/moe/experts/gate": (L, held, d, f),
                "moe_blocks/moe/experts/up": (L, held, d, f),
                "moe_blocks/moe/experts/down": (L, held, f, d),
                "moe_blocks/moe/shared/gate/w": (L, d, sf),
                "moe_blocks/moe/shared/up/w": (L, d, sf),
                "moe_blocks/moe/shared/down/w": (L, sf, d)})
    return out


def arch_config(cfg: dict) -> dict:
    """The program's ``ArchConfig`` keyword arguments, ``plan`` aside.  The
    program's drop-free router scores by sigmoid and renormalises the
    chosen weights; a configuration that routes otherwise is refused."""
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("the drop-free router takes sigmoid scores with "
                         "renormalised top-k weights")
    return {
        "name": cfg["name"], "family": "moe",
        "n_layers": cfg["num_hidden_layers"],
        "first_k_dense": cfg["first_k_dense_replace"],
        "d_model": cfg["hidden_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["v_head_dim"], "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"], "rope": "rope",
        "rope_theta": float(cfg["rope_theta"]),
        "tie_embeddings": cfg["tie_word_embeddings"],
        "norm_eps": cfg["rms_norm_eps"],
        "mla": {"kv_lora_rank": cfg["kv_lora_rank"],
                "qk_nope_head_dim": cfg["qk_nope_head_dim"],
                "qk_rope_head_dim": cfg["qk_rope_head_dim"],
                "v_head_dim": cfg["v_head_dim"],
                "q_lora_rank": cfg["q_lora_rank"],
                "kv_norm_eps": cfg["kv_norm_eps"]},
        "moe": {"n_experts": cfg["router_experts"],
                "top_k": cfg["num_experts_per_tok"],
                "n_shared": cfg["n_shared_experts"],
                "d_expert": cfg["moe_intermediate_size"],
                "routed_scale": cfg["routed_scaling_factor"],
                "shared_gate": False,
                "drop_free": True, "n_held": cfg["n_routed_experts"],
                "first_held": cfg["first_held"]},
    }


# ---------------------------------------------------------------- model
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """RoPE of x (B, S, heads, rope) at positions 0..S-1, pair (2i, 2i+1)
    rotated by theta^(-2i/rope); the result in (evens, odds) order, as the
    Hugging Face ``deepseek_v3`` code leaves it."""
    s, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv     # (S, r/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, ev * sin + od * cos], -1)


def _attention(cfg, es, x, p):
    z = _sizes(cfg)
    b, s, _ = x.shape
    H, nope, rope, vd, r = z["H"], z["nope"], z["rope"], z["vd"], z["r"]
    h = _rmsnorm(x, p["ln1/scale"], cfg["rms_norm_eps"])
    q = es("bsd,de->bse", h, p["attn/wq/w"]).reshape(b, s, H, nope + rope)
    kv_a = es("bsd,de->bse", h, p["attn/wkv_a/w"])
    c = _rmsnorm(kv_a[..., :r], p["attn/kv_norm/scale"], cfg["kv_norm_eps"])
    kv = es("bsr,re->bse", c, p["attn/wkv_b/w"]).reshape(b, s, H,
                                                          nope + vd)
    theta = float(cfg["rope_theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k_pe = _rope(kv_a[..., None, r:], theta)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, H, rope))], -1)
    v = kv[..., nope:]
    qb = min(Q_BLOCK, s)
    qs = q.reshape(b, s // qb, qb, H, nope + rope).swapaxes(0, 1)

    @jax.checkpoint
    def block(i, qblk):
        sc = es("bqhe,bkhe->bhqk", qblk, k) * (nope + rope) ** -0.5
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return es("bhqk,bkhe->bqhe", pr, v)

    _, o = jax.lax.scan(lambda i, qblk: (i + 1, block(i, qblk)), 0, qs)
    o = o.swapaxes(0, 1).reshape(b, s, H * vd)
    return x + es("bse,ed->bsd", o, p["attn/wo/w"])


def _swiglu(es, h, gate, up, down):
    g = es("td,df->tf", h, gate)
    return es("tf,fd->td", jax.nn.silu(g) * es("td,df->tf", h, up), down)


def _dense_layer(cfg, es, x, p):
    x = _attention(cfg, es, x, p)
    b, s, d = x.shape
    h = _rmsnorm(x, p["ln2/scale"], cfg["rms_norm_eps"]).reshape(b * s, d)
    y = _swiglu(es, h, p["mlp/gate/w"], p["mlp/up/w"], p["mlp/down/w"])
    return x + y.reshape(b, s, d)


def routing(cfg, es, h, router):
    """(T, held) weight of each held expert for each token of h (T, d)."""
    z = _sizes(cfg)
    scores = jax.nn.sigmoid(es("td,de->te", h, router))
    bias = jnp.zeros((z["E"],), jnp.float32)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), z["k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    held = cfg["first_held"] + jnp.arange(z["held"])
    return jnp.einsum("tk,tke->te", w,
                      (idx[..., None] == held).astype(jnp.float32))


def expert_layer(cfg, es, h, p):
    """The held experts' part of the routed output plus the shared experts,
    for h (T, d)."""
    wt = routing(cfg, es, h, p["moe/router"])

    @jax.checkpoint
    def one(acc, e):
        w_e, gate, up, down = e
        return acc + w_e[:, None] * _swiglu(es, h, gate, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        wt.T, p["moe/experts/gate"], p["moe/experts/up"],
        p["moe/experts/down"]))
    return out + _swiglu(es, h, p["moe/shared/gate/w"],
                         p["moe/shared/up/w"], p["moe/shared/down/w"])


def _moe_layer(cfg, es, x, p):
    x = _attention(cfg, es, x, p)
    b, s, d = x.shape
    h = _rmsnorm(x, p["ln2/scale"], cfg["rms_norm_eps"]).reshape(b * s, d)
    return x + expert_layer(cfg, es, h, p).reshape(b, s, d)


def loss_sum(params: dict, tokens, labels, cfg: dict, einsum):
    """Summed next-token cross-entropy over the batch (labels < 0 masked).
    Layers run under ``jax.checkpoint`` and the head's logits in chunks."""
    es = einsum
    x = params["embed/table"][tokens]
    for stack, names, layer in (("dense_blocks", DENSE_NAMES, _dense_layer),
                                ("moe_blocks", MOE_NAMES, _moe_layer)):
        stacked = {n: params[f"{stack}/{n}"] for n in names}
        x, _ = jax.lax.scan(
            jax.checkpoint(lambda c, p, _f=layer: (_f(cfg, es, c, p), None)),
            x, stacked)
    x = _rmsnorm(x, params["final_norm/scale"], cfg["rms_norm_eps"])
    head = params["unembed/table"]
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
def matmul_params(cfg: dict) -> float:
    """Parameters a token multiplies by: every layer's projections, the
    router, the shared experts, the routed experts held here at the share
    of a token they see (``num_experts_per_tok`` x held / ``router_experts``
    experts a token), and the head."""
    z = _sizes(cfg)
    d, H, r = z["d"], z["H"], z["r"]
    attn = (d * H * (z["nope"] + z["rope"]) + d * (r + z["rope"])
            + r * H * (z["nope"] + z["vd"]) + H * z["vd"] * d)
    expert = 3 * d * z["f"]
    moe = (d * z["E"] + z["ns"] * expert
           + z["k"] * z["held"] / z["E"] * expert)
    return (z["Ld"] * (attn + 3 * d * z["ff"]) + z["Lm"] * (attn + moe)
            + z["V"] * d)


def attention_work(cfg: dict, seq: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one sequence's causal MLA attention in every
    layer, forward and backward: QK^T at nope + rope and PV at v, half of
    the square kept by the mask, the backward twice the forward; bytes of
    q, k, v and the output read or written once by the forward, and q, k,
    v, the output, its gradient, dq, dk and dv once by the backward, in
    bfloat16."""
    z = _sizes(cfg)
    dqk, dv = z["nope"] + z["rope"], z["vd"]
    layers = z["Ld"] + z["Lm"]
    flops = 3 * layers * seq * seq * z["H"] * (dqk + dv)
    bytes_ = 2 * layers * seq * z["H"] * 6 * (dqk + dv)
    return float(flops), float(bytes_)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul parameters (forward 2,
    backward 4) plus causal attention, 3 x layers x seq x heads x (qk + v
    head sizes).  Recomputation under remat is not counted."""
    flops, _ = attention_work(cfg, seq)
    return 6.0 * matmul_params(cfg) + flops / seq
