"""The dense block: LayerNorm, rotary attention with optional q/k/v
biases, a tanh-GELU MLP with optional biases, tied embeddings.

An architecture module of the benchmark (named by a configuration's
``"architecture"``) gives the reference and the counts three functions:
``init_params(cfg, seed)``, ``make_loss(cfg, matmul, half_batch)`` and
``train_flops_per_token(cfg, seq)``, where ``cfg`` is the configuration's
``model``. Like the reference, it imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import precision


def _normal(key, shape, scale):
    return scale * jax.random.normal(key, shape, jnp.float32)


def init_params(cfg: dict, seed: int) -> dict:
    """Weights from the seed: N(0, 0.02) token table, N(0, 1/fan_in)
    projections, unit LayerNorm scales, zero biases; one key per layer
    split from the block key, in the configuration's key order."""
    d, h, kv, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    k_embed, k_blocks, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    ln = lambda: {"bias": jnp.zeros((d,)), "scale": jnp.ones((d,))}
    params = {"embed": {"tok": _normal(jax.random.split(k_embed, 2)[0],
                                       (cfg["vocab_size"], d), 0.02)},
              "final_norm": ln(), "blocks_list": {}}
    for i, key in enumerate(jax.random.split(k_blocks, cfg["n_layers"])):
        k_attn, _, k_mlp = jax.random.split(key, 3)
        ka = jax.random.split(k_attn, 4)
        attn = {"wq": _normal(ka[0], (d, h, hd), d ** -0.5),
                "wk": _normal(ka[1], (d, kv, hd), d ** -0.5),
                "wv": _normal(ka[2], (d, kv, hd), d ** -0.5),
                "wo": _normal(ka[3], (h, hd, d), (h * hd) ** -0.5)}
        if cfg.get("qkv_bias"):
            attn.update(bq=jnp.zeros((h, hd)), bk=jnp.zeros((kv, hd)),
                        bv=jnp.zeros((kv, hd)))
        km = jax.random.split(k_mlp, 3)
        mlp = {"w_in": _normal(km[0], (d, ff), d ** -0.5),
               "w_down": _normal(km[2], (ff, d), ff ** -0.5)}
        if cfg.get("mlp_bias"):
            mlp.update(b_in=jnp.zeros((ff,)), b_down=jnp.zeros((d,)))
        params["blocks_list"][f"layer_{i:02d}"] = {
            "norm1": ln(), "attn": attn, "norm2": ln(), "mlp": mlp}
    return params


def make_loss(cfg: dict, matmul: str = "bf16", half_batch: bool = False):
    """loss(params, tokens, labels): mean next-token cross-entropy, with
    the matrix products at ``matmul`` (``precision``); ``half_batch``
    takes it over the first half of each batch only."""
    bf = jnp.bfloat16
    q8 = precision.operand(matmul)
    mm = precision.product(matmul)
    eps = cfg.get("norm_eps", 1e-5)
    theta = cfg.get("rope_theta", 10000.0)

    def norm(p, x):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
                + p["bias"]).astype(bf)

    def rope(x):
        dh = x.shape[-1]
        freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32)
                                 / dh))
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(bf)

    def attention(p, x):
        q = mm("bsd,dhk->bshk", x, p["wq"])
        k = mm("bsd,dhk->bshk", x, p["wk"])
        v = mm("bsd,dhk->bshk", x, p["wv"])
        if "bq" in p:
            q, k, v = q + p["bq"].astype(bf), k + p["bk"].astype(bf), \
                v + p["bv"].astype(bf)
        q, k = rope(q), rope(k)
        g = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhk,bshk->bhqs", q8(q), q8(k),
                       preferred_element_type=jnp.float32)
        s = s * q.shape[-1] ** -0.5
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
        probs = jax.nn.softmax(s, axis=-1).astype(bf)
        ctx = jnp.einsum("bhqs,bshk->bqhk", q8(probs), q8(v),
                         preferred_element_type=jnp.float32).astype(bf)
        return mm("bshk,hkd->bsd", ctx, p["wo"])

    def mlp(p, x):
        hdn = mm("bsd,df->bsf", x, p["w_in"])
        if "b_in" in p:
            hdn = hdn + p["b_in"].astype(bf)
        out = mm("bsf,fd->bsd", jax.nn.gelu(hdn), p["w_down"])
        if "b_down" in p:
            out = out + p["b_down"].astype(bf)
        return out

    def loss(params, tokens, labels):
        if half_batch:
            tokens, labels = tokens[: len(tokens) // 2], labels[
                : len(labels) // 2]
        x = params["embed"]["tok"].astype(bf)[tokens]
        for name in sorted(params["blocks_list"]):
            lp = params["blocks_list"][name]
            x = x + attention(lp["attn"], norm(lp["norm1"], x))
            x = x + mlp(lp["mlp"], norm(lp["norm2"], x))
        x = norm(params["final_norm"], x)
        logits = mm("bsd,vd->bsv", x, params["embed"]["tok"]).astype(
            jnp.float32)
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
        return nll.mean()

    return loss


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product, the tied output head once."""
    d, h, kv, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    layer = d * h * hd * 2 + d * kv * hd * 2 + 2 * d * ff
    return cfg["n_layers"] * layer + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward matmul operations per trained token: 6 per
    matmul weight, plus 12 * layers * heads * head_dim * seq for the
    attention scores and their weighted sum (PaLM's model-FLOPs count;
    no recomputation)."""
    attn = 12 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * seq
    return 6.0 * matmul_params(cfg) + attn
