"""Shared NN layers — pure-function JAX (no flax): params are nested dicts.

Covers everything the assigned LM architectures need:
  * RMSNorm / LayerNorm, RoPE
  * grouped-query attention (MQA/GQA, optional QKV bias) — train + KV-cache decode
  * MLA (DeepSeek multi-head latent attention) — compressed-latent KV cache
  * MLPs: SwiGLU, squared-ReLU (Nemotron), GELU
  * MoE: sort-based grouped dispatch (top-k, capacity factor, optional
    shared expert / dense residual) with EP sharding hooks
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


# -- sharding hints -----------------------------------------------------------

def _ambient_dp_axes():
    """Data-parallel axis names of the ambient mesh (None outside one)."""
    try:
        m = jax.sharding.get_abstract_mesh()
        names = tuple(m.axis_names)
    except Exception:
        return None
    if "model" not in names:
        return None
    return tuple(a for a in names if a != "model")


def hint_activation(x: jax.Array) -> jax.Array:
    """Constrain (B, ..., d) activations to (dp, ..., 'model')."""
    dp = _ambient_dp_axes()
    if dp is None:
        return x
    spec = P(dp, *([None] * (x.ndim - 2)), "model")
    return jax.lax.with_sharding_constraint(x, spec)


def hint_replicated(x: jax.Array) -> jax.Array:
    """Constrain activations to (dp, None, ...) — replicated over model.

    This is the Megatron layer-boundary convention: column-parallel
    up-projections shard the INTERMEDIATE, row-parallel down-projections
    psum back to replicated.  Leaving the boundary activation d-sharded
    (as the embed shard_map emits it) makes every dot in the layer re-
    gather x: 11 × 268 MB all-gathers per layer-iteration on qwen2
    train_4k (§Perf iteration 2)."""
    dp = _ambient_dp_axes()
    if dp is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(dp, *([None] * (x.ndim - 1))))


def embed_lookup(table: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    """Sharded embedding lookup as an explicit shard_map.

    Table is (vocab, d) with d sharded over `model`, tokens sharded over
    the data axes: the gather is device-local (each chip reads its d-slice
    of its token rows) and the backward is a local scatter + psum over the
    data axes.  Leaving this to the SPMD partitioner instead materializes
    a full-vocab f32 table gradient per device (12.6 GB vs 0.8 GB on
    nemotron train_4k — EXPERIMENTS.md §Perf) or trips partitioner bugs
    under remat."""
    dp = _ambient_dp_axes()
    if dp is None:
        return table[tokens].astype(dtype)

    def local(tbl, tok):
        return tbl[tok]

    out = jax.shard_map(
        local,
        in_specs=(P(None, "model"), P(dp, *([None] * (tokens.ndim - 1)))),
        out_specs=P(dp, *([None] * (tokens.ndim - 1)), "model"),
    )(table, tokens)
    return out.astype(dtype)


# -- init helpers -----------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def linear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.bfloat16, scale: Optional[float] = None) -> Params:
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": _normal(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def norm_init(d: int, dtype=jnp.bfloat16) -> Params:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * p["g"].astype(jnp.float32)).astype(x.dtype)


# -- RoPE -------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float = 1e4) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, pos: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """x (..., L, H, dh) with pos (..., L)."""
    ang = pos[..., :, None].astype(jnp.float32) * inv_freq  # (..., L, dh/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- grouped-query attention ------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    qkv_bias: bool = False
    rope_theta: float = 1e4


def attn_init(key, cfg: AttnConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "q": linear_init(ks[0], cfg.d_model, cfg.n_heads * cfg.d_head,
                         bias=cfg.qkv_bias, dtype=dtype),
        "k": linear_init(ks[1], cfg.d_model, cfg.n_kv * cfg.d_head,
                         bias=cfg.qkv_bias, dtype=dtype),
        "v": linear_init(ks[2], cfg.d_model, cfg.n_kv * cfg.d_head,
                         bias=cfg.qkv_bias, dtype=dtype),
        "o": linear_init(ks[3], cfg.n_heads * cfg.d_head, cfg.d_model,
                         dtype=dtype),
    }


def _gqa_scores(q, k, cfg: AttnConfig):
    """q (B,Lq,H,dh), k (B,Lk,Kv,dh) -> scores (B,Lq,Kv,G,Lk) in f32."""
    b, lq, h, dh = q.shape
    g = cfg.n_heads // cfg.n_kv
    qg = q.reshape(b, lq, cfg.n_kv, g, dh)
    return jnp.einsum("bqkgd,blkd->bqkgl", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) / (dh ** 0.5)


def attn_forward(p: Params, x: jax.Array, cfg: AttnConfig,
                 pos: Optional[jax.Array] = None,
                 q_block: Optional[int] = None,
                 return_kv: bool = False):
    """Causal self-attention (training / prefill). x (B, L, d).

    ``q_block`` enables query-blocked attention (lax.scan over query
    chunks against the full K/V): live score memory drops from O(L²) to
    O(q_block · L) — required for the 32k prefill shapes."""
    b, l, _ = x.shape
    inv_freq = rope_freqs(cfg.d_head, cfg.rope_theta)
    if pos is None:
        pos = jnp.arange(l)[None, :]
    q = linear(p["q"], x).reshape(b, l, cfg.n_heads, cfg.d_head)
    k = linear(p["k"], x).reshape(b, l, cfg.n_kv, cfg.d_head)
    v = linear(p["v"], x).reshape(b, l, cfg.n_kv, cfg.d_head)
    q = apply_rope(q, pos, inv_freq)
    k = apply_rope(k, pos, inv_freq)

    # repeat KV to full heads ("repeat_kv"): with KV projections
    # replicated over the model axis and Q head-sharded, the whole
    # attention chain stays head-local — no per-layer activation
    # all-gathers (the bqkgd grouped form defeated SPMD head-sharding
    # propagation: measured 3 GB/layer of collectives on qwen2 train_4k,
    # EXPERIMENTS.md §Perf iteration 1).
    g_rep = cfg.n_heads // cfg.n_kv
    k_full = jnp.repeat(k, g_rep, axis=2)               # (B,L,H,dh)
    v_full = jnp.repeat(v, g_rep, axis=2)

    def attend(q_blk, pos_q):
        scores = jnp.einsum("bqhd,blhd->bhql",
                            q_blk.astype(jnp.float32),
                            k_full.astype(jnp.float32)) / (cfg.d_head ** 0.5)
        mask = pos_q[:, :, None] >= pos[:, None, :]     # (B, qb, Lk)
        scores = jnp.where(mask[:, None, :, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhql,blhd->bqhd", w, v_full.astype(jnp.float32))
        return out.reshape(b, q_blk.shape[1],
                           cfg.n_heads * cfg.d_head).astype(x.dtype)

    if q_block is None or l <= q_block:
        out = attend(q, pos)
        y = linear(p["o"], out)
        if return_kv:
            return y, (k, v)
        return y
    else:
        assert l % q_block == 0, (l, q_block)
        nb = l // q_block
        qs = q.reshape(b, nb, q_block, cfg.n_heads, cfg.d_head)
        ps = jnp.broadcast_to(pos, (b, l)).reshape(b, nb, q_block)

        def body(_, inp):
            qb, pb = inp
            return None, attend(qb, pb)

        # remat per q-block: backward recomputes scores/probs block-by-block
        # instead of saving O(L²) softmax intermediates (flash-style)
        _, outs = jax.lax.scan(
            jax.checkpoint(body, prevent_cse=False), None,
            (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(ps, 1, 0)))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, l, -1)
    y = linear(p["o"], out)
    if return_kv:
        return y, (k, v)
    return y


def _masked_cache_write(buf: jax.Array, new: jax.Array, pos: jax.Array,
                        active: jax.Array) -> jax.Array:
    """Write new (B, 1, ...) into buf (B, L, ...) at per-row pos where
    active; inactive rows keep their current contents."""
    b = buf.shape[0]
    rows = jnp.arange(b)
    old = buf[rows, pos]
    val = jnp.where(
        active.reshape((b,) + (1,) * (new.ndim - 2)),
        new[:, 0].astype(buf.dtype), old)
    return buf.at[rows, pos].set(val)


def attn_decode(p: Params, x: jax.Array, cache: Params, cfg: AttnConfig,
                active: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
    """One decode step. x (B, 1, d); cache {k,v: (B, Lmax, Kv, dh),
    pos: (B,) int32 per-row positions}.  ``active`` (B,) bool rows advance;
    inactive rows are frozen (continuous-batching support)."""
    b = x.shape[0]
    inv_freq = rope_freqs(cfg.d_head, cfg.rope_theta)
    cur = cache["pos"]                                  # (B,) int32
    if active is None:
        active = jnp.ones((b,), jnp.bool_)
    pos = cur[:, None]                                  # (B, 1)
    q = linear(p["q"], x).reshape(b, 1, cfg.n_heads, cfg.d_head)
    k = linear(p["k"], x).reshape(b, 1, cfg.n_kv, cfg.d_head)
    v = linear(p["v"], x).reshape(b, 1, cfg.n_kv, cfg.d_head)
    q = apply_rope(q, pos, inv_freq)
    k = apply_rope(k, pos, inv_freq)
    kc = _masked_cache_write(cache["k"], k, cur, active)
    vc = _masked_cache_write(cache["v"], v, cur, active)
    scores = _gqa_scores(q, kc, cfg)                    # (B,1,Kv,G,Lmax)
    lk = kc.shape[1]
    valid = jnp.arange(lk)[None, :] <= cur[:, None]     # (B, Lmax)
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqkgl,blkd->bqkgd", w, vc.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.n_heads * cfg.d_head).astype(x.dtype)
    return linear(p["o"], out), {"k": kc, "v": vc,
                                 "pos": cur + active.astype(jnp.int32)}


# -- MLA (DeepSeek-V3 multi-head latent attention) ---------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    rope_theta: float = 1e4


def mla_init(key, cfg: MLAConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 7)
    h = cfg.n_heads
    return {
        "q_a": linear_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype=dtype),
        "q_a_norm": norm_init(cfg.q_lora_rank, dtype),
        "q_b": linear_init(ks[1], cfg.q_lora_rank,
                           h * (cfg.d_nope + cfg.d_rope), dtype=dtype),
        "kv_a": linear_init(ks[2], cfg.d_model,
                            cfg.kv_lora_rank + cfg.d_rope, dtype=dtype),
        "kv_a_norm": norm_init(cfg.kv_lora_rank, dtype),
        "kv_b": linear_init(ks[3], cfg.kv_lora_rank,
                            h * (cfg.d_nope + cfg.d_v), dtype=dtype),
        "o": linear_init(ks[4], h * cfg.d_v, cfg.d_model, dtype=dtype),
    }


def _mla_qkv(p, x, cfg: MLAConfig, pos, inv_freq):
    b, l, _ = x.shape
    h = cfg.n_heads
    q = linear(p["q_b"], rmsnorm(p["q_a_norm"], linear(p["q_a"], x)))
    q = q.reshape(b, l, h, cfg.d_nope + cfg.d_rope)
    q_nope, q_rope = q[..., :cfg.d_nope], q[..., cfg.d_nope:]
    q_rope = apply_rope(q_rope, pos, inv_freq)
    kv = linear(p["kv_a"], x)                           # (B,L,rank+rope)
    latent = rmsnorm(p["kv_a_norm"], kv[..., :cfg.kv_lora_rank])
    k_rope = apply_rope(kv[..., None, cfg.kv_lora_rank:], pos, inv_freq)
    return q_nope, q_rope, latent, k_rope               # k_rope (B,L,1,dr)


def _mla_attend(p, q_nope, q_rope, latent, k_rope, cfg: MLAConfig, mask):
    b, lq = q_nope.shape[:2]
    h = cfg.n_heads
    kv = linear(p["kv_b"], latent).reshape(
        b, -1, h, cfg.d_nope + cfg.d_v)
    k_nope, v = kv[..., :cfg.d_nope], kv[..., cfg.d_nope:]
    scale = (cfg.d_nope + cfg.d_rope) ** -0.5
    s = (jnp.einsum("bqhd,blhd->bqhl", q_nope.astype(jnp.float32),
                    k_nope.astype(jnp.float32))
         + jnp.einsum("bqhd,bld->bqhl", q_rope.astype(jnp.float32),
                      k_rope[:, :, 0].astype(jnp.float32))) * scale
    s = jnp.where(mask[:, :, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhl,blhd->bqhd", w, v.astype(jnp.float32))
    return linear(p["o"], out.reshape(b, lq, h * cfg.d_v).astype(jnp.bfloat16))


def mla_forward(p: Params, x: jax.Array, cfg: MLAConfig,
                pos: Optional[jax.Array] = None,
                q_block: Optional[int] = None,
                return_kv: bool = False):
    b, l, _ = x.shape
    if pos is None:
        pos = jnp.arange(l)[None, :]
    inv_freq = rope_freqs(cfg.d_rope, cfg.rope_theta)
    qn, qr, latent, kr = _mla_qkv(p, x, cfg, pos, inv_freq)
    if q_block is None or l <= q_block:
        mask = pos[:, :, None] >= pos[:, None, :]
        y = _mla_attend(p, qn, qr, latent, kr, cfg, mask).astype(x.dtype)
    else:
        assert l % q_block == 0, (l, q_block)
        nb = l // q_block

        def body(_, inp):
            qn_b, qr_b, pos_b = inp
            mask = pos_b[:, :, None] >= pos[:, None, :]
            return None, _mla_attend(p, qn_b, qr_b, latent, kr, cfg, mask)

        body = jax.checkpoint(body, prevent_cse=False)
        split = lambda a: jnp.moveaxis(
            a.reshape((b, nb, q_block) + a.shape[2:]), 1, 0)
        pos_b = jnp.broadcast_to(pos, (b, l))
        _, outs = jax.lax.scan(body, None,
                               (split(qn), split(qr), split(pos_b)))
        y = jnp.moveaxis(outs, 0, 1).reshape(b, l, -1).astype(x.dtype)
    if return_kv:
        return y, (latent, kr)
    return y


def mla_decode(p: Params, x: jax.Array, cache: Params, cfg: MLAConfig,
               active: Optional[jax.Array] = None, *, absorb: bool = True
               ) -> Tuple[jax.Array, Params]:
    """Decode with compressed cache {latent: (B,Lmax,rank), k_rope:
    (B,Lmax,1,dr), pos: (B,)} — the MLA memory saving (rank+dr ≪ H·dh).

    ``absorb=True`` (default) applies DeepSeek's weight-absorption: W_kv_b
    is folded into the query/context sides so attention runs directly in
    the rank-512 latent space — O(B·H·L·rank) per token instead of
    reconstructing K/V: O(B·L·rank·H·(dn+dv)), a (dn+dv)/2 = 128× flop
    reduction at L=32k (EXPERIMENTS.md §Perf iteration 3)."""
    b = x.shape[0]
    cur = cache["pos"]                                  # (B,)
    if active is None:
        active = jnp.ones((b,), jnp.bool_)
    pos = cur[:, None]
    inv_freq = rope_freqs(cfg.d_rope, cfg.rope_theta)
    qn, qr, latent_t, kr_t = _mla_qkv(p, x, cfg, pos, inv_freq)
    lat = _masked_cache_write(cache["latent"], latent_t, cur, active)
    krc = _masked_cache_write(cache["k_rope"], kr_t, cur, active)
    lk = lat.shape[1]
    new_cache = {"latent": lat, "k_rope": krc,
                 "pos": cur + active.astype(jnp.int32)}
    if not absorb:
        mask = (jnp.arange(lk)[None, None, :] <= cur[:, None, None])
        out = _mla_attend(p, qn, qr, lat, krc, cfg, mask)
        return out.astype(x.dtype), new_cache

    h = cfg.n_heads
    wkv = p["kv_b"]["w"].reshape(cfg.kv_lora_rank, h, cfg.d_nope + cfg.d_v)
    wk = wkv[..., :cfg.d_nope].astype(jnp.float32)
    wv = wkv[..., cfg.d_nope:].astype(jnp.float32)
    lat32 = lat.astype(jnp.float32)
    scale = (cfg.d_nope + cfg.d_rope) ** -0.5
    q_lat = jnp.einsum("bqhd,rhd->bqhr", qn.astype(jnp.float32), wk)
    s = (jnp.einsum("bqhr,blr->bqhl", q_lat, lat32)
         + jnp.einsum("bqhd,bld->bqhl", qr.astype(jnp.float32),
                      krc[:, :, 0].astype(jnp.float32))) * scale
    mask = (jnp.arange(lk)[None, None, None, :] <= cur[:, None, None, None])
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bqhl,blr->bqhr", w, lat32)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, wv)
    out = linear(p["o"], out.reshape(b, 1, h * cfg.d_v).astype(x.dtype))
    return out.astype(x.dtype), new_cache


# -- MLPs --------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, act: str,
             dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    p = {"up": linear_init(ks[0], d_model, d_ff, dtype=dtype),
         "down": linear_init(ks[1], d_ff, d_model, dtype=dtype)}
    if act in ("swiglu",):
        p["gate"] = linear_init(ks[2], d_model, d_ff, dtype=dtype)
    return p


def mlp_forward(p: Params, x: jax.Array, act: str) -> jax.Array:
    if act == "swiglu":
        h = jax.nn.silu(linear(p["gate"], x).astype(jnp.float32)) \
            * linear(p["up"], x).astype(jnp.float32)
    elif act == "relu2":  # Nemotron squared-ReLU
        h = jnp.square(jax.nn.relu(linear(p["up"], x).astype(jnp.float32)))
    else:
        h = jax.nn.gelu(linear(p["up"], x).astype(jnp.float32))
    return linear(p["down"], h.astype(x.dtype))


# -- MoE ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int
    capacity_factor: float = 1.25
    n_groups: int = 1            # routing groups (== data-parallel shards)
    shared_expert_ff: int = 0    # DeepSeek shared expert (0 = none)
    dense_residual_ff: int = 0   # Arctic dense residual MLP (0 = none)
    act: str = "swiglu"


def moe_init(key, cfg: MoEConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": _normal(ks[0], (d, e), d ** -0.5, jnp.float32),
        "w_gate": _normal(ks[1], (e, d, f), d ** -0.5, dtype),
        "w_up": _normal(ks[2], (e, d, f), d ** -0.5, dtype),
        "w_down": _normal(ks[3], (e, f, d), f ** -0.5, dtype),
    }
    if cfg.shared_expert_ff:
        p["shared"] = mlp_init(ks[1], d, cfg.shared_expert_ff, cfg.act, dtype)
    if cfg.dense_residual_ff:
        p["residual"] = mlp_init(ks[2], d, cfg.dense_residual_ff, cfg.act, dtype)
    return p


def _capacity(tokens_per_group: int, cfg: MoEConfig) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor
            / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)


def moe_forward(p: Params, x: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Sort-based grouped dispatch.  x (T, d) -> (T, d).

    Tokens are routed within ``n_groups`` groups (group dim sharded over the
    data axes → local sort; expert dim sharded over ``model`` → the
    reshard between token and expert layout is the EP all-to-all,
    inserted by GSPMD from the sharding constraint)."""
    t, d = x.shape
    g = cfg.n_groups
    assert t % g == 0, (t, g)
    tg = t // g
    cap = _capacity(tg, cfg)
    e, k = cfg.n_experts, cfg.top_k

    def route(xg):  # (Tg, d)
        logits = xg.astype(jnp.float32) @ p["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)              # (Tg, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        e_flat = idx.reshape(-1)                         # (Tg*k,)
        order = jnp.argsort(e_flat)
        e_sorted = e_flat[order]
        tok_sorted = order // k
        counts = jnp.bincount(e_flat, length=e)
        start = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                 jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(tg * k) - start[e_sorted]
        valid = pos < cap
        slot = jnp.where(valid, e_sorted * cap + pos, e * cap)  # sentinel row
        # gate weight per sorted entry; zero for dropped (over-capacity)
        gate_sorted = jnp.where(
            valid, gate.reshape(-1)[order], 0).astype(x.dtype)
        buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(
            xg[tok_sorted])[:-1]
        return buf.reshape(e, cap, d), (tok_sorted, slot, gate_sorted)

    xg = x.reshape(g, tg, d)
    buf, aux = jax.vmap(route)(xg)                       # (G, E, C, d)
    dp = _ambient_dp_axes()
    if dp is not None:
        # EP reshard: groups over the data axes, experts over model.
        # Decode-sized token counts additionally shard d over data so the
        # expert contraction runs on local weight shards + a small psum —
        # otherwise GSPMD all-gathers 1.4 GB/layer of expert weights to
        # chase a handful of tokens (§Perf deepseek decode iteration 2).
        g_ax = dp if g > 1 else None
        d_ax = "data" if (t <= 4096 and g == 1) else None
        buf = jax.lax.with_sharding_constraint(
            buf, P(g_ax, "model", None, d_ax))

    h = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    if cfg.act == "swiglu":
        h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) \
            * jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w_down"])  # (G,E,C,d)

    def combine(out_b, xg_i, aux_i):
        # direct weighted segment-add back to tokens: avoids materializing
        # the (Tg·k, d) unsort buffer + (Tg, k, d) reshape (4 full-token
        # copies → 1; measured on deepseek-v3 train_4k, §Perf)
        tok_sorted, slot, gate_sorted = aux_i
        flat = out_b.reshape(e * cap, d)
        contrib = flat[jnp.minimum(slot, e * cap - 1)] \
            * gate_sorted[:, None]
        return jnp.zeros((tg, d), x.dtype).at[tok_sorted].add(contrib)

    out = jax.vmap(combine)(out_buf, xg, aux).reshape(t, d)
    if "shared" in p:
        out = out + mlp_forward(p["shared"], x, cfg.act)
    if "residual" in p:
        out = out + mlp_forward(p["residual"], x, cfg.act)
    return out


def moe_aux_loss(p: Params, x: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Switch-style load-balance loss (fraction·probability product)."""
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(idx, cfg.n_experts), axis=0)
    imp = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac * imp)
