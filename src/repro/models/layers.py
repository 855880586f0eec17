"""Shared neural layers: norms, RoPE, GQA attention (full/sliding/chunked),
GLU MLP — functional style, params as nested dicts, sharding via
``with_sharding_constraint`` (no-op when no mesh is active)."""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, ShardingConfig

Params = Dict[str, Any]


# -- sharding helpers ----------------------------------------------------------

def shard(x: jax.Array, shd: ShardingConfig, *spec) -> jax.Array:
    """Apply a sharding constraint if a mesh is active; otherwise no-op."""
    if not shd.enabled:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x


def dp(shd: ShardingConfig):
    """Batch/fsdp axes tuple (possibly multi-axis: ('pod','data'))."""
    return shd.fsdp if shd.fsdp else None


def tp_size(shd: ShardingConfig) -> int:
    """Extent of the tensor-parallel axis in the ambient (abstract) mesh."""
    if not shd.enabled or shd.tp is None:
        return 1
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return 1
        return dict(mesh.shape).get(shd.tp, 1)
    except Exception:
        return 1


def tp_if_divisible(shd: ShardingConfig, dim: int):
    """'model' axis name if it divides ``dim`` evenly, else None —
    avoids GSPMD involuntary-remat on padded shardings (e.g. 8 kv heads
    on a 16-way model axis → replicate kv, shard q heads: MQA-style TP)."""
    t = tp_size(shd)
    return shd.tp if (t > 1 and dim % t == 0) else None


# -- initialization -------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


# -- norms ----------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: int) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
    return out.astype(x.dtype)


# -- rotary position embedding ---------------------------------------------------

def rope_freqs(cfg: ModelConfig) -> jax.Array:
    hd = cfg.hd
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, freqs: jax.Array) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    ang = positions[..., :, None].astype(jnp.float32)[..., None, :] * freqs  # (...,S,1,hd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- attention --------------------------------------------------------------------

def attn_init(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    p = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, kv * hd, dt),
        "wv": dense_init(ks[2], d, kv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    return p


def _attn_mask(
    cfg: ModelConfig,
    q_pos: jax.Array,     # (Sq,)
    k_pos: jax.Array,     # (Sk,)
    is_global: bool,
    causal: bool = True,
) -> jax.Array:
    """(Sq, Sk) boolean mask — full / sliding-window / chunked-local."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    m = (kp <= qp) if causal else jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if cfg.attention == "full":
        return m
    if cfg.attention == "sliding":
        local = m & (kp > qp - cfg.window)
    elif cfg.attention == "chunked":  # Llama-4 style chunked-local
        local = m & ((kp // cfg.window) == (qp // cfg.window))
    else:
        raise ValueError(cfg.attention)
    # is_global may be a traced per-layer flag (scan-over-layers)
    return jnp.where(jnp.asarray(is_global), m, local)


def mha(
    cfg: ModelConfig,
    shd: ShardingConfig,
    p: Params,
    x: jax.Array,                      # (B, S, d)
    positions: jax.Array,              # (B, S)
    freqs: jax.Array,
    is_global: bool,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
    causal: bool = True,
    use_rope: bool = True,
) -> jax.Array:
    b, s, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    q = jnp.einsum("bsd,dk->bsk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(b, s, h, hd)
    if kv_override is None:
        k = jnp.einsum("bsd,dk->bsk", x, p["wk"])
        v = jnp.einsum("bsd,dk->bsk", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = k.reshape(b, s, kv, hd)
        v = v.reshape(b, s, kv, hd)
        if causal and use_rope:  # RoPE on self-attention only (Whisper: learned abs pos)
            k = apply_rope(k, positions, freqs)
        k_pos = positions[0]
    else:
        k, v = kv_override
        k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    if causal and use_rope:
        q = apply_rope(q, positions, freqs)

    if (cfg.attn_impl == "flash" and kv_override is None
            and s == k.shape[1] and s % 128 == 0):
        out = _attn_flash(cfg, shd, q, k, v, is_global, causal)
    elif cfg.attn_impl == "chunked_q":
        out = _attn_chunked_q(cfg, shd, q, k, v, positions, k_pos,
                              is_global, causal)
    else:
        out = _attn_naive(cfg, shd, q, k, v, positions, k_pos,
                          is_global, causal)
    out = out.reshape(b, s, h * hd)
    out = shard(out, shd, dp(shd), None, shd.tp)
    return jnp.einsum("bsk,kd->bsd", out, p["wo"])


def _attn_naive(cfg, shd, q, k, v, positions, k_pos, is_global, causal):
    """Paper-faithful baseline: full (…,S,S) score materialization."""
    b, s, h, hd = q.shape
    q = shard(q, shd, dp(shd), None, tp_if_divisible(shd, h), None)
    k = shard(k, shd, dp(shd), None, tp_if_divisible(shd, k.shape[2]), None)
    qg = q.reshape(b, s, k.shape[2], -1, hd)     # grouped-query folding
    scores = jnp.einsum("bsgqh,btgh->bgqst", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    mask = _attn_mask(cfg, positions[0], k_pos, is_global, causal)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgqst,btgh->bsgqh", w, v)
    return out


def _attn_flash(cfg, shd, q, k, v, is_global, causal):
    """§Perf optimized path: Pallas flash-attention kernels (fwd + bwd) —
    no S² HBM residency.  KV heads expand to full heads and heads pad to
    a model-axis multiple so the kernel shards evenly via shard_map over
    the ambient mesh (kernels/flash_attention.py)."""
    from repro.kernels import flash_attention as FA

    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    t = tp_size(shd)
    h_pad = ((h + t - 1) // t) * t
    if h_pad != h:
        pad = ((0, 0), (0, 0), (0, h_pad - h), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)

    interpret = jax.default_backend() != "tpu"
    window = cfg.window if cfg.attention in ("sliding", "chunked") else 0
    glob = jnp.asarray(is_global, jnp.int32).reshape(1)

    def local(qs, ks, vs, g):
        bl, sl, hl, _ = qs.shape
        fold = lambda x: x.transpose(0, 2, 1, 3).reshape(bl * hl, sl, hd)
        o = FA.flash_attention_nhsd(
            fold(qs), fold(ks), fold(vs), cfg.attention, window, causal,
            g[0] != 0, FA.BQ, FA.BK, interpret)
        return o.reshape(bl, hl, sl, hd).transpose(0, 2, 1, 3)

    mesh = None
    if shd.enabled:
        try:
            m = jax.sharding.get_abstract_mesh()
            mesh = None if (m is None or m.empty) else m
        except Exception:
            mesh = None
    if mesh is not None:
        spec = P(dp(shd), None, shd.tp, None)
        out = jax.shard_map(local, mesh=mesh,
                            in_specs=(spec,) * 3 + (P(None),),
                            out_specs=spec, check_vma=False)(q, k, v, glob)
    else:
        out = local(q, k, v, glob)
    return out[:, :, :h, :]


def _attn_chunked_q(cfg, shd, q, k, v, positions, k_pos, is_global, causal):
    """§Perf optimized path: scan over query chunks with exact row
    softmax — peak scores residency is (b, h, Qc, S) per chunk instead of
    (b, h, S, S); KV heads are expanded to full heads so the head dim
    shards evenly over the model axis (beyond-paper change, EXPERIMENTS.md
    §Perf iteration 1)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if kvh != h:                                  # GQA → full heads
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    q = shard(q, shd, dp(shd), None, shd.tp, None)
    k = shard(k, shd, dp(shd), None, shd.tp, None)
    v = shard(v, shd, dp(shd), None, shd.tp, None)
    qc = min(cfg.attn_q_chunk, s)
    nc = s // qc if s % qc == 0 else 1
    qc = s // nc
    scale = 1.0 / math.sqrt(hd)
    q_chunks = q.reshape(b, nc, qc, h, hd).transpose(1, 0, 2, 3, 4)
    pos_chunks = positions[0].reshape(nc, qc)

    def chunk_fn(_, inp):
        qb, pos_q = inp                           # (b,qc,h,hd), (qc,)
        sc = jnp.einsum("bqhd,bthd->bhqt", qb, k).astype(jnp.float32) * scale
        mask = _attn_mask(cfg, pos_q, k_pos, is_global, causal)
        sc = jnp.where(mask[None, None], sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(qb.dtype)
        ob = jnp.einsum("bhqt,bthd->bqhd", w, v)
        return None, ob

    _, out_chunks = jax.lax.scan(chunk_fn, None, (q_chunks, pos_chunks))
    out = out_chunks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)
    return out


def kv_groups(cfg: ModelConfig, k: jax.Array) -> int:
    return k.shape[2]


def _tp_size(shd: ShardingConfig) -> int:
    return 1  # resolved by GSPMD; constraint validity handled by `shard`


# -- GLU MLP -----------------------------------------------------------------------

def mlp_init(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    dt = jnp.dtype(cfg.dtype)
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], cfg.d_model, f, dt),
        "w_up": dense_init(ks[1], cfg.d_model, f, dt),
        "w_down": dense_init(ks[2], f, cfg.d_model, dt),
    }


def _act(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        return jax.nn.silu(x)
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(cfg.act)


def mlp(cfg: ModelConfig, shd: ShardingConfig, p: Params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    hdn = _act(cfg, g) * u
    hdn = shard(hdn, shd, dp(shd), None, shd.tp)
    return jnp.einsum("bsf,fd->bsd", hdn, p["w_down"])


# -- embeddings ----------------------------------------------------------------------

def embed_init(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 2)
    p = {"tok": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["out"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)
    return p


def embed(cfg: ModelConfig, shd: ShardingConfig, p: Params, tokens: jax.Array) -> jax.Array:
    e = jnp.take(p["tok"], tokens, axis=0)
    return shard(e, shd, dp(shd), None, None)


def unembed(cfg: ModelConfig, shd: ShardingConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["tok"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["out"])
    return shard(logits, shd, dp(shd), None, shd.tp)
