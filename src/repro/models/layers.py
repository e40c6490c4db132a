"""Neural-net layers for the unified transformer stack.

Pure functions over param pytrees (no framework dependency).  Compute is
bf16-friendly: matmuls accept whatever dtype params carry; softmax, norms
and the SSD scan accumulate in f32.

Parallelism: activations get logical-axis sharding constraints
(parallel.api.constrain); the MoE layer is a shard_map island —
activations are replicated across the 'model' axis (standard TP), each
model-lane owns E/M experts, routes the *same* token set to its local
experts, and a single psum over 'model' combines — comm cost of one
all-reduce, identical to a TP dense layer (DESIGN.md §5; an all_to_all
variant is the §Perf hillclimb comparison).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.parallel import api as par

Params = dict


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / positional
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _f32c(x):
    return x.astype(jnp.float32)


def _f32c_fwd(x):
    return x.astype(jnp.float32), jnp.zeros((0,), x.dtype)


def _f32c_bwd(token, dy):
    # Norms upcast to f32 internally; without this, the residual-stream
    # cotangent crosses the TP all-reduce in f32 — 2x the wire bytes
    # (§Perf iteration C3).  Standard mixed-precision practice: the
    # boundary cotangent lives in the params' dtype.  (The zero-size
    # ``token`` smuggles the static dtype through the vjp residuals.)
    return (dy.astype(token.dtype),)


_f32c.defvjp(_f32c_fwd, _f32c_bwd)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = _f32c(x)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x, w, b, eps: float = 1e-5):
    x32 = _f32c(x)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def norm_apply(cfg, p: Params, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)


def norm_init(cfg, d: int) -> Params:
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,)), "b": jnp.zeros((d,))}
    return {"w": jnp.ones((d,))}


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, S, D) with even D; positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[None, None, :, None] * freqs
    else:
        ang = positions.astype(jnp.float32)[:, None, :, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.astype(x.dtype)


def sinusoid_pos(seq: int, d: int, offset: int = 0) -> jax.Array:
    pos = np.arange(offset, offset + seq)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / d))
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, jnp.float32)


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def mlp_init(cfg, key, d: int, ff: int) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w1": _init(ks[0], (d, ff)), "w2": _init(ks[1], (ff, d))}
    if cfg.act == "silu":
        p["w3"] = _init(ks[2], (d, ff))
    return p


def mlp_apply(cfg, p: Params, x):
    h = x @ p["w1"]
    h = par.constrain(h, "batch", None, "ff")
    if cfg.act == "silu":
        h = jax.nn.silu(h) * (x @ p["w3"])
    else:
        h = jax.nn.gelu(h)
    out = h @ p["w2"]
    return par.constrain(out, "batch", None, None)


# ---------------------------------------------------------------------------
# Attention — GQA/MQA (+ qk-norm, windows) and MLA
# ---------------------------------------------------------------------------


def attn_init(cfg, key, *, cross: bool = False) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.attn_kind == "mla" and not cross:
        return mla_init(cfg, key)
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d, h * hd)),
        "wk": _init(ks[1], (d, kv * hd)),
        "wv": _init(ks[2], (d, kv * hd)),
        "wo": _init(ks[3], (h * hd, d), scale=1.0 / math.sqrt(h * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,))
        p["k_norm"] = jnp.ones((hd,))
    return p


def _split_heads(x, n):  # (B,S,n*hd) -> (B,n,S,hd)
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)


def _merge_heads(x):  # (B,n,S,hd) -> (B,S,n*hd)
    b, n, s, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, n * hd)


def gqa_qkv(cfg, p, x, positions):
    q = _split_heads(x @ p["wq"], cfg.n_heads)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embed == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(cfg, p, x, *, causal=True, window=None, positions=None):
    """Training/prefill attention.  Returns (out, (k, v)) so prefill can
    seed the cache."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = gqa_qkv(cfg, p, x, positions)
    q = par.constrain(q, "batch", "heads", None, None)
    k = par.constrain(k, "batch", "kv_heads", None, None)
    o = ops.flash_attention(q, k, v, causal=causal, window=window)
    out = _merge_heads(o) @ p["wo"]
    return par.constrain(out, "batch", None, None), (k, v)


def attn_decode(cfg, p, x, cache, pos, window=None, ring=False):
    """One-token decode against a (B, kv, S, hd) cache.  ``pos``: () int.

    ``ring``: the cache is a circular buffer of exactly ``window`` slots
    (long-context local attention) — slot = pos % S, and every slot's
    absolute position is recovered arithmetically for masking.
    """
    k_cache, v_cache = cache["k"], cache["v"]
    b = x.shape[0]
    s_max = k_cache.shape[2]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = gqa_qkv(cfg, p, x, positions)
    slot = jnp.asarray(pos) % s_max if ring else jnp.asarray(pos)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot, axis=2)
    kv = k_cache.shape[1]
    rep = cfg.n_heads // kv
    qg = q.reshape(b, kv, rep, cfg.head_dim)  # (B,kv,rep,hd) from (B,H,1,hd)
    logits = jnp.einsum(
        "bkrd,bksd->bkrs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) / math.sqrt(cfg.head_dim)
    slots = jnp.arange(s_max)
    if ring:
        # Absolute position stored in each slot: the largest value <= pos
        # congruent to the slot index (mod s_max); negative = never written.
        abs_pos = pos - ((pos - slots) % s_max)
        mask = (abs_pos >= 0)[None, None, None, :]
    else:
        mask = (slots <= pos)[None, None, None, :]
        if window is not None:
            mask = mask & (slots > pos - window)[None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkrs,bksd->bkrd", w, v_cache.astype(jnp.float32))
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return o @ p["wo"], {"k": k_cache, "v": v_cache}


# --- Cross-attention (enc-dec: whisper) -----------------------------------


def cross_kv(cfg, p, enc_out):
    """Precompute cross-attention K/V from encoder output (once per
    sequence; cached for decode)."""
    k = _split_heads(enc_out @ p["wk"], cfg.n_kv_heads)
    v = _split_heads(enc_out @ p["wv"], cfg.n_kv_heads)
    return k, v


def cross_apply(cfg, p, x, kv):
    """Decoder cross-attention: no mask, no rope."""
    k, v = kv
    q = _split_heads(x @ p["wq"], cfg.n_heads)
    o = ops.flash_attention(q, k, v, causal=False)
    out = _merge_heads(o) @ p["wo"]
    return par.constrain(out, "batch", None, None)


# --- MLA (multi-head latent attention, DeepSeek/MiniCPM3 style) ----------


def mla_init(cfg, key) -> Params:
    d = cfg.d_model
    h = cfg.n_heads
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_dim_per_head
    qr = cfg.q_lora_rank
    ks = jax.random.split(key, 8)
    p: Params = {}
    if qr:
        p["w_dq"] = _init(ks[0], (d, qr))
        p["q_norm"] = jnp.ones((qr,))
        p["w_uq"] = _init(ks[1], (qr, h * (nope + rope_d)))
    else:
        p["w_uq"] = _init(ks[1], (d, h * (nope + rope_d)))
    p["w_dkv"] = _init(ks[2], (d, cfg.kv_lora_rank + rope_d))
    p["kv_norm"] = jnp.ones((cfg.kv_lora_rank,))
    p["w_uk"] = _init(ks[3], (cfg.kv_lora_rank, h * nope))
    p["w_uv"] = _init(ks[4], (cfg.kv_lora_rank, h * vd))
    p["wo"] = _init(ks[5], (h * vd, d), scale=1.0 / math.sqrt(h * vd))
    return p


def _mla_q(cfg, p, x, positions):
    h, nope, rope_d = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = x
    if cfg.q_lora_rank:
        cq = rmsnorm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    q = _split_heads(cq @ p["w_uq"], h)               # (B,H,S,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(cfg, p, x, positions):
    rope_d = cfg.qk_rope_dim
    dkv = x @ p["w_dkv"]                              # (B,S,kv_lora+rope)
    c_kv = rmsnorm(dkv[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = dkv[..., cfg.kv_lora_rank :][:, None]    # (B,1,S,rope)
    k_rope = rope(k_rope, positions, cfg.rope_theta)
    return c_kv, k_rope


def mla_apply(cfg, p, x, *, causal=True, window=None, positions=None,
              pad_v: bool = True):
    b, s, d = x.shape
    h, nope, rope_d = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_dim_per_head
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    c_kv, k_rope = _mla_ckv(cfg, p, x, positions)
    k_nope = _split_heads(c_kv @ p["w_uk"], h)        # (B,H,S,nope)
    v = _split_heads(c_kv @ p["w_uv"], h)             # (B,H,S,vd)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, h, s, rope_d))], -1)
    dq = nope + rope_d
    if pad_v and vd < dq:
        # Pad V to the QK head dim so the flash kernel path applies — MLA
        # with d_v != d_qk otherwise falls back to exact attention, which
        # materialises the (S, S) logits (§Perf iteration A: the padding
        # costs (dq/vd - 1)x extra PV flops but removes the O(S^2) HBM
        # traffic; same trick the TPU Pallas kernel uses).
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dq - vd)))
        o = ops.flash_attention(
            q, k, v, causal=causal, window=window, scale=1.0 / math.sqrt(dq)
        )[..., :vd]
    else:
        o = ops.flash_attention(
            q, k, v, causal=causal, window=window, scale=1.0 / math.sqrt(dq)
        )
    out = _merge_heads(o) @ p["wo"]
    return par.constrain(out, "batch", None, None), (c_kv, k_rope)


def mla_decode(cfg, p, x, cache, pos):
    """Absorbed MLA decode: the cache stores only (c_kv, k_rope) —
    the latent compression is the whole point of MLA."""
    b = x.shape[0]
    h, nope, rope_d = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_dim_per_head
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(cfg, p, x, positions)     # (B,H,1,·)
    c_new, kr_new = _mla_ckv(cfg, p, x, positions)    # (B,1,r) / (B,1,1,rope)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], c_new, pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache["kr"], kr_new[:, 0], pos, axis=1
    )                                                  # (B,S,rope)
    s_max = ckv.shape[1]
    w_uk = p["w_uk"].reshape(cfg.kv_lora_rank, h, nope)
    # Absorb W_uk into q: q_lat (B,H,1,r)
    q_lat = jnp.einsum("bhqn,rhn->bhqr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    logits = (
        jnp.einsum("bhqr,bsr->bhqs", q_lat, ckv.astype(jnp.float32))
        + jnp.einsum("bhqd,bsd->bhqs", q_rope.astype(jnp.float32),
                     krope.astype(jnp.float32))
    ) / math.sqrt(nope + rope_d)
    mask = (jnp.arange(s_max) <= pos)[None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    wts = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bhqr", wts, ckv.astype(jnp.float32))  # (B,H,1,r)
    w_uv = p["w_uv"].reshape(cfg.kv_lora_rank, h, vd)
    o = jnp.einsum("bhqr,rhv->bhqv", o_lat, w_uv.astype(jnp.float32))
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * vd).astype(x.dtype)
    return o @ p["wo"], {"ckv": ckv, "kr": krope}


# ---------------------------------------------------------------------------
# MoE — expert parallel over the 'model' axis
# ---------------------------------------------------------------------------


def moe_init(cfg, key) -> Params:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, e), scale=0.02),
        "w1": _init(ks[1], (e, d, f)),
        "w3": _init(ks[2], (e, d, f)),
        "w2": _init(ks[3], (e, f, d), scale=1.0 / math.sqrt(f)),
    }
    if cfg.n_shared_experts:
        sf = (cfg.shared_d_ff or cfg.expert_ff) * cfg.n_shared_experts
        p["shared"] = mlp_init(cfg, ks[4], d, sf)
    return p


def _moe_local(cfg, p_router, w1, w3, w2, x_flat, e_lo, e_local: int,
               capacity: int):
    """Route x_flat (t, d) to experts [e_lo, e_lo + e_local) held locally.

    ``e_local``/``capacity`` are static (shape-bearing); ``e_lo`` may be a
    traced ``axis_index`` product.  Returns (y (t, d), aux loss).  Used
    verbatim by the single-device fallback (e_lo=0, e_local=E) and by
    each model-lane in the shard_map island.
    """
    t, d = x_flat.shape
    e_hi = e_lo + e_local
    k = cfg.topk
    logits = (x_flat @ p_router).astype(jnp.float32)          # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                      # (t, k)
    gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    fe = topi.reshape(-1)                                     # (t*k,)
    gate_flat = gates.reshape(-1)
    mine = (fe >= e_lo) & (fe < e_hi)
    le = jnp.where(mine, fe - e_lo, e_local)                  # local expert id
    order = jnp.argsort(le, stable=True)
    le_s = le[order]
    tok_s = order // k
    gate_s = gate_flat[order]
    first = jnp.searchsorted(le_s, jnp.arange(e_local + 1))
    rank = jnp.arange(t * k) - first[jnp.clip(le_s, 0, e_local)]
    keep = (le_s < e_local) & (rank < capacity)
    slot = jnp.where(keep, le_s * capacity + rank, e_local * capacity)

    xe = jnp.zeros((e_local * capacity + 1, d), x_flat.dtype)
    xe = xe.at[slot].set(jnp.where(keep[:, None], x_flat[tok_s], 0))
    xe = xe[:-1].reshape(e_local, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xe, w3)
    ye = jnp.einsum("ecf,efd->ecd", h, w2).reshape(e_local * capacity, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], 0)

    contrib = ye[slot] * (gate_s * keep)[:, None].astype(ye.dtype)
    y = jnp.zeros((t, d), x_flat.dtype).at[tok_s].add(contrib)

    # Load-balance aux parts (Switch): per-expert top-1 counts and prob
    # sums.  Returned as SUMS so shards combine linearly (aux is nonlinear
    # in the means, so per-shard aux values cannot simply be averaged).
    onehot = jax.nn.one_hot(topi[:, 0], cfg.n_experts, dtype=jnp.float32)
    aux_parts = (onehot.sum(0), probs.sum(0), jnp.asarray(t, jnp.float32))
    return y, aux_parts


def _moe_a2a_island(cfg, x_loc, router, w1, w3, w2, *, n_dlanes: int,
                    tokens_sharded: bool, int8_wire: bool = False):
    """DeepSeek-style expert parallelism: expert weights are FULLY sharded
    (experts over 'data', expert-FFN dim over 'model') and never move;
    only the routed tokens cross the wire via all_to_all over 'data'.

    This is the paper's core insight applied to MoE dispatch — ship the
    small representatives (top-k routed tokens, ~k/E of activations), not
    the big thing (expert weights).  §Perf iteration B replaces the
    epsum baseline (replicated activations + FSDP weight re-gathers)
    with this; collective bytes drop by the weights/activations ratio.
    """
    d = x_loc.shape[-1]
    e, k = cfg.n_experts, cfg.topk
    D = n_dlanes
    e_per = e // D
    t = x_loc.shape[0] * x_loc.shape[1]
    xf = x_loc.reshape(t, d)

    logits = (xf @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    fe = topi.reshape(-1)
    gate_flat = gates.reshape(-1)

    if tokens_sharded:
        # --- dispatch: sort token-copies by destination data-lane -------
        dest = fe // e_per
        le = fe % e_per
        order = jnp.argsort(dest, stable=True)
        dest_s, le_s = dest[order], le[order]
        tok_s, gate_s = order // k, gate_flat[order]
        first = jnp.searchsorted(dest_s, jnp.arange(D + 1))
        rank = jnp.arange(t * k) - first[jnp.clip(dest_s, 0, D)]
        cap = int(math.ceil(t * k / D * cfg.capacity_factor))
        keep = rank < cap
        slot = jnp.where(keep, dest_s * cap + rank, D * cap)
        # Send-buffer build = one fused gather pass on TPU
        # (kernels/moe_gather.dispatch_gather); the jnp chain below is its
        # stand-in, so intermediates count as VMEM in the roofline.
        with jax.named_scope("vmem_kernel_dispatch"):
            send_x = jnp.zeros((D * cap + 1, d), xf.dtype).at[slot].set(
                jnp.where(keep[:, None], xf[tok_s], 0))[: D * cap]
        send_le = jnp.full((D * cap + 1,), -1, jnp.int32).at[slot].set(
            jnp.where(keep, le_s, -1))[: D * cap]
        if int8_wire:
            from repro.parallel.compress import int8_all_to_all
            recv_x = int8_all_to_all(
                send_x.reshape(D, cap, d), "data").reshape(D * cap, d)
        else:
            recv_x = jax.lax.all_to_all(
                send_x.reshape(D, cap, d), "data", 0, 0).reshape(D * cap, d)
        recv_le = jax.lax.all_to_all(
            send_le.reshape(D, cap), "data", 0, 0).reshape(D * cap)
        n_recv = D * cap
    else:
        # Tokens replicated over 'data' (tiny batches): every lane holds
        # all tokens — just select the copies routed to MY experts.
        dlane = jax.lax.axis_index("data")
        mine = (fe >= dlane * e_per) & (fe < (dlane + 1) * e_per)
        recv_le = jnp.where(mine, fe - dlane * e_per, -1)
        recv_x = xf[jnp.arange(t * k) // k]
        n_recv = t * k

    # --- group received tokens by local expert -------------------------
    key2 = jnp.where(recv_le >= 0, recv_le, e_per)
    order2 = jnp.argsort(key2, stable=True)
    rl_s = key2[order2]
    first2 = jnp.searchsorted(rl_s, jnp.arange(e_per + 1))
    rank2 = jnp.arange(n_recv) - first2[jnp.clip(rl_s, 0, e_per)]
    # n_recv already carries the dispatch capacity factor; don't stack a
    # second one (§Perf iteration B2).
    cap_e = int(math.ceil(n_recv / e_per))
    keep2 = (rl_s < e_per) & (rank2 < cap_e)
    slot2 = jnp.where(keep2, rl_s * cap_e + rank2, e_per * cap_e)
    with jax.named_scope("vmem_kernel_dispatch"):  # second gather pass
        xe = jnp.zeros((e_per * cap_e + 1, d), recv_x.dtype).at[slot2].set(
            jnp.where(keep2[:, None], recv_x[order2], 0)
        )[:-1].reshape(e_per, cap_e, d)

    # --- expert compute (f sharded over 'model') ------------------------
    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xe, w3)
    ye = jnp.einsum("ecf,efd->ecd", h, w2)
    # ye is PARTIAL over the f-shards ('model' lanes).  The psum happens
    # AFTER the return-trip combine, on (t, d) token rows instead of
    # (E_local, cap_e, d) expert slots — k*cf times fewer all-reduce
    # bytes (§Perf iteration B2; linearity of the f-contraction makes the
    # reordering exact).

    # --- un-group + return trip + combine -------------------------------
    with jax.named_scope("vmem_kernel_dispatch"):  # inverse gather pass
        ye_flat = jnp.concatenate(
            [ye.reshape(e_per * cap_e, d), jnp.zeros((1, d), ye.dtype)])
        back = jnp.zeros((n_recv, d), ye.dtype).at[order2].set(ye_flat[slot2])
    if tokens_sharded:
        if int8_wire:
            from repro.parallel.compress import int8_all_to_all
            ret = int8_all_to_all(
                back.reshape(D, cap, d), "data").reshape(D * cap, d)
        else:
            ret = jax.lax.all_to_all(
                back.reshape(D, cap, d), "data", 0, 0).reshape(D * cap, d)
        ret = jnp.concatenate([ret, jnp.zeros((1, d), ret.dtype)])
        contrib = ret[slot] * (gate_s * keep)[:, None].astype(ret.dtype)
        y = jnp.zeros((t, d), xf.dtype).at[tok_s].add(contrib)
        y = jax.lax.psum(y, "model")
    else:
        contrib = back * jnp.where(recv_le >= 0, gate_flat, 0.0)[:, None].astype(back.dtype)
        y = jnp.zeros((t, d), xf.dtype).at[jnp.arange(n_recv) // k].add(contrib)
        y = jax.lax.psum(y, ("data", "model"))

    onehot = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)
    aux_parts = (onehot.sum(0), probs.sum(0), jnp.asarray(t, jnp.float32))
    return y.reshape(x_loc.shape), aux_parts


def _aux_from_parts(e, parts):
    f_sum, p_sum, t = parts
    t = jnp.maximum(t, 1.0)
    return e * jnp.sum((f_sum / t) * (p_sum / t))


def moe_apply(cfg, p: Params, x):
    """x: (B, S, d) -> (y, aux_loss).

    Implementations (ParallelCtx.moe_impl):
      epsum — activations replicated over 'model', experts sharded over
              'model', psum combine.  Simple; weights FSDP-gathered.
      a2a   — experts over 'data' x FFN-dim over 'model' (weights never
              move); routed tokens all_to_all'd (§Perf iteration B).
      (no mesh) — single-device fallback, identical math.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.topk
    c = par.ctx()
    m = c.axis_size("experts")
    from jax.sharding import PartitionSpec as P

    pod = "pod" if c.mesh is not None and "pod" in c.mesh.shape else None
    batch_axes = (pod, "data") if pod else ("data",)
    dp = 1
    if c.mesh is not None:
        for a_ in batch_axes:
            if a_:
                dp *= c.mesh.shape[a_]
    # Tiny batches (long-context decode, global_batch=1) replicate across
    # DP inside the island instead of sharding.
    bspec = batch_axes if b % dp == 0 else None
    psum_axes = tuple(a_ for a_ in batch_axes if a_) if bspec else ()

    n_data = c.mesh.shape.get("data", 1) if c.mesh is not None else 1
    f_loc_ok = cfg.expert_ff % max(m, 1) == 0

    if c.mesh is None or m <= 1 or e % m != 0:
        t = b * s
        cap = int(math.ceil(t * k / e * cfg.capacity_factor))
        y, parts = _moe_local(cfg, p["router"], p["w1"], p["w3"], p["w2"],
                              x.reshape(t, d), e_lo=0, e_local=e, capacity=cap)
        y = y.reshape(x.shape)
        aux = _aux_from_parts(e, parts)
    elif (c.moe_impl == "a2a" and e % n_data == 0 and n_data > 1 and f_loc_ok):
        def island(x_loc, router, w1, w3, w2):
            y, parts = _moe_a2a_island(
                cfg, x_loc, router, w1, w3, w2, n_dlanes=n_data,
                tokens_sharded=bspec is not None, int8_wire=c.a2a_int8)
            if psum_axes:
                parts = jax.tree.map(lambda a_: jax.lax.psum(a_, psum_axes), parts)
            return y, parts

        y, parts = jax.shard_map(
            island,
            mesh=c.mesh,
            in_specs=(
                P(bspec, None, None),
                P(None, None),
                P("data", None, "model"),
                P("data", None, "model"),
                P("data", "model", None),
            ),
            out_specs=(P(bspec, None, None), (P(), P(), P())),
            check_vma=False,
        )(x, p["router"], p["w1"], p["w3"], p["w2"])
        aux = _aux_from_parts(e, parts)
    else:
        def island(x_loc, router, w1, w3, w2):
            lane = jax.lax.axis_index("model")
            t = x_loc.shape[0] * x_loc.shape[1]
            cap = int(math.ceil(t * k / e * cfg.capacity_factor))
            e_local = e // m
            y, parts = _moe_local(
                cfg, router, w1, w3, w2, x_loc.reshape(t, d),
                e_lo=lane * e_local, e_local=e_local, capacity=cap)
            y = jax.lax.psum(y.reshape(x_loc.shape), "model")
            if psum_axes:
                parts = jax.tree.map(lambda a_: jax.lax.psum(a_, psum_axes), parts)
            return y, parts

        y, parts = jax.shard_map(
            island,
            mesh=c.mesh,
            in_specs=(
                P(bspec, None, None),
                P(None, None),
                P("model", None, None),
                P("model", None, None),
                P("model", None, None),
            ),
            out_specs=(P(bspec, None, None), (P(), P(), P())),
            check_vma=False,
        )(x, p["router"], p["w1"], p["w3"], p["w2"])
        aux = _aux_from_parts(e, parts)

    if cfg.n_shared_experts:
        y = y + mlp_apply(cfg, p["shared"], x)
    return par.constrain(y, "batch", None, None), aux


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) block
# ---------------------------------------------------------------------------


def mamba_init(cfg, key) -> Params:
    d, di, st, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 4)
    conv_dim = di + 2 * st
    return {
        "w_in": _init(ks[0], (d, 2 * di + 2 * st + h)),
        "conv": _init(ks[1], (cfg.conv_kernel, conv_dim), scale=0.2),
        "a_log": jnp.zeros((h,)),
        "dt_bias": jnp.zeros((h,)),
        "d_skip": jnp.ones((h,)),
        "out_norm": jnp.ones((di,)),
        "w_out": _init(ks[2], (di, d)),
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv.  x: (B, S, C); w: (K, C).  ``state``: (B, K-1, C)
    tail from the previous segment (decode).  Returns (y, new_state)."""
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], k - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(y), xp[:, -(k - 1) :]


def _mamba_project(cfg, p, x):
    di, st, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    zxbcdt = x @ p["w_in"]
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : di + di + 2 * st]
    dt = jax.nn.softplus(zxbcdt[..., -h:] + p["dt_bias"])     # (B,S,h)
    return z, xbc, dt


def _mamba_ssd_inputs(cfg, p, xbc, dt):
    b_, s_ = xbc.shape[0], xbc.shape[1]
    di, st, h, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    xs = xbc[..., :di].reshape(b_, s_, h, hd)
    bmat = xbc[..., di : di + st][:, :, None, :]               # (B,S,1,st)
    cmat = xbc[..., di + st :][:, :, None, :]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))               # (h,) < 0
    a_dt = a[None, None, :] * dt                               # (B,S,h) log-decay
    b_eff = jnp.broadcast_to(bmat, (b_, s_, h, st)) * dt[..., None]
    c_eff = jnp.broadcast_to(cmat, (b_, s_, h, st))
    return xs, a_dt, b_eff, c_eff


def mamba_apply(cfg, p: Params, x, conv_state=None, return_state: bool = False):
    """Full-sequence Mamba-2 block.  Returns (out, cache|None); with
    ``return_state`` the cache {"conv", "ssm"} seeds decode."""
    z, xbc, dt = _mamba_project(cfg, p, x)
    xbc, conv_tail = _causal_conv(xbc, p["conv"], conv_state)
    xs, a_dt, b_eff, c_eff = _mamba_ssd_inputs(cfg, p, xbc, dt)
    y = ops.ssd_scan(xs, a_dt, b_eff, c_eff)                   # (B,S,h,hd)
    y = y + xs * p["d_skip"][None, None, :, None]
    y = y.reshape(x.shape[0], x.shape[1], cfg.d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["out_norm"], cfg.norm_eps)
    out = y @ p["w_out"]
    cache = None
    if return_state:
        # Final SSM state: S = sum_j exp(cum_last - cum_j) b_j^T x_j
        # (decayed contributions of every step; old steps underflow to 0,
        # which is the mathematically correct limit).
        cum = jnp.cumsum(a_dt.astype(jnp.float32), axis=1)      # (B,S,h)
        w = jnp.exp(cum[:, -1:, :] - cum)                       # (B,S,h)
        s_fin = jnp.einsum("bsht,bshd,bsh->bhtd", b_eff.astype(jnp.float32),
                           xs.astype(jnp.float32), w)
        cache = {"conv": conv_tail, "ssm": s_fin}
    return par.constrain(out, "batch", None, None), cache


def mamba_decode(cfg, p: Params, x, cache, pos):
    """One-step Mamba-2 recurrence.  cache: {"conv": (B,K-1,C), "ssm":
    (B,h,st,hd)}."""
    z, xbc, dt = _mamba_project(cfg, p, x)                     # S = 1
    xbc, conv_tail = _causal_conv(xbc, p["conv"], cache["conv"])
    xs, a_dt, b_eff, c_eff = _mamba_ssd_inputs(cfg, p, xbc, dt)
    s_prev = cache["ssm"]                                      # (B,h,st,hd)
    decay = jnp.exp(a_dt[:, 0])[..., None, None]               # (B,h,1,1)
    s_new = s_prev * decay + b_eff[:, 0][..., :, None] * xs[:, 0][..., None, :]
    y = jnp.einsum("bhs,bhsd->bhd", c_eff[:, 0], s_new)[:, None]  # (B,1,h,hd)
    y = y + xs * p["d_skip"][None, None, :, None]
    y = y.reshape(x.shape[0], 1, cfg.d_inner).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["out_norm"], cfg.norm_eps)
    return y @ p["w_out"], {"conv": conv_tail, "ssm": s_new}
