"""Shared transformer building blocks for the assigned architecture pool.

Everything is a pure function over explicit param pytrees (nested dicts of
jnp arrays) so the same code paths run under ``jax.eval_shape`` (the dry-run
lowers against ShapeDtypeStructs — no allocation) and under jit on device.

Sharding is expressed through ``shard(x, spec, mesh)`` constraint points; the
actual PartitionSpecs come from ``repro.dist.sharding`` so the layer code is
policy-free.  Two attention distribution modes are supported (DESIGN.md §5):

  head-TP   q/k/v head axes sharded over ``model`` — only legal when BOTH
            num_heads and num_kv_heads divide the model-axis size
            (olmo/seamless/zamba2 on a 16-way axis);
  context   sequence axis sharded over ``model`` (context parallelism): K/V
            are all-gathered per layer, each device attends for its S-slice.
            Divisibility-proof (yi 56H, qwen2 28H, llama4 40H, ...).

Decode attends one query token against a cache whose SEQUENCE axis may be
sharded (flash-decoding): the softmax reductions over the sharded axis lower
to partial reduce + all-reduce — exactly the (m, l, o) merge — so the code
is written as plain jnp and XLA SPMD emits the merge collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from repro.models import scanctl
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# sharding constraint helper
# ---------------------------------------------------------------------------


def shard(x: jax.Array, spec: P | None, mesh: Mesh | None) -> jax.Array:
    """Constraint point; no-op when mesh or spec is absent (smoke tests)."""
    if mesh is None or spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# initializers (all take an rng key; shapes only — dry-run eval_shapes these)
# ---------------------------------------------------------------------------


def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) > 1 else 1
    s = scale if scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def init_norm(cfg: ModelConfig, d: int, dtype) -> Params:
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if cfg.norm_type == "ln":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if cfg.norm_type == "nonparam_ln":
        return {}
    raise ValueError(cfg.norm_type)


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    # (nonparam_)ln
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    if cfg.norm_type == "ln":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _rms_head(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Per-head-dim RMS norm (chameleon / llama4 QK-norm)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [..., S, H, dh]; positions [..., S] (broadcastable)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # [dh/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, dh/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs (paper pool: swiglu / squared-relu / gelu)
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, d: int, f: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {"wi": _dense_init(ks[0], (d, f), dtype),
         "wo": _dense_init(ks[1], (f, d), dtype)}
    if cfg.mlp_type == "swiglu":
        p["wg"] = _dense_init(ks[2], (d, f), dtype)
    return p


def apply_mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    h = x @ p["wi"]
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * h
    elif cfg.mlp_type == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    elif cfg.mlp_type == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(cfg.mlp_type)
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static attention wiring for one layer position."""
    use_rope: bool = True
    window: int = 0          # >0: chunked-local (block-diagonal causal)
    causal: bool = True
    cross: bool = False      # cross-attention (enc-dec memory)


def init_attention(cfg: ModelConfig, key, d_in: int, dtype,
                   *, d_out: int | None = None) -> Params:
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    d_out = d_out if d_out is not None else d_in
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d_in, H * dh), dtype),
        "wk": _dense_init(ks[1], (d_in, KV * dh), dtype),
        "wv": _dense_init(ks[2], (d_in, KV * dh), dtype),
        "wo": _dense_init(ks[3], (H * dh, d_out), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((KV * dh,), dtype)
        p["bv"] = jnp.zeros((KV * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array,
                 kv_x: jax.Array | None = None):
    """x [B, S, Din] -> q [B, S, H, dh], k/v [B, Skv, KV, dh]."""
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kv_x = x if kv_x is None else kv_x
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(*x.shape[:-1], H, dh)
    k = k.reshape(*kv_x.shape[:-1], KV, dh)
    v = v.reshape(*kv_x.shape[:-1], KV, dh)
    if cfg.qk_norm:
        q = _rms_head(q, p["q_norm"])
        k = _rms_head(k, p["k_norm"])
    return q, k, v


def _sdpa(q, k, v, mask, *, kv_groups: int) -> jax.Array:
    """Grouped scaled-dot-product attention.

    q [B, Sq, H, dh] with H = KV * kv_groups; k/v [B, Sk, KV, dh];
    mask [Sq, Sk] bool (True = attend) or None.  f32 softmax.
    """
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Sq, KV, kv_groups, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits / np.sqrt(dh)
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


def _chunked_sdpa(q, k, v, *, kv_groups: int, q_positions, kv_positions,
                  spec: AttnSpec, chunk: int) -> jax.Array:
    """Flash-style blockwise attention: scan over KV chunks with running
    (m, l, acc).  Never materializes the [Sq, Sk] score matrix — the memory
    bound that makes prefill_32k / train_4k lowerable.

    q [B, Sq, H, dh]; k/v [B, Sk, KV, dh]; positions give causal/window
    masks under context parallelism (q_positions are the GLOBAL indices of
    this shard's queries).
    """
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad),
                               constant_values=jnp.iinfo(jnp.int32).max)
    kc = k.reshape(B, n_chunks, chunk, KV, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, dh).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(n_chunks, chunk)

    qg = q.reshape(B, Sq, KV, kv_groups, dh)
    scale = 1.0 / np.sqrt(dh)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, pj = xs
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((Sq, chunk), bool)
        if spec.causal:
            mask &= q_positions[:, None] >= pj[None, :]
        if spec.window > 0:  # chunked-local (llama4 iRoPE)
            mask &= (q_positions[:, None] // spec.window) == \
                    (pj[None, :] // spec.window)
        mask &= pj[None, :] < jnp.iinfo(jnp.int32).max  # padding
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, kv_groups, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, kv_groups, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, kv_groups, Sq, dh), jnp.float32)
    (m, l, acc), _ = scanctl.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, dh)
    return out.astype(q.dtype)


def attention_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                      spec: AttnSpec, *, splan=None,
                      positions: jax.Array | None = None,
                      kv_x: jax.Array | None = None,
                      kv_positions: jax.Array | None = None,
                      attn_chunk: int | None = None) -> jax.Array:
    """Full-sequence attention (train / prefill).  x [B, S, D].

    ``splan`` (repro.dist.sharding.ShardingPlan) steers the distribution:
    head-TP constrains the head axis to 'model'; context parallelism keeps
    queries S-sharded and constrains K/V replicated on 'model' (the
    per-layer KV all-gather).
    """
    B, S = x.shape[:2]
    H, KV = cfg.num_heads, cfg.num_kv_heads
    kv_groups = H // KV
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = (positions if kv_x is None
                        else jnp.arange(k.shape[1], dtype=jnp.int32))
    if spec.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if not spec.cross:
            k = apply_rope(k, kv_positions, cfg.rope_theta)
    if splan is not None and splan.mesh is not None:
        q = shard(q, splan.qkv, splan.mesh)
        k = shard(k, splan.kv_ctx, splan.mesh)
        v = shard(v, splan.kv_ctx, splan.mesh)
    out = _chunked_sdpa(q, k, v, kv_groups=kv_groups, q_positions=positions,
                        kv_positions=kv_positions, spec=spec,
                        chunk=min(attn_chunk or cfg.attn_kv_chunk,
                                  k.shape[1]))
    return out.reshape(B, S, H * cfg.head_dim) @ p["wo"]


def attention_forward_with_cache(cfg: ModelConfig, p: Params, x: jax.Array,
                                 spec: AttnSpec, *, splan=None,
                                 positions: jax.Array | None = None,
                                 ctx: int | None = None,
                                 attn_chunk: int | None = None):
    """Prefill: like attention_forward but also emits the {k, v} cache
    (post-RoPE), zero-padded to ``ctx`` positions for later decode appends."""
    B, S = x.shape[:2]
    H, KV = cfg.num_heads, cfg.num_kv_heads
    kv_groups = H // KV
    q, k, v = _project_qkv(cfg, p, x)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if spec.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if splan is not None and splan.mesh is not None:
        q = shard(q, splan.qkv, splan.mesh)
        k = shard(k, splan.kv_ctx, splan.mesh)
        v = shard(v, splan.kv_ctx, splan.mesh)
    out = _chunked_sdpa(q, k, v, kv_groups=kv_groups, q_positions=positions,
                        kv_positions=positions, spec=spec,
                        chunk=min(attn_chunk or cfg.attn_kv_chunk,
                                  k.shape[1]))
    out = out.reshape(B, S, H * cfg.head_dim) @ p["wo"]
    ctx = ctx or S
    if ctx > S:
        k = jnp.pad(k, ((0, 0), (0, ctx - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, ctx - S), (0, 0), (0, 0)))
    if splan is not None and splan.mesh is not None:
        k = shard(k, splan.decode_cache, splan.mesh)
        v = shard(v, splan.decode_cache, splan.mesh)
    return out, {"k": k, "v": v}


def attention_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                     cache: dict[str, jax.Array], spec: AttnSpec,
                     *, splan=None,
                     update_cache: bool = True) -> tuple[jax.Array, dict]:
    """One-token decode. x [B, 1, D]; cache {k,v: [B, Sc, KV, dh], index: []}.

    The cache S axis may be sharded (flash-decoding) — the softmax over it
    lowers to partial reduce + all-reduce (the (m,l,o) merge), so this is
    plain jnp.  Local (windowed) layers keep a ring cache of size window.
    """
    B = x.shape[0]
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kv_groups = H // KV
    Sc = cache["k"].shape[1]
    # index: [] (lockstep batch) or [B] (continuous batching, per-slot)
    index = jnp.broadcast_to(jnp.atleast_1d(cache["index"]), (B,))
    q, k_new, v_new = _project_qkv(cfg, p, x)
    pos = index[:, None]
    if spec.use_rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        if not spec.cross:
            k_new = apply_rope(k_new, pos, cfg.rope_theta)
    if spec.cross:
        k, v = cache["k"], cache["v"]
        valid = jnp.ones((B, Sc), bool)
        new_cache = cache
    else:
        slot = jnp.mod(index, Sc)
        bix = jnp.arange(B)
        k = cache["k"].at[bix, slot].set(k_new[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[bix, slot].set(v_new[:, 0].astype(cache["v"].dtype))
        slots = jnp.arange(Sc)
        valid = slots[None, :] <= index[:, None]
        if spec.window > 0:  # chunked-local (iRoPE): same window block only
            valid &= (slots[None, :] // spec.window) == \
                (index[:, None] // spec.window)
        if splan is not None and splan.mesh is not None:
            k = shard(k, splan.decode_cache, splan.mesh)
            v = shard(v, splan.decode_cache, splan.mesh)
        new_cache = ({"k": k, "v": v, "index": cache["index"] + 1}
                     if update_cache else cache)

    logits = jnp.einsum("bqkgd,bskd->bkgqs",
                        q.reshape(B, 1, KV, kv_groups, dh), k,
                        preferred_element_type=jnp.float32) / np.sqrt(dh)
    logits = jnp.where(valid[:, None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, H * dh).astype(x.dtype)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# Mixture of Experts (llama4): top-1 routing + shared expert, EP all-to-all
# ---------------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key, d: int, f: int, dtype) -> Params:
    E = cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, E), jnp.float32),
        "wi": _dense_init(ks[1], (E, d, f), dtype),
        "wg": _dense_init(ks[2], (E, d, f), dtype),
        "wo": _dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(
            dataclasses.replace(cfg, mlp_type="swiglu"), ks[4], d, f, dtype)
    return p


def _moe_dispatch_compute(p: Params, tokens: jax.Array, capacity: int,
                          *, ep_axis: str | None) -> jax.Array:
    """tokens [T, D] -> routed expert output [T, D] (top-1, capacity drop).

    Local math: scatter tokens into an [E, C, D] buffer keyed by
    (expert, position-in-expert); batched expert GEMMs; gather back.
    With ``ep_axis`` (inside shard_map) the buffer's E axis is exchanged via
    all_to_all so each device computes ONLY its local experts — the paper's
    model parallelism (trees ↔ experts; DESIGN.md §4) at the MoE layer.
    """
    T, D = tokens.shape
    E = p["router"].shape[1]
    logits = tokens.astype(jnp.float32) @ p["router"]          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)                             # [T]
    eidx = jnp.argmax(probs, axis=-1).astype(jnp.int32)        # [T]

    onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32)          # [T, E]
    pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                              eidx[:, None], 1)[:, 0]          # [T]
    keep = pos < capacity
    slot = jnp.where(keep, eidx * capacity + pos, E * capacity)

    buf = jnp.zeros((E * capacity + 1, D), tokens.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], tokens, 0))
    buf = buf[:-1].reshape(E, capacity, D)

    wi, wg, wo = p["wi"], p["wg"], p["wo"]
    if ep_axis is not None:
        # self-transposing all_to_all (split==concat axis) so the VJP maps
        # back onto the same primitive with matching axis order
        n = jax.lax.axis_size(ep_axis)
        buf = jax.lax.all_to_all(buf.reshape(n, E // n, capacity, D),
                                 ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False)          # [n_src, E/n, C, D]
        buf = jnp.moveaxis(buf, 0, 1).reshape(E // n, n * capacity, D)
    h = jnp.einsum("ecd,edf->ecf", buf, wi,
                   preferred_element_type=jnp.float32).astype(tokens.dtype)
    g = jnp.einsum("ecd,edf->ecf", buf, wg,
                   preferred_element_type=jnp.float32).astype(tokens.dtype)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo,
                   preferred_element_type=jnp.float32).astype(tokens.dtype)
    if ep_axis is not None:
        n = jax.lax.axis_size(ep_axis)
        y = jnp.moveaxis(y.reshape(E // n, n, capacity, D), 1, 0)
        y = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0,
                               tiled=False)            # [n_dst, E/n, C, D]
        y = y.reshape(E, capacity, D)
    y = jnp.concatenate([y.reshape(E * capacity, D),
                         jnp.zeros((1, D), y.dtype)], 0)
    out = y[slot] * (gate * keep)[:, None].astype(y.dtype)
    return out


def apply_moe(cfg: ModelConfig, p: Params, x: jax.Array, *,
              splan=None) -> jax.Array:
    """x [B, S, D] -> [B, S, D]; EP over the mesh 'model' axis when present."""
    B, S, D = x.shape
    mesh = splan.mesh if splan is not None else None
    use_ep = (mesh is not None and "model" in mesh.axis_names
              and cfg.num_experts % mesh.shape["model"] == 0)
    cf = cfg.capacity_factor

    if not use_ep:
        cap = max(1, int(B * S * cf / cfg.num_experts))
        out = _moe_dispatch_compute(p, x.reshape(B * S, D), cap, ep_axis=None)
        out = out.reshape(B, S, D)
    else:
        n_model = mesh.shape["model"]
        data_axes = splan.data_axes
        # local tokens per (data..., model) block (activations are CP-sharded
        # for the MoE archs: [B -> data..., S -> model, D])
        t_local = (B // int(np.prod([mesh.shape[a] for a in data_axes]))) * \
                  (S // n_model)
        # capacity per SOURCE device per expert (before all_to_all concat)
        cap_src = max(1, int(t_local * cf / cfg.num_experts))

        def local(xb, router, wi, wg, wo):
            b, s, d = xb.shape
            pp = {"router": router, "wi": wi, "wg": wg, "wo": wo}
            y = _moe_dispatch_compute(pp, xb.reshape(b * s, d), cap_src,
                                      ep_axis="model")
            return y.reshape(b, s, d)

        da = data_axes if len(data_axes) > 1 else data_axes[0]
        in_specs = (P(da, "model", None),                 # x
                    P(),                                  # router replicated
                    P("model", None, None),               # wi (E sharded)
                    P("model", None, None),               # wg
                    P("model", None, None))               # wo
        out = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                            out_specs=P(da, "model", None),
                            check_vma=False)(
            x, p["router"], p["wi"], p["wg"], p["wo"])

    if cfg.shared_expert:
        shared_cfg = dataclasses.replace(cfg, mlp_type="swiglu")
        out = out + apply_mlp(shared_cfg, p["shared"], x)
    return out


def moe_decode(cfg: ModelConfig, p: Params, x: jax.Array,
               *, splan=None) -> jax.Array:
    """Decode-path MoE.

    Default: per-token expert-weight gather — simple, but against
    E-sharded expert weights XLA materializes cross-device weight
    gathers (the collective bottleneck §Perf found on decode cells).

    ``cfg.moe_decode_ep``: EP-local compute + psum — tokens are tiny at
    decode, so replicate them over 'model', let each device run ONLY its
    local experts (zero-masking tokens routed elsewhere) and psum the
    [B, 1, D] outputs: moves activations (KB), never weights (GB).
    """
    B, S, D = x.shape
    mesh = splan.mesh if splan is not None else None
    use_ep = (cfg.moe_decode_ep and mesh is not None
              and "model" in mesh.axis_names
              and cfg.num_experts % mesh.shape["model"] == 0)
    if use_ep:
        n = mesh.shape["model"]
        E = cfg.num_experts
        E_l = E // n
        da = (splan.data_axes if len(splan.data_axes) > 1
              else (splan.data_axes[0] if splan.data_axes else None))
        b_spec = splan.decode_hidden[0]

        def local(xb, router, wi, wg, wo):
            # xb [b, s, d] (replicated over model); wi/wg/wo local experts.
            # Masked EINSUM over all E_l local experts: token counts are
            # tiny at decode, so E_l× extra FLOPs are free while a
            # per-token weight gather would materialize [T, D, F] copies
            # (the memory term iteration 2 removed, EXPERIMENTS §Perf).
            my = jax.lax.axis_index("model")
            b, s, d = xb.shape
            t = xb.reshape(b * s, d)
            logits = t.astype(jnp.float32) @ router
            gate = jnp.max(jax.nn.softmax(logits, -1), -1)
            eidx = jnp.argmax(logits, -1).astype(jnp.int32)
            local_e = eidx - my * E_l                    # [T]
            onehot = jax.nn.one_hot(local_e, E_l, dtype=t.dtype)  # [T, E_l]
            h = jnp.einsum("td,edf->tef", t, wi)         # [T, E_l, F]
            g = jnp.einsum("td,edf->tef", t, wg)
            y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h, wo)
            y = jnp.einsum("ted,te->td", y, onehot)
            y = y * gate[:, None].astype(y.dtype)
            return jax.lax.psum(y.reshape(b, s, d), "model")

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(b_spec, None, None), P(),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(b_spec, None, None), check_vma=False)(
            x, p["router"], p["wi"], p["wg"], p["wo"])
    else:
        tokens = x.reshape(B * S, D)
        logits = tokens.astype(jnp.float32) @ p["router"]
        gate = jnp.max(jax.nn.softmax(logits, -1), -1)
        eidx = jnp.argmax(logits, -1)
        wi = p["wi"][eidx]                               # [T, D, F] gather
        wg = p["wg"][eidx]
        wo = p["wo"][eidx]
        h = jnp.einsum("td,tdf->tf", tokens, wi)
        g = jnp.einsum("td,tdf->tf", tokens, wg)
        y = jnp.einsum("tf,tfd->td", jax.nn.silu(g) * h, wo)
        out = (y * gate[:, None].astype(y.dtype)).reshape(B, S, D)
    if cfg.shared_expert:
        shared_cfg = dataclasses.replace(cfg, mlp_type="swiglu")
        out = out + apply_mlp(shared_cfg, p["shared"], x)
    return out
