"""Decoder-only transformer family covering the five assigned LM archs.

Features: GQA (separate kv head count), explicit head_dim (gemma: 256 ≠
d_model/n_heads), RoPE, optional per-head qk RMS-norm (qwen3), GeGLU/SwiGLU
MLPs, capacity-based top-k MoE with interleaved MoE layers (llama4: every
other layer; grok-1: all layers), scan-over-layers (compact HLO at 48–95
layers), blocked causal attention (memory-bound-safe at 32k prefill), chunked
cross-entropy (never materializes (T, 202k) logits), and a KV-cache decode
path (``decode_step``) for the serve shapes.

Layer pattern: the layer stack is a scan over ``n_super`` super-layers, each
containing the sub-layers in ``cfg.layer_pattern`` (e.g. ("dense", "moe")).
Every sub-layer kind has its own stacked parameter group, so dense and MoE
layers can interleave without ragged pytrees.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import rms_norm

Array = jax.Array


def _psc(x, cfg: "LMConfig", *spec):
    """with_sharding_constraint if the config names mesh axes, else no-op.

    spec entries: "dp" → cfg.dp_axes, "tp" → cfg.tp_axis, None → unsharded.
    """
    if not cfg.dp_axes and not cfg.tp_axis:
        return x
    from jax.sharding import PartitionSpec as P
    resolved = tuple(
        cfg.dp_axes if s == "dp" else (cfg.tp_axis or None) if s == "tp" else None
        for s in spec)
    return jax.lax.with_sharding_constraint(x, P(*resolved))


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str = "lm"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 1024
    vocab: int = 1024
    act: str = "silu"                 # "silu" (SwiGLU) | "gelu" (GeGLU)
    qk_norm: bool = False
    rope_theta: float = 10000.0
    tied_embeddings: bool = False
    # MoE
    n_experts: int = 0                # 0 ⇒ all-dense
    top_k: int = 1
    capacity_factor: float = 1.25
    moe_layer_period: int = 1         # 1 ⇒ every layer MoE (when n_experts>0)
    # numerics
    param_dtype: str = "float32"
    act_dtype: str = "float32"
    # attention blocking
    q_chunk: int = 512
    kv_chunk: int = 512
    # remat: "full" (recompute layer in bwd), "none"
    remat: str = "full"
    # activation-sharding constraints (empty ⇒ single-device / GSPMD-free)
    dp_axes: Tuple[str, ...] = ()
    tp_axis: str = ""
    # Megatron-style sequence parallelism: inter-layer activations (and remat
    # residuals) sharded (B: dp, S: tp); GSPMD inserts AG at QKV / RS at WO.
    seq_shard: bool = True

    @property
    def layer_pattern(self) -> Tuple[str, ...]:
        if self.n_experts <= 0:
            return ("dense",)
        if self.moe_layer_period <= 1:
            return ("moe",)
        return ("dense",) * (self.moe_layer_period - 1) + ("moe",)

    @property
    def n_super(self) -> int:
        p = len(self.layer_pattern)
        assert self.n_layers % p == 0, (self.n_layers, self.layer_pattern)
        return self.n_layers // p

    @property
    def pdt(self):
        return jnp.dtype(self.param_dtype)

    @property
    def adt(self):
        return jnp.dtype(self.act_dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _norm(key, d, dtype):
    del key
    return jnp.ones((d,), dtype)


def _attn_init(key, cfg: LMConfig, n: int):
    ks = jax.random.split(key, 4)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (n, d, h * hd), cfg.pdt) * s,
        "wk": jax.random.normal(ks[1], (n, d, kv * hd), cfg.pdt) * s,
        "wv": jax.random.normal(ks[2], (n, d, kv * hd), cfg.pdt) * s,
        "wo": jax.random.normal(ks[3], (n, h * hd, d), cfg.pdt)
        * (1.0 / math.sqrt(h * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((n, hd), cfg.pdt)
        p["k_norm"] = jnp.ones((n, hd), cfg.pdt)
    return p


def _dense_mlp_init(key, cfg: LMConfig, n: int):
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    s = 1.0 / math.sqrt(d)
    return {
        "wg": jax.random.normal(ks[0], (n, d, f), cfg.pdt) * s,
        "wu": jax.random.normal(ks[1], (n, d, f), cfg.pdt) * s,
        "wd": jax.random.normal(ks[2], (n, f, d), cfg.pdt) * (1.0 / math.sqrt(f)),
    }


def _moe_mlp_init(key, cfg: LMConfig, n: int):
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    s = 1.0 / math.sqrt(d)
    return {
        "router": jax.random.normal(ks[0], (n, d, e), cfg.pdt) * s,
        "wg": jax.random.normal(ks[1], (n, e, d, f), cfg.pdt) * s,
        "wu": jax.random.normal(ks[2], (n, e, d, f), cfg.pdt) * s,
        "wd": jax.random.normal(ks[3], (n, e, f, d), cfg.pdt)
        * (1.0 / math.sqrt(f)),
    }


def init_params(key, cfg: LMConfig):
    keys = jax.random.split(key, 4 + 2 * len(cfg.layer_pattern))
    n = cfg.n_super
    params = {
        "embed": jax.random.normal(keys[0], (cfg.vocab, cfg.d_model), cfg.pdt)
        * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdt),
    }
    if not cfg.tied_embeddings:
        params["unembed"] = (
            jax.random.normal(keys[1], (cfg.d_model, cfg.vocab), cfg.pdt) * 0.02
        )
    for i, kind in enumerate(cfg.layer_pattern):
        sub = {
            "ln1": jnp.ones((n, cfg.d_model), cfg.pdt),
            "ln2": jnp.ones((n, cfg.d_model), cfg.pdt),
            "attn": _attn_init(keys[2 + 2 * i], cfg, n),
            "mlp": (_moe_mlp_init if kind == "moe" else _dense_mlp_init)(
                keys[3 + 2 * i], cfg, n
            ),
        }
        params[f"sub{i}"] = sub
    return params


def param_specs(cfg: LMConfig):
    """Parameter pytree as ShapeDtypeStructs (no allocation) — dry-run path."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., S, half)
    if ang.ndim == 2:  # (S, half) -> broadcast over batch
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _qkv(p, cfg: LMConfig, x: Array, positions: Array):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, s, kv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"].astype(x.dtype))
        k = rms_norm(k, p["k_norm"].astype(x.dtype))
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def blocked_causal_attention(q: Array, k: Array, v: Array, cfg: LMConfig) -> Array:
    """Online-softmax blocked attention (pure JAX; Pallas kernel is the TPU
    fast path — see repro/kernels/flash_attention).

    q: (B, S, H, hd), k/v: (B, S, KV, hd).  Returns (B, S, H, hd).

    GQA kv heads are repeated up to H before the score einsums so the head
    axis shards cleanly over the tensor-parallel mesh axis (the grouped
    (kvh, g) layout fragments under GSPMD; the repeat is transient and lives
    under remat).
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    # GQA repeat + full-head tp sharding.  The grouped (B,S,KV,G,hd) layout
    # with kv-head sharding was tried and REFUTED (§Perf iteration 3: the
    # kvh=8→16 pad and reshape-resharding cost more than the repeat).
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    q = _psc(q, cfg, "dp", None, "tp", None)
    k = _psc(k, cfg, "dp", None, "tp", None)
    v = _psc(v, cfg, "dp", None, "tp", None)
    qc = min(cfg.q_chunk, s)
    kc = min(cfg.kv_chunk, s)
    if s % qc:
        qc = s                 # odd lengths (tests/short prompts): one chunk
    if s % kc:
        kc = s
    nq, nk = s // qc, s // kc
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(b, nq, qc, h, hd)
    kg = k.reshape(b, nk, kc, h, hd)
    vg = v.reshape(b, nk, kc, h, hd)

    q_pos = jnp.arange(s).reshape(nq, qc)
    k_pos = jnp.arange(s).reshape(nk, kc)

    def per_q_chunk(qi):
        qq = qg[:, qi]  # (b, qc, h, hd)

        def kv_step(carry, ki):
            m, l, acc = carry
            kk, vv = kg[:, ki], vg[:, ki]
            sc = jnp.einsum(
                "bqhd,bchd->bhqc", qq, kk, preferred_element_type=jnp.float32
            ) * scale
            mask = q_pos[qi][:, None] >= k_pos[ki][None, :]
            sc = jnp.where(mask[None, None], sc, -1e30)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum(
                "bhqc,bchd->bhqd", p.astype(vv.dtype), vv,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        a0 = jnp.zeros((b, h, qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3)  # (b, qc, h, hd)

    # flash-attention memory law: recompute scores in bwd, never store S².
    out = jax.lax.map(jax.checkpoint(per_q_chunk), jnp.arange(nq))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def attention_block(p, cfg: LMConfig, x: Array, positions: Array) -> Array:
    b, s, _ = x.shape
    # anchor: batch-sharded, full-seq at the projection boundary — keeps the
    # transpose (bwd) from replicating the activation (§Perf llama4 iter 6)
    x = _psc(x, cfg, "dp", None, None)
    q, k, v = _qkv(p, cfg, x, positions)
    o = blocked_causal_attention(q, k, v, cfg)
    o = _psc(o.reshape(b, s, -1), cfg, "dp", None, "tp")
    return o @ p["wo"].astype(x.dtype)


def decode_attention_block(p, cfg: LMConfig, x: Array, k_cache: Array,
                           v_cache: Array, cache_index: Array):
    """One-token decode.  x: (B, 1, D); caches: (B, S_max, KV, hd)."""
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    pos = jnp.full((b, 1), cache_index, jnp.int32)
    q, k, v = _qkv(p, cfg, x, pos)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, cache_index, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, cache_index, 0, 0))
    s_max = k_cache.shape[1]
    qg = q.reshape(b, kvh, g, hd)
    sc = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                    preferred_element_type=jnp.float32) / math.sqrt(hd)
    mask = jnp.arange(s_max)[None, None, None] <= cache_index
    sc = jnp.where(mask, sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    return o @ p["wo"].astype(x.dtype), k_cache, v_cache


def decode_attention_block_ragged(p, cfg: LMConfig, x: Array, k_cache: Array,
                                  v_cache: Array, positions: Array):
    """Per-row cache positions (continuous batching).  x: (B, 1, D);
    caches: (B, S_max, KV, hd); positions: (B,) int32."""
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    q, k, v = _qkv(p, cfg, x, positions[:, None])
    rows = jnp.arange(b)
    k_cache = k_cache.at[rows, positions].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[rows, positions].set(v[:, 0].astype(v_cache.dtype))
    s_max = k_cache.shape[1]
    qg = q.reshape(b, kvh, g, hd)
    sc = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                    preferred_element_type=jnp.float32) / math.sqrt(hd)
    mask = jnp.arange(s_max)[None, None, None, :] <= positions[:, None, None,
                                                              None]
    sc = jnp.where(mask, sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    return o @ p["wo"].astype(x.dtype), k_cache, v_cache


def decode_step_ragged(params, cfg: LMConfig, tokens: Array, cache,
                       positions: Array):
    """One-token decode with PER-ROW cache positions — the continuous-
    batching engine step (repro/train/serving.py)."""
    b = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.adt)
    cap = moe_capacity(cfg, b) if cfg.n_experts > 0 else 0
    subs = [params[f"sub{i}"] for i in range(len(cfg.layer_pattern))]
    caches = [cache[f"sub{i}"] for i in range(len(cfg.layer_pattern))]

    def super_layer(x, scanned):
        layer_params, layer_cache = scanned
        new_cache = []
        for kind, p, c in zip(cfg.layer_pattern, layer_params, layer_cache):
            h = rms_norm(x, p["ln1"].astype(x.dtype))
            o, k_new, v_new = decode_attention_block_ragged(
                p["attn"], cfg, h, c["k"], c["v"], positions)
            x = x + o
            h = rms_norm(x, p["ln2"].astype(x.dtype))
            if kind == "moe":
                x = x + moe_mlp(p["mlp"], cfg, h, cap)
            else:
                x = x + dense_mlp(p["mlp"], cfg, h)
            new_cache.append({"k": k_new, "v": v_new})
        return x, tuple(new_cache)

    x, new_caches = jax.lax.scan(super_layer, x, (tuple(subs), tuple(caches)))
    x = rms_norm(x, params["final_norm"].astype(x.dtype))
    logits = x[:, 0] @ unembed_matrix(params, cfg).astype(x.dtype)
    out_cache = {f"sub{i}": new_caches[i]
                 for i in range(len(cfg.layer_pattern))}
    return logits.astype(jnp.float32), out_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def _act(cfg: LMConfig):
    return jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu


def dense_mlp(p, cfg: LMConfig, x: Array) -> Array:
    a = _act(cfg)
    x = _psc(x, cfg, "dp", None, None)
    h = a(x @ p["wg"].astype(x.dtype)) * (x @ p["wu"].astype(x.dtype))
    h = _psc(h, cfg, "dp", None, "tp")
    return h @ p["wd"].astype(x.dtype)


def moe_mlp(p, cfg: LMConfig, x: Array, capacity: int) -> Array:
    """Capacity-based top-k MoE with DRHM-deterministic tie-breaking.

    x: (B, S, D) → flatten to tokens (T, D).  Dispatch/combine are expressed
    as segment ops (the same decoupled multiply/accumulate structure as the
    paper's SpGEMM: dispatch ≙ multiply-stage gather, combine ≙ accumulate).
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(t, d)
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                    # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)        # (T, k, E)
    flat_oh = onehot.reshape(t * k, e)
    pos_in_e = jnp.cumsum(flat_oh, axis=0) - flat_oh          # exclusive
    pos = (pos_in_e * flat_oh).sum(-1).reshape(t, k)          # (T, k)

    keep = pos < capacity
    slot = jnp.where(keep, top_e * capacity + pos, e * capacity)  # drop → ghost

    # dispatch (multiply-stage analogue): scatter tokens into (E*C, D)
    xk = jnp.broadcast_to(xt[:, None], (t, k, d)).reshape(t * k, d)
    buf = jax.ops.segment_sum(xk, slot.reshape(-1), num_segments=e * capacity + 1)
    buf = buf[: e * capacity].reshape(e, capacity, d).astype(x.dtype)
    # expert-parallel layout: experts over tp when divisible (llama4 128e);
    # otherwise (grok 8e) keep experts whole and shard the FFN hidden over tp
    # — constraining hidden to full-F per device would force every tp rank to
    # recompute the same (E, C, F) activation (§Perf grok iteration 1).
    e_spec = "tp" if (cfg.tp_axis and e % 16 == 0) else None
    f_spec = None if e_spec else "tp"
    buf = _psc(buf, cfg, e_spec, "dp", None)

    a = _act(cfg)
    hidden = a(
        jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(x.dtype))
    ) * jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(x.dtype))
    hidden = _psc(hidden, cfg, e_spec, "dp", f_spec)
    out_buf = jnp.einsum("ecf,efd->ecd", hidden, p["wd"].astype(x.dtype))
    out_buf = _psc(out_buf, cfg, e_spec, "dp", None)

    # combine (accumulate-stage analogue): gather slots back, prob-weighted
    flat = out_buf.reshape(e * capacity, d)
    gathered = jnp.take(flat, jnp.minimum(slot, e * capacity - 1).reshape(-1),
                        axis=0).reshape(t, k, d)
    gathered = jnp.where(keep[..., None], gathered, 0)
    y = (gathered * top_p[..., None].astype(x.dtype)).sum(axis=1)
    return y.reshape(b, s, d)


def moe_mlp_sharded(p, cfg: LMConfig, x: Array, capacity: int,
                    tp_size: int = 16) -> Array:
    """Manual (shard_map) MoE: token-local dispatch, per-device capacity.

    GSPMD cannot partition the global dispatch scatter — it materializes a
    replicated (E·C, D) buffer and all-reduces it (grok train: 64 GB buffer,
    12 TB/device of collective traffic; §Perf grok iteration 2).  Production
    systems dispatch per device; we do the same under shard_map:

    * tokens are sharded over every mesh axis (dp × tp);
    * each device routes its own tokens into a local (E, C_loc, D) buffer —
      zero dispatch communication, DRHM-grade balance by router randomness;
    * expert FFN:  E % tp == 0 → expert-parallel: all_to_all over tp moves
      token slots to their expert's owner (llama4);  otherwise the FFN hidden
      dim is tp-sharded and the down-projection psums over tp (grok);
    * combine is again token-local.
    FSDP weight gathers happen at the shard_map boundary (in_specs).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tp = cfg.tp_axis
    try:  # prefer the ambient mesh's actual tp extent
        amesh = jax.sharding.get_abstract_mesh()
        tp_size = dict(zip(amesh.axis_names, amesh.axis_sizes)).get(tp, tp_size)
    except Exception:  # noqa: BLE001 — keep the caller-provided default
        pass
    ep = e % tp_size == 0
    # EP: tokens shard over dp×tp (a2a re-groups by expert owner).
    # F-shard: tp carries the hidden dim, so tokens shard over dp only —
    # sharding tokens over tp too would psum outputs of DIFFERENT tokens.
    token_axes = cfg.dp_axes + ((tp,) if ep else ())
    a = _act(cfg)

    def local_fn(router, wg, wu, wd, xt):
        t_loc = xt.shape[0]
        c_loc = max(8, capacity * t_loc // (b * s))
        logits = (xt @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32).reshape(t_loc * k, e)
        pos = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
        pos = pos.reshape(t_loc, k)
        keep = pos < c_loc
        slot = jnp.where(keep, top_e * c_loc + pos, e * c_loc)
        xk = jnp.broadcast_to(xt[:, None], (t_loc, k, d)).reshape(t_loc * k, d)
        buf = jax.ops.segment_sum(xk, slot.reshape(-1),
                                  num_segments=e * c_loc + 1)
        buf = buf[: e * c_loc].reshape(e, c_loc, d).astype(x.dtype)

        if ep:
            # expert-parallel: ship slots to expert owners over tp
            buf = jax.lax.all_to_all(buf, tp, split_axis=0, concat_axis=1,
                                     tiled=True)          # (E/tp, C·tp, D)
            hidden = a(jnp.einsum("ecd,edf->ecf", buf, wg)) \
                * jnp.einsum("ecd,edf->ecf", buf, wu)
            out = jnp.einsum("ecf,efd->ecd", hidden, wd)
            out = jax.lax.all_to_all(out, tp, split_axis=1, concat_axis=0,
                                     tiled=True)          # (E, C_loc, D)
        else:
            # hidden-sharded: every tp rank computes its F-slice, psum join
            hidden = a(jnp.einsum("ecd,edf->ecf", buf, wg)) \
                * jnp.einsum("ecd,edf->ecf", buf, wu)
            out = jnp.einsum("ecf,efd->ecd", hidden, wd)
            out = jax.lax.psum(out, tp)

        flat = out.reshape(e * c_loc, d)
        gathered = jnp.take(flat, jnp.minimum(slot, e * c_loc - 1).reshape(-1),
                            axis=0).reshape(t_loc, k, d)
        gathered = jnp.where(keep[..., None], gathered, 0)
        return (gathered * top_p[..., None].astype(x.dtype)).sum(axis=1)

    if ep:
        w_spec = (P(tp, None, None),) * 3
    else:
        w_spec = (P(None, None, tp), P(None, None, tp), P(None, tp, None))
    fn = jax.shard_map(
        local_fn,
        in_specs=(P(), *w_spec, P(token_axes, None)),
        out_specs=P(token_axes, None),
    )
    xt = x.reshape(b * s, d)
    y = fn(p["router"].astype(x.dtype), p["wg"].astype(x.dtype),
           p["wu"].astype(x.dtype), p["wd"].astype(x.dtype), xt)
    return y.reshape(b, s, d)


def moe_capacity(cfg: LMConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, ((c + 127) // 128) * 128)


# ---------------------------------------------------------------------------
# Forward (train) — scan over super-layers
# ---------------------------------------------------------------------------

def forward(params, cfg: LMConfig, tokens: Array) -> Array:
    """tokens (B, S) → final hidden states (B, S, D)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.adt)
    positions = jnp.arange(s)
    cap = moe_capacity(cfg, b * s) if cfg.n_experts > 0 else 0

    subs = [params[f"sub{i}"] for i in range(len(cfg.layer_pattern))]

    seq_spec = "tp" if cfg.seq_shard else None

    def super_layer(x, layer_params):
        x = _psc(x, cfg, "dp", seq_spec, None)
        for kind, p in zip(cfg.layer_pattern, layer_params):
            h = rms_norm(x, p["ln1"].astype(x.dtype))
            # residual stream stays sequence-sharded (Megatron-SP: the wo /
            # wd matmul outputs reduce-scatter over seq at each boundary)
            x = _psc(x + attention_block(p["attn"], cfg, h, positions),
                     cfg, "dp", seq_spec, None)
            h = rms_norm(x, p["ln2"].astype(x.dtype))
            if kind == "moe":
                if cfg.dp_axes:
                    x = x + moe_mlp_sharded(p["mlp"], cfg, h, cap)
                else:
                    x = x + moe_mlp(p["mlp"], cfg, h, cap)
            else:
                x = x + dense_mlp(p["mlp"], cfg, h)
            x = _psc(x, cfg, "dp", seq_spec, None)
        return x, None

    if cfg.remat == "full":
        super_layer = jax.checkpoint(super_layer)
    x, _ = jax.lax.scan(super_layer, x, tuple(subs))
    return rms_norm(x, params["final_norm"].astype(x.dtype))


def prefill(params, cfg: LMConfig, tokens: Array):
    """Forward pass that also materializes the KV cache (serving prefill).

    Returns (last-token logits (B, V), cache pytree as in ``init_cache``).
    """
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.adt)
    positions = jnp.arange(s)
    cap = moe_capacity(cfg, b * s) if cfg.n_experts > 0 else 0
    subs = [params[f"sub{i}"] for i in range(len(cfg.layer_pattern))]

    seq_spec = "tp" if cfg.seq_shard else None

    def super_layer(x, layer_params):
        x = _psc(x, cfg, "dp", seq_spec, None)
        kvs = []
        for kind, p in zip(cfg.layer_pattern, layer_params):
            h = rms_norm(x, p["ln1"].astype(x.dtype))
            q, k, v = _qkv(p["attn"], cfg, h, positions)
            o = blocked_causal_attention(q, k, v, cfg)
            x = x + o.reshape(b, s, -1) @ p["attn"]["wo"].astype(x.dtype)
            h = rms_norm(x, p["ln2"].astype(x.dtype))
            if kind == "moe":
                if cfg.dp_axes:
                    x = x + moe_mlp_sharded(p["mlp"], cfg, h, cap)
                else:
                    x = x + moe_mlp(p["mlp"], cfg, h, cap)
            else:
                x = x + dense_mlp(p["mlp"], cfg, h)
            kvs.append({"k": k, "v": v})
        return x, tuple(kvs)

    x, kv_stacked = jax.lax.scan(super_layer, x, tuple(subs))
    x = rms_norm(x, params["final_norm"].astype(x.dtype))
    logits = x[:, -1] @ unembed_matrix(params, cfg).astype(x.dtype)
    cache = {f"sub{i}": kv_stacked[i] for i in range(len(cfg.layer_pattern))}
    return logits.astype(jnp.float32), cache


def unembed_matrix(params, cfg: LMConfig):
    if cfg.tied_embeddings:
        return params["embed"].T
    return params["unembed"]


def chunked_xent_loss(params, cfg: LMConfig, hidden: Array, labels: Array,
                      chunk: int = 4096) -> Array:
    """Mean next-token cross-entropy without materializing (T, V) logits."""
    b, s, d = hidden.shape
    h = hidden[:, :-1].reshape(-1, d)
    y = labels[:, 1:].reshape(-1)
    t = h.shape[0]
    w = unembed_matrix(params, cfg).astype(hidden.dtype)
    chunk = min(chunk, t)
    n_chunks = t // chunk
    rem = t - n_chunks * chunk

    def body(tot, i):
        hc = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, 0)
        yc = jax.lax.dynamic_slice_in_dim(y, i * chunk, chunk, 0)
        logits = (hc @ w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return tot + jnp.sum(lse - ll), None

    # recompute (chunk, V) logits in bwd instead of storing all chunks
    body = jax.checkpoint(body)
    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), jnp.arange(n_chunks))
    if rem:
        logits = (h[n_chunks * chunk:] @ w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[n_chunks * chunk:, None], axis=-1)[:, 0]
        tot = tot + jnp.sum(lse - ll)
    return tot / t


def loss_fn(params, cfg: LMConfig, tokens: Array) -> Array:
    hidden = forward(params, cfg, tokens)
    return chunked_xent_loss(params, cfg, hidden, tokens)


# ---------------------------------------------------------------------------
# Decode path (serve shapes)
# ---------------------------------------------------------------------------

def init_cache(cfg: LMConfig, batch: int, s_max: int, dtype=None):
    """KV cache pytree: per sub-layer kind, stacked over super-layers."""
    dt = dtype or cfg.adt
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    n = cfg.n_super
    return {
        f"sub{i}": {
            "k": jnp.zeros((n, batch, s_max, kv, hd), dt),
            "v": jnp.zeros((n, batch, s_max, kv, hd), dt),
        }
        for i in range(len(cfg.layer_pattern))
    }


def cache_specs(cfg: LMConfig, batch: int, s_max: int, dtype=None):
    return jax.eval_shape(lambda: init_cache(cfg, batch, s_max, dtype))


def decode_step(params, cfg: LMConfig, tokens: Array, cache, cache_index):
    """tokens (B, 1) + cache → (logits (B, V), new cache)."""
    b = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.adt)
    cap = moe_capacity(cfg, b) if cfg.n_experts > 0 else 0
    subs = [params[f"sub{i}"] for i in range(len(cfg.layer_pattern))]
    caches = [cache[f"sub{i}"] for i in range(len(cfg.layer_pattern))]

    def super_layer(x, scanned):
        layer_params, layer_cache = scanned
        new_cache = []
        for kind, p, c in zip(cfg.layer_pattern, layer_params, layer_cache):
            h = rms_norm(x, p["ln1"].astype(x.dtype))
            o, k_new, v_new = decode_attention_block(
                p["attn"], cfg, h, c["k"], c["v"], cache_index)
            x = x + o
            h = rms_norm(x, p["ln2"].astype(x.dtype))
            if kind == "moe":
                x = x + moe_mlp(p["mlp"], cfg, h, cap)
            else:
                x = x + dense_mlp(p["mlp"], cfg, h)
            new_cache.append({"k": k_new, "v": v_new})
        return x, tuple(new_cache)

    x, new_caches = jax.lax.scan(super_layer, x, (tuple(subs), tuple(caches)))
    x = rms_norm(x, params["final_norm"].astype(x.dtype))
    logits = (x[:, 0] @ unembed_matrix(params, cfg).astype(x.dtype))
    out_cache = {
        f"sub{i}": new_caches[i] for i in range(len(cfg.layer_pattern))
    }
    return logits.astype(jnp.float32), out_cache
