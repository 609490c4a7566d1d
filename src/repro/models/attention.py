"""Chunked (flash-style) attention in pure JAX, GQA-native.

Two schedules compute the same function:

  * "masked"  -- scan over all (q_chunk, kv_chunk) pairs, mask inside the
                 chunk.  Baseline: simple, but for causal masks ~2x the
                 useful FLOPs are spent on fully-masked pairs.
  * "banded"  -- scan only the chunk pairs that can contain unmasked
                 entries (triangular band for causal, diagonal band for
                 sliding-window).  The §Perf compute-term optimization.

Online-softmax statistics are carried in f32; QK^T and PV contractions
run in the compute dtype with f32 accumulation, mirroring the MXU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _chunk_mask(qpos, kpos, kind: str, window, prefix_len):
    """Boolean mask (..., qc, kc): True = attend."""
    if kind == "none":
        return None
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    causal = k <= q
    if kind == "causal":
        m = causal
    elif kind == "local":
        m = causal & (k > q - window)
    elif kind == "prefix":
        m = causal | (k < prefix_len)
    else:
        raise ValueError(kind)
    return m


def _score_block(q_blk, k_blk, scale, logit_cap):
    # q_blk: (B, qc, KV, G, D), k_blk: (B, kc, KV, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk,
                   preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        s = jnp.tanh(s / logit_cap) * logit_cap
    return s


def _pv_block(p, v_blk):
    # p: (B, KV, G, qc, kc) f32; v_blk: (B, kc, KV, D)
    return jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk,
                      preferred_element_type=jnp.float32)


def _band_pairs(n_q: int, n_k: int, kind: str, window, k_chunk: int,
                prefix_len) -> list:
    """Chunk pairs that may contain unmasked entries (static)."""
    pairs = []
    band = None
    if kind == "local" and window is not None:
        band = -(-window // k_chunk)           # chunks back from diagonal
    prefix_chunks = 0
    if kind == "prefix" and prefix_len:
        prefix_chunks = -(-prefix_len // k_chunk)
    for qi in range(n_q):
        for ki in range(n_k):
            if kind == "none":
                pairs.append((qi, ki))
                continue
            diag = (qi * n_k) // n_q            # kv chunk containing diagonal
            if ki > diag and ki >= prefix_chunks:
                continue                        # fully in the future
            if band is not None and ki < diag - band and ki >= prefix_chunks:
                continue                        # fully outside the window
            pairs.append((qi, ki))
    return pairs


@functools.partial(
    jax.jit,
    static_argnames=("mask_kind", "window", "prefix_len", "logit_cap",
                     "q_chunk", "k_chunk", "schedule"))
def flash_attention(q, k, v, *, mask_kind: str = "causal",
                    window: int | None = None, prefix_len: int | None = None,
                    logit_cap: float | None = None,
                    q_chunk: int = 512, k_chunk: int = 512,
                    schedule: str = "masked", q_offset=0,
                    k_offset=0) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D) -> (B, Sq, H, D).

    H must be a multiple of KV (GQA groups are never materialized).
    q_offset/k_offset shift the absolute positions of q/k rows -- used
    by the context-parallel path where each shard holds a sequence
    slice (may be traced values; "banded" requires static offsets = 0).
    """
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    q = q.reshape(b, sq, kv, g, d)

    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    if sq % q_chunk or sk % k_chunk:
        q_chunk, k_chunk = sq, sk               # fallback: single chunk
    n_q, n_k = sq // q_chunk, sk // k_chunk

    if schedule == "banded" and mask_kind != "none":
        return _banded(q, k, v, scale, mask_kind, window, prefix_len,
                       logit_cap, q_chunk, k_chunk, n_q, n_k
                       ).reshape(b, sq, h, d)

    def q_step(_, qi):
        q_blk = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * k_chunk, k_chunk, 1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * k_chunk, k_chunk, 1)
            kpos = k_offset + ki * k_chunk + jnp.arange(k_chunk)
            s = _score_block(q_blk, k_blk, scale, logit_cap)
            msk = _chunk_mask(qpos, kpos, mask_kind, window, prefix_len)
            if msk is not None:
                s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + _pv_block(p, v_blk)
            return (m_new, l, acc), None

        m0 = jnp.full((b, kv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kv, g, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_k))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, KV, G, qc, D) -> (B, qc, KV, G, D)
        return None, jnp.moveaxis(out, 3, 1)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(n_q))
    # blocks: (n_q, B, qc, KV, G, D)
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, sq, kv, g, d)
    return out.reshape(b, sq, h, d).astype(v.dtype)


def _banded(q, k, v, scale, mask_kind, window, prefix_len, logit_cap,
            q_chunk, k_chunk, n_q, n_k):
    """Band-scheduled exact attention: skip fully-masked chunk pairs."""
    b, sq, kv, g, d = q.shape
    pairs = _band_pairs(n_q, n_k, mask_kind, window, k_chunk, prefix_len)
    qi_idx = jnp.asarray([p[0] for p in pairs])
    ki_idx = jnp.asarray([p[1] for p in pairs])

    m0 = jnp.full((n_q, b, kv, g, q_chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_q, b, kv, g, q_chunk), jnp.float32)
    a0 = jnp.zeros((n_q, b, kv, g, q_chunk, d), jnp.float32)

    def step(carry, xs):
        m_all, l_all, acc_all = carry
        qi, ki = xs
        q_blk = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, 1)
        k_blk = jax.lax.dynamic_slice_in_dim(k, ki * k_chunk, k_chunk, 1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, ki * k_chunk, k_chunk, 1)
        qpos = qi * q_chunk + jnp.arange(q_chunk)
        kpos = ki * k_chunk + jnp.arange(k_chunk)
        s = _score_block(q_blk, k_blk, scale, logit_cap)
        msk = _chunk_mask(qpos, kpos, mask_kind, window, prefix_len)
        if msk is not None:
            s = jnp.where(msk, s, NEG_INF)
        m = jax.lax.dynamic_index_in_dim(m_all, qi, 0, keepdims=False)
        l = jax.lax.dynamic_index_in_dim(l_all, qi, 0, keepdims=False)
        acc = jax.lax.dynamic_index_in_dim(acc_all, qi, 0, keepdims=False)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + _pv_block(p, v_blk)
        m_all = jax.lax.dynamic_update_index_in_dim(m_all, m_new, qi, 0)
        l_all = jax.lax.dynamic_update_index_in_dim(l_all, l, qi, 0)
        acc_all = jax.lax.dynamic_update_index_in_dim(acc_all, acc, qi, 0)
        return (m_all, l_all, acc_all), None

    (m_all, l_all, acc_all), _ = jax.lax.scan(step, (m0, l0, a0),
                                              (qi_idx, ki_idx))
    out = acc_all / jnp.maximum(l_all, 1e-30)[..., None]
    # (n_q, B, KV, G, qc, D) -> (B, n_q*qc = Sq, KV, G, D)
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(b, sq, kv, g, d)
    return out.astype(v.dtype)


def flash_attention_context_parallel(
        q, k, v, mesh, *, mask_kind: str = "causal",
        window: int | None = None, prefix_len: int | None = None,
        logit_cap: float | None = None, q_chunk: int = 512,
        k_chunk: int = 512) -> jax.Array:
    """Context-parallel attention: Q sharded over sequence on the model
    axis via shard_map; K/V replicated over model (batch-sharded over
    data).  Each shard computes its own sequence slice with offset masks
    -- zero collectives inside the attention loop, per-device attention
    FLOPs divided by the model-axis size.  For sliding-window layers
    each shard slices only the (S/n + window) keys it can see, so local
    layers additionally drop ~S/(S/n+window)x of the K reads.

    The production layout for archs whose head count cannot use the
    model axis (gemma3/paligemma kv=1, 4-8 q heads).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = mesh.axis_names
    da = tuple(a for a in ("pod", "data") if a in axes)
    da_spec = (da if len(da) != 1 else da[0]) if da else None
    n = mesh.shape["model"] if "model" in axes else 1
    b, s, h, d = q.shape
    if n <= 1 or s % n or (s // n) < 1:
        return flash_attention(q, k, v, mask_kind=mask_kind, window=window,
                               prefix_len=prefix_len, logit_cap=logit_cap,
                               q_chunk=q_chunk, k_chunk=k_chunk)
    s_loc = s // n

    def local(qs, kf, vf):
        i = jax.lax.axis_index("model")
        off = i * s_loc
        k_off = 0
        kf_use, vf_use = kf, vf
        if mask_kind == "local" and window is not None and window < s:
            klen = min(s, s_loc + -(-window // k_chunk) * k_chunk)
            start = jnp.clip(off + s_loc - klen, 0, s - klen)
            kf_use = jax.lax.dynamic_slice_in_dim(kf, start, klen, 1)
            vf_use = jax.lax.dynamic_slice_in_dim(vf, start, klen, 1)
            k_off = start
        return flash_attention(
            qs, kf_use, vf_use, mask_kind=mask_kind, window=window,
            prefix_len=prefix_len, logit_cap=logit_cap,
            q_chunk=min(q_chunk, s_loc), k_chunk=k_chunk,
            schedule="masked", q_offset=off, k_offset=k_off)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(da_spec, "model", None, None),
                  P(da_spec, None, None, None),
                  P(da_spec, None, None, None)),
        out_specs=P(da_spec, "model", None, None),
        check_vma=False)(q, k, v)


@functools.partial(jax.jit, static_argnames=("logit_cap",))
def decode_attention_int8(q, k_q, k_scale, v_q, v_scale, valid, *,
                          logit_cap: float | None = None) -> jax.Array:
    """Integer-domain decode attention over an int8 KV cache.

    MCIM structure applied to attention: the int8 QK^T dot is the PPM
    (1-byte HBM reads, int8 MXU path), the int32 accumulator is the
    carry-free compressor, and the per-row scales applied after the dot
    are the final adder.  The P·V contraction folds V's per-position
    scales into the probabilities *before* quantizing them, so both
    large reads (K and V caches) stay int8 end to end.

    q: (B, 1, H, D) bf16;  k_q/v_q: (B, S, KV, D) int8;
    k_scale/v_scale: (B, S, KV) f32;  valid: (B, S) bool.
    """
    b, _, h, d = q.shape
    s, kv = k_q.shape[1], k_q.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, 1, kv, g, d)
    # quantize q per (b, kv, g) row
    qf = qg.astype(jnp.float32)
    qmax = jnp.max(jnp.abs(qf), axis=-1, keepdims=True)
    qs = jnp.where(qmax == 0, 1.0, qmax / 127.0)
    q8 = jnp.clip(jnp.round(qf / qs), -127, 127).astype(jnp.int8)

    scores_i = jnp.einsum("bqkgd,bskd->bkgqs", q8, k_q,
                          preferred_element_type=jnp.int32)
    qs_b = qs[:, 0][..., None]                             # (B,KV,G,1,1)
    ks_b = k_scale.transpose(0, 2, 1)[:, :, None, None, :]  # (B,KV,1,1,S)
    scores = scores_i.astype(jnp.float32) * qs_b * ks_b * scale
    if logit_cap is not None:
        scores = jnp.tanh(scores / logit_cap) * logit_cap
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)                # (B,KV,G,1,S)
    # fold V scales into probs, then quantize probs
    pv = probs * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    pmax = jnp.max(pv, axis=-1, keepdims=True)
    ps = jnp.where(pmax == 0, 1.0, pmax / 127.0)
    p8 = jnp.clip(jnp.round(pv / ps), -127, 127).astype(jnp.int8)
    out_i = jnp.einsum("bkgqs,bskd->bqkgd", p8, v_q,
                       preferred_element_type=jnp.int32)
    out = out_i.astype(jnp.float32) \
        * jnp.moveaxis(ps, 4, 1).reshape(b, 1, kv, g, 1)
    return out.reshape(b, 1, h, d).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("logit_cap",))
def decode_attention(q, k_cache, v_cache, valid, *,
                     logit_cap: float | None = None) -> jax.Array:
    """Single-token attention over a (possibly ring) KV cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, S, KV, D) with keys pre-roped;
    valid: (B, S) bool -- which cache slots hold live entries.
    """
    b, _, h, d = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, 1, kv, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        scores = jnp.tanh(scores / logit_cap) * logit_cap
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(v_cache.dtype)
