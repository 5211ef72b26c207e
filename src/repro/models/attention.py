"""Multi-head attention: GQA, RoPE, optional QKV bias, sliding-window masks,
KV caches (prefill/decode), cross-attention, and a chunked (flash-style)
implementation for long sequences.

Modes (``mode`` argument of ``attn_apply``):
  "train"    causal self-attention over the whole sequence, no cache
  "encoder"  bidirectional self-attention (whisper encoder)
  "prefill"  causal self-attention that also RETURNS the (k, v) to cache
  "decode"   single-step: q has T=1; reads keys/values from the cache
  "cross"    queries over a fixed memory (encoder output / image tokens)

KV cache layout: {"k": (B, S, n_kv, hd), "v": (B, S, n_kv, hd)} with S the
static max length; ``cache_pos`` scalar gives the current fill.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import (
    ModelConfig, apply_rope, keygen, param, rope_freqs,
)

NEG_INF = -2.0 ** 30  # large-but-finite: keeps padded rows NaN-free


def attn_init(key, cfg: ModelConfig):
    kg = keygen(key)
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": param(next(kg), (d, h, hd), ("embed", "heads", "head_dim"),
                    cfg.param_dtype),
        "wk": param(next(kg), (d, kvh, hd), ("embed", "kv_heads", "head_dim"),
                    cfg.param_dtype),
        "wv": param(next(kg), (d, kvh, hd), ("embed", "kv_heads", "head_dim"),
                    cfg.param_dtype),
        "wo": param(next(kg), (h, hd, d), ("heads", "head_dim", "embed"),
                    cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = param(None, (h, hd), ("heads", "head_dim"), cfg.param_dtype)
        p["bk"] = param(None, (kvh, hd), ("kv_heads", "head_dim"), cfg.param_dtype)
        p["bv"] = param(None, (kvh, hd), ("kv_heads", "head_dim"), cfg.param_dtype)
    return p


def _project_q(p, x, cfg):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    return q


def _project_kv(p, x, cfg):
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(x.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return k, v


def _repeat_kv(k, n_heads):
    """(B, S, kvh, hd) -> (B, S, H, hd) by repeating each group."""
    b, s, kvh, hd = k.shape
    if kvh == n_heads:
        return k
    rep = n_heads // kvh
    return jnp.repeat(k, rep, axis=2)


FULL_WINDOW = 2 ** 30  # "no sliding window" sentinel (works traced or static)


def _mask_bias(mode, q_pos, k_pos, window, dtype):
    """(Tq, Tk) additive bias from mode/window; f32.

    ``window`` may be a TRACED scalar (per-layer window array under scan —
    gemma3's 5-local:1-global pattern); 0 / FULL_WINDOW both mean full.
    """
    if mode == "encoder" or mode == "cross":
        return None
    keep = k_pos[None, :] <= q_pos[:, None]              # causal
    w = jnp.where(jnp.asarray(window) <= 0, FULL_WINDOW, window)
    keep &= (q_pos[:, None] - k_pos[None, :]) < w
    return jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa_full(q, k, v, bias):
    """q (B,Tq,H,hd), k/v (B,Tk,H,hd); logits in f32.

    The logits tensor carries a GSPMD hint: heads on "model" when they
    divide, otherwise Tq on "model" (sequence parallelism) — see
    sharding/hints.py.  No-op off-mesh.
    """
    from repro.sharding import hints
    hd = q.shape[-1]
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    logits = hints.constrain(logits / jnp.sqrt(jnp.float32(hd)), "attn_logits")
    if bias is not None:
        logits = logits + bias[None, None]
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return hints.constrain(jnp.einsum("bhqs,bshk->bqhk", w, v), "attn_out")


def _sdpa_chunked(q, k, v, q_pos, k_pos, window, mode, chunk, unroll=False):
    """Flash-style: lax.scan over KV chunks with running (max, sum, acc).

    Memory: O(Tq * chunk) logits instead of O(Tq * Tk) — required for the
    500k-token cells and available to every arch via cfg.attn_impl.
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    n_chunks = -(-tk // chunk)
    pad = n_chunks * chunk - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2**30)
    kc = k.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    from repro.sharding import hints

    @jax.checkpoint   # don't save per-chunk logits in backward (O(T^2) mem)
    def body(carry, inp):
        m, s, acc = carry
        kb, vb, pb = inp
        logits = jnp.einsum("bqhk,bshk->bhqs", q, kb,
                            preferred_element_type=jnp.float32) * scale
        logits = hints.constrain(logits, "attn_logits")
        if mode not in ("encoder", "cross"):
            keep = pb[None, :] <= q_pos[:, None]
            w = jnp.where(jnp.asarray(window) <= 0, FULL_WINDOW, window)
            keep &= (q_pos[:, None] - pb[None, :]) < w
            logits = logits + jnp.where(keep, 0.0, NEG_INF)[None, None]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(logits - m_new[..., None])
        s_new = s * alpha + pexp.sum(axis=-1)
        # f32 accumulator: keeps the scan carry type stable and the sum exact
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqs,bshk->bhqk", pexp.astype(q.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, s_new, acc_new), None

    m0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, h, tq, hd), jnp.float32)
    (m, s, acc), _ = lax.scan(body, (m0, s0, acc0), (kc, vc, pc),
                              unroll=True if unroll else 1)
    out = (acc / jnp.maximum(s, 1e-30)[..., None]).astype(q.dtype)
    return out.transpose(0, 2, 1, 3)  # (B, Tq, H, hd)


def attn_apply(p, x, cfg: ModelConfig, *, mode: str = "train",
               window: int = 0, positions=None, cache=None, cache_pos=None,
               memory=None):
    """Returns (out, new_cache_kv).

    new_cache_kv is None except: "prefill" returns the (k, v) to store;
    "decode" returns the updated cache dict.
    """
    from repro.sharding import hints
    b, t, d = x.shape
    q = hints.constrain(_project_q(p, x, cfg), "qkv")

    if mode == "cross":
        k, v = _project_kv(p, memory, cfg)
        k_pos = jnp.arange(memory.shape[1])
        q_pos = jnp.arange(t) if positions is None else positions
    else:
        k, v = _project_kv(p, x, cfg)
        q_pos = jnp.arange(t) if positions is None else positions
        if mode != "encoder":
            sin, cos = rope_freqs(cfg.hd, cfg.rope_theta, q_pos)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        k_pos = q_pos

    new_cache = None
    if mode == "prefill":
        new_cache = {"k": k, "v": v}
    elif mode == "decode":
        assert cache is not None and cache_pos is not None
        if hints.flag("kv_masked_write"):
            # S is sharded (long_500k): one-hot masked merge keeps the write
            # shard-local (a traced-pos dynamic_update_slice would regather)
            slot = (jnp.arange(cache["k"].shape[1]) == cache_pos
                    )[None, :, None, None]
            ck = jnp.where(slot, k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(slot, v.astype(cache["v"].dtype), cache["v"])
        else:
            # one index dtype for every start index
            pos = jnp.asarray(cache_pos)
            z = jnp.zeros((), pos.dtype)
            ck = lax.dynamic_update_slice(cache["k"], k, (z, pos, z, z))
            cv = lax.dynamic_update_slice(cache["v"], v, (z, pos, z, z))
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        s = ck.shape[1]
        k_pos = jnp.arange(s)
        # mask out unwritten slots
        q_pos = jnp.full((t,), cache_pos, dtype=k_pos.dtype) \
            if positions is None else positions

    kf = _repeat_kv(k, cfg.n_heads)
    vf = _repeat_kv(v, cfg.n_heads)

    if mode == "decode":
        # single-token query: a (B, H, 1, S) einsum — linear in S
        valid = k_pos <= cache_pos
        keep = valid[None, :] & (k_pos[None, :] <= q_pos[:, None])
        w = jnp.where(jnp.asarray(window) <= 0, FULL_WINDOW, window)
        keep &= (q_pos[:, None] - k_pos[None, :]) < w
        bias = jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)
        out = _sdpa_full(q, kf, vf, bias)
    elif cfg.attn_impl == "chunked" and mode in ("train", "prefill"):
        out = _sdpa_chunked(q, kf, vf, q_pos, k_pos, window, mode,
                            cfg.attn_chunk, unroll=not cfg.scan_layers)
    else:
        bias = _mask_bias(mode, q_pos, k_pos, window, x.dtype)
        out = _sdpa_full(q, kf, vf, bias)

    o = jnp.einsum("bthk,hkd->btd", out, p["wo"].astype(x.dtype))
    return hints.constrain(o, "residual"), new_cache
