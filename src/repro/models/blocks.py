"""Shared model blocks: norms, RoPE, GQA attention (full / SWA /
local:global), SwiGLU MLP, MoE with top-k routing.

Pure functions over parameter pytrees (dict leaves), shard_map/pjit
friendly: no global state, no framework. Tensor-parallel sharding is
applied from outside via PartitionSpecs on the parameter trees
(``repro.distributed.sharding``); where the TP collective appears in
the math (attention out-proj, MLP down-proj, MoE combine) the calls go
through ``repro.distributed.collectives`` so the paper's collective
stack is on the critical path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rms_norm", "rope", "apply_rope", "attention", "decode_attention",
    "prefill_attention", "mlp_swiglu", "moe_layer", "init_linear",
    "init_attn", "init_mlp", "init_moe", "padded_heads",
]

Params = dict


# ---------------------------------------------------------------------------
# norms / rope
#
# Cross-program bit-exactness: fused prefill runs the same math as
# token-by-token decode but in a differently-shaped XLA program, and
# the serving layer's differential tests require the two to agree BIT
# FOR BIT. XLA CPU does not guarantee that: a `reduce` fused with a
# strided producer picks a shape-dependent accumulation order, and
# transcendental lowering (cos/sin) varies with the surrounding fusion.
# (optimization_barrier does not help — the CPU pipeline drops it
# before fusion.) So every order-sensitive reduction below is an
# explicit pairwise tree (each stage adds disjoint element pairs, so
# the dataflow graph pins the association), and RoPE angles come from
# a host-precomputed table gathered by integer position.
# ---------------------------------------------------------------------------
def _tree_sum(x):
    """Sum over the last axis with a fixed pairwise association.

    Equivalent to ``jnp.sum(x, axis=-1)`` up to ordering, but the
    reduction tree is spelled out op by op so the result cannot depend
    on how XLA schedules a monolithic reduce (shape- and fusion-
    dependent on CPU). Zero-padding to even length is exact for f32."""
    n = x.shape[-1]
    while n > 1:
        if n % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[..., :1])], axis=-1)
            n += 1
        x = x[..., 0::2] + x[..., 1::2]
        n //= 2
    return x[..., 0]


def rms_norm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = _tree_sum(jnp.square(xf)) / x.shape[-1]
    # 1/sqrt, not lax.rsqrt: sqrt and divide are exactly rounded (IEEE),
    # while rsqrt lowers to a context-dependent approximation on CPU.
    out = xf * (1.0 / jnp.sqrt(var + eps))[..., None]
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


_ROPE_MIN_TABLE = 4096


@functools.lru_cache(maxsize=None)
def _rope_tables(head_dim: int, theta: float, n_pos: int):
    """(cos, sin) tables of shape (n_pos, head_dim/2), computed ONCE on
    the host with numpy so every program gathers identical bytes
    (device cos/sin codegen is fusion-context-dependent). Row ``p``
    holds ``p * freqs`` independent of ``n_pos``, so tables of different
    sizes agree byte-for-byte on their shared prefix — growing the
    table never perturbs angles an earlier program already gathered."""
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                             / np.float32(head_dim)))
    angles = np.arange(n_pos, dtype=np.float32)[:, None] * freqs
    return np.cos(angles), np.sin(angles)


def _rope_table_size(max_pos: int) -> int:
    """Power-of-two table size covering ``max_pos`` positions, floored
    at ``_ROPE_MIN_TABLE`` (keeps the lru_cache to a small ladder of
    sizes instead of one entry per distinct sequence length)."""
    n = _ROPE_MIN_TABLE
    while n < max_pos:
        n *= 2
    return n


def rope(positions, head_dim: int, theta: float,
         max_pos: Optional[int] = None):
    """positions: (...,) int -> cos/sin tables (..., head_dim/2).

    The host table grows on demand to cover the positions actually
    requested — positions never wrap. Concrete positions size it from
    their true maximum; traced (abstract) positions require an explicit
    static ``max_pos`` bound from the caller (the table shape must be
    known at trace time; attention paths pass the model's ``max_seq``).
    Out-of-range positions fail loudly instead of aliasing: concrete
    positions past an explicit ``max_pos`` raise, and a traced gather
    past the table end is NaN-poisoned (XLA would otherwise clamp it
    silently), so a long-context overrun surfaces as NaN activations
    rather than period-aliased rotary angles.
    """
    concrete = not isinstance(positions, jax.core.Tracer)
    if concrete:
        pos_np = np.asarray(positions)
        lo = int(pos_np.min()) if pos_np.size else 0
        hi = int(pos_np.max()) if pos_np.size else 0
        if lo < 0:
            raise ValueError(f"rope(): negative position {lo}")
        if max_pos is not None and hi >= max_pos:
            raise ValueError(
                f"rope(): position {hi} >= declared max_pos {max_pos}")
        n = _rope_table_size(hi + 1)
    else:
        if max_pos is None:
            raise ValueError(
                "rope(): traced positions need an explicit static "
                "max_pos bound to size the host angle table")
        n = _rope_table_size(int(max_pos))
    cos_t, sin_t = _rope_tables(head_dim, float(theta), n)
    cos = jnp.asarray(cos_t)[positions]
    sin = jnp.asarray(sin_t)[positions]
    if not concrete:
        oob = (positions >= n)[..., None]
        cos = jnp.where(oob, jnp.float32(np.nan), cos)
        sin = jnp.where(oob, jnp.float32(np.nan), sin)
    return cos, sin


def _max_pos(cfg, window, kv_len: int) -> int:
    """Bound on the positions a cached attention layer can hold: a
    global layer writes position ``p`` to cache slot ``p``, so its
    positions stay below the cache length; a windowed (ring) layer
    wraps, so only the model's ``max_seq`` bounds it. The RoPE table is
    a constant of the compiled step, sized by this bound."""
    return kv_len if window is None else cfg.max_seq


def apply_rope(x, cos, sin):
    """x: (..., seq, head_dim); cos/sin: (seq, head_dim/2), or already
    broadcast to ``x.ndim`` (vector-pos decode: (b, 1, 1, head_dim/2),
    one rotation angle per batch row)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim != x.ndim:
        shape = (1,) * (x.ndim - 2) + cos.shape
        cos = cos.reshape(shape)
        sin = sin.reshape(shape)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _qkv_proj(p: Params, x, cfg):
    """QKV projection to (b, n_heads, s, hd) for the decode/prefill
    cache paths. qk-norm runs in the projection's natural (b, s, n, h)
    layout BEFORE the head transpose: the norm's reduction must read
    ``h`` contiguously, or XLA CPU fuses the transpose into the reduce
    and picks a shape-dependent accumulation order (see module note —
    this is load-bearing for fused-prefill bit-exactness)."""
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))


def _softmax(logits):
    """Softmax whose normalizing sum uses the fixed-order pairwise tree
    (see module note): masked attention logits underflow to exact zeros
    after ``exp``, and the tree keeps the sum identical between the
    decode- and prefill-shaped programs."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    return e / _tree_sum(e)[..., None]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _attn_mask(q_len: int, kv_len: int, *, causal: bool,
               window: Optional[int], q_offset: int = 0):
    """(q_len, kv_len) boolean mask. ``window``: SWA of that many tokens."""
    q_pos = jnp.arange(q_len) + q_offset
    k_pos = jnp.arange(kv_len)
    rel = q_pos[:, None] - k_pos[None, :]
    mask = jnp.ones((q_len, kv_len), bool)
    if causal:
        mask &= rel >= 0
    if window is not None:
        mask &= rel < window
    return mask


def attention(p: Params, x, cfg, *, window: Optional[int], positions=None,
              chunk: Optional[int] = None):
    """Full-sequence GQA attention. x: (batch, seq, d_model).

    Uses the online-softmax KV-chunked formulation whenever
    ``seq > chunk`` so the (s, s) logits tensor is never materialized —
    at 32k context the naive form needs tens of GB per device of
    attention scores alone (caught by the roofline memory term). The
    chunked form is the flash-attention recurrence in pure JAX; the
    Pallas kernel version is the TPU fast path.
    """
    b, s, d = x.shape
    hd = cfg.hd
    nh, nkv = padded_heads(cfg)
    chunk = chunk or getattr(cfg, "attn_chunk", 1024)
    if positions is None:
        positions = jnp.arange(s)

    q = jnp.einsum("bsd,dnh->bnsh", x, p["wq"])       # (b, nh, s, hd)
    k = jnp.einsum("bsd,dnh->bnsh", x, p["wk"])       # (b, nkv, s, hd)
    v = jnp.einsum("bsd,dnh->bnsh", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    # positions are normally a concrete arange (the table sizes itself);
    # user-supplied traced positions fall back to the architectural bound
    cos, sin = rope(positions, hd, cfg.rope_theta,
                    max_pos=cfg.max_seq
                    if isinstance(positions, jax.core.Tracer) else None)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    g = nh // nkv
    q = q.reshape(b, nkv, g, s, hd)
    if s > chunk and s % chunk == 0:
        out = _chunked_attn(q, k, v, cfg, window=window, chunk=chunk)
    else:
        logits = jnp.einsum("bngsh,bnth->bngst", q, k).astype(jnp.float32)
        logits *= hd ** -0.5
        mask = _attn_mask(s, s, causal=cfg.causal, window=window)
        logits = jnp.where(mask, logits, _NEG)
        probs = _softmax(logits).astype(x.dtype)
        out = jnp.einsum("bngst,bnth->bngsh", probs, v)
    out = out.reshape(b, nh, s, hd)
    if nh > cfg.n_heads:
        # hard-mask padded heads: exact math AND zero gradient into the
        # padded wo rows (so they stay inert under training)
        head_mask = (jnp.arange(nh) < cfg.n_heads).astype(out.dtype)
        out = out * head_mask[None, :, None, None]
    return jnp.einsum("bnsh,nhd->bsd", out, p["wo"])


_NEG = -1e30  # large-negative instead of -inf: keeps exp() well-defined
               # for fully-masked rows in the online-softmax recurrence


def _chunked_attn(q, k, v, cfg, *, window: Optional[int], chunk: int):
    """Online-softmax over KV chunks: O(s·chunk) live memory.

    q: (b, nkv, g, s, hd); k/v: (b, nkv, s, hd). Running (max, denom,
    acc) carried across chunks — the flash-attention recurrence.
    """
    b, nkv, g, s, hd = q.shape
    n_chunks = s // chunk
    scale = hd ** -0.5
    q_pos = jnp.arange(s)

    k_c = k.reshape(b, nkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    v_c = v.reshape(b, nkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)

    def body(carry, inp):
        m, l, acc = carry
        ci, kc, vc = inp
        logits = jnp.einsum("bngsh,bnth->bngst", q, kc).astype(jnp.float32)
        logits *= scale
        k_pos = ci * chunk + jnp.arange(chunk)
        rel = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones((s, chunk), bool)
        if cfg.causal:
            mask &= rel >= 0
        if window is not None:
            mask &= rel < window
        logits = jnp.where(mask[None, None, None], logits, _NEG)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(logits - m_new[..., None])
        l = l * alpha + pr.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bngst,bnth->bngsh", pr, vc.astype(jnp.float32))
        return (m_new, l, acc), ()

    m0 = jnp.full((b, nkv, g, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, s), jnp.float32)
    a0 = jnp.zeros((b, nkv, g, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), k_c, v_c))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention(p: Params, x, cache_k, cache_v, layer, pos, cfg,
                     *, window: Optional[int], k_scale=None, v_scale=None,
                     head_offset=None, active=None):
    """One-token decode with KV cache.

    x: (batch, 1, d_model); cache_k/v (and the int8 scales): the stacked
    ``(groups, batch, nkv, max_kv, hd)`` leaves that the layer scan
    carries, of which this layer owns group index ``layer``: the new
    token lands there, in place, and attention reads that group's slice.
    pos: current position — a scalar shared by the whole batch, or a
    ``(batch,)`` vector of per-slot positions (continuous batching:
    every slot decodes at its own depth; RoPE, the cache write, and the
    validity mask are then applied per row). Returns (out, new_k,
    new_v[, new_k_scale, new_v_scale]), the stacked leaves.

    The write scatters one ``hd`` row per (batch row, head) at that
    row's slot (a scalar ``pos`` is every row's). ``active``: a
    ``(batch,)`` bool mask; a row where it is False is not written (its
    index is pushed out of range and the update dropped).

    int8 KV quantization (§Perf hillclimb C): when the cache dtype is
    int8, new tokens are written as round(x/s·127) with a per-(batch,
    head, token) scale; the read path folds the scale into the attention
    products so the full-cache stream stays 1 byte/element.

    ``head_offset`` (explicit-TP decode, §5.2 hot path): when given, ``p``
    holds a contiguous slice of the query/output heads starting at that
    global head index, while the KV projections and cache are replicated
    over the TP axis. Each local head gathers its own KV head, so any
    head split works (no per-shard whole-group requirement), and the
    returned projection is this shard's PARTIAL sum over d_model — the
    caller completes it with the per-layer AllReduce plan. Composes
    with the int8 KV cache: every rank quantizes the same new token
    against the same scale (KV projections are replicated, so the
    TP-replicated cache and scale entries stay bit-consistent), and the
    per-head dequantize gathers its head's scales alongside the KV
    gather — no extra collective.
    """
    b, _, d = x.shape
    hd = cfg.hd
    nh, nkv = padded_heads(cfg)
    max_kv = cache_k.shape[-2]
    quant = cache_k.dtype == jnp.int8

    q, k_new, v_new = _qkv_proj(p, x, cfg)
    vec = jnp.ndim(pos) > 0                 # per-slot positions (batch,)
    cos, sin = rope(pos if vec else pos[None], hd, cfg.rope_theta,
                    max_pos=_max_pos(cfg, window, max_kv))
    if vec:
        # (b, hd/2) -> (b, 1, 1, hd/2): each slot rotates at its own pos
        cos, sin = cos[:, None, None, :], sin[:, None, None, :]
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)

    # ring-buffer update for windowed layers, linear for global layers
    slot = pos % max_kv if window is not None else pos

    def _put(cache, v):
        # one row per slot and head: (b, nkv, hd) at [layer, b, n, row[b]]
        idx = (layer, jnp.arange(b)[:, None],
               jnp.arange(cache.shape[2])[None, :], row[:, None])
        return cache.at[idx].set(v, mode="drop")

    def _write(cache, scales, val):
        v = val[:, :, 0]
        if not quant:
            return _put(cache, v), scales
        sc = (jnp.max(jnp.abs(v.astype(jnp.float32)),
                      axis=-1, keepdims=True) / 127.0 + 1e-8)
        qv = jnp.clip(jnp.round(v.astype(jnp.float32) / sc),
                      -127, 127).astype(jnp.int8)
        return _put(cache, qv), _put(scales, sc.astype(scales.dtype))

    # scoped ``cache_write``: a profile charges the write to it
    with jax.named_scope("cache_write"):
        # an inactive row's index is out of range, so its write drops
        row = jnp.broadcast_to(slot, (b,))
        if active is not None:
            row = jnp.where(active, row, max_kv)
        cache_k, k_scale = _write(cache_k, k_scale, k_new)
        cache_v, v_scale = _write(cache_v, v_scale, v_new)
    new_caches = ((cache_k, cache_v, k_scale, v_scale) if quant
                  else (cache_k, cache_v))
    cache_k, cache_v = cache_k[layer], cache_v[layer]
    if quant:
        k_scale, v_scale = k_scale[layer], v_scale[layer]

    g = nh // nkv
    if head_offset is not None:
        out = _decode_attn_tp_shard(p, q, cache_k, cache_v, pos, cfg,
                                    window=window, head_offset=head_offset,
                                    slot=slot, g=g,
                                    k_scale=k_scale, v_scale=v_scale)
        return (out,) + new_caches
    q = q.reshape(b, nkv, g, 1, hd)
    if quant:
        # int8 dot in bf16 compute (C2: halves the dequant materialization
        # vs f32; accumulate in f32), scale folded after the dot
        logits = jnp.einsum("bngsh,bnth->bngst", q.astype(jnp.bfloat16),
                            cache_k.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        logits = logits * k_scale[:, :, None, :, 0][:, :, :, None, :].astype(jnp.float32)
    else:
        logits = jnp.einsum("bngsh,bnth->bngst", q, cache_k).astype(jnp.float32)
    logits *= hd ** -0.5
    k_pos = jnp.arange(max_kv)
    if window is not None:
        # ring buffer holds the last `max_kv` tokens; valid = within window
        age = ((slot[:, None] if vec else slot) - k_pos) % max_kv
        lim = jnp.minimum(pos + 1, max_kv)
        valid = age < (lim[:, None] if vec else lim)
    else:
        valid = k_pos <= (pos[:, None] if vec else pos)
    vmask = (valid[:, None, None, None, :] if vec
             else valid[None, None, None, None, :])
    logits = jnp.where(vmask, logits, jnp.finfo(jnp.float32).min)
    if quant:
        probs = _softmax(logits)
        # scale folds into probs (per key position) before the value dot
        pscaled = probs * v_scale[:, :, None, :, 0][:, :, :, None, :].astype(jnp.float32)
        out = jnp.einsum("bngst,bnth->bngsh", pscaled.astype(jnp.bfloat16),
                         cache_v.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        probs = _softmax(logits).astype(x.dtype)
        out = jnp.einsum("bngst,bnth->bngsh", probs, cache_v)
    out = out.reshape(b, nh, 1, hd)
    if nh > cfg.n_heads:
        head_mask = (jnp.arange(nh) < cfg.n_heads).astype(out.dtype)
        out = out * head_mask[None, :, None, None]
    ret = jnp.einsum("bnsh,nhd->bsd", out, p["wo"])
    return (ret,) + new_caches


def _decode_attn_tp_shard(p: Params, q, cache_k, cache_v, pos, cfg,
                          *, window: Optional[int], head_offset, slot, g,
                          k_scale=None, v_scale=None):
    """Per-shard attention for the explicit-TP decode path.

    q: (b, nh_local, 1, hd) — this shard's heads; cache_k/v hold the
    FULL (replicated) KV heads. Each local head attends to its own KV
    head via a gather, computing exactly the reference per-head math;
    the final ``wo`` projection over local heads is a partial sum the
    caller AllReduces. With an int8 cache the per-head gather also
    pulls that head's ``k_scale``/``v_scale`` rows (replicated like the
    cache), and the dequantize folds them into the attention products
    exactly as the unsharded quant path does — bf16 dots, f32
    accumulation, scale applied per key position."""
    b, nh_l, _, hd = q.shape
    max_kv = cache_k.shape[2]
    quant = cache_k.dtype == jnp.int8
    hid = head_offset + jnp.arange(nh_l)            # global head ids
    k_sel = jnp.take(cache_k, hid // g, axis=1)     # (b, nh_l, max_kv, hd)
    v_sel = jnp.take(cache_v, hid // g, axis=1)
    if quant:
        ks_sel = jnp.take(k_scale, hid // g, axis=1)   # (b, nh_l, max_kv, 1)
        vs_sel = jnp.take(v_scale, hid // g, axis=1)
        logits = jnp.einsum("bnsh,bnth->bnst", q.astype(jnp.bfloat16),
                            k_sel.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        logits = logits * ks_sel[..., 0][:, :, None, :].astype(jnp.float32)
    else:
        logits = jnp.einsum("bnsh,bnth->bnst", q, k_sel).astype(jnp.float32)
    logits *= hd ** -0.5
    k_pos = jnp.arange(max_kv)
    vec = jnp.ndim(pos) > 0                 # per-slot positions (batch,)
    if window is not None:
        age = ((slot[:, None] if vec else slot) - k_pos) % max_kv
        lim = jnp.minimum(pos + 1, max_kv)
        valid = age < (lim[:, None] if vec else lim)
    else:
        valid = k_pos <= (pos[:, None] if vec else pos)
    vmask = (valid[:, None, None, :] if vec
             else valid[None, None, None, :])
    logits = jnp.where(vmask, logits, jnp.finfo(jnp.float32).min)
    if quant:
        probs = _softmax(logits)
        pscaled = probs * vs_sel[..., 0][:, :, None, :].astype(jnp.float32)
        out = jnp.einsum("bnst,bnth->bnsh", pscaled.astype(jnp.bfloat16),
                         v_sel.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32).astype(q.dtype)
    else:
        probs = _softmax(logits).astype(q.dtype)
        out = jnp.einsum("bnst,bnth->bnsh", probs, v_sel)
    nh, _ = padded_heads(cfg)
    if nh > cfg.n_heads:
        head_mask = (hid < cfg.n_heads).astype(out.dtype)
        out = out * head_mask[None, :, None, None]
    return jnp.einsum("bnsh,nhd->bsd", out, p["wo"])


def prefill_attention(p: Params, x, cache_k, cache_v, pos, n_tok, cfg,
                      *, window: Optional[int], k_scale=None, v_scale=None,
                      head_offset=None):
    """Fused multi-token prefill with KV cache — the chunked analogue of
    :func:`decode_attention`, bit-identical to running it token by token.

    x: (batch, S, d_model) — one prompt chunk per row; pos: (batch,)
    position of each row's FIRST chunk token; n_tok: (batch,) how many
    of the S positions are real for that row (the rest are padding:
    their cache writes are masked off and their outputs are garbage the
    caller discards, exactly like the scheduler's inactive-slot
    contract). Returns (out (b, S, d_model), new_k, new_v[, new_k_scale,
    new_v_scale]).

    Exactness: each chunk token's K/V is projected, rotated, and (for
    int8 caches) quantized by the SAME per-token math as the decode
    write, then *selected* (never summed) into its cache slot; the read
    masks each query row ``j`` down to positions ``<= pos+j``, and fully
    masked logits underflow to exact zeros in the softmax — so every
    (query, key) product matches the token-by-token path bit for bit.

    Caller contract for windowed (ring-buffer) layers: a chunk must not
    wrap the ring past keys its own queries still read, i.e. per row
    either ``n_tok == 1`` (the decode write — safe at any depth) or
    ``pos + n_tok <= kv_len``. The scheduler enforces this when sizing
    fused chunks. ``head_offset`` is the explicit-TP path, as in
    :func:`decode_attention`.
    """
    b, S, _ = x.shape
    hd = cfg.hd
    nh, nkv = padded_heads(cfg)
    kv_len = cache_k.shape[2]
    quant = cache_k.dtype == jnp.int8

    q, k_new, v_new = _qkv_proj(p, x, cfg)
    pmat = pos[:, None] + jnp.arange(S)[None, :]            # (b, S)
    # padded chunk columns may index past a row's real end; the +S head-
    # room keeps their (discarded) lanes off the NaN-poison path
    cos, sin = rope(pmat, hd, cfg.rope_theta,
                    max_pos=_max_pos(cfg, window, kv_len) + S)  # (b,S,hd/2)
    cos, sin = cos[:, None], sin[:, None]                   # (b, 1, S, hd/2)
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)

    slot = pmat % kv_len if window is not None else pmat
    valid_j = jnp.arange(S)[None, :] < n_tok[:, None]       # (b, S)
    # M[i, j, t]: chunk token j of row i lands on cache slot t. At most
    # one j per (i, t) — chunk slots are distinct (S <= kv_len).
    M = ((slot[:, :, None] == jnp.arange(kv_len)[None, None, :])
         & valid_j[:, :, None])
    hit = M.any(axis=1)                                     # (b, kv_len)
    j_of = jnp.argmax(M, axis=1)                            # (b, kv_len)

    def _write(cache, scales, val):
        def sel(a):
            idx = jnp.broadcast_to(
                j_of[:, None, :, None],
                (b, a.shape[1], kv_len, a.shape[-1]))
            return jnp.take_along_axis(a, idx, axis=2)
        m = hit[:, None, :, None]
        if not quant:
            return jnp.where(m, sel(val), cache), scales
        sc = (jnp.max(jnp.abs(val.astype(jnp.float32)),
                      axis=-1, keepdims=True) / 127.0 + 1e-8)
        qv = jnp.clip(jnp.round(val.astype(jnp.float32) / sc),
                      -127, 127).astype(jnp.int8)
        return (jnp.where(m, sel(qv), cache),
                jnp.where(m, sel(sc.astype(scales.dtype)), scales))

    cache_k, k_scale = _write(cache_k, k_scale, k_new)
    cache_v, v_scale = _write(cache_v, v_scale, v_new)

    k_pos = jnp.arange(kv_len)
    if window is not None:
        age = (slot[:, :, None] - k_pos[None, None, :]) % kv_len
        lim = jnp.minimum(pmat + 1, kv_len)
        valid = age < lim[:, :, None]                       # (b, S, kv_len)
    else:
        valid = k_pos[None, None, :] <= pmat[:, :, None]

    g = nh // nkv
    if head_offset is not None:
        out = _prefill_attn_tp_shard(p, q, cache_k, cache_v, valid, cfg,
                                     head_offset=head_offset, g=g,
                                     k_scale=k_scale, v_scale=v_scale)
        if quant:
            return out, cache_k, cache_v, k_scale, v_scale
        return out, cache_k, cache_v
    q = q.reshape(b, nkv, g, S, hd)
    if quant:
        logits = jnp.einsum("bngsh,bnth->bngst", q.astype(jnp.bfloat16),
                            cache_k.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        logits = logits * k_scale[:, :, None, :, 0][:, :, :, None, :].astype(jnp.float32)
    else:
        logits = jnp.einsum("bngsh,bnth->bngst", q, cache_k).astype(jnp.float32)
    logits *= hd ** -0.5
    vmask = valid[:, None, None, :, :]
    logits = jnp.where(vmask, logits, jnp.finfo(jnp.float32).min)
    if quant:
        probs = _softmax(logits)
        pscaled = probs * v_scale[:, :, None, :, 0][:, :, :, None, :].astype(jnp.float32)
        out = jnp.einsum("bngst,bnth->bngsh", pscaled.astype(jnp.bfloat16),
                         cache_v.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        probs = _softmax(logits).astype(x.dtype)
        out = jnp.einsum("bngst,bnth->bngsh", probs, cache_v)
    out = out.reshape(b, nh, S, hd)
    if nh > cfg.n_heads:
        head_mask = (jnp.arange(nh) < cfg.n_heads).astype(out.dtype)
        out = out * head_mask[None, :, None, None]
    ret = jnp.einsum("bnsh,nhd->bsd", out, p["wo"])
    if quant:
        return ret, cache_k, cache_v, k_scale, v_scale
    return ret, cache_k, cache_v


def _prefill_attn_tp_shard(p: Params, q, cache_k, cache_v, valid, cfg, *,
                           head_offset, g, k_scale=None, v_scale=None):
    """Per-shard chunked attention for the explicit-TP prefill path —
    :func:`_decode_attn_tp_shard` generalized to S query positions.
    ``valid`` is the precomputed (b, S, kv_len) per-(row, query)
    validity mask; everything else matches the decode variant op for
    op, so each query position's math is bit-identical to its
    one-token decode step."""
    b, nh_l, S, hd = q.shape
    quant = cache_k.dtype == jnp.int8
    hid = head_offset + jnp.arange(nh_l)            # global head ids
    k_sel = jnp.take(cache_k, hid // g, axis=1)     # (b, nh_l, kv_len, hd)
    v_sel = jnp.take(cache_v, hid // g, axis=1)
    if quant:
        ks_sel = jnp.take(k_scale, hid // g, axis=1)
        vs_sel = jnp.take(v_scale, hid // g, axis=1)
        logits = jnp.einsum("bnsh,bnth->bnst", q.astype(jnp.bfloat16),
                            k_sel.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        logits = logits * ks_sel[..., 0][:, :, None, :].astype(jnp.float32)
    else:
        logits = jnp.einsum("bnsh,bnth->bnst", q, k_sel).astype(jnp.float32)
    logits *= hd ** -0.5
    logits = jnp.where(valid[:, None, :, :], logits,
                       jnp.finfo(jnp.float32).min)
    if quant:
        probs = _softmax(logits)
        pscaled = probs * vs_sel[..., 0][:, :, None, :].astype(jnp.float32)
        out = jnp.einsum("bnst,bnth->bnsh", pscaled.astype(jnp.bfloat16),
                         v_sel.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32).astype(q.dtype)
    else:
        probs = _softmax(logits).astype(q.dtype)
        out = jnp.einsum("bnst,bnth->bnsh", probs, v_sel)
    nh, _ = padded_heads(cfg)
    if nh > cfg.n_heads:
        head_mask = (hid < cfg.n_heads).astype(out.dtype)
        out = out * head_mask[None, :, None, None]
    return jnp.einsum("bnsh,nhd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------
def mlp_swiglu(p: Params, x):
    gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("bsf,fd->bsd", act, p["w_down"])


def moe_layer(p: Params, x, cfg):
    """Top-k routed MoE, dense-einsum formulation (EP shards the expert
    axis; dispatch becomes an all_to_all under shard_map — see
    distributed.collectives.expert_dispatch for the sparse path)."""
    b, s, d = x.shape
    e = cfg.moe.num_experts
    k = cfg.moe.top_k
    router = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    weights, idx = jax.lax.top_k(router, k)                    # (b, s, k)
    weights = jax.nn.softmax(weights, axis=-1).astype(x.dtype)
    onehot = jax.nn.one_hot(idx, e, dtype=x.dtype)             # (b, s, k, e)
    combine = jnp.einsum("bsk,bske->bse", weights, onehot)     # (b, s, e)

    gate = jnp.einsum("bsd,edf->bsef", x, p["w_gate"])
    up = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    out = jnp.einsum("bsef,efd->bsed", act, p["w_down"])
    return jnp.einsum("bsed,bse->bsd", out, combine)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def init_linear(key, shape, dtype, scale=None):
    scale = scale if scale is not None else shape[0] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def padded_heads(cfg):
    """(n_heads_padded, n_kv_padded) under cfg.pad_heads_to."""
    if not cfg.pad_heads_to or cfg.pad_heads_to <= cfg.n_heads:
        return cfg.n_heads, cfg.n_kv_heads
    nh = cfg.pad_heads_to
    g = cfg.group_size
    nkv = (nh + g - 1) // g
    return nh, nkv


def init_attn(key, cfg) -> Params:
    hd, d = cfg.hd, cfg.d_model
    nh, nkv = padded_heads(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], (d, nh, hd), cfg.jdtype),
        "wk": init_linear(ks[1], (d, nkv, hd), cfg.jdtype),
        "wv": init_linear(ks[2], (d, nkv, hd), cfg.jdtype),
        "wo": init_linear(ks[3], (nh, hd, d), cfg.jdtype, scale=(nh * hd) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), cfg.jdtype)
        p["k_norm"] = jnp.zeros((hd,), cfg.jdtype)
    return p


def init_mlp(key, cfg, d_ff=None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": init_linear(ks[0], (d, f), cfg.jdtype),
        "w_up": init_linear(ks[1], (d, f), cfg.jdtype),
        "w_down": init_linear(ks[2], (f, d), cfg.jdtype, scale=f ** -0.5),
    }


def init_moe(key, cfg) -> Params:
    d = cfg.d_model
    e = cfg.moe.num_experts
    f = cfg.moe.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": init_linear(ks[0], (d, e), cfg.jdtype),
        "w_gate": init_linear(ks[1], (e, d, f), cfg.jdtype),
        "w_up": init_linear(ks[2], (e, d, f), cfg.jdtype),
        "w_down": init_linear(ks[3], (e, f, d), cfg.jdtype, scale=f ** -0.5),
    }
