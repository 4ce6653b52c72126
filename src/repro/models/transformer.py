"""Unified LM: dense / MoE / RWKV6 / hybrid / encoder families behind
one functional interface.

Layers are stacked per *period group* and iterated with ``lax.scan`` so
the HLO stays one-group-sized regardless of depth (compile-time control
for the 512-device dry-run; same trick as MaxText). E.g. gemma3's
5-local:1-global pattern scans over 8 groups of 6 layers.

Parameter layout: ``params["layers"]`` is a list (length = period) of
per-slot layer dicts whose leaves carry a leading ``groups`` dim; scan
slices every leaf per group.

Public surface:
    init_params(cfg, key)            -> param pytree
    forward(params, cfg, tokens)     -> final hidden states
    logits_fn / loss_fn
    init_cache(cfg, batch, max_kv)   -> decode cache pytree
    decode_step(params, cfg, cache, tokens, pos) -> (logits, cache)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import blocks, rwkv6, ssm
from repro.models.blocks import rms_norm
from repro.models.config import ModelConfig

__all__ = ["init_params", "forward", "logits_fn", "loss_fn", "init_cache",
           "decode_step", "prefill_step", "layer_windows", "KV_LEAVES"]


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------
def layer_windows(cfg: ModelConfig) -> list[Optional[int]]:
    """Attention window per layer within one period group (gemma3:
    period-1 local layers then 1 global; SWA archs: window everywhere)."""
    per = cfg.local_global_period
    if per > 1:
        return [cfg.window] * (per - 1) + [None]
    return [cfg.window]


def n_groups(cfg: ModelConfig) -> int:
    per = cfg.local_global_period
    assert cfg.n_layers % per == 0, (cfg.n_layers, per)
    return cfg.n_layers // per


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_layer(key, cfg: ModelConfig):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if cfg.family == "rwkv6":
        return rwkv6.init_rwkv_layer(ks[0], cfg)
    p = {
        "ln_attn": jnp.zeros((d,), cfg.jdtype),
        "ln_mlp": jnp.zeros((d,), cfg.jdtype),
        "attn": blocks.init_attn(ks[0], cfg),
    }
    if cfg.family == "moe":
        p["moe"] = blocks.init_moe(ks[1], cfg)
    else:
        p["mlp"] = blocks.init_mlp(ks[1], cfg)
    if cfg.family == "hybrid":
        # parallel SSM heads beside attention (Hymba); outputs averaged
        p["ssm"] = ssm.init_ssm_head(ks[2], cfg, d_inner=d)
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 3)
    groups = n_groups(cfg)
    per = len(layer_windows(cfg))

    gkeys = jax.random.split(ks[0], groups * per).reshape(groups, per)
    slots = []
    for i in range(per):
        per_group = [_init_layer(gkeys[g, i], cfg) for g in range(groups)]
        slots.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group))

    params = {
        "embed": blocks.init_linear(ks[1], (cfg.vocab, cfg.d_model),
                                    cfg.jdtype, scale=1.0),
        "ln_f": jnp.zeros((cfg.d_model,), cfg.jdtype),
        "layers": slots,
    }
    if not cfg.tie_embeddings:
        params["unembed"] = blocks.init_linear(
            ks[2], (cfg.d_model, cfg.vocab), cfg.jdtype)
    return params


# ---------------------------------------------------------------------------
# forward (full sequence)
# ---------------------------------------------------------------------------
def _run_layer(p, x, cfg: ModelConfig, win, positions):
    if cfg.family == "rwkv6":
        b = x.shape[0]
        st = rwkv6.init_rwkv_state(cfg, b)
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        att, st = rwkv6.rwkv_time_mix(p, h, st)
        x = x + att
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        ffn, _ = rwkv6.rwkv_channel_mix(p, h, st)
        return x + ffn
    h = rms_norm(x, p["ln_attn"], cfg.norm_eps)
    att = blocks.attention(p["attn"], h, cfg, window=win, positions=positions)
    if cfg.family == "hybrid":
        s_out, _ = ssm.ssm_forward(p["ssm"], h, cfg)
        att = (att + s_out) * 0.5
    x = x + att
    h = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    if cfg.family == "moe":
        x = x + blocks.moe_layer(p["moe"], h, cfg)
    else:
        x = x + blocks.mlp_swiglu(p["mlp"], h)
    return x


def embed_tokens(params, cfg: ModelConfig, tokens):
    """Integer tokens -> embedding lookup; float inputs are precomputed
    frontend embeddings (audio frames / vision patches — stub per
    assignment) and pass through."""
    if not jnp.issubdtype(tokens.dtype, jnp.integer):
        return tokens.astype(cfg.jdtype)
    return params["embed"][tokens]


def forward(params, cfg: ModelConfig, tokens, *, positions=None,
            remat_policy: str = "none"):
    """tokens: (b, s) int32 — or (b, s, d) embeddings for frontend archs."""
    x = embed_tokens(params, cfg, tokens)
    if positions is None:
        positions = jnp.arange(x.shape[1])
    wins = layer_windows(cfg)

    def body(x, gp):  # gp: list of per-slot dicts (leaves sliced per group)
        for i, win in enumerate(wins):
            x = _run_layer(gp[i], x, cfg, win, positions)
        return x, ()

    if remat_policy == "full":
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["ln_f"], cfg.norm_eps)


def logits_fn(params, cfg: ModelConfig, hidden):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return jnp.einsum("bsd,dv->bsv", hidden, w).astype(jnp.float32)


def loss_fn(params, cfg: ModelConfig, batch, *, remat_policy: str = "none"):
    """batch: dict(tokens (b,s)[, labels (b,s)]). Mean next-token CE.

    Sharded-vocab-friendly formulation: the gold logit is a one-hot
    einsum and the logsumexp is explicit max/sum reductions, so under a
    vocab-sharded unembedding GSPMD lowers this to partial reductions +
    tiny (b, s) all-reduces instead of all-gathering the full (b, s,
    vocab) logits (~40 GB/device at 151k vocab — caught by the roofline
    collective term).
    """
    hidden = forward(params, cfg, batch["tokens"], remat_policy=remat_policy)
    logits = logits_fn(params, cfg, hidden)
    labels = batch["labels"]
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, cfg.vocab, dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
#: decode-cache leaves that hold one entry per token position (attention
#: K/V and their int8 scales), as against recurrent state (SSM, RWKV)
KV_LEAVES = ("k", "v", "k_scale", "v_scale")


def init_cache(cfg: ModelConfig, batch: int, max_kv: int, dtype=None):
    """Decode cache pytree.

    Attention families: per period slot, (groups, b, nkv, kv_i, hd) with
    kv_i = min(window, max_kv) for local slots (ring buffer) else max_kv.
    RWKV6: O(1) recurrent state per group. Hybrid: + SSM state.
    """
    dtype = dtype or cfg.jdtype
    g = n_groups(cfg)
    if cfg.family == "rwkv6":
        st = rwkv6.init_rwkv_state(cfg, batch)
        return jax.tree.map(lambda x: jnp.zeros((g,) + x.shape, x.dtype), st)
    wins = layer_windows(cfg)

    _, nkv = blocks.padded_heads(cfg)

    def kv(win):
        size = min(win, max_kv) if win is not None else max_kv
        return jnp.zeros((g, batch, nkv, size, cfg.hd), dtype)

    cache = {"k": [kv(w) for w in wins], "v": [kv(w) for w in wins]}
    if dtype == jnp.int8:
        def sc(win):
            size = min(win, max_kv) if win is not None else max_kv
            return jnp.zeros((g, batch, nkv, size, 1), jnp.bfloat16)
        cache["k_scale"] = [sc(w) for w in wins]
        cache["v_scale"] = [sc(w) for w in wins]
    if cfg.family == "hybrid":
        cache["ssm"] = [jnp.zeros((g, batch, cfg.d_model, cfg.ssm.state_dim),
                                  jnp.float32) for _ in wins]
    return cache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, *, comms=None,
                active=None):
    """tokens: (b,) int32 (or (b, d) embeddings); pos: scalar int32, or
    a (b,) int32 vector of per-slot positions (continuous batching —
    see ``blocks.decode_attention``; the SSM/RWKV recurrences are
    position-free, so only attention branches on it).
    Returns (logits (b, vocab) f32, new cache).

    The attention leaves (K, V and the int8 scales) ride through the
    layer scan as its carry, and each layer writes its new token into
    them at its group index (``blocks.decode_attention``), so XLA
    updates the cache in place instead of restacking every layer's
    cache as the scan's output. ``active`` (a ``(b,)`` bool mask): rows
    where it is False write no K/V. Recurrent state (SSM, RWKV) is
    still computed for every row; the caller selects it.

    ``comms`` — the per-layer TP/EP communication hook of the explicit
    decode path (``repro.distributed.step.TPDecodeComms``). When given,
    this function runs INSIDE a shard_map that is manual over the TP
    axis: parameters arrive as TP shards, the per-layer hidden-state
    partial sums (attention out-proj, MLP down-proj, and the hybrid
    family's SSM out-proj) are completed by ``comms.hidden`` (a replay
    of the engine's init-compiled AllReduce plan, not a GSPMD-inserted
    psum), the embedding lookup and final logits go through
    ``comms.embed`` / ``comms.logits`` (vocab-sharded tables), and
    attention receives its shard's global head offset — with an int8 KV
    cache the per-head dequantize runs against the TP-replicated
    ``k_scale``/``v_scale`` entries, gathered per head alongside the KV
    gather. For the MoE family the per-layer expert block runs
    ``comms.moe`` — expert-parallel dispatch/combine through the
    init-compiled capacity-bucketed all_to_all plan — instead of the
    dense-einsum oracle. For the hybrid family the SSM branch runs on
    its shard's ``d_inner`` rows (``comms.ssm_offset``; state arrives
    model-sharded). ``comms=None`` is the auto/GSPMD path, unchanged.
    """
    if comms is not None and (
            cfg.family not in ("dense", "moe", "hybrid")
            or (cfg.family == "moe" and comms.moe_plan is None)):
        raise NotImplementedError(
            "explicit decode covers the dense, hybrid (attention+SSM), and "
            "MoE (with a compiled moe_alltoall plan) families — fp and "
            "int8 KV caches alike; rwkv6/encoder configs stay on "
            "auto/GSPMD")
    if not jnp.issubdtype(tokens.dtype, jnp.integer):
        x = tokens.astype(cfg.jdtype)[:, None]          # embedded input
    elif comms is not None:
        x = comms.embed(params["embed"], tokens)[:, None]
    else:
        x = params["embed"][tokens][:, None]            # (b, 1, d)
    wins = layer_windows(cfg)

    if cfg.family == "rwkv6":
        def body(x, scanned):
            gp_list, st = scanned
            out, st = rwkv6.rwkv_decode_step(gp_list[0], x, st, cfg)
            return out, st

        x, new_state = jax.lax.scan(body, x, (params["layers"], cache))
        h = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return logits_fn(params, cfg, h)[:, 0], new_state

    kv_names = [n for n in KV_LEAVES if n in cache]

    def body(carry, scanned):
        x, kv = carry
        gp_list, g, sst = scanned
        new_kv = {n: [] for n in kv_names}
        new_s = []
        for i, win in enumerate(wins):
            lp = gp_list[i]
            h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
            ho = (comms.head_offset(lp["attn"]["wq"].shape[-2])
                  if comms is not None else None)
            att, *upd = blocks.decode_attention(
                lp["attn"], h, kv["k"][i], kv["v"][i], g, pos, cfg,
                window=win, head_offset=ho, active=active,
                **{n: kv[n][i] for n in kv_names[2:]})       # int8 scales
            for n, u in zip(kv_names, upd):
                new_kv[n].append(u)
            if cfg.family == "hybrid":
                if comms is not None:
                    # SSM runs on this shard's d_inner rows; its w_out
                    # partial is completed by its own replay of the
                    # layer AllReduce plan (matching auto's psum
                    # placement: attention and SSM reduce separately,
                    # then average)
                    s_out, s_new = ssm.ssm_decode_step(
                        lp["ssm"], h, sst[i], cfg,
                        d_offset=comms.ssm_offset(lp["ssm"]["a_log"].shape[0]))
                    s_out = comms.hidden(s_out)
                else:
                    s_out, s_new = ssm.ssm_decode_step(lp["ssm"], h,
                                                       sst[i], cfg)
                new_s.append(s_new)
            if comms is not None:
                att = comms.hidden(att)     # complete the out-proj partial
            if cfg.family == "hybrid":
                att = (att + s_out) * 0.5
            x = x + att
            h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
            if cfg.family == "moe":
                if comms is not None:
                    # expert-parallel dispatch/combine: both all_to_alls
                    # replay the init-compiled capacity-bucketed plan
                    x = x + comms.moe(lp["moe"], h)
                else:
                    x = x + blocks.moe_layer(lp["moe"], h, cfg)
            else:
                mlp_out = blocks.mlp_swiglu(lp["mlp"], h)
                if comms is not None:
                    mlp_out = comms.hidden(mlp_out)  # down-proj partial
                x = x + mlp_out
        return (x, new_kv), new_s

    groups = n_groups(cfg)
    sst = cache.get("ssm", [jnp.zeros((groups, 1)) for _ in wins])
    kv = {n: cache[n] for n in kv_names}
    (x, kv), ns = jax.lax.scan(body, (x, kv),
                               (params["layers"], jnp.arange(groups), sst))
    new_cache = dict(cache, **kv)
    if "ssm" in cache:
        new_cache["ssm"] = ns
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if comms is not None:
        return comms.logits(params, h), new_cache
    return logits_fn(params, cfg, h)[:, 0], new_cache


def prefill_step(params, cfg: ModelConfig, cache, tokens, pos, n_tok, *,
                 comms=None):
    """Fused multi-token prefill: advance every row's KV cache by a
    whole prompt chunk in ONE step, bit-identically to feeding the same
    tokens through :func:`decode_step` one at a time.

    tokens: (b, S) int32 — each row's next prompt chunk (rows pad with
    arbitrary tokens past their count); pos: (b,) int32 position of
    each row's first chunk token; n_tok: (b,) int32 how many of the S
    tokens are real per row (0 = the row doesn't advance: its cache —
    including the hybrid SSM state — passes through bit-exactly, the
    scheduler's inactive-slot contract, so no outer mask-select is
    needed). Returns the new cache only: prefill produces no logits —
    the scheduler always runs a row's FINAL prompt token through the
    combined decode step, whose logits row seeds the first sampled
    token, so the fused path never needs the vocab collective.

    ``comms`` is the explicit-TP hook (:class:`~repro.distributed.step.
    TPDecodeComms`), exactly as in :func:`decode_step`: per-layer
    partials complete through the replayed AllReduce plan (now at
    (b*S, d_model) sequence-bucketed rows), MoE dispatch/combine
    through the capacity-bucketed all_to_all. rwkv6/encoder families
    have no fused prefill (the scheduler keeps them token-by-token).

    Windowed-layer contract (see :func:`blocks.prefill_attention`): per
    row, either ``n_tok == 1`` or ``pos + n_tok <= kv_len`` for every
    ring-buffer layer. The scheduler sizes chunks to satisfy it.
    """
    if cfg.family not in ("dense", "moe", "hybrid") or (
            comms is not None and cfg.family == "moe"
            and comms.moe_plan is None):
        raise NotImplementedError(
            "fused prefill covers the dense, MoE, and hybrid families "
            "(explicit mode additionally needs a compiled moe_alltoall "
            "plan for MoE); rwkv6/encoder configs prefill token-by-token "
            "through the decode path")
    b, S = tokens.shape
    if comms is not None:
        x = comms.embed(params["embed"], tokens.reshape(-1)).reshape(
            b, S, -1)
    else:
        x = params["embed"][tokens]                     # (b, S, d)
    wins = layer_windows(cfg)
    quant = "k_scale" in cache

    def body(x, scanned):
        gp_list, ck, cv, sst, ksc, vsc = scanned
        new_k, new_v, new_s, new_ksc, new_vsc = [], [], [], [], []
        for i, win in enumerate(wins):
            lp = gp_list[i]
            h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
            ho = (comms.head_offset(lp["attn"]["wq"].shape[-2])
                  if comms is not None else None)
            if quant:
                att, k_upd, v_upd, ks_upd, vs_upd = blocks.prefill_attention(
                    lp["attn"], h, ck[i], cv[i], pos, n_tok, cfg,
                    window=win, k_scale=ksc[i], v_scale=vsc[i],
                    head_offset=ho)
                new_ksc.append(ks_upd)
                new_vsc.append(vs_upd)
            else:
                att, k_upd, v_upd = blocks.prefill_attention(
                    lp["attn"], h, ck[i], cv[i], pos, n_tok, cfg,
                    window=win, head_offset=ho)
            if cfg.family == "hybrid":
                d_off = (comms.ssm_offset(lp["ssm"]["a_log"].shape[0])
                         if comms is not None else None)
                s_out, s_new = ssm.ssm_prefill_scan(
                    lp["ssm"], h, sst[i], cfg, n_tok, d_offset=d_off)
                if comms is not None:
                    s_out = comms.hidden(s_out)
                new_s.append(s_new)
            if comms is not None:
                att = comms.hidden(att)     # complete the out-proj partial
            if cfg.family == "hybrid":
                att = (att + s_out) * 0.5
            x = x + att
            h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
            if cfg.family == "moe":
                if comms is not None:
                    x = x + comms.moe(lp["moe"], h)
                else:
                    x = x + blocks.moe_layer(lp["moe"], h, cfg)
            else:
                mlp_out = blocks.mlp_swiglu(lp["mlp"], h)
                if comms is not None:
                    mlp_out = comms.hidden(mlp_out)  # down-proj partial
                x = x + mlp_out
            new_k.append(k_upd)
            new_v.append(v_upd)
        return x, (new_k, new_v, new_s, new_ksc, new_vsc)

    sst = cache.get("ssm", [jnp.zeros((n_groups(cfg), 1)) for _ in wins])
    dummy = [jnp.zeros((n_groups(cfg), 1)) for _ in wins]
    ksc = cache.get("k_scale", dummy)
    vsc = cache.get("v_scale", dummy)
    _, (nk, nv, ns, nksc, nvsc) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], sst, ksc, vsc))
    new_cache = dict(cache, k=nk, v=nv)
    if "ssm" in cache:
        new_cache["ssm"] = ns
    if quant:
        new_cache["k_scale"] = nksc
        new_cache["v_scale"] = nvsc
    return new_cache
