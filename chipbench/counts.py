"""Operations and useful bytes of the served model, from the shapes in
a configuration file's ``model`` block (the keys the program's
``ModelConfig`` takes). Nothing here reads the program.

A matrix product of an ``m x k`` by a ``k x n`` operand counts
``2 m k n`` operations. Useful bytes of a decode step are every weight
once (a tied embedding once, as the output head reads it whole), plus
the key/value bytes of each active row's valid context: not the
allocated cache, so a step that stops reading padding reads fewer
bytes than the count and scores higher against it.
"""
from __future__ import annotations

__all__ = ["param_count", "weight_bytes", "kv_bytes_per_token",
           "token_flops", "prefill_flops", "decode_bytes"]

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(m: dict):
    d, nh, nkv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // nh
    return d, nh, nkv, hd


def _layer_matmul_params(m: dict) -> int:
    d, nh, nkv, hd = _dims(m)
    return d * nh * hd * 2 + d * nkv * hd * 2 + 3 * d * m["d_ff"]


def param_count(m: dict) -> int:
    d, nh, nkv, hd = _dims(m)
    per = _layer_matmul_params(m) + 2 * d
    if m.get("qk_norm"):
        per += 2 * hd
    emb = m["vocab"] * d * (1 if m.get("tie_embeddings") else 2)
    return emb + m["n_layers"] * per + d


def weight_bytes(m: dict) -> int:
    return param_count(m) * _BYTES[m["dtype"]]


def _attended(m: dict, ctx: int) -> int:
    w = m.get("window")
    return min(ctx, w) if w else ctx


def kv_bytes_per_token(m: dict) -> int:
    _, _, nkv, hd = _dims(m)
    return m["n_layers"] * 2 * nkv * hd * _BYTES[m["dtype"]]


def token_flops(m: dict, ctx: int, logits: bool) -> int:
    """One token that attends over ``ctx`` positions (itself included);
    ``logits`` adds the output head."""
    d, nh, _, hd = _dims(m)
    f = m["n_layers"] * (2 * _layer_matmul_params(m)
                         + 4 * nh * hd * _attended(m, ctx))
    if logits:
        f += 2 * d * m["vocab"]
    return f


def prefill_flops(m: dict, start: int, n: int) -> int:
    """``n`` prompt tokens at positions ``start .. start+n-1``, no
    output head."""
    return sum(token_flops(m, p + 1, False) for p in range(start, start + n))


def decode_bytes(m: dict, contexts) -> int:
    """One decode step over rows attending ``contexts`` positions."""
    kv = kv_bytes_per_token(m)
    return weight_bytes(m) + sum(_attended(m, c) * kv for c in contexts)
