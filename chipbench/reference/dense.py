"""Plain float32 reference of the dense family (GQA, optional qk-norm,
rotary positions, SwiGLU), written from the architecture's equations in
``jax.numpy``: no kernels, no cache, no batching.

The benchmark makes the weights itself (``make_weights``) in the layout
the served program takes (``layers`` holds one dict per period slot,
every leaf stacked over the layers), and hands the same arrays to the
program and to this reference. RMSNorm gains are stored as offsets
from 1 (the gain is ``1 + w``), as the served program stores them.

``precision="f32"`` computes every matrix product in float32 at
``highest``; ``precision="fp8"`` rounds both operands of every product
to float8 e4m3 (per-row scales for activations, per-column for
weights) and accumulates in float32: the control, one precision step
below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_weights", "forward", "next_token_gaps", "WEIGHT_RULES"]

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)

#: leaf name -> (kind, fan-in axis of the per-layer leaf). ``normal``
#: leaves are N(0, fan_in**-0.5) (the embedding N(0, 1), or, where it
#: is also the output head, N(0, d_model**-0.5) like an untied head);
#: ``gain`` leaves are small offsets from a unit gain.
WEIGHT_RULES = {
    "embed": ("normal", None), "unembed": ("normal", 0),
    "wq": ("normal", 0), "wk": ("normal", 0), "wv": ("normal", 0),
    "wo": ("normal", (0, 1)),
    "w_gate": ("normal", 0), "w_up": ("normal", 0), "w_down": ("normal", 0),
    "ln_attn": ("gain", None), "ln_mlp": ("gain", None),
    "ln_f": ("gain", None), "q_norm": ("gain", None),
    "k_norm": ("gain", None),
}


def _normal(key, shape, dtype):
    """Standard normals in ``dtype``, drawn one slab of the leading axis
    at a time (at most 32 slabs), so that the random bits of a whole
    leaf never exist at once on the device."""
    n = shape[0] if shape else 1
    k = max(d for d in range(1, min(n, 32) + 1) if n % d == 0)
    if k == 1:
        return jax.random.normal(key, shape, dtype)
    slab = (n // k,) + tuple(shape[1:])
    out = jax.lax.map(lambda kk: jax.random.normal(kk, slab, dtype),
                      jax.random.split(key, k))
    return out.reshape(shape)


def _leaf(rule, shape, dtype, key, stacked: bool):
    # drawn in the served dtype: no float32 copy of the model on the way
    kind, fan = rule
    if kind == "gain":
        return 0.05 * _normal(key, shape, dtype)
    if kind == "normal":
        per = shape[1:] if stacked else shape
        axes = () if fan is None else ((fan,) if isinstance(fan, int) else fan)
        n_in = int(np.prod([per[a] for a in axes])) if axes else 1
        return _normal(key, shape, dtype) * jnp.asarray(n_in ** -0.5, dtype)
    raise ValueError(f"unknown weight rule {kind!r}")


def make_weights(shapes, key):
    """Weights for the tree of ``jax.ShapeDtypeStruct`` ``shapes``: one
    leaf per path, each from its own fold of ``key``. Call under
    ``jax.jit``."""
    rules = WEIGHT_RULES
    if "unembed" not in shapes:          # the embedding is the head too
        rules = dict(rules, embed=("normal", 1))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, sds) in enumerate(leaves):
        name = path[-1].key
        stacked = any(getattr(p, "key", None) == "layers" for p in path)
        k = jax.random.fold_in(key, i)
        if name not in rules:
            raise ValueError(f"no weight rule for leaf {jax.tree_util.keystr(path)}")
        out.append(_leaf(rules[name], sds.shape, sds.dtype, k, stacked))
    return jax.tree_util.tree_unflatten(treedef, out)


# -- arithmetic ------------------------------------------------------------
def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(a, w, precision: str, spec: str):
    """``einsum(spec, a, w)`` in float32 at ``highest``; under ``fp8``
    the operands are first rounded to e4m3 (activation scales over its
    contracted axes, weight scales over the weight's contracted axes)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        lhs, rest = spec.split(",")
        rhs, out = rest.split("->")
        a = _q8(a, tuple(i for i, c in enumerate(lhs) if c not in out))
        w = _q8(w, tuple(i for i, c in enumerate(rhs) if c not in out))
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, w, precision=HIGHEST)


def rms(x, gain, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + gain.astype(jnp.float32))


def rope(x, positions, theta):
    """Rotary positions on (T, n, hd): the first and second halves of
    each head are the two coordinates of each rotated pair."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(p, h, m, precision):
    """Causal GQA over the whole sequence; query head ``n`` reads
    key/value head ``n // (n_heads / n_kv_heads)``; a window of ``w``
    keeps the keys with ``0 <= i - j < w``."""
    T = h.shape[0]
    q = mm(h, p["wq"], precision, "td,dnh->tnh")
    k = mm(h, p["wk"], precision, "td,dnh->tnh")
    v = mm(h, p["wv"], precision, "td,dnh->tnh")
    if m.get("qk_norm"):
        q = rms(q, p["q_norm"], m["norm_eps"])
        k = rms(k, p["k_norm"], m["norm_eps"])
    pos = jnp.arange(T)
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    g = m["n_heads"] // m["n_kv_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = mm(q, k, precision, "tnh,snh->nts") * m["head_dim"] ** -0.5
    rel = pos[:, None] - pos[None, :]
    ok = rel >= 0
    if m.get("window"):
        ok &= rel < m["window"]
    s = jnp.where(ok[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm(a, v, precision, "nts,snh->tnh")
    return mm(o, p["wo"], precision, "tnh,nhd->td")


def mlp(p, h, precision):
    gate = mm(h, p["w_gate"], precision, "td,df->tf")
    up = mm(h, p["w_up"], precision, "td,df->tf")
    return mm(jax.nn.silu(gate) * up, p["w_down"], precision, "tf,fd->td")


def forward(w, m, tokens, precision="f32"):
    """tokens (T,) int -> logits (T, vocab) float32."""
    if len(w["layers"]) != 1:
        raise ValueError("the reference covers one layer kind repeated")
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        h = rms(x, lp["ln_attn"], m["norm_eps"])
        x = x + attention(lp["attn"], h, m, precision)
        h = rms(x, lp["ln_mlp"], m["norm_eps"])
        return x + mlp(lp["mlp"], h, precision), None

    x, _ = jax.lax.scan(layer, x, w["layers"][0])
    x = rms(x, w["ln_f"], m["norm_eps"])
    head = w["embed"].T if m.get("tie_embeddings") else w["unembed"]
    return mm(x, head, precision, "td,dv->tv")


def next_token_gaps(w, m, tokens, control=False):
    """Per position ``p``, how far below the reference's best logit lies
    the logit of ``tokens[p + 1]``, the token the program served there
    (the last position reads 0); with ``control``, also how far below it
    lies the logit of the token the fp8 control puts first at ``p``
    (else zeros). Both read in float32 at ``highest``."""
    ref = forward(w, m, tokens, "f32")
    best = ref.max(axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    served = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    last = jnp.arange(tokens.shape[0]) == tokens.shape[0] - 1
    gaps = jnp.where(last, 0.0, best - served)
    if not control:
        return gaps, jnp.zeros_like(gaps)
    pick = jnp.argmax(forward(w, m, tokens, "fp8"), axis=-1)
    chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return gaps, best - chosen
