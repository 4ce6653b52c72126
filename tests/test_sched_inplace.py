"""The scheduler step writes each active slot's new K/V row in place.

* One ``make_sched_step`` call with a mixed ``active`` mask changes the
  attention leaves (K, V and the int8 scales) only at
  ``[group, row, :, slot(row)]`` of the active rows, and its whole cache
  equals, bit for bit, the scalar-position step's rows (the Engine's
  step, every row at one shared position) selected over the old cache:
  dense, int8 KV, windowed layers whose ring index wraps, and the
  hybrid family, whose ``ssm`` state is still selected per slot.
* The optimized step program selects over no whole cache leaf. The
  Scheduler's auto step aliases its cache parameter to its output
  (donation); under the numeric guard, and in explicit mode, where a
  recovery re-run needs the pre-step cache, it does not.
* Donating the cache changes no emitted token.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.distributed import sharding as shd
from repro.distributed import step as step_mod
from repro.models import transformer as tf
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, Scheduler

AX = shd.MeshAxes()
ROW_WRITTEN = ("k", "v", "k_scale", "v_scale")

B, KV = 4, 16
TOKENS = np.asarray([3, 5, 7, 11], np.int32)
POS = np.asarray([2, 9, 15, 12], np.int32)   # rows 1-3 wrap a ring of 8
ACTIVE = np.asarray([True, False, True, True])

_QWEN = configs.reduced(configs.get_config("qwen3-1.7b"))
CASES = {
    "dense": (_QWEN, False),
    "int8": (_QWEN, True),
    "window": (dataclasses.replace(_QWEN, window=8), False),
    "hybrid": (configs.reduced(configs.get_config("hymba-1.5b")), False),
}


def _mesh(tp: int = 1):
    return Mesh(np.asarray(jax.devices()[:tp]).reshape(1, tp),
                (AX.data[0], AX.model))


def _filled_cache(cfg, quant: bool):
    """A decode cache of random values, so that every entry a step
    leaves alone is told apart from one it writes."""
    cache = tf.init_cache(cfg, B, KV, dtype=jnp.int8 if quant else None)
    leaves, tree = jax.tree.flatten(cache)
    rng = np.random.default_rng(0)
    out = []
    for a in leaves:
        if a.dtype == jnp.int8:
            v = rng.integers(-127, 128, a.shape)
        else:
            v = rng.uniform(0.5, 2.0, a.shape)
        out.append(jnp.asarray(v, a.dtype))
    return jax.tree.unflatten(tree, out)


@pytest.mark.parametrize("case", list(CASES))
def test_step_writes_one_row_per_active_slot(case):
    cfg, quant = CASES[case]
    mesh = _mesh()
    params, _ = step_mod.init_sharded(cfg, mesh, AX, jax.random.key(0))
    cache = _filled_cache(cfg, quant)
    fn, _ = step_mod.make_sched_step(cfg, mesh, AX, batch=B, max_kv=KV,
                                     kv_quant=quant)
    logits, new = fn(params, cache, TOKENS, POS, ACTIVE)

    # reference: the scalar-position step at each active row's position,
    # that row taken, every other row kept from the old cache
    scalar = jax.jit(lambda p, c, t, q: tf.decode_step(p, cfg, c, t, q))
    expect = jax.tree.map(np.array, cache)
    for r in np.flatnonzero(ACTIVE):
        lg_r, c_r = scalar(params, cache, TOKENS, jnp.int32(POS[r]))
        np.testing.assert_array_equal(np.asarray(logits[r]),
                                      np.asarray(lg_r[r]))
        for e, c in zip(jax.tree.leaves(expect), jax.tree.leaves(c_r)):
            e[:, r] = np.asarray(c[:, r])
    assert jax.tree.structure(new) == jax.tree.structure(expect)
    for got, want in zip(jax.tree.leaves(new), jax.tree.leaves(expect)):
        np.testing.assert_array_equal(np.asarray(got), want)

    for name in ROW_WRITTEN:
        for old, nw in zip(cache.get(name, []), new.get(name, [])):
            changed = np.asarray(old) != np.asarray(nw)
            allowed = np.zeros(changed.shape, bool)
            for r in np.flatnonzero(ACTIVE):
                allowed[:, r, :, POS[r] % old.shape[3]] = True
            assert not (changed & ~allowed).any(), name
            assert changed.any(), name
    if "ssm" in cache:
        for old, nw in zip(cache["ssm"], new["ssm"]):
            np.testing.assert_array_equal(np.asarray(old)[:, ~ACTIVE],
                                          np.asarray(nw)[:, ~ACTIVE])


# -- the step program ------------------------------------------------------
SLOTS = 8


def _engine(setting: str):
    cfg = configs.reduced(configs.get_config("qwen3-1.7b"))
    tp = 2 if setting == "explicit" else 1
    mesh = _mesh(tp)
    params, _ = step_mod.init_sharded(cfg, mesh, AX, jax.random.key(0))
    scfg = ServeConfig(batch=SLOTS, max_kv=64,
                       mode="explicit" if setting == "explicit" else "auto",
                       guard_numerics=setting == "guarded")
    return Engine(cfg, params, mesh, scfg, ax=AX)


def _aliased_params(text: str) -> set:
    """Parameter numbers the module aliases to an output."""
    head = text.splitlines()[0]
    return {int(p) for p in re.findall(r"\{\d+\}: \((\d+), \{\}", head)}


@pytest.mark.parametrize("setting", ["auto", "guarded", "explicit"])
def test_step_program_selects_no_leaf_and_donates_in_auto(setting):
    eng = _engine(setting)
    assert eng.mode == ("explicit" if setting == "explicit" else "auto")
    sched = Scheduler(eng)
    fn = sched._step_fn(SLOTS)
    vec = np.zeros(SLOTS, np.int32)
    text = fn.lower(eng.params, sched.cache, vec, vec,
                    np.ones(SLOTS, bool)).compile().as_text()
    assert "HloModule jit_step_sched" in text

    leaves = [a for n in ROW_WRITTEN for a in sched.cache.get(n, [])]
    for a in leaves:
        shape = "[%s]" % ",".join(map(str, a.shape))
        whole = [ln for ln in text.splitlines()
                 if re.search(r"= \w+%s\S* select\(" % re.escape(shape), ln)]
        assert not whole, whole[:1]

    n_params = len(jax.tree.leaves(eng.params))
    cache_params = set(range(n_params,
                             n_params + len(jax.tree.leaves(sched.cache))))
    donated = _aliased_params(text) & cache_params
    assert donated == (cache_params if setting == "auto" else set())


def test_donating_the_cache_changes_no_token():
    streams = []
    for setting in ("auto", "guarded"):
        sched = Scheduler(_engine(setting), prefill_chunk=3)
        for rid in range(5):
            sched.submit(Request(
                rid=rid, prompt=np.arange(1, 3 + 2 * rid, dtype=np.int32),
                max_new_tokens=2 + rid, temperature=0.8 if rid % 2 else 0.0,
                seed=rid, arrival_s=float(rid)))
        sched.run_until_drained()
        streams.append(sched.streams)
    assert streams[0] == streams[1]
    assert sorted(streams[0]) == list(range(5))
