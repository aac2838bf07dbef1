"""The sampler's top-k / top-p filter (serve/sampling.py).

  * `filter_logits` finds each row's threshold by bisection over the
    logits' order-preserving int32 keys; it must keep exactly the set
    that a full descending sort keeps (the oracle below), ties and
    signed zeros included, for every top_k / top_p setting;
  * the compiled decode program of a smoke qwen2.5-3b engine holds no
    sort, so a vocabulary sort cannot come back unnoticed.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import count_ops
from repro.configs.registry import get_config
from repro.models import model as mdl
from repro.serve.engine import Engine
from repro.serve.sampling import filter_logits

TOP_K = (0, 1, 7, "V", "V+3")
TOP_P = (0.0, 0.3, 0.95, 1.0)


@jax.jit
def sorted_filter(logits, top_k, top_p):
    """The sort-based filter: the k-th largest logit and the smallest
    logit whose exclusive cumulative probability is below p."""
    v = logits.shape[-1]
    desc = jnp.sort(logits, axis=-1)[..., ::-1]
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, v), v)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    keep = logits >= kth
    probs = jax.nn.softmax(desc, axis=-1)
    excl = jnp.cumsum(probs, axis=-1) - probs
    p_eff = jnp.where(top_p >= 1.0, 2.0, top_p)
    kept_sorted = (excl < p_eff[:, None]).at[..., 0].set(True)
    thresh = jnp.min(jnp.where(kept_sorted, desc, jnp.inf), axis=-1)
    keep = keep & (logits >= thresh[:, None])
    return jnp.where(keep, logits, -jnp.inf)


def _rows(kind, v, rng):
    """Two rows of one kind: distinct logits; a coarse grid full of ties
    with +0.0 and -0.0 among them; or logits that span almost every f32
    key, -inf to the largest finite, so the search needs all its passes."""
    x = rng.standard_normal((2, v)).astype(np.float32)
    x[1] *= 0.05                                   # nearly flat
    if kind == "ties":
        x = np.round(x * 2.0) / 2.0                # rounds to -0.0 too
        x[:, : min(v, 3)] = np.float32(-0.0)
        x[:, -1:] = np.float32(0.0)
    if kind == "wide":
        big = np.finfo(np.float32).max
        x[0] = x[0] * 3e37
        x[0, :3] = -np.inf, big, -big
        x[1] = np.where(rng.random(v) < 0.5, -np.inf, x[1])
    return x


def _at_boundary(logits, top_p, tol=1e-6):
    """Tokens whose exclusive mass (in f64: the probability of the logits
    above them) lies within tol of their row's top_p, where 0 < p < 1.
    Only there may f32 summation order decide whether one is kept."""
    x = logits.astype(np.float64)
    probs = np.exp(x - x.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros(x.shape, bool)
    for r, (row, pr) in enumerate(zip(x, probs)):
        order = np.argsort(row)
        # tail[i]: the mass of the i-th smallest logit and all above it
        tail = np.append(np.cumsum(pr[order][::-1])[::-1], 0.0)
        mass = tail[np.searchsorted(row[order], row, side="right")]
        out[r] = (0 < top_p[r] < 1) & (np.abs(mass - top_p[r]) <= tol)
    return out


@pytest.mark.parametrize("v, kind", [
    (3, "distinct"), (3, "ties"), (257, "distinct"), (257, "ties"),
    (2048, "distinct"), (2048, "ties"), (3, "wide"), (257, "wide"),
    (2048, "wide"), (151936, "distinct"), (151936, "ties"),
])
def test_filter_keeps_what_a_sort_keeps(v, kind):
    rng = np.random.default_rng(v)
    base = _rows(kind, v, rng)
    combos = list(itertools.product(TOP_K, TOP_P))
    logits = np.repeat(base, len(combos), axis=0)
    ks = [{"V": v, "V+3": v + 3}.get(k, k) for k, _ in combos]
    top_k = np.tile(np.asarray(ks, np.int32), len(base))
    top_p = np.tile(np.asarray([p for _, p in combos], np.float32),
                    len(base))
    args = jnp.asarray(logits), jnp.asarray(top_k), jnp.asarray(top_p)
    got = np.asarray(jax.jit(filter_logits)(*args))
    want = np.asarray(sorted_filter(*args))
    kept = np.isfinite(got)
    differ = (kept != np.isfinite(want)) & ~_at_boundary(logits, top_p)
    bad = np.flatnonzero(differ.any(-1))
    assert bad.size == 0, [(int(top_k[i]), float(top_p[i])) for i in bad]
    np.testing.assert_array_equal(got[kept], logits[kept])
    assert kept.any(-1).all()          # the top-1 token always survives


@pytest.mark.parametrize("kind", ["distinct", "ties", "wide"])
def test_filter_without_top_k_matches_sort(kind):
    """The branch that skips the top-k count (no row asks for top-k)."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(_rows(kind, 257, rng))
    top_k = jnp.asarray([0, 257], jnp.int32)
    top_p = jnp.asarray([0.3, 0.95], jnp.float32)
    got = filter_logits(logits, top_k, top_p)
    want = sorted_filter(logits, top_k, top_p)
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))


def test_decode_program_holds_no_sort(rng):
    cfg = get_config("qwen2.5-3b", smoke=True)
    eng = Engine(cfg, mdl.init_params(cfg, rng), max_slots=2, max_len=32,
                 eos_id=-1)
    n = eng.num_slots
    hlo = eng._decode.lower(
        eng.params, eng.cache, jnp.zeros((n,), jnp.int32),
        jnp.zeros((n, 2), jnp.uint32), jnp.asarray([0.0, 0.7]),
        jnp.asarray([0, 5], jnp.int32),
        jnp.asarray([1.0, 0.95])).compile().as_text()
    assert count_ops(hlo, "sort") == 0
