"""Per-request sampling, vectorized inside the jitted decode step.

Every decoding slot carries its own (temperature, top_k, top_p, PRNG
key); `sample` applies all of them in ONE batched computation so the
engine's single jitted decode step honors per-request sampling without
per-slot host round-trips.  Greedy slots (temperature <= 0) take the
exact `argmax` path — a greedy request's tokens are bitwise identical
to argmax decoding regardless of what its batch neighbors sample.

Keys are per-request (derived from `SamplingParams.seed`, or from the
engine seed + request id), so a request's sample stream is reproducible
independent of batch composition, admission order, or its slot index.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode-time sampling controls.

    temperature <= 0 means greedy (argmax); top_k <= 0 and top_p >= 1
    disable their filters.  `stop` lists extra stop-token ids (the
    engine's eos_id always stops); `seed` pins the request's PRNG stream
    (None: derived from the engine seed and the request id).
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop: Tuple[int, ...] = ()
    seed: Optional[int] = None


def _key(x):
    """f32 -> int32 keys in the same order (for non-NaN x): a negative
    float's magnitude bits are flipped, so larger magnitudes key lower."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ 0x7FFFFFFF, b)


def _value(k):
    """The f32 whose `_key` is k (the map is its own inverse)."""
    return jax.lax.bitcast_convert_type(jnp.where(k < 0, k ^ 0x7FFFFFFF, k),
                                        F32)


def filter_logits(logits, top_k, top_p):
    """Mask logits outside the per-row top-k / nucleus (top-p) sets.

    logits: (B, V) f32; top_k: (B,) int32 (<= 0 disables); top_p: (B,)
    f32 (>= 1 disables).  Returns (B, V) with filtered entries at -inf.

    A row keeps every logit >= its threshold t, the smallest logit for
    which both hold:
      * nucleus: the probability (softmax at temperature 1) of the logits
        > t is below top_p.  The tokens before t in descending order hold
        less than p, so the token whose mass carries the sum past p is
        kept, and so is every tie of it;
      * top-k: fewer than top_k logits are > t, so the k-th largest and
        its ties are kept.
    Both only get easier as t rises, so t is found without a sort, by
    bisection over the row's order-preserving int32 keys (`_key`): 32
    passes, each one reduction over (B, V) that reads the logits once,
    halve the bracket (min key - 1, max key], under 2**32 keys wide, to
    one key.  A pass compares floats (logits > the key's value), so -0.0
    and +0.0, adjacent keys, count as one value, as in the final `>=`.
    top_p = 0 leaves t at the row's max, so the top-1 token always
    survives and no row is all -inf.  The top-k count is traced only when
    some row asks for it.
    """
    v = logits.shape[-1]
    m = jnp.max(logits, axis=-1, keepdims=True)
    # nucleus at mass < p: the unnormalised mass exp(l - m) against p * Z
    p_z = (jnp.where(top_p >= 1.0, 2.0, top_p)
           * jnp.sum(jnp.exp(logits - m), axis=-1))
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, v), v)

    def search(with_k):
        def body(_, bracket):
            lo, hi = bracket            # the answer lies in (lo, hi]
            # floor((lo + hi) / 2) without int32 overflow
            mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
            mid = jnp.where(mid == lo, hi, mid)
            above = logits > _value(mid)[:, None]
            mass = jnp.where(above, jnp.exp(logits - m), 0.0)
            if with_k:      # one variadic reduction: one read of logits
                mass, count = jax.lax.reduce(
                    (mass, above.astype(jnp.int32)), (F32(0), jnp.int32(0)),
                    lambda a, b: (a[0] + b[0], a[1] + b[1]), (1,))
                ok = (mass < p_z) & (count < k_eff)
            else:
                ok = jnp.sum(mass, axis=-1) < p_z
            return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

        bracket = (_key(jnp.min(logits, axis=-1)) - 1, _key(m[:, 0]))
        return jax.lax.fori_loop(0, 32, body, bracket)[1]

    thresh = jax.lax.cond(jnp.any(k_eff < v), lambda: search(True),
                          lambda: search(False))
    return jnp.where(logits >= _value(thresh)[:, None], logits, -jnp.inf)


@jax.named_scope("sample")
def sample(logits, keys, temperature, top_k, top_p):
    """One sampling step for a batch of slots (jit-safe).

    logits: (B, V); keys: (B, 2) uint32 per-slot PRNG keys; temperature /
    top_p: (B,) f32; top_k: (B,) int32.  Returns (tokens (B,) int32,
    keys (B, 2)).  Rows with temperature <= 0 return the exact argmax.

    An ALL-greedy batch (the serving default) takes a `lax.cond` fast
    path: no PRNG split, no filter/softmax/gumbel work — just the
    argmax — and the keys pass through UNCHANGED (greedy rows never
    consume randomness, so advancing their keys bought nothing).  In a
    mixed batch every row's key advances, so a sampling request's
    stream depends only on its own key, never on its batch neighbors.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _all_greedy(_):
        return greedy, keys

    def _mixed(_):
        split = jax.vmap(jax.random.split)(keys)       # (B, 2, 2)
        new_keys, subs = split[:, 0], split[:, 1]
        filt = filter_logits(logits.astype(F32), top_k, top_p)
        scaled = filt / jnp.maximum(temperature, 1e-6)[:, None]
        drawn = jax.vmap(jax.random.categorical)(subs,
                                                 scaled).astype(jnp.int32)
        return jnp.where(temperature > 0, drawn, greedy), new_keys

    return jax.lax.cond(jnp.all(temperature <= 0.0), _all_greedy, _mixed,
                        None)


def request_key(sp: SamplingParams, engine_seed: int, rid: int):
    """The request's root PRNG key: its own seed, or engine seed x rid."""
    if sp.seed is not None:
        return jax.random.PRNGKey(sp.seed)
    return jax.random.fold_in(jax.random.PRNGKey(engine_seed), rid)
