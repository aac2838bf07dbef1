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


def filter_logits(logits, top_k, top_p):
    """Mask logits outside the per-row top-k / nucleus (top-p) sets.

    logits: (B, V) f32; top_k: (B,) int32 (<= 0 disables); top_p: (B,)
    f32 (>= 1 disables).  Returns (B, V) with filtered entries at -inf.
    The top-1 token always survives, so the filters can never produce an
    all--inf row.
    """
    v = logits.shape[-1]
    desc = jnp.sort(logits, axis=-1)[..., ::-1]                    # (B, V)
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, v), v)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)  # (B, 1)
    keep = logits >= kth
    # nucleus: keep tokens while the EXCLUSIVE cumulative mass < p, so
    # the first token is always kept and mass crosses p inclusively
    probs = jax.nn.softmax(desc, axis=-1)
    excl = jnp.cumsum(probs, axis=-1) - probs
    p_eff = jnp.where(top_p >= 1.0, 2.0, top_p)                    # disable
    kept_sorted = excl < p_eff[:, None]
    kept_sorted = kept_sorted.at[..., 0].set(True)  # top-1 survives p=0
    thresh = jnp.min(jnp.where(kept_sorted, desc, jnp.inf), axis=-1)
    keep = keep & (logits >= thresh[:, None])
    return jnp.where(keep, logits, -jnp.inf)


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
