"""Fused single-kernel decode steps (ROADMAP "fused epilogues").

Decode is one token per slot, so its cost is pure HBM streaming — yet
the unfused paths materialize intermediates between the attention math
and its epilogue:

  * linear/GLA decode (core.chunked.la_decode_step /
    core.gla.gla_decode_step) writes the un-normalized f = a*p + b*q.S
    to HBM, then runs the normalizer divide (and GLA's decay gate) as
    separate XLA ops — four round trips over O(B*Hkv*D^2) state;
  * softmax decode finalizes the online-softmax divide outside the
    kernel, and the contiguous-cache path never had a kernel at all
    (softmax_decode is an einsum chain with a (B,H,S) score tensor);
  * paged decode runs one grid cell per QUERY head, streaming each KV
    page `group` times under GQA.

This module is the fused alternative, one Pallas kernel per decode
step per family:

  `la_decode_fused_pallas` / `gla_decode_fused_pallas` — grid (B, Hkv);
  each cell reads the slot's recurrent state page (S: (Dk, Dv+1), p:
  (Dv+1)), applies the decay gate (GLA), rank-1-updates the state IN
  PLACE (input_output_aliases donates the state buffers), computes the
  grouped q.S and normalizer dots, and writes the already-divided
  output — one HBM round trip over the state instead of four.

  `softmax_decode_fused_pallas` — contiguous-cache softmax decode as an
  online-softmax kernel: grid (B, Hkv, S/block_k), grouped query heads
  (GQA head-fold: the (G, D) query block rides in one grid cell, each
  KV block streams ONCE per kv head), running max/sum in VMEM scratch,
  and the finalize divide folded into the last grid step — no (B, H, D)
  accumulator ever leaves VMEM.

  `paged_decode_fused_pallas` — the paged-KV walk of
  kernels/paged_attention.py with the same GQA head-fold: grid
  (B, Hkv, Pmax/ppb) instead of (B, H, Pmax), so arena pages are
  DMA'd once per kv head, not once per query head.

Shared conventions with the unfused kernels: f32 accumulation,
`preferred_element_type` on every dot, per-slot lengths via scalar
prefetch with the page/block walk clamped at the slot's frontier, and
a guarded finalize so a length-0 (retired) slot yields zeros, never
NaN.  The linear/GLA normalizer divide replicates
core.numerics.safe_div semantics (exact-zero denominators map to 0).

Dispatch lives in kernels/ops.py as the `*_decode_fused` KernelImpl
families; the xla/ref impls there ARE the unfused compositions, so the
fallback is byte-identical by construction.  Parity is pinned in
tests/test_decode_fused.py; docs/fused_decode.md has the HBM-traffic
accounting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.defaults import DEFAULT_TILES

F32 = jnp.float32
NEG_INF = -1e30
_SAFE_EPS = 1e-30  # core.numerics.safe_div's zero-denominator threshold
_BK = DEFAULT_TILES["softmax_decode_fused"]["block_k"]
_PPB = DEFAULT_TILES["paged_decode_fused"]["pages_per_block"]


# ---------------------------------------------------------------------------
# Linear / GLA: state-update + normalizer epilogue in one kernel
# ---------------------------------------------------------------------------

def _recurrent_step_kernel(*refs, a: float, b: float, dv: int,
                           gated: bool):
    if gated:
        s_ref, p_ref, q_ref, k_ref, v_ref, ld_ref = refs[:6]
        s_out, p_out, o_ref = refs[6:]
    else:
        s_ref, p_ref, q_ref, k_ref, v_ref = refs[:5]
        s_out, p_out, o_ref = refs[5:]
    s = s_ref[0, 0].astype(F32)                    # (dk, dv+1)
    p = p_ref[0, 0].astype(F32)                    # (1, dv+1)
    k = k_ref[0, 0].astype(F32)                    # (1, dk)
    v = v_ref[0, 0].astype(F32)                    # (1, dv)
    vaug = jnp.concatenate([v, jnp.ones((1, 1), F32)], -1)   # (1, dv+1)
    if gated:
        gamma = jnp.exp(ld_ref[0, 0].astype(F32))  # (1, 1)
        s = gamma * s
        p = gamma * p
    s_new = s + jnp.dot(k.T, vaug, preferred_element_type=F32)
    p_new = p + vaug
    qg = q_ref[0, 0].astype(F32)                   # (g, dk)
    f = a * p_new + b * jnp.dot(qg, s_new, preferred_element_type=F32)
    num, den = f[:, :dv], f[:, dv:]                # (g, dv), (g, 1)
    # safe_div inline: exact-zero denominators (padding rows) -> 0
    den_safe = jnp.where(jnp.abs(den) < _SAFE_EPS, 1.0, den)
    o = jnp.where(jnp.abs(den) < _SAFE_EPS, 0.0, num / den_safe)
    s_out[0, 0] = s_new.astype(s_out.dtype)
    p_out[0, 0] = p_new.astype(p_out.dtype)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _recurrent_decode_call(s, p, q, k, v, log_decay, a, b, interpret):
    bsz, h, dk = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    dv1 = s.shape[-1]
    assert dv1 == dv + 1, (s.shape, v.shape)
    qg = q.reshape(bsz, hkv, g, dk)
    gated = log_decay is not None

    # every per-head vector rides as a (1, n) row of a 4-D array: a
    # (1, 1, n) block of (B, Hkv, n) would tile a unit dim over Hkv
    def row(x):
        return x.reshape(bsz, hkv, 1, -1)

    def spec(*tail):
        return pl.BlockSpec((1, 1) + tail, lambda bi, hi: (bi, hi, 0, 0))

    in_specs = [spec(dk, dv1), spec(1, dv1), spec(g, dk), spec(1, dk),
                spec(1, dv)]
    args = [s, row(p), qg, row(k), row(v)]
    if gated:
        in_specs.append(spec(1, 1))
        args.append(row(log_decay))

    s_new, p_new, og = pl.pallas_call(
        functools.partial(_recurrent_step_kernel, a=a, b=b, dv=dv,
                          gated=gated),
        grid=(bsz, hkv),
        in_specs=in_specs,
        out_specs=[spec(dk, dv1), spec(1, dv1), spec(g, dv)],
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct((bsz, hkv, 1, dv1), p.dtype),
            jax.ShapeDtypeStruct((bsz, hkv, g, dv), q.dtype),
        ],
        # the state is read, rank-1-updated, and rewritten in one pass;
        # donating it makes the update truly in place (no arena copy)
        input_output_aliases={0: 0, 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gla_decode_fused" if gated else "linear_decode_fused",
    )(*args)
    return s_new, p_new.reshape(p.shape), og.reshape(bsz, h, dv)


def la_decode_fused_pallas(s, p, q, k, v, a: float, b: float,
                           interpret: bool = False):
    """One fused linear-attention decode step.

    s: (B, Hkv, Dk, Dv+1) f32 state; p: (B, Hkv, Dv+1) f32 normalizer;
    q: (B, H, Dk); k, v: (B, Hkv, D).  Returns (s_new, p_new, o) with
    o: (B, H, Dv) in q.dtype — already divided, nothing left to do.
    """
    return _recurrent_decode_call(s.astype(F32), p.astype(F32),
                                  q, k, v, None, a, b, interpret)


def gla_decode_fused_pallas(s, p, q, k, v, log_decay, a: float, b: float,
                            interpret: bool = False):
    """One fused decay-gated (GLA) decode step.

    Same contract as `la_decode_fused_pallas` plus log_decay: (B, Hkv)
    per-step log gate; the kernel applies gamma = exp(log_decay) to the
    state before the rank-1 update.
    """
    return _recurrent_decode_call(s.astype(F32), p.astype(F32),
                                  q, k, v, log_decay, a, b, interpret)


# ---------------------------------------------------------------------------
# Softmax (contiguous cache): online softmax + finalize + GQA head-fold
# ---------------------------------------------------------------------------

def _softmax_fused_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, scale: float,
                          nblk: int, bk: int):
    bi = pl.program_id(0)
    blk = pl.program_id(2)
    length = len_ref[bi]

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # blocks at or past the slot's frontier were clamped in the index
    # map (no DMA) and contribute nothing — skip their compute
    @pl.when(blk * bk < length)
    def _step():
        q = q_ref[0, 0].astype(F32)                # (g, d)
        k = k_ref[0, 0].astype(F32)                # (bk, d)
        v = v_ref[0, 0].astype(F32)
        s = scale * jnp.dot(q, k.T, preferred_element_type=F32)  # (g, bk)
        jj = blk * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(jj < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = corr * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=F32)
        m_ref[...] = m_new

    @pl.when(blk == nblk - 1)
    def _finalize():
        # a length-0 (retired) slot accumulates l == 0; guard the
        # divide so it finalizes to zeros, not NaN
        l = l_ref[...]
        l_safe = jnp.where(l <= 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def softmax_decode_fused_pallas(q, k, v, lengths, block_k: int = _BK,
                                interpret: bool = False):
    """Fused contiguous-cache softmax decode.

    q: (B, H, 1, d); k, v: (B, Hkv, S, d); lengths: (B,) int32 valid
    keys per slot.  Grid (B, Hkv, ceil(S/block_k)): grouped query heads
    share one grid cell (each KV block streams once per KV head, not
    once per query head) and the finalize divide runs inside the last
    grid step.  A length-0 slot yields zeros (paged-family semantics).
    """
    b, h, nq, d = q.shape
    assert nq == 1, f"softmax_decode_fused is a decode kernel (nq={nq})"
    hkv, s_len = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    bk = max(1, min(block_k, s_len))
    pad = (-s_len) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nblk = (s_len + pad) // bk
    scale = 1.0 / d ** 0.5
    qg = q.reshape(b, hkv, g, d)

    def kv_index(bi, hi, blk, lens):
        # clamp the walk at the slot's last populated block: iterations
        # past it keep the same block index, so no new DMA is issued
        frontier = jnp.maximum(lens[bi] - 1, 0) // bk
        return (bi, hi, jnp.minimum(blk, frontier), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bi, hi, blk, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bi, hi, blk, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), F32),
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, 1), F32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_softmax_fused_kernel, scale=scale, nblk=nblk,
                          bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="softmax_decode_fused",
    )(lengths.astype(jnp.int32), qg, k, v)
    return o.reshape(b, h, 1, d)


# ---------------------------------------------------------------------------
# Paged KV: the page walk of kernels/paged_attention.py, head-folded
# ---------------------------------------------------------------------------

def _paged_fused_kernel(pt_ref, len_ref, q_ref, *refs, scale: float,
                        nblk: int, ppb: int):
    kv_refs, o_ref = refs[:2 * ppb], refs[2 * ppb]
    acc_ref, m_ref, l_ref = refs[2 * ppb + 1:]
    bi = pl.program_id(0)
    blk = pl.program_id(2)
    length = len_ref[bi]
    ps = kv_refs[0].shape[2]

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    for j in range(ppb):
        pi = blk * ppb + j
        k_ref, v_ref = kv_refs[2 * j], kv_refs[2 * j + 1]

        @pl.when(pi * ps < length)
        def _step(k_ref=k_ref, v_ref=v_ref, pi=pi):
            q = q_ref[0, 0].astype(F32)            # (g, d)
            k = k_ref[0, 0].astype(F32)            # (ps, d)
            v = v_ref[0, 0].astype(F32)
            s = scale * jnp.dot(q, k.T,
                                preferred_element_type=F32)  # (g, ps)
            jj = pi * ps + lax.broadcasted_iota(jnp.int32, (1, ps), 1)
            s = jnp.where(jj < length, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = corr * l_ref[...] + p.sum(axis=1, keepdims=True)
            acc_ref[...] = corr * acc_ref[...] + jnp.dot(
                p, v, preferred_element_type=F32)
            m_ref[...] = m_new

    @pl.when(blk == nblk - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l <= 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_decode_fused_pallas(q, k_pages, v_pages, page_table, lengths,
                              pages_per_block: int = _PPB,
                              interpret: bool = False):
    """Fused paged-KV decode; same contract as paged_attention_pallas.

    The grid is (B, Hkv, Pmax/ppb) — the GQA head-fold: each arena page
    is DMA'd once per KV head and scored against all `group` query
    heads in that cell, vs once per QUERY head in the unfused kernel.
    The finalize divide stays in the epilogue as before.
    """
    b, h, nq, d = q.shape
    assert nq == 1, f"paged_decode_fused is a decode kernel (nq={nq})"
    hkv, ps = k_pages.shape[1], k_pages.shape[2]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    pmax = page_table.shape[1]
    ppb = max(1, min(pages_per_block, pmax))
    nblk = -(-pmax // ppb)
    scale = 1.0 / d ** 0.5
    qg = q.reshape(b, hkv, g, d)

    def kv_index_for(j):
        def kv_index(bi, hi, blk, pt, lens):
            frontier = jnp.maximum(lens[bi] - 1, 0) // ps
            pi = jnp.minimum(blk * ppb + j, frontier)
            return (pt[bi, pi], hi, 0, 0)
        return kv_index

    kv_specs = []
    for j in range(ppb):
        kv_specs += [pl.BlockSpec((1, 1, ps, d), kv_index_for(j)),
                     pl.BlockSpec((1, 1, ps, d), kv_index_for(j))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bi, hi, blk, pt, lens: (bi, hi, 0, 0)),
            *kv_specs,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bi, hi, blk, pt, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), F32),
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, 1), F32),
        ],
    )
    kv_args = []
    for _ in range(ppb):
        kv_args += [k_pages, v_pages]
    o = pl.pallas_call(
        functools.partial(_paged_fused_kernel, scale=scale, nblk=nblk,
                          ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_fused",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, *kv_args)
    return o.reshape(b, h, 1, d)
