"""Full model assembly: embed -> scan over blocks -> norm -> unembed.

Covers every assigned architecture family:
  dense / moe / vlm   — decoder-only LM (attention or MLA mixer, MoE FFN)
  ssm                 — pure Mamba-2 stack
  hybrid              — zamba2: groups of mamba layers + one SHARED
                        attention block re-applied between groups
  encdec              — whisper: bidirectional encoder + cross-attn decoder

Layers are scanned with stacked params (compact HLO for 60+ layer archs);
cfg.remat wraps the scan body in jax.checkpoint.  The training loss is a
sequence-chunked cross-entropy that never materializes (B, N, V) logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.act_sharding import BATCH, MODEL, constrain
from repro.models import blocks as blk
from repro.models.common import dtype_of, embed_init, embed_lookup, \
    norm_apply, norm_init, sinusoid_positions, dense, dense_init, unembed

F32 = jnp.float32


def _stack_init(init_fn, key, num: int):
    """vmap an init over `num` layer keys -> params stacked on axis 0."""
    return jax.vmap(init_fn)(jax.random.split(key, num))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg, key):
    pd = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    params = {"embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, pd),
              "ln_f": norm_init(cfg.d_model, cfg.norm, pd)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size,
                                       dtype=pd)

    if cfg.family == "encdec":
        params["enc_blocks"] = _stack_init(
            lambda k: blk.enc_block_init(k, cfg, pd), ks[2],
            cfg.encoder_layers)
        params["ln_enc"] = norm_init(cfg.d_model, cfg.norm, pd)
        params["dec_blocks"] = _stack_init(
            lambda k: blk.xdec_block_init(k, cfg, pd), ks[3], cfg.num_layers)
        return params

    if cfg.family == "hybrid":
        g, m, t = cfg.hybrid_groups, cfg.hybrid_mamba_per_group, \
            cfg.hybrid_tail
        params["mamba_groups"] = _stack_init(
            lambda k: _stack_init(lambda k2: blk.block_init(k2, cfg, pd),
                                  k, m), ks[2], g)
        params["shared_attn"] = blk.block_init(
            ks[3], _attn_variant(cfg), pd)
        if t:
            params["tail"] = _stack_init(
                lambda k: blk.block_init(k, cfg, pd), ks[4], t)
        return params

    n_prefix = cfg.moe.first_dense_layers if cfg.moe else 0
    if n_prefix:
        params["prefix_blocks"] = [
            blk.block_init(k, cfg, pd, dense_ffn=True)
            for k in jax.random.split(ks[2], n_prefix)]
    params["blocks"] = _stack_init(
        lambda k: blk.block_init(k, cfg, pd), ks[3],
        cfg.num_layers - n_prefix)
    return params


def _attn_variant(cfg):
    """Config view for zamba2's shared attention block (attention mixer)."""
    import dataclasses
    return dataclasses.replace(cfg, mixer="attention", moe=None)


# ---------------------------------------------------------------------------
# Forward (training) — returns final hidden + aux loss
# ---------------------------------------------------------------------------

def _positions(cfg, batch, tokens):
    if "positions" in batch:
        return batch["positions"]
    b, n = tokens.shape
    return jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _scan_blocks(blocks, cfg, x, positions, compute_dtype):
    def body(carry, layer_params):
        h, aux = carry
        y, aux_i = blk.block_apply(layer_params, cfg, h, positions,
                                   compute_dtype)
        y = constrain(y, BATCH, None, None)
        return (y, aux + aux_i.astype(F32)), None

    (x, aux), _ = lax.scan(_maybe_remat(body, cfg), (x, F32(0.0)), blocks)
    return x, aux


def forward_hidden(params, cfg, batch):
    """batch: {"tokens": (B, N) int32, ...}.  Returns (hidden, aux_loss)."""
    cdt = dtype_of(cfg.compute_dtype)
    tokens = batch["tokens"]
    positions = _positions(cfg, batch, tokens)
    x = constrain(embed_lookup(params["embed"], tokens, cdt),
                  BATCH, None, None)
    aux = F32(0.0)

    if cfg.family == "encdec":
        enc = batch["frames"].astype(cdt)
        enc = enc + sinusoid_positions(enc.shape[1], cfg.d_model).astype(cdt)

        def enc_body(h, lp):
            return constrain(blk.enc_block_apply(lp, cfg, h, cdt),
                             BATCH, None, None), None
        enc, _ = lax.scan(_maybe_remat(enc_body, cfg), enc,
                          params["enc_blocks"])
        enc = norm_apply(params["ln_enc"], enc, cfg.norm)

        x = x + sinusoid_positions(x.shape[1], cfg.d_model).astype(cdt)

        def dec_body(h, lp):
            return constrain(
                blk.xdec_block_apply(lp, cfg, h, enc, positions, cdt),
                BATCH, None, None), None
        x, _ = lax.scan(_maybe_remat(dec_body, cfg), x,
                        params["dec_blocks"])

    elif cfg.family == "hybrid":
        def group_body(carry, group_params):
            h, aux_c = carry
            def inner(c2, lp):
                y, a = blk.block_apply(lp, cfg, c2[0], positions, cdt)
                y = constrain(y, BATCH, None, None)
                return (y, c2[1] + a.astype(F32)), None
            (h, aux_c), _ = lax.scan(_maybe_remat(inner, cfg), (h, aux_c),
                                     group_params)
            h, a = blk.block_apply(params["shared_attn"], _attn_variant(cfg),
                                   h, positions, cdt)
            h = constrain(h, BATCH, None, None)
            return (h, aux_c + a.astype(F32)), None
        # remat at the group level too: the shared attention block's
        # internals must not be stashed for all 13 group applications
        (x, aux), _ = lax.scan(_maybe_remat(group_body, cfg), (x, aux),
                               params["mamba_groups"])
        if "tail" in params:
            x, a = _scan_blocks(params["tail"], cfg, x, positions, cdt)
            aux = aux + a

    else:
        if cfg.rope_kind == "sinusoid":
            x = x + sinusoid_positions(x.shape[1], cfg.d_model).astype(cdt)
        for lp in params.get("prefix_blocks", []):
            x, a = blk.block_apply(lp, cfg, x, positions, cdt)
            aux = aux + a.astype(F32)
        x, a = _scan_blocks(params["blocks"], cfg, x, positions, cdt)
        aux = aux + a

    return norm_apply(params["ln_f"], x, cfg.norm), aux


def _unembed_weight(params, cfg):
    """(d_model, vocab) in f32 for the loss."""
    with jax.named_scope("unembed"):
        if cfg.tie_embeddings:
            return params["embed"]["table"].astype(F32).T
        return params["lm_head"]["w"].astype(F32)


def forward_logits(params, cfg, batch):
    """Full logits — small-scale use only (examples, decode)."""
    hidden, _ = forward_hidden(params, cfg, batch)
    with jax.named_scope("unembed"):
        if cfg.tie_embeddings:
            logits = unembed(params["embed"], hidden.astype(F32))
        else:
            logits = dense(params["lm_head"], hidden, F32)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(
                logits / cfg.logit_softcap)
        return logits


# ---------------------------------------------------------------------------
# Loss — sequence-chunked cross-entropy (never materializes (B, N, V))
# ---------------------------------------------------------------------------

@jax.named_scope("unembed")
def chunked_cross_entropy(hidden, w, labels, mask, chunk: int = 512):
    """hidden: (B, N, d); w: (d, V) f32; labels/mask: (B, N).

    Scans over N in chunks; jax.checkpoint on the body keeps only chunk
    inputs as residuals so the backward recomputes per-chunk logits.
    """
    b, n, d = hidden.shape
    c = min(chunk, n)
    t = -(-n // c)
    n_pad = t * c
    if n_pad != n:
        hidden = jnp.pad(hidden, [(0, 0), (0, n_pad - n), (0, 0)])
        labels = jnp.pad(labels, [(0, 0), (0, n_pad - n)])
        mask = jnp.pad(mask, [(0, 0), (0, n_pad - n)])
    h_c = jnp.moveaxis(hidden.reshape(b, t, c, d), 1, 0)
    y_c = jnp.moveaxis(labels.reshape(b, t, c), 1, 0)
    m_c = jnp.moveaxis(mask.reshape(b, t, c), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        loss_sum, count = carry
        h, y, m = inp
        logits = jnp.einsum("bcd,dv->bcv", h.astype(F32), w,
                            preferred_element_type=F32)
        logits = constrain(logits, BATCH, None, MODEL)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        loss_sum = loss_sum + jnp.sum((logz - ll) * m)
        count = count + jnp.sum(m)
        return (loss_sum, count), None

    (loss_sum, count), _ = lax.scan(body, (F32(0.0), F32(0.0)),
                                    (h_c, y_c, m_c))
    return loss_sum / jnp.maximum(count, 1.0)


def loss_fn(params, cfg, batch):
    """Next-token CE + MoE aux.  batch needs "tokens" (+family extras)."""
    hidden, aux = forward_hidden(params, cfg, batch)
    tokens = batch["tokens"]
    labels = jnp.pad(tokens[:, 1:], [(0, 0), (0, 1)])
    mask = jnp.ones_like(tokens, F32).at[:, -1].set(0.0)
    if "loss_mask" in batch:
        mask = mask * batch["loss_mask"].astype(F32)
    w = _unembed_weight(params, cfg)
    ce = chunked_cross_entropy(hidden, w, labels, mask)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    return ce + aux_w * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def _zeros_like_struct(struct):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def _stacked_cache(cfg, num: int, batch: int, max_len: int, kind="block",
                   dtype=jnp.bfloat16):
    one = (blk.block_init_cache(cfg, batch, max_len, dtype) if kind == "block"
           else {"self": blk.block_init_cache(cfg, batch, max_len, dtype),
                 "cross": None})
    return jax.tree.map(
        lambda x: jnp.zeros((num,) + x.shape, x.dtype), one)


def init_cache(cfg, batch: int, max_len: int, dtype=None):
    """Decode cache for the whole model (+ position counter)."""
    if dtype is None:
        dtype = dtype_of(cfg.compute_dtype)
    if cfg.family == "encdec":
        from repro.mixers.cache import CrossState
        hd = cfg.resolved_head_dim
        hkv = cfg.num_kv_heads
        self_c = _stacked_cache(cfg, cfg.num_layers, batch, max_len,
                                dtype=dtype)
        cross = CrossState(
            s=jnp.zeros((cfg.num_layers, batch, hkv, hd, hd + 1), F32),
            p=jnp.zeros((cfg.num_layers, batch, hkv, hd + 1), F32))
        return {"self": self_c, "cross": cross,
                "pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "hybrid":
        g, m, t = cfg.hybrid_groups, cfg.hybrid_mamba_per_group, \
            cfg.hybrid_tail
        one_m = blk.block_init_cache(cfg, batch, max_len, dtype)
        acfg = _attn_variant(cfg)
        one_a = blk.block_init_cache(acfg, batch, max_len, dtype)
        cache = {
            "mamba": jax.tree.map(
                lambda x: jnp.zeros((g, m) + x.shape, x.dtype), one_m),
            "shared": jax.tree.map(
                lambda x: jnp.zeros((g,) + x.shape, x.dtype), one_a),
            "pos": jnp.zeros((batch,), jnp.int32)}
        if t:
            cache["tail"] = jax.tree.map(
                lambda x: jnp.zeros((t,) + x.shape, x.dtype), one_m)
        return cache
    n_prefix = cfg.moe.first_dense_layers if cfg.moe else 0
    cache = {"blocks": _stacked_cache(cfg, cfg.num_layers - n_prefix,
                                      batch, max_len, dtype=dtype),
             "pos": jnp.zeros((batch,), jnp.int32)}
    if n_prefix:
        cache["prefix"] = [blk.block_init_cache(cfg, batch, max_len, dtype)
                           for _ in range(n_prefix)]
    if cfg.rope_kind == "mrope":
        # next rope position value per sequence (can lag the token count
        # because image patches share t/h/w grid positions)
        cache["rope_pos"] = jnp.zeros((batch,), jnp.int32)
    return cache


def prefill(params, cfg, batch, cache):
    """Run a prompt (or a continuation window of one) against `cache`;
    returns (last-token logits (B, V), cache).

    Positions and the pos counter CONTINUE from cache["pos"], so
    chunked prefill (feeding the prompt window by window, carrying the
    recurrent state) is exact — see train/step.py::build_prefill_step.
    """
    cdt = dtype_of(cfg.compute_dtype)
    tokens = batch["tokens"]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        b_, n_ = tokens.shape
        positions = (cache["pos"][:, None]
                     + jnp.arange(n_, dtype=jnp.int32)[None])
    x = embed_lookup(params["embed"], tokens, cdt)

    if cfg.family == "encdec":
        enc = batch["frames"].astype(cdt)
        enc = enc + sinusoid_positions(enc.shape[1], cfg.d_model).astype(cdt)

        def enc_body(h, lp):
            return blk.enc_block_apply(lp, cfg, h, cdt), None
        enc, _ = lax.scan(enc_body, enc, params["enc_blocks"])
        enc = norm_apply(params["ln_enc"], enc, cfg.norm)
        x = x + sinusoid_positions(x.shape[1], cfg.d_model).astype(cdt)
        # NOTE: whisper prefill is single-shot (cross-attn state is
        # precomputed here); chunked prefill targets decoder-only archs

        def dec_body(h, inp):
            lp, lc = inp
            y, nc = blk.xdec_block_prefill(lp, cfg, h, enc, positions,
                                           {"self": lc, "cross": None}, cdt)
            return y, (nc["self"], nc["cross"])
        x, (self_c, cross_c) = lax.scan(
            dec_body, x, (params["dec_blocks"], cache["self"]))
        new_cache = {"self": self_c, "cross": cross_c,
                     "pos": cache["pos"] + tokens.shape[1]}

    elif cfg.family == "hybrid":
        def group_body(h, inp):
            gp, gc_m, gc_a = inp
            def inner(h2, inp2):
                lp, lc = inp2
                y, nc = blk.block_prefill(lp, cfg, h2, positions, lc, cdt)
                return y, nc
            h, nc_m = lax.scan(inner, h, (gp, gc_m))
            h, nc_a = blk.block_prefill(params["shared_attn"],
                                        _attn_variant(cfg), h, positions,
                                        gc_a, cdt)
            return h, (nc_m, nc_a)
        x, (m_c, a_c) = lax.scan(
            group_body, x,
            (params["mamba_groups"], cache["mamba"], cache["shared"]))
        new_cache = {"mamba": m_c, "shared": a_c,
                     "pos": cache["pos"] + tokens.shape[1]}
        if "tail" in params:
            def tail_body(h, inp):
                lp, lc = inp
                y, nc = blk.block_prefill(lp, cfg, h, positions, lc, cdt)
                return y, nc
            x, t_c = lax.scan(tail_body, x, (params["tail"], cache["tail"]))
            new_cache["tail"] = t_c

    else:
        if cfg.rope_kind == "sinusoid":
            x = x + sinusoid_positions(x.shape[1], cfg.d_model).astype(cdt)
        new_cache = {"pos": cache["pos"] + tokens.shape[1]}
        if "prefix_blocks" in params:
            new_cache["prefix"] = []
            for lp, lc in zip(params["prefix_blocks"], cache["prefix"]):
                x, nc = blk.block_prefill(lp, cfg, x, positions, lc, cdt)
                new_cache["prefix"].append(nc)

        def body(h, inp):
            lp, lc = inp
            y, nc = blk.block_prefill(lp, cfg, h, positions, lc, cdt)
            return y, nc
        x, b_c = lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = b_c
        if cfg.rope_kind == "mrope":
            new_cache["rope_pos"] = (
                positions[:, :, -1].max(axis=0) + 1).astype(jnp.int32)

    x = norm_apply(params["ln_f"], x[:, -1:], cfg.norm)
    logits = _last_logits(params, cfg, x)
    return logits, new_cache


@jax.named_scope("unembed")
def _last_logits(params, cfg, x_last):
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x_last.astype(F32))
    else:
        logits = dense(params["lm_head"], x_last, F32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits[:, 0]


def _sinusoid_at(pos, d: int):
    """Per-sequence sinusoidal embedding at positions pos (B,) -> (B, 1, d)."""
    inv = jnp.exp(-jnp.arange(0, d, 2, dtype=F32) / d * jnp.log(10000.0))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)[:, None]


def decode_step(params, cfg, cache, tokens):
    """tokens: (B,) int32 — one new token per sequence.

    With the paper's linear backend this is O(D^2) per head regardless of
    context length (the cache is the recurrent state).
    """
    cdt = dtype_of(cfg.compute_dtype)
    b = tokens.shape[0]
    pos = cache["pos"]  # (B,) — slots may be at different depths
    position = pos[:, None].astype(jnp.int32)
    if cfg.rope_kind == "mrope":
        # text decode: all three streams advance together from rope_pos
        position = jnp.broadcast_to(cache["rope_pos"][None, :, None],
                                    (3, b, 1))
    x = embed_lookup(params["embed"], tokens[:, None], cdt)

    if cfg.family == "encdec":
        x = x + _sinusoid_at(pos, cfg.d_model).astype(cdt)

        def body(h, inp):
            lp, lc_self, lc_cross = inp
            y, nc = blk.xdec_block_decode(
                lp, cfg, h, position,
                {"self": lc_self, "cross": lc_cross}, cdt)
            return y, (nc["self"], nc["cross"])
        x, (self_c, cross_c) = lax.scan(
            body, x, (params["dec_blocks"], cache["self"], cache["cross"]))
        new_cache = {"self": self_c, "cross": cross_c, "pos": pos + 1}

    elif cfg.family == "hybrid":
        def group_body(h, inp):
            gp, gc_m, gc_a = inp
            def inner(h2, inp2):
                lp, lc = inp2
                y, nc = blk.block_decode(lp, cfg, h2, position, lc, cdt)
                return y, nc
            h, nc_m = lax.scan(inner, h, (gp, gc_m))
            h, nc_a = blk.block_decode(params["shared_attn"],
                                       _attn_variant(cfg), h, position,
                                       gc_a, cdt)
            return h, (nc_m, nc_a)
        x, (m_c, a_c) = lax.scan(
            group_body, x,
            (params["mamba_groups"], cache["mamba"], cache["shared"]))
        new_cache = {"mamba": m_c, "shared": a_c, "pos": pos + 1}
        if "tail" in params:
            def tail_body(h, inp):
                lp, lc = inp
                y, nc = blk.block_decode(lp, cfg, h, position, lc, cdt)
                return y, nc
            x, t_c = lax.scan(tail_body, x, (params["tail"], cache["tail"]))
            new_cache["tail"] = t_c

    else:
        if cfg.rope_kind == "sinusoid":
            x = x + _sinusoid_at(pos, cfg.d_model).astype(cdt)
        new_cache = {"pos": pos + 1}
        if "prefix_blocks" in params:
            new_cache["prefix"] = []
            for lp, lc in zip(params["prefix_blocks"], cache["prefix"]):
                x, nc = blk.block_decode(lp, cfg, x, position, lc, cdt)
                new_cache["prefix"].append(nc)

        def body(h, inp):
            lp, lc = inp
            y, nc = blk.block_decode(lp, cfg, h, position, lc, cdt)
            return y, nc
        x, b_c = lax.scan(body, x, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = b_c
        if cfg.rope_kind == "mrope":
            new_cache["rope_pos"] = cache["rope_pos"] + 1

    x = norm_apply(params["ln_f"], x, cfg.norm)
    return _last_logits(params, cfg, x), new_cache
