"""A serving cell: the program's Engine, driven by the mix's traffic.

Set-up draws the weights, builds the Engine and runs one request per
prefill-window program of the mix (so every program the window drives
is compiled before it), then the traffic runs for `preroll_s` so that
the window starts from a loaded engine.  Open loop: requests arrive on
the seeded schedule whether or not the engine keeps up, and each is
timed from when it was due.  Closed loop: a fixed pool of clients, each
sending its next request when its last one finishes.

After the window, with the program's memory peak read and its state
freed, the reference runs over a seeded sample of the requests the
window finished, greedy and sampled apart (the longest of each kind
among them), prompt and served tokens together.  For a greedy token it
reads how far below the reference's best logit the token lies; for a
sampled one, whether it lies inside the reference's nucleus and how
far its logit lies from the mean of the reference's tempered nucleus
(`compare.serve_checks` turns these into the numbers compared).
"""
from __future__ import annotations

import collections
import functools
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, generator, harness, spec, weights
from bench.harness import now, span
from bench.reference import model as ref

WARM_RID = 1 << 30


# a fault planted in what the engine is asked to sample (for the
# benchmark's tests): the nucleus left out
SAMPLER_FAULTS = {"top_p": {"top_p": 1.0}}


def _sampling(traffic: dict, r, fault=None):
    """Greedy, or the mix's sampling with the request's own seed."""
    from repro.serve.sampling import SamplingParams
    s = traffic["sampling"]
    if r.greedy:
        return SamplingParams(temperature=0.0)
    asked = dict(temperature=s["temperature"], top_p=s.get("top_p", 1.0))
    asked.update(SAMPLER_FAULTS.get(fault, {}))
    return SamplingParams(seed=r.sample_seed, **asked)


def build_engine(cfg, traffic, params, tracer=None):
    """The engine's own seed stays 0: the engine compiles its root PRNG
    key into its completion programs, so a seed per run would compile
    them anew in every run.  Requests carry their seeds instead."""
    from repro.serve.engine import Engine
    from repro.serve.scheduler import FixedSlots
    e = traffic["engine"]
    return Engine(spec.model_config(cfg), params,
                  policy=FixedSlots(e["slots"]), max_len=e["max_len"],
                  eos_id=-1, seed=0, prefill_chunk=e["prefill_chunk"],
                  kernel_backend=cfg["attention"]["kernel"], tracer=tracer)


def warm_up(engine, traffic, vocab: int) -> None:
    """One request per window program of the mix, sampled, two tokens
    each: every program the window drives runs once."""
    from repro.serve.engine import Request
    for j, n in enumerate(generator.warmup_prompts(traffic)):
        r = generator.Request(WARM_RID + j, [j % vocab] * n, 2,
                              greedy=False, sample_seed=j)
        engine.submit(Request(rid=r.index, prompt=r.prompt,
                              max_new_tokens=2, sampling=_sampling(traffic,
                                                                   r)))
    engine.run()


def _alter_decoded_tokens(engine, vocab: int) -> None:
    """Fault: the decode step's token of every slot is replaced by the
    next id where it is produced, before the engine sees it."""
    decode = engine._decode

    def altered(*args):
        toks, cache, keys = decode(*args)
        return (toks + 1) % vocab, cache, keys

    engine._decode = altered


class Book:
    """What the benchmark sees of each request, on its own clock."""

    def __init__(self, fault=None):
        self.fault = fault                # a sampler fault, or None
        self.due = {}                     # rid -> due time (host clock)
        self.greedy = {}
        self.prompt = {}
        self.tokens = collections.defaultdict(list)
        self.times = collections.defaultdict(list)
        self.finished = {}                # rid -> finish time
        self.lateness = []

    def submit(self, engine, traffic, r, due: float):
        from repro.serve.engine import Request
        t = now()
        with span("Engine.submit"):
            engine.submit(Request(rid=r.index, prompt=r.prompt,
                                  max_new_tokens=r.max_new,
                                  sampling=_sampling(traffic, r,
                                                     self.fault)))
        self.due[r.index] = due
        self.greedy[r.index] = r.greedy
        self.prompt[r.index] = r.prompt
        self.lateness.append((due, t - due))

    def step(self, engine):
        with span("Engine.step"):
            outs = engine.step()
        t = now()
        emitted = 0
        for o in outs:
            if o.token is not None:
                self.tokens[o.rid].append(o.token)
                self.times[o.rid].append(t)
                emitted += 1
            if o.finished:
                self.finished[o.rid] = t
        return outs, t, emitted


def run(run_ctx, fault=None) -> dict:
    from repro.models import model as mdl
    from repro.obs.events import ServeTracer

    cfg, traffic, seed = run_ctx.cfg, run_ctx.traffic, run_ctx.seed
    vocab = cfg["vocab_size"]
    t0 = now()
    mc = spec.model_config(cfg)
    weights.check_layout(cfg, jax.eval_shape(
        lambda: mdl.init_params(mc, jax.random.PRNGKey(0))))
    params = weights.make_fn(cfg)(weights.seed_key(seed))
    jax.block_until_ready(params)
    parts = {"weights_s": now() - t0}
    tracer = ServeTracer() if run_ctx.trace else None
    engine = build_engine(cfg, traffic, params, tracer)
    if fault == "token":
        _alter_decoded_tokens(engine, vocab)
    parts["engine_s"] = now() - t0 - parts["weights_s"]
    warm_up(engine, traffic, vocab)
    parts["warm_up_s"] = now() - t0 - parts["weights_s"] - parts["engine_s"]
    if tracer is not None:
        tracer.reset()

    book = Book(fault if fault in SAMPLER_FAULTS else None)
    open_loop = traffic["loop"] == "open"
    preroll, seconds = traffic["preroll_s"], run_ctx.seconds
    if open_loop:
        reqs = generator.open_loop(traffic, vocab, seed,
                                   preroll + seconds + 30.0)
    else:
        closed = generator.ClosedLoop(traffic, vocab, seed)
    t_arrivals = now()
    nxt = 0

    def pump():
        nonlocal nxt
        t = now()
        if open_loop:
            while nxt < len(reqs) and t_arrivals + reqs[nxt].due <= t:
                book.submit(engine, traffic, reqs[nxt],
                            t_arrivals + reqs[nxt].due)
                nxt += 1
            return (t_arrivals + reqs[nxt].due) if nxt < len(reqs) else None
        return None

    def refill(outs):
        if not open_loop:
            for o in outs:
                if o.finished:
                    book.submit(engine, traffic, closed.next(), now())

    if not open_loop:
        for _ in range(traffic["clients"]):
            book.submit(engine, traffic, closed.next(), now())

    def advance(until: float):
        """Serve until the host clock passes `until`; returns per-step
        (start, end, prefill tokens, decode tokens, tokens emitted)."""
        steps = []
        while True:
            t = now()
            if t >= until:
                return steps
            next_due = pump()
            if engine.scheduler.has_work():
                outs, t1, emitted = book.step(engine)
                budget = engine.last_step_budget
                steps.append((t, t1, budget["prefill"], budget["decode"],
                              emitted))
                refill(outs)
            else:
                with span("wait_arrival"):
                    wake = until if next_due is None else min(until, next_due)
                    time.sleep(max(0.0, wake - now()))

    advance(t_arrivals + preroll)
    setup_s = now() - t0
    parts["preroll_s"] = now() - t_arrivals
    if tracer is not None:
        tracer.reset()

    lowered0 = run_ctx.counter.snapshot()
    watch = harness.HostWatch()
    watch.start()
    t_start = now()
    steps = advance(t_start + seconds)
    t_end = now()
    host = watch.stop()
    queue_at_close = len(engine.scheduler.queue)
    compiles = [b - a for a, b in zip(lowered0, run_ctx.counter.snapshot())]
    window_s = t_end - t_start
    served = sum(p + e for _, _, p, _, e in steps)

    # requests due in the window: every one has to show a first token
    due = [rid for rid, d in book.due.items() if t_start <= d < t_end] \
        if open_loop else []
    deadline = t_end + 60.0
    while open_loop and now() < deadline and \
            any(not book.times[r] for r in due):
        advance(now() + 0.05)
    profile, traced = None, None
    if run_ctx.trace:
        # the profiler runs after the window, on the same traffic, so
        # that writing the trace out stalls nothing the window measures
        profile = harness.Profile(run_ctx.cell)
        profile.start()
        traced = _traced_counts(advance(now() + traffic["trace_seconds"]),
                                profile, engine)
    peak = harness.memory_peak(run_ctx.devices)
    span_stats = _tracer_stats(tracer, t_start, t_end)

    e2e = {"serve_tokens_per_s": served / window_s}
    failed = 0
    if open_loop:
        ttft = []
        for r in due:
            if book.times[r]:
                ttft.append((book.times[r][0] - book.due[r]) * 1e3)
            else:
                failed += 1
                ttft.append(float("inf"))
        gaps = [(b - a) * 1e3 for r, ts in book.times.items()
                for a, b in zip(ts, ts[1:]) if t_start <= b <= t_end]
        e2e["itl_p95_ms"] = _p95(gaps)
        attempted = len(due)
        side_extra = {"ttft_p50_ms": float(np.median(ttft)) if ttft else None,
                      "ttft_p95_ms": _p95(ttft),
                      "itl_p50_ms": float(np.median(gaps)) if gaps else None,
                      "itl_gaps": len(gaps)}
    else:
        attempted = sum(1 for d in book.due.values() if t_start <= d < t_end)
        side_extra = {}
    late = [x * 1e3 for d, x in book.lateness if t_start <= d < t_end]
    side = {**side_extra, **parts, "requests_due": attempted,
            "requests_finished": sum(1 for t in book.finished.values()
                                     if t_start <= t <= t_end),
            "steps": len(steps),
            # host time in the window outside submitting and stepping
            "outside_steps_s": window_s - sum(b - a for a, b, *_ in steps),
            "host": {**host, **harness.timeline(steps, t_start)},
            "generator_late_p95_ms": _p95(late),
            "generator_late_max_ms": max(late) if late else None,
            "served_tokens": served, "queue_at_close": queue_at_close}

    check = traffic["check"]
    sample = {kind: _sample(book, t_end, seed, check.get(kind, 0),
                            kind == "greedy")
              for kind in ("greedy", "sampled")}
    del engine, params
    gc.collect()
    t_ref = now()
    readings = reference_readings(cfg, traffic, seed, book, sample)
    side["reference_s"] = now() - t_ref
    side["tokens_compared"] = {k: len(v["gap"] if k == "greedy" else
                                      v["excess"])
                               for k, v in readings.items()}
    side["requests_compared"] = {k: len(v) for k, v in sample.items()}
    return {
        "setup_s": setup_s, "window_s": window_s,
        "attempted": attempted, "failed": failed, "e2e": e2e,
        "compiles_in_window": compiles, "memory_peak_bytes": peak,
        "profile": profile, "traced": traced, "side": side,
        "spans": span_stats,
        "checks": compare.serve_checks(readings, traffic,
                                       run_ctx.limits),
        "book": book, "sample": sample,
    }


def _p95(xs):
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), 95,
                               method="inverted_cdf"))


def _traced_counts(steps, profile, engine) -> dict:
    """Stops the profiler; what the engine did while it ran."""
    profile.stop()
    return {"steps": len(steps),
            "prefill_tokens": sum(s[2] for s in steps),
            "decode_tokens": sum(s[3] for s in steps),
            "emitted_tokens": sum(s[4] for s in steps),
            "decode_steps": sum(1 for s in steps if s[3]),
            "slots": engine.num_slots,
            "window_s": profile.t1 - profile.t0}


def _tracer_stats(tracer, t_start, t_end):
    """Prefill-window times of the program's own tracer (repro.obs),
    over the windows run inside the measured window."""
    if tracer is None:
        return None
    return {"prefill_window_ms": [
        (b - a) * 1e3 for r in tracer.records()
        for a, b, _ in r.prefill_windows if t_start <= a and b <= t_end]}


def _sample(book, t_end, seed, k: int, greedy: bool) -> list:
    """Requests of one kind (greedy or sampled) finished in the window:
    the one with the most tokens, and k - 1 more drawn from the seed."""
    done = sorted(r for r, t in book.finished.items()
                  if t <= t_end and book.greedy.get(r) == greedy)
    if not done or k < 1:
        return []
    longest = max(done, key=lambda r: len(book.prompt[r])
                  + len(book.tokens[r]))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), int(greedy)])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


# the reference's sequence is padded up to a multiple of this, so that a
# few programs serve every request of a mix
REF_BUCKET = 2048


def served_rows(traffic: dict) -> int:
    """Rows of served tokens the reference reads at once: the most a
    request of the mix can be given."""
    return max(generator.sizes(traffic["output"]))


def reference_logits_fn(cfg, precision="f32"):
    return _logits_fn(json.dumps(cfg, sort_keys=True), precision)


@functools.lru_cache(maxsize=None)
def _logits_fn(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, t, r: ref.logits_at(p, cfg, t, r, precision))


def _inputs(book, rid, t_pad):
    """The request's prompt and served tokens as one padded sequence,
    the rows whose logits chose each served token, and those tokens."""
    prompt, served = book.prompt[rid], book.tokens[rid]
    full = prompt + served[:-1]
    seq = np.zeros((-(-len(full) // REF_BUCKET) * REF_BUCKET,), np.int32)
    seq[:len(full)] = full
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    rows = np.concatenate([rows, np.full((t_pad - len(rows),), rows[-1])])
    toks = np.zeros((t_pad,), np.int32)
    toks[:len(served)] = served
    return jnp.asarray(seq), jnp.asarray(rows.astype(np.int32)), \
        jnp.asarray(toks), len(served)


ROW_BLOCK = 128


def _by_row_blocks(fn, *arrays):
    """fn over blocks of ROW_BLOCK rows (the sort of a whole vocabulary
    for every row at once would not fit beside the weights)."""
    n = arrays[0].shape[0]
    pad = -n % ROW_BLOCK
    blocks = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (-1, ROW_BLOCK) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks))
    return jax.tree.map(lambda y: y.reshape((-1,) + y.shape[2:])[:n], out)


def nucleus(logits, top_p):
    """Mask of the nucleus of each row: the tokens whose exclusive
    cumulative probability (at temperature 1, most likely first) is
    under top_p, the most likely always among them.  The temperature
    then shapes the draw inside it (the order of llama.cpp's samplers,
    and the program's)."""
    desc = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    kept = (jnp.cumsum(probs, axis=-1) - probs) < top_p
    kept = kept.at[:, 0].set(True)
    floor = jnp.min(jnp.where(kept, desc, jnp.inf), axis=-1)
    return logits >= floor[:, None]


def token_readings(logits, toks, temperature: float, top_p: float):
    """Per row of reference logits (R, V) and the token served there:
    gap: the best logit minus the token's (greedy);
    excess: the token's exclusive cumulative probability at temperature
    1 minus top_p (over 0: outside the nucleus);
    dev, var: the token's logit minus the mean logit of the reference's
    tempered nucleus, and that distribution's variance."""
    def block(lg, tk):
        got = jnp.take_along_axis(lg, tk[:, None], axis=-1)
        probs = jax.nn.softmax(lg, axis=-1)
        above = jnp.sum(jnp.where(lg > got, probs, 0.0), axis=-1)
        q = jax.nn.softmax(jnp.where(nucleus(lg, top_p), lg, -jnp.inf)
                           / max(temperature, 1e-6), axis=-1)
        mean = jnp.sum(q * lg, axis=-1)
        var = jnp.sum(q * (lg - mean[:, None]) ** 2, axis=-1)
        return {"gap": jnp.max(lg, axis=-1) - got[:, 0],
                "excess": above - top_p, "dev": got[:, 0] - mean,
                "var": var}
    return _by_row_blocks(block, logits, toks)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _readings_fn(logits, toks, temperature, top_p):
    return token_readings(logits, toks, temperature, top_p)


def _draw(logits, key, temperature: float, top_p: float):
    """Tokens drawn by a sampler with the nucleus and temperature given
    (the reference in the program's place, for calibration)."""
    def block(lg, keys):
        keep = nucleus(lg, top_p) if top_p < 1.0 else \
            jnp.ones_like(lg, bool)
        scaled = jnp.where(keep, lg, -jnp.inf) / max(temperature, 1e-6)
        return jax.vmap(jax.random.categorical)(keys, scaled)
    keys = jax.random.split(key, logits.shape[0])
    return _by_row_blocks(block, logits, keys).astype(jnp.int32)


_draw_fn = jax.jit(_draw, static_argnums=(2, 3))


def _stated(traffic: dict) -> tuple:
    s = traffic["sampling"]
    return float(s.get("temperature", 0.0)), float(s.get("top_p", 1.0))


def reference_readings(cfg, traffic, seed, book, sample, draw=None) -> dict:
    """Token readings of the sample: {"greedy": {"gap": [...]},
    "sampled": {"excess": [...], "dev": [...], "var": [...]}}, each
    list over every served token of the kind.  With `draw` (a sampler
    fault's (temperature, top_p) and a key), the sampled requests'
    tokens are those the reference draws with it at each position of
    the same prompts and served tokens: the fault planted in the
    reference in the program's place."""
    t_pad = served_rows(traffic)
    temperature, top_p = _stated(traffic)
    params = weights.make_fn(cfg)(weights.seed_key(seed))
    fn = reference_logits_fn(cfg)
    out = {"greedy": {"gap": []},
           "sampled": {"excess": [], "dev": [], "var": []}}
    for kind, rids in sample.items():
        for rid in rids:
            seq, rows, toks, n = _inputs(book, rid, t_pad)
            logits = fn(params, seq, rows)
            if draw is not None and kind == "sampled":
                (t_f, p_f), key = draw
                toks = _draw_fn(logits, jax.random.fold_in(key, rid),
                                t_f, p_f)
            r = jax.device_get(_readings_fn(logits, toks, temperature,
                                            top_p))
            for k in out[kind]:
                out[kind][k] += [float(x) for x in r[k][:n]]
    return out


def control_gaps(cfg, traffic, seed, book, rids, precision="fp8"):
    """The control: at each position of the same prompts and served
    tokens, the reference's gap of the token that the lower precision
    puts first."""
    t_pad = served_rows(traffic)
    params = weights.make_fn(cfg)(weights.seed_key(seed))
    fn, low = reference_logits_fn(cfg), reference_logits_fn(cfg, precision)
    gaps = []
    for rid in rids:
        seq, rows, _, n = _inputs(book, rid, t_pad)
        logits = fn(params, seq, rows)
        pick = jnp.argmax(low(params, seq, rows), axis=-1).astype(jnp.int32)
        r = jax.device_get(_readings_fn(logits, pick, 0.0, 1.0))
        gaps += [float(x) for x in r["gap"][:n]]
    return gaps
