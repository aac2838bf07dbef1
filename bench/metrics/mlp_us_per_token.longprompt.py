"""mlp_us_per_token.longprompt: device time of the operations in the
model's `mlp` scope (the FFN of every layer) in the traced window, per
token the engine ran through the model there: the `tokens` of its
`engine.prefill` and `engine.complete` spans plus the `slots` of its
`engine.decode` spans, in us."""
from bench import progtrace


def read(ctx):
    prog = progtrace.of(ctx)
    took = progtrace.scope_seconds(prog["ops"], "mlp")
    spans = prog["spans"]
    tokens = progtrace.count(spans, ("engine.prefill", "engine.complete"),
                             "tokens") \
        + progtrace.count(spans, ("engine.decode",), "slots")
    if took is None or not tokens:
        return None
    return 1e6 * took / tokens
