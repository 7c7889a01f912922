"""Device milliseconds per decode program under the program's ``attn``
name scope (repro.models.attention.gqa_attention: projections, the K/V
row write and the attention over the cache, every layer)."""
from bench.metrics._scoped import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "attn")
