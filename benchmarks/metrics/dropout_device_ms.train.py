"""Device time per update of the ops named for dropout (``Dropout_*``,
``dropout``, ``attention_dropout``, the bernoulli draw, ``rng-bit-generator``).
A lower bound: a mask multiply fused into its neighbour carries the
neighbour's name, and the flash kernel draws its own masks inside itself."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.device_ms(ctx, "by_part", "dropout", "attention_dropout")
