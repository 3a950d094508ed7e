"""Device time per update of the indexer, in all passes: ``dsa_index_proj``
(its three projections and rotary), ``dsa_scores`` (every causal pair's score,
for the choice) and ``dsa_index_loss`` (the KL: the scores and the core's
probabilities made again, both softmaxes, and the backward to the three
indexer matrices)."""
from benchmarks.trace import scopes_keye


def read(ctx):
    return scopes_keye.device_ms(ctx, *scopes_keye.INDEXER_PARTS)
