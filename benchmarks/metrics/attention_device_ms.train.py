"""Device time per update of the attention core in all passes: the
``attention_core`` scope (scores, softmax, dropout, context on the XLA path;
the layout changes, the row sums and the bias reduction round the kernels on
the Pallas path) and the ``flash_*`` kernels themselves. The XLA path's
``attention_dropout`` lies inside the scope and is counted here as well as in
``dropout_device_ms.train``."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.device_ms(ctx, "by_part", "attention_core",
                            "attention_dropout")
