"""What an encoder layer keeps across rematerialization.

``remat='dots'`` keeps the outputs of dots without batch dimensions (the
projections and the FFN) and recomputes all else in the backward pass. That
rule goes by the KIND of primitive, and attention holds the values for which
it is most wrong: cheap to keep by the byte, the dearest in the layer to make
again. So the ops name them where they are made
(``jax.ad_checkpoint.checkpoint_name``) and the policy keeps them by name:

  - ``KEEP_MASK`` (ops/attention.py, XLA path with dropout on): the boolean
    keep mask of the attention probabilities, [B, H, S, S] at one byte an
    element. Recomputed, every layer draws its random words a second time.
  - ``FLASH_OUT``, ``FLASH_LSE`` (ops/pallas/attention.py): the flash
    kernel's output ([B*H, S, D], activation dtype) and log-sum-exp
    ([B*H, 1, S], fp32), the residuals its backward kernels read.
    Recomputed, the forward kernel runs a second time to reproduce them.

  - ``DSA_CHOICE`` (ops/sparse_attention.py, kernel path): the keys each
    query's indexer chose, one BIT a query-key pair ([B, S, 512] int32 for
    rows of up to 16,384: 33.5 MB a layer and row there). Recomputed, every
    layer scores all its causal pairs and makes its exact choice a second
    time. Kept under ``remat='full'`` too: it is an eighth of what the layer's
    input costs, and the choice is the dearest thing in the layer by the byte.
  - ``DSA_INDEX_GRADS`` (ops/pallas/sparse_attention.py): the gradients of the
    indexer's KL to qI ([B, J, S, E]), kI ([B, S, E]), both in the
    activations' dtype, and w ([B, J, S] float32), which the objective's
    kernel makes in the same walk as the KL's value (its inputs from the core
    are constants, so its backward is known in the forward): 36.7 MB a layer
    and row of 16,384 in bfloat16 at 16 indexer heads of 64. Recomputed, the
    forward rule of the kernel's ``custom_vjp`` runs a second time inside the
    layer's recompute, the with-gradients kernel whole (the heads'
    probabilities rebuilt, the indexer's pairs scored three times), to make
    again what the forward pass threw away. Kept under ``remat='full'``
    too, as the choice is: the dearest thing left in the layer to make again
    by the byte.

  - ``DSA_CORE_OUT``, ``DSA_CORE_LSE`` (ops/pallas/sparse_attention.py): the
    output of the core's forward kernel ([B * KV, G, S, D], activation dtype)
    and each head's log-sum-exp over its chosen keys ([B * KV, G, 1, S]
    float32), in the kernel's own layout: the residuals its two backward
    kernels read (the output for ``delta``). 134.2 MB + 2.1 MB a layer and
    row of 16,384 in bfloat16 at 32 heads of 128. Recomputed, the forward
    kernel runs a second time inside the layer's recompute (every causal
    tile up to the diagonal, a quarter of the core's time) only to hand the
    backward rule what the forward pass threw away. Kept under
    ``remat='full'`` too: the largest thing left in that recompute. They are
    also the largest thing kept, so the model says in how many of its layers
    (models/keye_vl.py ``CORE_KEPT_LAYERS``) and builds the other layers'
    policy ``without`` the two names.

``remat='full'`` keeps these four names of the sparse attention's and nothing
else: the user asked for least memory. The other names do nothing there
unless the caller asks for them by name (``keeping``): a decoder family
whose chip has the room says that its blocks keep ``FLASH_OUT`` and
``FLASH_LSE`` under 'full' too (``KEPT_ACROSS_REMAT`` in models/joyai.py,
models/zaya.py and models/qwen3_next.py, each with its arithmetic), because
the recompute of a block otherwise runs the flash forward kernel a second
time only to hand the backward kernels those two; every other family says
nothing and gets the four. No name does anything
under ``remat='none'`` (no policy) or where no gradient is taken, and a name
that no tensor of a program carries changes nothing in that program.
``remat_policy`` is the ONE place that builds the policy: the scanned encoder
(models/bert.py), the decoders' layers (models/decoder.py) and the
pipeline's stages (pretrain.py) all call it, and ``kept_residual_bytes``
asks it (``kept_names``) what a policy keeps.
"""

from __future__ import annotations

import jax
import numpy as np

KEEP_MASK = "attention_dropout_keep"
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
DSA_CHOICE = "dsa_choice"
DSA_INDEX_GRADS = "dsa_index_grads"
DSA_CORE_OUT = "dsa_core_out"
DSA_CORE_LSE = "dsa_core_lse"
KEPT_UNDER_FULL = (DSA_CHOICE, DSA_INDEX_GRADS, DSA_CORE_OUT, DSA_CORE_LSE)
KEPT_NAMES = (KEEP_MASK, FLASH_OUT, FLASH_LSE) + KEPT_UNDER_FULL


def kept_names(remat: str, without: tuple = (), keeping: tuple = ()) -> tuple:
    """The names of ``KEPT_NAMES`` that ``remat``'s policy keeps: all under
    'dots', ``KEPT_UNDER_FULL`` and the caller's ``keeping`` under 'full',
    none under 'none'; never one of ``without``."""
    unknown = (set(without) | set(keeping)) - set(KEPT_NAMES)
    if unknown:
        raise ValueError(f"no kept name {sorted(unknown)}: {KEPT_NAMES}")
    if remat not in ("none", "dots", "full"):
        raise ValueError(f"remat must be none|dots|full, got {remat!r}")
    names = {"none": (), "dots": KEPT_NAMES,
             "full": KEPT_UNDER_FULL + tuple(keeping)}[remat]
    return tuple(name for name in KEPT_NAMES
                 if name in names and name not in without)


def remat_policy(remat: str, without: tuple = (), keeping: tuple = ()):
    """The ``jax.checkpoint`` policy for a ``remat`` value; None for 'none'.
    ``without``: names of ``KEPT_NAMES`` that this policy does not keep (a
    model whose layers cannot all afford a name: models/keye_vl.py).
    ``keeping``: names of ``KEPT_NAMES`` that this policy keeps under 'full'
    beside ``KEPT_UNDER_FULL`` (a model whose chip has the room for them:
    models/joyai.py); 'dots' keeps every name as it is."""
    names = kept_names(remat, without, keeping)  # (refuses an unknown value)
    if remat == "none":
        return None
    by_name = jax.checkpoint_policies.save_only_these_names(*names)
    if remat == "full":
        return by_name
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims, by_name)


def kept_residual_bytes(remat: str, path: str, dropout: bool, batch: int,
                        seq: int, heads: int, head_dim: int, dtype,
                        keeping: tuple = ()) -> dict:
    """Bytes one layer keeps of attention's names for one micro-batch of
    ``batch`` rows, from shapes: {name: bytes}, empty where the policy
    (``remat`` and the caller's ``keeping``, as ``remat_policy`` takes them)
    keeps none of them. ``path`` is what attention runs (ops/attention.py
    ``resolve_backend``): the 'pallas' kernel (``head_dim`` the VALUES'
    width, which is the output's), or the 'xla' path, which has a mask to
    keep only with ``dropout`` on; the ring paths draw their own masks and
    name nothing."""
    made = {}
    if path == "pallas":
        made = {
            FLASH_OUT: batch * heads * seq * head_dim * np.dtype(dtype).itemsize,
            FLASH_LSE: batch * heads * seq * 4,
        }
    elif path == "xla" and dropout:
        made = {KEEP_MASK: batch * heads * seq * seq}
    kept = kept_names(remat, keeping=keeping)
    return {name: size for name, size in made.items() if name in kept}
