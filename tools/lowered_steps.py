"""The accepted families' train steps lowered FOR THE TPU at a small size, and
the flash kernels' jaxprs, written to a directory: the rehearsal a PR runs when
it touches ``ops/pallas/attention.py``, ``ops/attention.py`` or
``models/decoder.py`` and says the accepted cells' programs did not move (PRs
29-34 each did this by hand). Run it once on the parent's checkout and once on
the change's, then compare the two directories file by file:

    git archive <parent> | tar -x -C /root/scratch/parent
    JAX_PLATFORMS=cpu python tools/lowered_steps.py /root/scratch/parent /root/scratch/low_parent
    JAX_PLATFORMS=cpu python tools/lowered_steps.py . /root/scratch/low_change
    for f in /root/scratch/low_parent/*; do cmp $f /root/scratch/low_change/$(basename $f); done

What it writes: ``nemotron_h.txt``, ``laguna.txt``, ``zaya.txt`` (a decoder of
each accepted family with the Pallas backend, ``--remat full``, AdamW; the
last on the carried path with the labelled kernels), ``phi4flash.txt``
(the family on ``models/decoder.py``'s carried path, at widths no kernel
compiles for: XLA attention, the scan's kernels unrolled by the interpreter)
and ``bert_phase2.txt`` (BERT with the flash kernel and its in-kernel dropout,
``--remat dots``): ``make_train_step(...).trace(...).lower(lowering_platforms=
("tpu",))`` as text, with the Mosaic payloads (the serialized kernels, which
hold the checkout's path and line numbers) and the source locations cut out;
and ``kernel_*.txt``: the jaxpr of ``flash_attention``'s forward and backward
for the bidirectional call with bias and dropout, the packed call, the causal
and the windowed one, which is how the kernels' BODIES are compared; since
PR 43 also ``kernel_gdn_mix.txt`` and ``kernel_gated_norm.txt``, the delta-rule
mixer's element-wise kernel pairs (``ops/gdn_mix.py``) the same way, where the
checkout has them. Nothing
runs on a device and nothing here is a test: equal files say the programs the
accepted cells compile did not change; they say nothing of speed.
"""

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
root, out = sys.argv[1], sys.argv[2]
sys.path.insert(0, root)  # the checkout under the glass, not this file's
os.makedirs(out, exist_ok=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bert_pytorch_tpu import optim, pretrain  # noqa: E402
from bert_pytorch_tpu.config import (BertConfig, LagunaConfig,  # noqa: E402
                                     NemotronHConfig, PhiFlashConfig,
                                     ZayaConfig)
from bert_pytorch_tpu.models import build_pretraining_model  # noqa: E402
from bert_pytorch_tpu.ops import moe  # noqa: E402
from bert_pytorch_tpu.ops.pallas import attention, common  # noqa: E402

for module in (common, attention, moe):  # kernels compiled, as on the chip
    module.interpret_mode = lambda: False

SEQ = 512
NEMOTRON_H = dict(
    vocab_size=256, hidden_size=128, num_hidden_layers=3,
    hybrid_override_pattern="ME*", num_attention_heads=4,
    num_key_value_heads=2, head_dim=128, mamba_num_heads=8, mamba_head_dim=16,
    n_groups=2, ssm_state_size=16, chunk_size=8, conv_kernel=4,
    n_routed_experts=4, ep_size=4, ep_rank=1, num_experts_per_tok=2,
    moe_intermediate_size=128, moe_shared_expert_intermediate_size=128)
LAGUNA = dict(
    vocab_size=256, hidden_size=128, intermediate_size=256,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=128, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"], mlp_layer_types=["dense"] + ["sparse"] * 4,
    sliding_window=128, num_experts=4, ep_size=4, ep_rank=1,
    num_experts_per_tok=3, moe_intermediate_size=128,
    shared_expert_intermediate_size=128)
PHI4FLASH = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=6,
    num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
    layer_indices=[0, 1, 16, 17, 18, 19], published_num_hidden_layers=32,
    mamba_dt_rank=4, scan_chunk=16)
ZAYA = dict(
    vocab_size=256, hidden_size=128, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=128,
    layer_types=["hybrid"] * 3, num_experts=4, ep_size=2, ep_rank=1,
    moe_intermediate_size=128, router_hidden_size=16)
BERT = dict(vocab_size=512, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=256,
            max_position_embeddings=512)


def write(name, text):
    with open(os.path.join(out, name + ".txt"), "w") as f:
        f.write(text)
    print(name, len(text.splitlines()), "lines,",
          text.count("tpu_custom_call"), "kernel calls")


def ids(*shape):
    return jax.ShapeDtypeStruct(shape, np.int32)


def lowered_step(model, batch):
    """The step's text for the TPU, less the Mosaic payloads and locations."""
    causal = getattr(model, "objective", "mlm") == "causal_lm"
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    sample = tuple(jnp.zeros((1, 16), jnp.int32) for _ in range(1 if causal else 3))
    with jax.default_prng_impl("rbg"):
        state = jax.eval_shape(
            pretrain.make_init_fn(model, tx, sample, None), jax.random.PRNGKey(0))
        step = pretrain.make_train_step(
            model, tx, next_sentence=not causal,
            **({} if causal else {"max_pred_per_seq": 80}))
        text = step.trace(state, batch).lower(
            lowering_platforms=("tpu",)).as_text()
    text = re.sub(r'backend_config\s*=\s*"(?:[^"\\]|\\.)*"',
                  'backend_config="<mosaic>"', text)
    return re.sub(r"loc\(.*?\)$", "", text, flags=re.M)


def kernel_jaxpr(**kwargs):
    """flash_attention's forward and backward, traced, as text."""
    q = jnp.zeros((2, SEQ, 4, 64), jnp.bfloat16)
    loss = lambda q_, k_, v_: jnp.sum(attention.flash_attention(
        q_, k_, v_, **kwargs).astype(jnp.float32))
    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))


for name, config in (("nemotron_h", NemotronHConfig(**NEMOTRON_H)),
                     ("laguna", LagunaConfig(**LAGUNA)),
                     ("zaya", ZayaConfig(**ZAYA))):
    write(name, lowered_step(
        build_pretraining_model(config, jnp.bfloat16, remat="full",
                                attention_backend="pallas"),
        {"input_ids": ids(2, 1, SEQ)}))
write("phi4flash", lowered_step(
    build_pretraining_model(PhiFlashConfig(**PHI4FLASH), jnp.bfloat16,
                            remat="full", attention_backend="xla"),
    {"input_ids": ids(2, 1, 64)}))
write("bert_phase2", lowered_step(
    build_pretraining_model(BertConfig(**BERT), jnp.bfloat16, remat="dots",
                            attention_backend="pallas"),
    dict({k: ids(2, 2, SEQ) for k in ("input_ids", "segment_ids", "input_mask",
                                      "masked_lm_labels")},
         next_sentence_labels=ids(2, 2))))
for name, kwargs in (
        ("bidirectional_dropout", dict(
            bias=jnp.zeros((2, 1, 1, SEQ)), dropout_rate=0.1,
            dropout_rng=jax.random.PRNGKey(0))),
        ("packed", dict(sequence_ids=jnp.ones((2, SEQ), jnp.int32))),
        ("causal", dict(causal=True)),
        ("window", dict(causal=True, window=128))):
    write("kernel_" + name, kernel_jaxpr(**kwargs))

try:
    from bert_pytorch_tpu.ops import gdn_mix  # noqa: E402
except ImportError:  # a checkout from before PR 43
    print("kernel_gdn_mix, kernel_gated_norm: not in this checkout")
else:
    def total(outs):
        return sum(jnp.sum(t.astype(jnp.float32))
                   for t in jax.tree_util.tree_leaves(outs))

    heads, lanes = (2, 4), 128
    raw = [jnp.zeros((2, SEQ, count * lanes), jnp.bfloat16)
           for count in heads[:1] + heads]
    taps = [jnp.zeros((4, t.shape[-1]), jnp.float32) for t in raw]
    write("kernel_gdn_mix", str(jax.make_jaxpr(jax.grad(
        lambda *args: total(gdn_mix.conv_silu_unit(*args, *heads)),
        argnums=tuple(range(6))))(*raw, *taps)))
    o = jnp.zeros((2, SEQ, heads[1], lanes), jnp.bfloat16)
    write("kernel_gated_norm", str(jax.make_jaxpr(jax.grad(
        lambda *args: total(gdn_mix.gated_head_norm(*args, 1e-6)),
        argnums=(0, 1, 2)))(o, o, jnp.ones((lanes,), jnp.float32))))
