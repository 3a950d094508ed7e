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

What it writes (``steps()`` lists them, in the order they are written):
``nemotron_h.txt``, ``laguna.txt``, ``zaya.txt`` (a decoder of each accepted
family with the Pallas backend, ``--remat full``, AdamW; the last on the
carried path with the labelled kernels), ``phi4flash.txt`` (the family on
``models/decoder.py``'s carried path, at widths no kernel compiles for: XLA
attention, the scan's kernels unrolled by the interpreter), ``bert_phase2.txt``
(BERT with the flash kernel and its in-kernel dropout, ``--remat dots``),
``qwen3_next.txt`` (a period of the family, three delta-rule layers to one of
gated attention, at the smallest shapes its kernels take: the rule's pair, the
element-wise pairs round it and the flash kernels at a head of 256),
``bert_phase1.txt`` (BERT at seq 128 with XLA attention, ``--remat dots``,
LAMB, dropout drawn by ``rbg``: the phase-1 cells' path) and ``KeyeVL2.txt``
(two layers of attention over the keys an indexer chooses, at the smallest
shapes its kernels take: the choice's, the core's three and the objective's)
and ``joyai_llm_flash.txt`` (a dense and an expert layer of latent attention
and the multi-token-prediction module: the causal flash kernels at a head of
128 + 64 against values of 128, the shared head run twice), ``mellum.txt``
(a sliding and a full layer before routed experts, on one device) and
``mellum_ep4.txt`` (the same step under ``--mesh ep=4`` over four virtual
devices: the micro-batches inside a ``shard_map``, the slots' all-to-alls,
the head's and the embedding's crossings, the one gradient sum):
``make_train_step(...).trace(...).lower(lowering_platforms=("tpu",))`` as
text, with the Mosaic payloads (the serialized kernels, which hold the
checkout's path and line numbers) and the source locations cut out; and
``kernel_*.txt``: the jaxpr of ``flash_attention``'s forward and backward for
the bidirectional call with bias and dropout, the packed call, the causal and
the windowed one, which is how the kernels' BODIES are compared, and
``kernel_gdn_mix.txt`` and ``kernel_gated_norm.txt``, the delta-rule mixer's
element-wise kernel pairs (``ops/gdn_mix.py``) the same way. A file whose
family or kernel the checkout under the glass does not have yet is left out
and named on stdout. Nothing runs on a device: equal files say the programs
the accepted cells compile did not change; they say nothing of speed.
``tests/test_lowered_steps.py`` holds the tool to what the comparison needs
(every family of ``config.MODEL_FAMILIES`` has a step, no file names the
checkout or a source line, two runs give the same bytes).
"""

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # (the step under an expert axis is lowered over four virtual devices)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEQ = 512
# each family's sizes here, by its name in ``config.MODEL_FAMILIES``
SIZES = {
    "nemotron_h": dict(
        vocab_size=256, hidden_size=128, num_hidden_layers=3,
        hybrid_override_pattern="ME*", num_attention_heads=4,
        num_key_value_heads=2, head_dim=128, mamba_num_heads=8,
        mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=8,
        conv_kernel=4,
        n_routed_experts=4, ep_size=4, ep_rank=1, num_experts_per_tok=2,
        moe_intermediate_size=128, moe_shared_expert_intermediate_size=128),
    "laguna": dict(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
        layer_types=["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"], mlp_layer_types=["dense"] + ["sparse"] * 4,
        sliding_window=128, num_experts=4, ep_size=4, ep_rank=1,
        num_experts_per_tok=3, moe_intermediate_size=128,
        shared_expert_intermediate_size=128),
    "phi4flash": dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=6, num_attention_heads=8, num_key_value_heads=4,
        sliding_window=8,
        layer_indices=[0, 1, 16, 17, 18, 19], published_num_hidden_layers=32,
        mamba_dt_rank=4, scan_chunk=16),
    "zaya": dict(
        vocab_size=256, hidden_size=128, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        layer_types=["hybrid"] * 3, num_experts=4, ep_size=2, ep_rank=1,
        moe_intermediate_size=128, router_hidden_size=16),
    # chunks of 64 and heads of 128, two value heads a key head, one dtype:
    # the least that ops/pallas/delta_rule.py fits and ops/gdn_mix.py
    # kernel_fit take
    "qwen3_next": dict(
        vocab_size=256, hidden_size=128, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=256,
        linear_num_key_heads=2, linear_num_value_heads=4, num_experts=4,
        ep_size=4, ep_rank=1, num_experts_per_tok=3,
        moe_intermediate_size=128, shared_expert_intermediate_size=128),
    # heads of 128 over rows of 512: the least that
    # ops/pallas/sparse_attention.py fits take (one bit plane of keys)
    "KeyeVL2": dict(
        vocab_size=256, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        num_experts=4, ep_size=4, ep_rank=1, num_experts_per_tok=3,
        moe_intermediate_size=128,
        sa_config=dict(indexer_num_heads=4, indexer_head_dim=64,
                       indexer_num_kv_heads=1, topk=128)),
    # the published lane proportions of a head (128 + 64 turned against
    # values of 128), one dense layer then one expert layer, and the module
    "joyai_llm_flash": dict(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=96, kv_lora_rank=64, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=4, ep_size=4,
        ep_rank=1, num_experts_per_tok=3, moe_intermediate_size=128),
    # a sliding and a full layer at a head of 128, every MLP routed, the
    # whole layer's experts (the expert axis divides them, not the config)
    "mellum": dict(
        vocab_size=256, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        layer_types=["sliding_attention", "full_attention"],
        mlp_layer_types=["sparse", "sparse"], sliding_window=128,
        num_experts=8, num_experts_per_tok=3, moe_intermediate_size=128),
    "bert": dict(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=256,
        max_position_embeddings=512),
}


def ids(*shape):
    return jax.ShapeDtypeStruct(shape, np.int32)


def lowered_step(model, batch, max_pred=80, lamb=False, mesh_spec=None):
    """The step's text for the TPU, less the Mosaic payloads and locations.
    ``mesh_spec`` (``ep=4``): the step under that mesh over the first virtual
    devices, its state divided by the model's axis names."""
    import contextlib

    from bert_pytorch_tpu import optim, pretrain

    causal = getattr(model, "objective", "mlm") == "causal_lm"
    tx = (optim.lamb if lamb else optim.adamw)(
        1e-3, max_grad_norm=1.0, weight_decay_mask=optim.no_decay_mask)
    sample = tuple(jnp.zeros((1, 16), jnp.int32) for _ in range(1 if causal else 3))
    mesh, placed = contextlib.nullcontext(), {}
    if mesh_spec:
        from bert_pytorch_tpu.parallel import (MeshSpec, create_mesh,
                                               logical_axis_rules)

        spec = MeshSpec.parse(mesh_spec)
        mesh = create_mesh(spec.mesh_config(), devices=jax.devices()[
            :max(spec.data, 1) * spec.expert])
        placed = dict(
            mesh=mesh, shardings=pretrain.state_shardings(
                mesh, model, logical_axis_rules(spec), sample),
            batch_shardings_=pretrain.batch_shardings(mesh, {"input_ids": 3}))
    with mesh, jax.default_prng_impl("rbg"):
        state = jax.eval_shape(
            pretrain.make_init_fn(model, tx, sample, placed.get("shardings")),
            jax.random.PRNGKey(0))
        step = pretrain.make_train_step(
            model, tx, next_sentence=not causal, **placed,
            **({} if causal else {"max_pred_per_seq": max_pred}))
        text = step.trace(state, batch).lower(
            lowering_platforms=("tpu",)).as_text()
    text = re.sub(r'backend_config\s*=\s*"(?:[^"\\]|\\.)*"',
                  'backend_config="<mosaic>"', text)
    return re.sub(r"loc\(.*?\)$", "", text, flags=re.M)


def family_model(family, remat, backend):
    """The family's pretraining model at its ``SIZES``, as the trainer builds
    it; ImportError where the checkout has no such family."""
    from bert_pytorch_tpu.config import MODEL_FAMILIES
    from bert_pytorch_tpu.models import build_pretraining_model

    if family not in MODEL_FAMILIES:
        raise ImportError(f"no {family} in config.MODEL_FAMILIES")
    return build_pretraining_model(
        MODEL_FAMILIES[family](**SIZES[family]), jnp.bfloat16, remat=remat,
        attention_backend=backend)


def decoder_step(family, seq=SEQ, backend="pallas", mesh_spec=None, rows=1):
    """A decoder family's step: ``--remat full``, AdamW, rows of token ids."""
    return lowered_step(family_model(family, "full", backend),
                        {"input_ids": ids(2, rows, seq)}, mesh_spec=mesh_spec)


def bert_step(seq, max_pred, backend, lamb=False):
    """BERT's step under ``--remat dots`` with dropout on."""
    batch = {k: ids(2, 2, seq) for k in ("input_ids", "segment_ids",
                                         "input_mask", "masked_lm_labels")}
    return lowered_step(family_model("bert", "dots", backend),
                        dict(batch, next_sentence_labels=ids(2, 2)),
                        max_pred, lamb)


def kernel_jaxpr(**kwargs):
    """flash_attention's forward and backward, traced, as text."""
    from bert_pytorch_tpu.ops.pallas import attention

    q = jnp.zeros((2, SEQ, 4, 64), jnp.bfloat16)
    loss = lambda q_, k_, v_: jnp.sum(attention.flash_attention(
        q_, k_, v_, **kwargs).astype(jnp.float32))
    return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))


def total(outs):
    return sum(jnp.sum(t.astype(jnp.float32))
               for t in jax.tree_util.tree_leaves(outs))


def gdn_mix_jaxpr(heads=(2, 4), lanes=128):
    """The convolution, silu and unit length before the rule, both ways."""
    from bert_pytorch_tpu.ops import gdn_mix

    raw = [jnp.zeros((2, SEQ, count * lanes), jnp.bfloat16)
           for count in heads[:1] + heads]
    taps = [jnp.zeros((4, t.shape[-1]), jnp.float32) for t in raw]
    return str(jax.make_jaxpr(jax.grad(
        lambda *args: total(gdn_mix.conv_silu_unit(*args, *heads)),
        argnums=tuple(range(6))))(*raw, *taps))


def gated_norm_jaxpr(heads=4, lanes=128):
    """The gated norm after the rule, both ways."""
    from bert_pytorch_tpu.ops import gdn_mix

    o = jnp.zeros((2, SEQ, heads, lanes), jnp.bfloat16)
    return str(jax.make_jaxpr(jax.grad(
        lambda *args: total(gdn_mix.gated_head_norm(*args, 1e-6)),
        argnums=(0, 1, 2)))(o, o, jnp.ones((lanes,), jnp.float32)))


def steps():
    """(file name less ``.txt``, what builds its text) in the order written.
    A builder imports the checkout ``main`` put first on the path, and raises
    ImportError where that checkout lacks its family or kernel."""
    return (
        ("nemotron_h", lambda: decoder_step("nemotron_h")),
        ("laguna", lambda: decoder_step("laguna")),
        ("zaya", lambda: decoder_step("zaya")),
        ("phi4flash", lambda: decoder_step("phi4flash", 64, "xla")),
        ("bert_phase2", lambda: bert_step(SEQ, 80, "pallas")),
        ("kernel_bidirectional_dropout", lambda: kernel_jaxpr(
            bias=jnp.zeros((2, 1, 1, SEQ)), dropout_rate=0.1,
            dropout_rng=jax.random.PRNGKey(0))),
        ("kernel_packed", lambda: kernel_jaxpr(
            sequence_ids=jnp.ones((2, SEQ), jnp.int32))),
        ("kernel_causal", lambda: kernel_jaxpr(causal=True)),
        ("kernel_window", lambda: kernel_jaxpr(causal=True, window=128)),
        ("kernel_gdn_mix", gdn_mix_jaxpr),
        ("kernel_gated_norm", gated_norm_jaxpr),
        ("qwen3_next", lambda: decoder_step("qwen3_next")),
        ("bert_phase1", lambda: bert_step(128, 20, "xla", lamb=True)),
        ("KeyeVL2", lambda: decoder_step("KeyeVL2")),
        ("joyai_llm_flash", lambda: decoder_step("joyai_llm_flash")),
        ("mellum", lambda: decoder_step("mellum")),
        ("mellum_ep4", lambda: decoder_step("mellum", mesh_spec="ep=4",
                                            rows=4)),
    )


def main(root, out):
    sys.path.insert(0, root)  # the checkout under the glass, not this file's
    os.makedirs(out, exist_ok=True)
    from bert_pytorch_tpu.ops import moe
    from bert_pytorch_tpu.ops.pallas import attention, common

    for module in (common, attention, moe):  # kernels compiled, as on the chip
        module.interpret_mode = lambda: False
    for name, build in steps():
        try:
            text = build()
        except ImportError as missing:
            print(f"{name}: not in this checkout ({missing})")
            continue
        with open(os.path.join(out, name + ".txt"), "w") as f:
            f.write(text)
        print(name, len(text.splitlines()), "lines,",
              text.count("tpu_custom_call"), "kernel calls")


if __name__ == "__main__":
    main(*sys.argv[1:3])
