"""Compile for the chip without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (``jax.experimental.topologies``). These tests
hand it every Pallas kernel of the main paths at BERT-large head geometry
(16 heads of 64) and at the decoder cells' shapes, and fail on what the
chip's compiler would refuse: a misaligned tile, a kernel that wants more
fast memory than it may use. Interpret-mode tests cannot see any of that.
The whole train steps (the phase-2 step against a 16 GB chip, the qwen3_next
cell's, the dp4 step's masks) are in ``tests/test_chip_compile_steps.py``, so
that the two files run on two workers at once.

Nothing runs — a compile that passes is not a chip run (chip_smoke.py is).
Skipped where the topology cannot be described. Two processes may compile at
once only where ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set (``described_chip.py``
sets it; the driver's command does too): without it the second fails on
libtpu's lock file and skips every test, which is not a pass.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from described_chip import (TRAIN_SHAPES, _assert_kernel,  # noqa: F401
                            _compile_for_the_chip, chip, topo)

HEADS, DEPTH, HIDDEN = 16, 64, 1024


def _compile(fn, chip, *shapes):
    """Lower ``fn`` for the described chip at ``shapes`` ((shape, dtype)
    pairs) and compile; returns the compiled executable."""
    sharding = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


def _qkv(batch, seq):
    return [((batch, seq, HEADS, DEPTH), jnp.bfloat16)] * 3


# -- the training kernel: forward, forward+backward, dropout, packed -------

# fwd at seq 512 is left out for the file's time budget (7 s): fwd_bwd-512
# compiles the very same forward kernel through the custom_vjp.
@pytest.mark.parametrize("variant,seq", [
    (variant, seq) for variant in ("fwd", "fwd_bwd", "dropout", "packed")
    for seq in sorted(TRAIN_SHAPES) if (variant, seq) != ("fwd", 512)])
def test_flash_attention_compiles(chip, variant, seq):
    from bert_pytorch_tpu.ops.pallas.attention import flash_attention

    batch = TRAIN_SHAPES[seq]
    bias = ((batch, 1, 1, seq), jnp.float32)
    extra = {"packed": [((batch, seq), jnp.int32)],   # sequence ids
             "dropout": [bias, ((4,), jnp.uint32)],   # + raw rbg key data
             }.get(variant, [bias])

    def loss(q, k, v, *extra):
        if variant == "packed":
            out = flash_attention(q, k, v, sequence_ids=extra[0])
        elif variant == "dropout":
            out = flash_attention(q, k, v, bias=extra[0], dropout_rate=0.1,
                                  dropout_rng=extra[1])
        else:
            out = flash_attention(q, k, v, bias=extra[0])
        return jnp.sum(out.astype(jnp.float32))

    fn = loss if variant == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    names = ("flash_fwd",) if variant == "fwd" else (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    _assert_kernel(_compile(fn, chip, *_qkv(batch, seq), *extra), *names)


def test_causal_flash_attention_compiles_at_8192(chip):
    """The causal flag at the nemotron_h cell's geometry: one row of 8192
    tokens, 32 query heads of 128 on 2 key-value heads (repeated by the
    wrapper), forward and both backward kernels; the loops' dynamic bounds
    and the whole-sequence K/V blocks have to pass Mosaic and fit VMEM."""
    from bert_pytorch_tpu.ops.attention import dot_product_attention

    def loss(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, backend="pallas", causal=True).astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), chip,
        ((1, 8192, 32, 128), jnp.bfloat16), ((1, 8192, 2, 128), jnp.bfloat16),
        ((1, 8192, 2, 128), jnp.bfloat16))
    _assert_kernel(compiled, "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def test_gated_flash_attention_compiles_at_a_head_of_256(chip):
    """The qwen3_next cell's attention core: a micro-batch of 2 rows of 8192
    tokens, 16 query heads of 256 on 2 key-value heads, under the caller's
    label ``gated``. K and V of one head group, whole and double-buffered, are
    16 MiB, Mosaic's whole default scoped VMEM: the three kernels pass only
    under the limit ``_wide_head_params`` raises for them, and every narrower
    head passes no compiler parameter at all."""
    from bert_pytorch_tpu.ops.attention import dot_product_attention
    from bert_pytorch_tpu.ops.pallas import attention

    def loss(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, backend="pallas", causal=True,
            label="gated").astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), chip,
        ((2, 8192, 16, 256), jnp.bfloat16), ((2, 8192, 2, 256), jnp.bfloat16),
        ((2, 8192, 2, 256), jnp.bfloat16))
    _assert_kernel(compiled, "flash_gated_fwd", "flash_gated_bwd_dq",
                   "flash_gated_bwd_dkv")
    wide = attention._wide_head_params(1, 8192, 256, 256, 2)
    assert wide["compiler_params"].vmem_limit_bytes == 32 * 1024 ** 2
    for seq, depth, depth_v, g in ((8192, 128, 128, 1), (8192, 64, 128, 1),
                                   (512, 64, 64, 8)):  # the accepted cells'
        assert attention._wide_head_params(g, seq, depth, depth_v, 2) == {}


def test_delta_rule_mixer_compiles_at_8192(chip):
    """The qwen3_next cell's delta-rule mixer whole at its geometry (a
    micro-batch of 2 rows of 8192 tokens, 16 key / 32 value heads of 128
    beside a stream of 2048), forward and backward: every scope under
    ``gdn``, and under ``delta_rule`` the chunked rule's two Pallas kernels
    (``ops/pallas/delta_rule.py``: Mosaic takes their lane gathers, the
    inverse's float32 products and the [16, 128, 128] float32 scratch); under
    ``gdn_conv`` and ``gdn_gate_norm`` the element-wise kernels of
    ``ops/pallas/gdn_mix.py`` (the sublane rotations, blocks of [256, 4096],
    the rows before a block by a second index map), each between neighbours
    whose layout it shares: no copy and no transpose under either scope."""
    from bert_pytorch_tpu.config import Qwen3NextConfig
    from bert_pytorch_tpu.models import qwen3_next

    layer = qwen3_next.GatedDeltaNet(Qwen3NextConfig(), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16, 2048), jnp.bfloat16)))
    sharding = SingleDeviceSharding(chip)
    place = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sharding)

    def loss(variables, x):
        return jnp.sum(jnp.square(
            layer.apply(variables, x)[0].astype(jnp.float32)))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree_util.tree_map(place, params),
        place(jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16))).compile()
    names = set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))
    for scope in ("gdn_in_proj", "gdn_conv", "gdn_gates", "delta_rule",
                  "gdn_gate_norm", "gdn_out_proj"):
        assert any(f"/gdn/{scope}/" in name for name in names), scope
    _assert_kernel(compiled, "delta_rule_fwd", "delta_rule_bwd",
                   "gdn_mix_fwd", "gdn_mix_bwd", "gated_norm_fwd",
                   "gated_norm_bwd")
    for line in compiled.as_text().splitlines():
        if re.search(r"/gdn/(gdn_conv|gdn_gate_norm)/", line):
            assert not re.search(r" = \S+ (copy|transpose)\(", line), line
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4 * 1024 ** 3


def test_windowed_flash_attention_compiles_at_8192(chip):
    """The window flag at the laguna cell's geometry: one row of 8192 tokens,
    36 query heads of 128 on 4 key-value heads, a window of one 512-wide
    tile; the loops' three dynamic segments (masked, unmasked, masked) have
    to pass Mosaic, and the calls carry the windowed kernels' names."""
    from bert_pytorch_tpu.ops.attention import dot_product_attention

    def loss(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, backend="pallas", causal=True,
            window=512).astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), chip,
        ((1, 8192, 36, 128), jnp.bfloat16), ((1, 8192, 4, 128), jnp.bfloat16),
        ((1, 8192, 4, 128), jnp.bfloat16))
    _assert_kernel(compiled, "flash_window_fwd", "flash_window_bwd_dq",
                   "flash_window_bwd_dkv")


@pytest.mark.parametrize("window,label,prefix", [
    (512, "diff", "flash_diff_window_"), (None, "diff_cross", "flash_diff_cross_")])
def test_differential_flash_attention_compiles_at_8192(chip, window, label,
                                                       prefix):
    """Values twice as wide as the keys at the phi4flash cell's geometry: one
    row of 8192 tokens, 10 query pairs and 5 key pairs of 64, values of 128,
    both maps as heads of one call (20 maps); the kernels' names say which
    kind of layer called."""
    from bert_pytorch_tpu.ops.attention import differential_attention

    def loss(q, k, v):
        a1, a2 = differential_attention(q, k, v, backend="pallas",
                                        window=window, label=label)
        return jnp.sum((a1 - 0.5 * a2).astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), chip,
        ((1, 8192, 10, 2, 64), jnp.bfloat16),
        ((1, 8192, 5, 2, 64), jnp.bfloat16), ((1, 8192, 5, 128), jnp.bfloat16))
    _assert_kernel(compiled, prefix + "fwd", prefix + "bwd_dq",
                   prefix + "bwd_dkv")


def test_selective_scan_compiles_at_8192(chip):
    """The Mamba-1 scan's two kernels at the phi4flash cell's geometry: one
    row of 8192 positions, 5120 channels, 16 states, chunks of 128: the time
    loop's tiles, the state's scratch and the chunk's kept states have to pass
    Mosaic and fit VMEM under the kernels' own limit."""
    from bert_pytorch_tpu.ops import ssm

    def loss(u, dt, a, b, c):
        return jnp.sum(ssm.selective_scan(u, dt, a, b, c, chunk=128))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), chip,
        ((1, 8192, 5120), jnp.bfloat16), ((1, 8192, 5120), jnp.float32),
        ((5120, 16), jnp.float32), ((1, 8192, 16), jnp.bfloat16),
        ((1, 8192, 16), jnp.bfloat16))
    _assert_kernel(compiled, "selective_scan_fwd", "selective_scan_bwd")


def test_ssd_scan_compiles_at_8192(chip):
    """The Mamba-2 scan's two kernels at the hybrid decoder cell's geometry:
    one row of 8192 positions, 64 heads of 64 in 8 groups, 128 states, chunks
    of 128: the dynamic lane offsets of a tile and a group, the lane gather
    that spreads a head's number over its lanes, the float32 state scratch and
    a chunk's blocks have to pass Mosaic and fit VMEM under the kernels' own
    limit."""
    from bert_pytorch_tpu.ops import ssm

    def loss(x, dt, a, b, c, d):
        return jnp.sum(jnp.square(ssm.ssd_chunked_scan(
            x, dt, a, b, c, d, 128).astype(jnp.float32)))

    heads = ((64,), jnp.float32)
    compiled = _compile(
        jax.grad(loss, argnums=tuple(range(6))), chip,
        ((1, 8192, 64, 64), jnp.bfloat16), ((1, 8192, 64), jnp.float32), heads,
        ((1, 8192, 8, 128), jnp.bfloat16), ((1, 8192, 8, 128), jnp.bfloat16),
        heads)
    _assert_kernel(compiled, "ssd_scan_fwd", "ssd_scan_bwd")


@pytest.mark.parametrize("rows,group,k,n", [
    pytest.param(15872, 963, 2048, 4096, id="zaya-up"),
    pytest.param(15872, 963, 2048, 2048, id="zaya-down"),
    pytest.param(5120, 320, 3072, 2048, id="laguna-up"),
    pytest.param(5120, 320, 1024, 3072, id="laguna-down"),
    pytest.param(6144, 384, 2688, 1856, id="hybrid-up"),
    pytest.param(6144, 384, 1856, 2688, id="hybrid-down"),  # k 14.5 lane tiles
    pytest.param(10240, 320, 2048, 1024, id="qwen-up"),
    pytest.param(10240, 320, 512, 2048, id="qwen-down"),
    pytest.param(8192, 1024, 2048, 1536, id="keye-up"),
    pytest.param(8192, 1024, 768, 2048, id="keye-down"),
    pytest.param(2048, 256, 2048, 1536, id="joyai-up"),  # a quarter of keye's
    pytest.param(2048, 256, 768, 2048, id="joyai-down"),  # fill, its shapes
    # a round's arrivals from four chips, sixteen experts a chip
    pytest.param(32768, 2048, 2304, 1792, id="mellum-up"),
    pytest.param(32768, 2048, 896, 2304, id="mellum-down"),
])
def test_grouped_products_compile_at_the_cells_shapes(chip, monkeypatch, rows,
                                                      group, k, n):
    """A grouped expert product and its two cotangents (megablox's ``gmm``
    twice, ``tgmm`` once) at a cell's piece of sorted slots, each at the
    tiles ``ops/moe.py gmm_tiles`` chooses for it: Mosaic, not the rule's
    arithmetic, says whether a grid step fits the scoped VMEM."""
    from bert_pytorch_tpu.ops import moe

    monkeypatch.setattr(moe, "interpret_mode", lambda: False)

    def fn(rows_in, weights, sizes, d_out):
        out, pull = jax.vjp(
            lambda r, w: moe.grouped_dot(r, w, sizes, group), rows_in,
            weights)
        return out, pull(d_out)

    compiled = _compile(
        fn, chip, ((rows, k), jnp.bfloat16), ((8, k, n), jnp.bfloat16),
        ((8,), jnp.int32), ((rows, n), jnp.bfloat16))
    _assert_kernel(compiled, "gmm", "tgmm")
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_the_exchange_compiles_over_four_chips_at_the_published_widths(
        topo, monkeypatch):
    """The mellum cell's expert layer whole under its expert axis, forward
    and backward, for the described 2x2 host: a row of 8192 tokens a chip,
    64 experts top-8 of 2304 x 896 divided sixteen a chip. The chip's own
    compiler takes the all-to-alls inside the two loops whose trip counts are
    traced (the rounds), the grouped kernels over a round's 32,768 arrivals
    and the sorts; what crosses the axis is in the program (all-to-all of the
    rows both ways) and nothing of a layer is gathered (no all-gather: a
    chip's 16 experts stay where they are); the temporaries stay under 3 GB a
    chip (two buffers of a round's rows, 151 MB each, and the piece's
    activations: nothing sized for 65,536 slots to one chip)."""
    import flax.linen as nn
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bert_pytorch_tpu import pretrain
    from bert_pytorch_tpu.config import MellumConfig
    from bert_pytorch_tpu.models.laguna import expert_layer
    from bert_pytorch_tpu.ops import moe
    from bert_pytorch_tpu.parallel.mesh import AXIS_EXPERT

    monkeypatch.setattr(moe, "interpret_mode", lambda: False)
    cfg = MellumConfig(num_hidden_layers=4)
    mesh = Mesh(np.asarray(topo.devices[:4]), (AXIS_EXPERT,))
    whole = expert_layer(cfg, jnp.bfloat16, axis_names=True)
    local = expert_layer(cfg, jnp.bfloat16, axis_names=True,
                         expert_axis=AXIS_EXPERT, expert_shards=4)
    boxed = jax.eval_shape(lambda: whole.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16, 2304), jnp.bfloat16)))
    specs = jax.tree_util.tree_map(
        lambda names: P(*(AXIS_EXPERT if n == "experts" else None
                          for n in names)), nn.get_partition_spec(boxed))
    place = lambda leaf, spec: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec))
    params = jax.tree_util.tree_map(place, nn.unbox(boxed), specs)
    x = place(jax.ShapeDtypeStruct((4, 8192, 2304), jnp.bfloat16),
              P(AXIS_EXPERT))

    def per_chip(variables, x_):
        def loss(v, h):
            out, counters = local.apply(v, h)
            return jnp.sum(jnp.square(out.astype(jnp.float32))), counters

        (_, counters), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(variables, x_)
        return grads, jax.lax.psum(counters["moe_dropped_slots"], AXIS_EXPERT)

    fn = pretrain.on_expert_axis(per_chip, mesh, (specs, P(AXIS_EXPERT)),
                                 ((specs, P(AXIS_EXPERT)), P()))
    compiled = jax.jit(fn).lower(params, x).compile()
    text = compiled.as_text()
    assert text.count("all-to-all") >= 7  # counts, rows, terms; four back
    assert "all-gather" not in text
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in ("moe_route", "moe_dispatch", "moe_exchange_out",
                  "moe_experts", "moe_exchange_back", "moe_combine"):
        assert any(f"/{scope}/" in name for name in names), scope
    _assert_kernel(compiled, "gmm", "tgmm")
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 1024 ** 3


def test_mellum_step_compiles_for_four_chips_at_the_published_widths(
        topo, monkeypatch):
    """The mellum cell's WHOLE train step at its real size (2124 M
    parameters over four chips, 4 micro-batches of 1 row of 8192 tokens a
    chip, ``--remat full``, AdamW) under its own mesh (``--mesh ep=4``)
    through the rehearsal's own ``compile_step``: the TPU's compiler takes it
    within a 16 GB chip (a chip's arguments are its 595.2 M parameters'
    twelve bytes: a quarter of the experts and of both tables, the rest
    whole) without rematerializing on its own account, with the windowed and
    the causal flash kernels, the grouped products and the exchange's
    all-to-alls in it. A compile that passes here is not a fit (PERF.md 4):
    the chips' own compiler has the last word. (Here and not in
    ``test_chip_compile_steps.py``, which holds two such compiles already.)"""
    import benchmarks.run as bench_run
    from benchmarks.rehearse.compile_real_mellum import WORKLOAD, compile_step
    from bert_pytorch_tpu.ops import moe

    monkeypatch.setattr(moe, "interpret_mode", lambda: False)
    step = compile_step(bench_run.context(bench_run.ROOT, WORKLOAD), topo)
    assert step["parameters"] == 2_123_976_960
    assert step["remat_fusions"] == 0
    a_chip = (2_123_976_960 - 0.75 * (4 * 64 * 3 * 2304 * 896
                                      + 2 * 98304 * 2304))
    assert a_chip == pytest.approx(595.2e6, rel=1e-3)
    assert step["argument_bytes"] == pytest.approx(12 * a_chip, rel=1e-3)
    assert step["argument_bytes"] + step["temp_bytes"] < 17.2e9
    assert step["window_kernels"] > 0 and step["tpu_custom_calls"] >= 40
    assert step["collectives"]["all-to-all"] >= 4 * 7
    # the tables' crossings: ids and hidden states gathered, never a table
    assert 1 <= step["collectives"]["all-gather"] <= 12


def test_sparse_attention_compiles_at_16384(chip):
    """The KeyeVL2 cell's attention layer whole at its geometry (one row of
    16,384 tokens, 32 / 4 heads of 128 over the 2048 keys a 16 x 64 indexer
    chooses, beside a stream of 2048), forward and backward with the
    indexer's KL in the objective: every scope of the layer, and under ``dsa``
    the five Pallas kernels of ``ops/pallas/sparse_attention.py`` (Mosaic
    takes the [32, 128, 512] int32 scratch of ordered scores, the bit-plane
    shifts, K and V of a whole row and all 32 heads of a query block in
    VMEM under the raised limit); no [S, S] tensor in HBM but the bits:
    the layer's temporaries stay under 2 GB."""
    from bert_pytorch_tpu.config import KeyeVLConfig
    from bert_pytorch_tpu.models import keye_vl

    layer = keye_vl.SparseAttention(KeyeVLConfig(), jnp.bfloat16, "pallas")
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 512, 2048), jnp.bfloat16)))
    sharding = SingleDeviceSharding(chip)
    place = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sharding)

    def loss(variables, x):
        out, counters = layer.apply(variables, x)
        return jnp.sum(jnp.square(out.astype(jnp.float32))) + counters[
            "dsa_index_kl"]

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree_util.tree_map(place, params),
        place(jax.ShapeDtypeStruct((1, 16384, 2048), jnp.bfloat16))).compile()
    names = set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))
    for scope in ("attn_qkv", "attn_qk_norm", "attn_rope", "attn_out"):
        assert any(f"/{scope}/" in name for name in names), scope
    for scope in ("dsa_index_proj", "dsa_select", "dsa_core", "dsa_index_loss"):
        assert any(f"/dsa/{scope}/" in name for name in names), scope
    _assert_kernel(compiled, "dsa_select", "dsa_core_fwd", "dsa_core_bwd_dq",
                   "dsa_core_bwd_dkv", "dsa_index_loss")
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 * 1024 ** 3


def test_latent_attention_compiles_at_8192(chip):
    """The joyai_llm_flash cell's attention layer whole at its geometry (one
    row of 8192 tokens, 32 heads of 128 + 64 turned on one shared turned key
    over values of 128, latents of 1536 and 512, beside a stream of 2048),
    forward and backward: every scope of the layer, and under ``mla_core`` the
    three causal flash kernels by their ``mla`` names at a head of a lane tile
    and a half (K of a whole row is 12 MiB of VMEM in 256 lanes with V beside
    it: ``_wide_head_params`` raises the limit); no [S, S] tensor in HBM."""
    from bert_pytorch_tpu.config import JoyAIConfig
    from bert_pytorch_tpu.models import joyai

    layer = joyai.LatentAttention(JoyAIConfig(), jnp.bfloat16, "pallas")
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 512, 2048), jnp.bfloat16)))
    sharding = SingleDeviceSharding(chip)
    place = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sharding)

    def loss(variables, x):
        out, _ = layer.apply(variables, x)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree_util.tree_map(place, params),
        place(jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16))).compile()
    names = set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))
    for scope in ("mla_q_proj", "mla_kv_proj", "attn_rope", "mla_core",
                  "attn_out"):
        assert any(f"/mla/{scope}/" in name for name in names), scope
    _assert_kernel(compiled, "flash_mla_fwd", "flash_mla_bwd_dq",
                   "flash_mla_bwd_dkv")
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 * 1024 ** 3


@pytest.mark.parametrize("heads,rotary_dim", [(24, 64), (36, 128)])
def test_rotary_turn_compiles_at_8192(chip, heads, rotary_dim):
    """The turn's kernel at the laguna cell's geometry (queries and the four
    key-value heads of a full layer under YaRN's half-width tables, of a
    sliding layer under whole ones), forward and backward: the tables widened
    in VMEM from 64 lanes, the lane rotations and the blocks' VMEM have to
    pass Mosaic."""
    from bert_pytorch_tpu.ops import rope

    def loss(q, k, cos, sin):
        return sum(jnp.sum(jnp.square(  # squared: the backward needs the forward
            rope.apply_rotary(t, cos, sin).astype(jnp.float32))) for t in (q, k))

    table = ((8192, rotary_dim), jnp.float32)
    compiled = _compile(
        jax.grad(loss, argnums=(0, 1)), chip,
        ((1, 8192, heads, 128), jnp.bfloat16),
        ((1, 8192, 4, 128), jnp.bfloat16), table, table)
    _assert_kernel(compiled, "rotary_turn")
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          compiled.as_text())) == 4


def test_latent_attention_compiles_at_8192(chip):
    """The zaya cell's attention layer whole at its geometry (a micro-batch
    of 2 rows of 8192 tokens, 8 / 2 heads of 128 in a latent of 1024 + 256
    beside a stream of 2048), forward and backward: the causal flash kernels
    under the caller's label ``cca`` on 4 query heads a key-value head, the
    rotary turn on 8 and on 2 heads under half-width tables, and XLA's part
    between them (both convolutions, the q-k mean, the norm, the shift)."""
    from bert_pytorch_tpu.config import ZayaConfig
    from bert_pytorch_tpu.models import zaya

    layer = zaya.CompressedConvAttention(ZayaConfig(), jnp.bfloat16, "pallas")
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16, 2048), jnp.bfloat16)))
    sharding = SingleDeviceSharding(chip)
    place = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sharding)

    def loss(variables, x):
        return jnp.sum(jnp.square(layer.apply(variables, x).astype(jnp.float32)))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree_util.tree_map(place, params),
        place(jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16))).compile()
    _assert_kernel(compiled, "flash_cca_fwd", "flash_cca_bwd_dq",
                   "flash_cca_bwd_dkv", "rotary_turn")
    names = set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))
    for scope in ("attn_qkv", "cca_conv", "cca_qk_mean", "cca_value_shift",
                  "cca_norm", "attn_rope", "attention_core", "attn_out"):
        assert any(f"/cca/{scope}/" in name for name in names), scope


# -- the serving kernels ----------------------------------------------------

@pytest.mark.parametrize("seq", [32, 128, 512])
@pytest.mark.parametrize("kernel", ["flash_attention_infer",
                                    "flash_attention_infer_int8"])
def test_infer_attention_compiles(chip, kernel, seq):
    from bert_pytorch_tpu.ops.pallas import attention

    batch = 8  # run_server.py's default --max_batch_size
    fn = getattr(attention, kernel)
    _assert_kernel(_compile(
        lambda q, k, v, bias: fn(q, k, v, bias=bias), chip,
        *_qkv(batch, seq), ((batch, 1, 1, seq), jnp.float32)),
        {"flash_attention_infer": "flash_infer_fwd",
         "flash_attention_infer_int8": "flash_infer_fwd_int8"}[kernel])


# -- layer norm ---------------------------------------------------------------

@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_layer_norm_compiles(chip, direction):
    from bert_pytorch_tpu.ops.pallas.layernorm import layer_norm_pallas

    def loss(x, scale, bias):
        return jnp.sum(layer_norm_pallas(x, scale, bias, 1e-12)
                       .astype(jnp.float32))

    fn = loss if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    _assert_kernel(_compile(
        fn, chip, ((28, 512, HIDDEN), jnp.bfloat16),
        ((HIDDEN,), jnp.float32), ((HIDDEN,), jnp.float32)),
        "layernorm_fwd")
