"""Pipeline parallelism: a GPipe schedule over the mesh 'pipe' axis.

The reference's only model-training parallelism is data parallel (SURVEY.md
§2.2); this module is a beyond-parity strategy for models whose layer stack
does not fit (or does not scale) on one chip. TPU-native design: the encoder's
stacked layer parameters ([L, ...] from ``nn.scan``, bert.py) shard over a
'pipe' mesh axis — each stage holds L/P *contiguous* layers — and microbatch
activations rotate stage-to-stage with ``ppermute`` under ``shard_map``. The
communication pattern IS the algorithm here, so this is hand-written
collective code, like ops/ring.py and unlike everything under pjit.

Schedule: plain GPipe. M microbatches flow through P stages in M + P - 1
ticks; every stage applies its layer block each tick (bubble fraction
(P-1)/(M+P-1)). The backward pass is jax autodiff through the tick scan,
which reverses the rotation into the symmetric backward pipeline. Combine
with ``remat`` so each stage keeps only block boundaries alive.

Composition: only 'pipe' is MANUAL (``shard_map(axis_names={'pipe'})``) —
every other mesh axis stays automatic, so 'data'/'fsdp' batch sharding and
'model' tensor parallelism inside a stage compose for free: the stage's
matmuls see model-sharded weights (the 'pp_tp' rules) and GSPMD inserts the
tensor-parallel collectives, while the stage-to-stage rotation stays an
explicit ``ppermute``. 'seq' (ring attention) composes too, but not by
nesting (the nested partial-manual backward is rejected by Shardy's
lowering): pass ``seq_axis`` and the SAME shard_map goes manual over
{pipe, seq}, activations arrive sequence-sharded, and the stage body runs
the manual ring-attention collective (ops/attention.py
``backend='ring_manual'``) so K/V rotate over 'seq' inside this region —
pp x sp x tp in one step (tests/test_pipeline.py equivalence vs dp).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from bert_pytorch_tpu.parallel.mesh import AXIS_PIPE, AXIS_SEQ


def shard_map(f, *, mesh, axis_names, in_specs, out_specs):
    """``jax.shard_map`` manual over ``axis_names`` only; every other mesh
    axis stays automatic (module docstring, "Composition")."""
    return jax.shard_map(f, mesh=mesh, axis_names=axis_names,
                         in_specs=in_specs, out_specs=out_specs)


def _pcast_varying(x, axis_name):
    return jax.lax.pcast(x, axis_name, to="varying")


def stage_layer_count(n_layers: int, n_stages: int) -> int:
    if n_layers % n_stages != 0:
        raise ValueError(
            f"num_hidden_layers={n_layers} must divide by pipeline stages "
            f"={n_stages} (contiguous equal blocks per stage)"
        )
    return n_layers // n_stages


def gpipe(
    stage_fn: Callable[..., jax.Array],
    stacked_params: Any,
    x: jax.Array,
    consts: Any,
    mesh: Mesh,
    replicated: Any = None,
    axis: str = AXIS_PIPE,
    seq_axis: str = None,
    x_seq_dim: int = 2,
    consts_seq_dims: Any = None,
) -> jax.Array:
    """Run ``x`` microbatches through the pipelined layer stack.

    Args:
      stage_fn: ``(local_params, x_mb, consts_mb, replicated, stage_id,
        mb_idx) -> y_mb``; applies one stage's L/P layers to one microbatch.
        ``mb_idx`` is the microbatch index (for PRNG folding); during bubble
        ticks it is clipped garbage and the result is discarded.
      stacked_params: pytree with leaves ``[L, ...]``, sharded over ``axis``
        on dim 0 (the 'pp'/'pp_tp' rules in parallel/mesh.py); any 'model'
        sharding on other dims flows through the automatic axes.
      x: ``[M, B, ...]`` microbatched activations, replicated over ``axis``;
        batch sharding over 'data'/'fsdp' flows through automatically.
      consts: pytree of per-microbatch side inputs (e.g. the attention bias),
        leaves ``[M, B, ...]``, sharded like ``x``.
      mesh: the device mesh; ``mesh.shape[axis]`` is the stage count.
      replicated: pytree passed to ``stage_fn`` verbatim on every stage
        (fully replicated — e.g. a PRNG key). Traced values must come in
        this way rather than by closure: ``shard_map`` rejects closed-over
        tracers.
      seq_axis: if set (the 'pp_sp' composition), that mesh axis joins the
        manual set and activations/consts are SHARDED over it — each device
        holds an S/n sequence slice and ``stage_fn`` must run the manual
        ring-attention body (attention ``backend='ring_manual'``) so K/V
        rotate over ``seq_axis`` inside this same region. One shard_map
        manual over {pipe, seq} sidesteps the nested-manual backward that
        Shardy rejects (the reason pp x sp was previously refused). Must
        be the mesh axis literally named 'seq': the ring_manual attention
        body and the stage dropout folding hardcode that axis name.
      x_seq_dim: dimension of ``x`` carrying the sequence (default 2:
        ``[M, B, S, ...]``).
      consts_seq_dims: pytree matching ``consts`` giving each leaf's
        sequence dimension (-1 = replicated over ``seq_axis``).

    Returns ``[M, B, ...]`` outputs, replicated over ``axis`` (every stage
    ends up with the full result — heads after the pipeline run replicated)
    and, when ``seq_axis`` is set, still sequence-sharded over it.
    """
    n_stages = mesh.shape[axis]
    n_mb = x.shape[0]
    if n_mb < n_stages:
        raise ValueError(
            f"need at least as many microbatches as pipeline stages: "
            f"{n_mb} < {n_stages} (the bubble would dominate anyway)"
        )
    if seq_axis is not None and seq_axis != AXIS_SEQ:
        # The ring_manual attention body (ops/attention.py) and the stage
        # dropout folding (pretrain.make_pp_train_step) hardcode the axis
        # name 'seq'; a differently-named axis would shard the activations
        # here but trace an unbound axis name deep inside the stage body.
        raise ValueError(
            f"gpipe seq_axis must be the mesh axis named 'seq' "
            f"(got {seq_axis!r})")
    if seq_axis is None and mesh.shape.get(AXIS_SEQ, 1) > 1:
        # Without the manual-ring composition, a seq>1 mesh would need ring
        # attention's own 'seq'-manual shard_map NESTED inside this region;
        # that type-checks, but Shardy's lowering verifier rejects the
        # backward pass today (propagation shards a residual dimension as
        # {pipe, seq} and "manual axes must come before free axes" within a
        # dim sharding). Callers compose pp with 'seq' by passing
        # ``seq_axis`` instead (pretrain.make_pp_train_step does).
        raise ValueError(
            "pipeline parallelism with a 'seq' mesh axis requires the "
            "manual ring composition: pass seq_axis='seq' (and a "
            "ring_manual stage_fn); see parallel/pipeline.py"
        )

    # 'pipe' (and 'seq' under pp_sp) are manual: specs mention only the
    # stacked-layer axis and the activation sequence axis, and every other
    # mesh axis (data/fsdp batch sharding, 'model' tensor parallelism)
    # keeps flowing through GSPMD automatically.
    manual = frozenset({axis}) if seq_axis is None else frozenset({axis, seq_axis})

    def param_spec(leaf):
        return P(axis, *(None,) * (leaf.ndim - 1))

    def rep_spec(leaf):
        return P(*(None,) * leaf.ndim)

    def seq_spec(leaf, seq_dim):
        if seq_axis is None or seq_dim < 0:
            return rep_spec(leaf)
        names = [None] * leaf.ndim
        names[seq_dim] = seq_axis
        return P(*names)

    # XLA's CPU AllReducePromotion pass crashes ("Invalid binary
    # instruction opcode copy") cloning bf16 all-reduces, and this region
    # implies two: the forward's last-stage psum and the transpose-inserted
    # psum for the cotangent of ``x`` (replicated over 'pipe' in its
    # in-spec). On the CPU test/dryrun path widen the boundary to f32 —
    # the TPU path keeps the half-width bf16 collectives over ICI.
    cpu_bf16 = x.dtype == jnp.bfloat16 and jax.default_backend() == "cpu"
    orig_dtype = x.dtype
    if cpu_bf16:
        x = x.astype(jnp.float32)

    x_spec = seq_spec(x, x_seq_dim if x_seq_dim is not None else -1)
    if consts_seq_dims is None:
        consts_specs = jax.tree_util.tree_map(rep_spec, consts)
    else:
        consts_specs = jax.tree_util.tree_map(seq_spec, consts, consts_seq_dims)

    in_specs = (
        jax.tree_util.tree_map(param_spec, stacked_params),
        x_spec,
        consts_specs,
        jax.tree_util.tree_map(rep_spec, replicated),
    )

    @partial(
        shard_map,
        mesh=mesh,
        axis_names=manual,
        in_specs=in_specs,
        out_specs=x_spec,
    )
    def run(local_params, x_local, consts_local, replicated_local):
        stage = jax.lax.axis_index(axis)
        ticks = n_mb + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        if seq_axis is not None:
            # Mark the (fp32 master) stage params varying over 'seq' HERE,
            # before any bf16 cast inside the stage body: the transpose of
            # this explicit pvary is the cross-shard cotangent psum, so it
            # runs on the fp32 cotangents (better gradient-reduction
            # numerics, and it sidesteps an XLA CPU AllReducePromotion
            # crash on the bf16 psums the auto-inserted invariance
            # conversions would otherwise create — Shardy leaks sharding
            # custom-calls into those reductions' to_apply computations).
            local_params = jax.tree_util.tree_map(
                lambda p: _pcast_varying(p, seq_axis),
                local_params)

        def tick(carry, t):
            outs, act = carry
            mb = jnp.clip(t - stage, 0, n_mb - 1)
            x_t = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(t, 0, n_mb - 1), 0, keepdims=False
            )
            c_t = jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_index_in_dim(c, mb, 0, keepdims=False),
                consts_local,
            )
            inp = jnp.where(stage == 0, x_t, act)
            y = stage_fn(local_params, inp, c_t, replicated_local, stage, mb)
            out_idx = t - (n_stages - 1)
            idx = jnp.clip(out_idx, 0, n_mb - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            keep = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(keep, y, cur), idx, 0
            )
            act_next = jax.lax.ppermute(y, axis, perm)
            return (outs, act_next), None

        # The carry is device-varying over 'pipe' after the first tick; mark
        # the zero initializers as varying so the scan carry type is stable
        # (shard_map's varying-manual-axes typing).
        outs0 = _pcast_varying(jnp.zeros_like(x_local), axis)
        act0 = _pcast_varying(jnp.zeros_like(x_local[0]), axis)
        (outs, _), _ = jax.lax.scan(
            tick, (outs0, act0), jnp.arange(ticks, dtype=jnp.int32)
        )
        # Only the last stage holds real outputs; give every stage the full
        # result so the (replicated) heads can run without a reshard.
        # (On the CPU path this psum — and the transpose-psum of x's
        # cotangent — run in f32 via the cpu_bf16 boundary cast above.)
        masked = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(masked, axis)

    out = run(stacked_params, x, consts, replicated)
    return out.astype(orig_dtype) if cpu_bf16 else out
