"""Weight quantization for the inference fast path (docs/serving.md).

Serving never updates weights, so the fp32 master copies training needs
are pure overhead there: a BERT-large replica holds ~1.3 GB of fp32
matmul weights that int8 stores in ~330 MB. This module owns the two
inference quantization levels (ZeroQuant lineage, arXiv:2206.01861 —
see PAPERS.md):

* ``"bf16"`` — matmul kernels and dense biases stored bfloat16; a pure
  storage cast (the compute path already runs bf16 activations).
* ``"int8"`` — matmul kernels stored int8 with ONE symmetric per-tensor
  scale (per-layer for the encoder's ``nn.scan`` stacks, whose kernels
  carry a leading 'layers' axis); the serve forward quantizes
  activations per token on the fly and runs ``int8 x int8 -> int32``
  GEMMs, rescaling once by ``act_scale * kernel_scale``. Biases ride
  bf16.

Embeddings and LayerNorm parameters stay fp32 in BOTH modes: they are a
small fraction of the bytes, they feed normalization statistics where
precision matters, and the MLM decoder is weight-tied to the word
embedding. The tiny task-head output layers (``EXCLUDE_MODULES``) also
skip int8 — a 2-class classifier kernel saves nothing and sits right
before the softmax where quantization noise is least welcome.

The quantization RULES live here once and are consumed from both sides:
:func:`quantize_params` converts an in-memory fp32 pytree (the engine's
demo/random-init path), and :func:`convert_module` is the per-module
hook :func:`bert_pytorch_tpu.utils.checkpoint.load_params_only` calls
from its STREAMING msgpack decode — each tensor converts as its bytes
arrive, so the full fp32 tree never exists on the serving host.

Measured on this repo's CPU CI box (XLA CPU has no fast s8 GEMM): int8
is ~3x SLOWER than fp32 per matmul — the latency win is a TPU(MXU)
property; CPU tests prove parity and the 4x weight-byte reduction
(tests/test_inference_fastpath.py).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

MODES = ("bf16", "int8")

# Dense modules whose kernels stay OUT of int8 (downgraded to bf16):
# the per-task output layers, each a [hidden, <=num_labels] matmul that
# is noise-sensitive (pre-softmax) and byte-irrelevant.
EXCLUDE_MODULES = frozenset({"classifier", "qa_outputs", "seq_relationship"})

# Symmetric int8 range. 127 (not 128) keeps the scale symmetric around
# zero so -w and +w quantize to -q and +q exactly.
_QMAX = 127.0


def check_mode(mode: Optional[str]) -> Optional[str]:
    if mode is not None and mode not in MODES:
        raise ValueError(f"quantize mode must be one of {MODES} or None, "
                         f"got {mode!r}")
    return mode


def quantize_array(w, per_axis0: bool = False
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """``(q_int8, scale_fp32)`` with symmetric per-tensor scaling.

    ``per_axis0=True`` treats the leading axis as a stack of independent
    tensors (the encoder's ``nn.scan`` layer stacks) and returns one
    scale per slice — shape ``(L,)`` — so a quiet layer is not forced
    onto a loud layer's grid. Host-side (numpy): this runs at load time,
    tensor by tensor, inside the streaming checkpoint decode.
    """
    w = np.asarray(w, dtype=np.float32)
    if per_axis0 and w.ndim >= 2:
        axes = tuple(range(1, w.ndim))
        amax = np.max(np.abs(w), axis=axes)
        scale = np.maximum(amax, 1e-12) / _QMAX
        bshape = (-1,) + (1,) * (w.ndim - 1)
        q = np.rint(w / scale.reshape(bshape))
    else:
        amax = np.max(np.abs(w)) if w.size else 0.0
        scale = np.float32(max(float(amax), 1e-12) / _QMAX)
        q = np.rint(w / scale)
    q = np.clip(q, -_QMAX, _QMAX).astype(np.int8)
    return q, np.asarray(scale, np.float32)


def dequantize_array(q, scale) -> np.ndarray:
    """Inverse of :func:`quantize_array` (tests / debugging)."""
    q = np.asarray(q, np.float32)
    scale = np.asarray(scale, np.float32)
    if scale.ndim:
        scale = scale.reshape((-1,) + (1,) * (q.ndim - 1))
    return q * scale


def int8_matmul(x, q_kernel, kernel_scale):
    """``x @ dequant(q_kernel)`` computed as an int8 GEMM.

    ``x`` [..., K] float activations; ``q_kernel`` [K, N] int8;
    ``kernel_scale`` a scalar (per-tensor). Activations are quantized
    PER TOKEN (last-axis abs-max) on the fly — dynamic quantization, no
    calibration pass — then one ``int8 x int8 -> int32`` dot runs on
    the MXU and the result rescales once by both scales. fp32 out; the
    caller casts to its activation dtype.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    a_scale = jnp.maximum(amax, 1e-8) / _QMAX
    qx = jnp.clip(jnp.round(xf / a_scale), -_QMAX, _QMAX).astype(jnp.int8)
    acc = jax.lax.dot_general(
        qx, q_kernel,
        (((xf.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * a_scale * kernel_scale.astype(jnp.float32)


def quantize_symmetric(x, axes: Union[int, Sequence[int]]):
    """``(q_int8, scale)`` symmetric dynamic quantization of a traced
    array, one scale per slice of the axes NOT in ``axes`` (kept as
    size-1 dims so the scale broadcasts back over ``q``).

    The jnp twin of :func:`int8_matmul`'s inline per-token activation
    quantization, factored out at a caller-chosen grain: the int8
    attention kernel (ops/pallas/attention.py
    ``flash_attention_infer_int8``) reduces over ``axes=(1, 2)`` of a
    [BH, S, D] tensor for one symmetric scale PER HEAD — the ZeroQuant
    activation-scale machinery generalized beyond the dense layers.
    """
    axes = (axes,) if isinstance(axes, int) else tuple(axes)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / _QMAX
    q = jnp.clip(jnp.round(xf / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def _normalize_axis(axis: Union[int, Sequence[int]], ndim: int
                    ) -> Tuple[int, ...]:
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return tuple(a % ndim for a in axes)


class Int8Dense(nn.Module):
    """Drop-in for the serve heads' ``nn.Dense``/``nn.DenseGeneral``
    call sites with an int8 kernel + per-tensor scale + bf16 bias.

    ``features`` may be an int or a tuple (DenseGeneral-style), and
    ``axis`` the contracted input axes — the kernel parameter keeps the
    EXACT shape its fp32 counterpart has (``(*in_dims, *out_dims)``), so
    :func:`quantize_array` of a checkpoint kernel drops straight in.
    Parameter values from ``init`` are placeholders (zeros/ones): the
    engine always overwrites them, either from a checkpoint via the
    streaming quantized load or from a seeded fp32 init via
    :func:`quantize_params`. Never used in training — no custom_vjp, the
    rounding is non-differentiable by design.
    """

    features: Union[int, Tuple[int, ...]]
    axis: Union[int, Tuple[int, ...]] = -1
    dtype: Any = jnp.bfloat16
    kernel_axes: Tuple[str, ...] = ()
    bias_axes: Tuple[str, ...] = ()

    @nn.compact
    def __call__(self, x):
        features = (self.features,) if isinstance(self.features, int) \
            else tuple(self.features)
        axes = _normalize_axis(self.axis, x.ndim)
        in_dims = tuple(x.shape[a] for a in axes)
        kernel_shape = in_dims + features
        q = self.param(
            "kernel_q",
            nn.with_logical_partitioning(nn.initializers.zeros,
                                         self.kernel_axes),
            kernel_shape, jnp.int8)
        scale = self.param("kernel_scale", nn.initializers.ones,
                           (), jnp.float32)
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros,
                                         self.bias_axes),
            features, jnp.bfloat16)
        # Collapse contracted/feature dims to one 2D GEMM; DenseGeneral
        # semantics (contracted axes are trailing at these call sites).
        batch_shape = tuple(s for i, s in enumerate(x.shape)
                            if i not in axes)
        k = int(np.prod(in_dims))
        n = int(np.prod(features))
        y = int8_matmul(x.reshape(batch_shape + (k,)),
                        q.reshape(k, n), scale)
        y = y.reshape(batch_shape + features)
        return y.astype(self.dtype) + bias.astype(self.dtype)


def make_dense(quant: Optional[str], features, *, dtype, init_stddev: float,
               kernel_axes: Tuple[str, ...], name: str,
               axis: Union[int, Tuple[int, ...]] = -1,
               bias_axes: Optional[Tuple[str, ...]] = None):
    """One factory for every dense call site the serve heads share with
    training (models/bert.py): ``quant=None`` builds the EXACT
    ``nn.Dense``/``nn.DenseGeneral`` training uses (fp32 params),
    ``"bf16"`` the same module with bf16 param storage, ``"int8"`` the
    :class:`Int8Dense` replacement. Parameter names/shapes per mode are
    what :func:`convert_module` produces from a checkpoint.
    """
    check_mode(quant)
    bias_axes = bias_axes if bias_axes is not None else (
        (kernel_axes[-1],) if kernel_axes else ())
    if quant == "int8":
        return Int8Dense(features=features, axis=axis, dtype=dtype,
                         kernel_axes=kernel_axes, bias_axes=bias_axes,
                         name=name)
    param_dtype = jnp.bfloat16 if quant == "bf16" else jnp.float32
    from bert_pytorch_tpu.models.bert import bert_normal_init

    kwargs = dict(
        features=features,
        dtype=dtype,
        param_dtype=param_dtype,
        kernel_init=nn.with_logical_partitioning(
            bert_normal_init(init_stddev), kernel_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros, bias_axes),
        name=name,
    )
    if isinstance(features, int) and (axis == -1 or axis == (-1,)):
        return nn.Dense(**kwargs)
    return nn.DenseGeneral(axis=axis, **kwargs)


def exclude(quant: Optional[str]) -> Optional[str]:
    """Quant mode for the EXCLUDE_MODULES output layers: int8 downgrades
    to bf16 storage, bf16/None pass through."""
    return "bf16" if quant == "int8" else quant


# -- checkpoint/pytree conversion -------------------------------------------


def _is_stacked(path: Tuple[str, ...]) -> bool:
    # The encoder's nn.scan stacks per-layer params under a path
    # component named by nn.PARTITION_NAME ('layers'); those kernels
    # carry a leading L axis and want one scale per layer.
    return "layers" in path


def convert_module(path: Tuple[str, ...], module: dict,
                   mode: str) -> dict:
    """Apply the quantization rules to ONE decoded module dict (the
    innermost state-dict dicts holding array leaves). Called bottom-up
    by the streaming checkpoint decode and by :func:`quantize_params` —
    the single place the rules live.

    Only dicts containing a ``kernel`` leaf convert (Dense/DenseGeneral
    modules); everything else — embeddings, LayerNorm scale/bias, the
    MLM vocab bias — passes through at checkpoint precision (fp32).
    """
    check_mode(mode)
    kernel = module.get("kernel")
    if not hasattr(kernel, "dtype"):
        return module
    out = dict(module)
    excluded = any(p in EXCLUDE_MODULES for p in path)
    if mode == "int8" and not excluded:
        q, scale = quantize_array(kernel, per_axis0=_is_stacked(path))
        del out["kernel"]
        out["kernel_q"] = q
        out["kernel_scale"] = scale
    else:
        out["kernel"] = np.asarray(kernel).astype(jnp.bfloat16)
    if hasattr(out.get("bias"), "dtype"):
        out["bias"] = np.asarray(out["bias"]).astype(jnp.bfloat16)
    return out


def quantize_params(params: Any, mode: str) -> dict:
    """fp32 params pytree -> quantized plain-dict tree (the engine's
    random-init/demo path; the checkpoint path converts while streaming
    from disk instead — utils/checkpoint.py ``load_params_only``)."""
    check_mode(mode)
    from flax import serialization

    state = serialization.to_state_dict(params)

    def walk(path, node):
        if not isinstance(node, dict):
            return node
        out = {k: walk(path + (k,), v) for k, v in node.items()}
        leaves_only = {k: v for k, v in out.items()
                       if not isinstance(v, dict)}
        if "kernel" in leaves_only:
            for k in leaves_only:
                del out[k]
            out.update(convert_module(path, leaves_only, mode))
        return out

    return walk((), jax.tree_util.tree_map(np.asarray, state))


def weight_bytes(params: Any) -> int:
    """Total parameter bytes of a (possibly quantized) tree — the
    serving HBM the weights pin; /statsz and bench stamp it."""
    return int(sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
        if hasattr(leaf, "nbytes")))
