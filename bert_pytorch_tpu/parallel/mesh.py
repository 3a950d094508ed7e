"""Device mesh construction and logical-axis rules.

Replaces the reference's process-group bootstrap (run_pretraining.py:183-185
``init_process_group('nccl')`` + torchrun rendezvous, sbatch:64-92). On TPU a
"process group" is a `jax.sharding.Mesh` over `jax.devices()`; multi-host
initialization is `jax.distributed.initialize` (see
bert_pytorch_tpu/parallel/launcher.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# The canonical mesh-axis names. Everything outside parallel/ must spell
# axes through these constants (enforced by jaxlint SD603, mirrored in
# analysis/axes.py): the one-mesh refactor then renames or splits an axis
# by editing this block, not by a repo-wide string hunt.
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_PIPE = "pipe"
AXIS_SEQ = "seq"
AXIS_MODEL = "model"
# Expert parallelism (ops/moe.py ``exchanged_experts``): a layer's experts
# are divided over this axis, the rows of the batch too (as over ``data``),
# and token-slots cross it to the chip that holds their expert.
AXIS_EXPERT = "expert"

MESH_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_PIPE, AXIS_SEQ, AXIS_MODEL,
             AXIS_EXPERT)
# The axes the rows of a batch are divided over.
BATCH_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)


@dataclasses.dataclass
class MeshConfig:
    """Sizes of each mesh axis; -1 on ``data`` means 'all remaining devices'.

    The product must equal the device count. The default is the reference's
    capability: pure data parallelism over every chip (§2.2). ``pipe`` is the
    pipeline-stage axis (parallel/pipeline.py).

    ``dcn_data`` > 1 builds a HYBRID mesh for multi-slice pods: that many
    data-parallel replicas span slices over DCN while every other axis
    (and the remaining data parallelism) stays within a slice on ICI —
    the standard multi-slice recipe (gradient all-reduce decomposes into
    a fast ICI phase and one small DCN phase per slice pair; XLA does the
    decomposition once the device order encodes slice adjacency).
    ``dcn_process_granule`` treats each PROCESS as the DCN granule instead
    of each TPU slice — the CPU multi-process test analog, where "slice"
    boundaries are process boundaries.
    """

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    seq: int = 1
    model: int = 1
    expert: int = 1
    dcn_data: int = 1
    dcn_process_granule: bool = False

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        """Per-ICI-granule axis sizes, in ``MESH_AXES``' order (the full
        mesh's data axis is ``resolve()[0] * dcn_data``)."""
        fixed = self.fsdp * self.pipe * self.seq * self.model * self.expert
        denom = fixed * self.dcn_data
        data = self.data
        if data == -1:
            if n_devices % denom != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"fsdp*pipe*seq*model*expert*dcn_data={denom}"
                )
            data = n_devices // denom
        if data * denom != n_devices:
            raise ValueError(
                f"mesh {data}x{self.fsdp}x{self.pipe}x{self.seq}"
                f"x{self.model}x{self.expert} (x{self.dcn_data} dcn)"
                f" != {n_devices} devices"
            )
        return (data, self.fsdp, self.pipe, self.seq, self.model, self.expert)


def create_mesh(
    mesh_config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the ('data', 'fsdp', 'pipe', 'seq', 'model', 'expert') mesh.

    Device order comes from `jax.devices()`, which JAX already returns in
    ICI-topology order — nearest-neighbor axes (model/seq) get the fastest
    links, matching the scaling-book layout recipe. With ``dcn_data`` > 1
    the device array instead comes from
    ``mesh_utils.create_hybrid_device_mesh`` so the data axis's leading
    dimension strides across DCN granules (slices, or processes under
    ``dcn_process_granule``) and every other axis stays granule-local.
    """
    mesh_config = mesh_config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    shape = mesh_config.resolve(len(devices))
    if mesh_config.dcn_data > 1:
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_hybrid_device_mesh(
            shape,
            (mesh_config.dcn_data,) + (1,) * (len(MESH_AXES) - 1),
            devices,
            process_is_granule=mesh_config.dcn_process_granule,
        )
    else:
        device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, MESH_AXES)


# Logical axis name -> mesh axis (or None = replicated), per strategy.
# Model code only knows logical names (bert.py); changing strategy never
# touches model code — this table is the entire parallelism configuration.
_BASE_RULES = [
    # batch shards over data (and fsdp, and the expert axis, if used)
    ("batch", BATCH_AXES),
    ("seq_act", "seq"),  # activation sequence axis (context parallelism)
    ("pos", None),
    ("types", None),
    ("classes", None),
    ("layers", None),  # scan axis; an active 'pipe' axis overrides this
    # the decoder families (models/decoder.py): a layer's stacked experts by
    # expert, embedding and head by row of the vocabulary; an active 'expert'
    # axis overrides both
    ("experts", None),
    ("vocab_rows", None),
]

# The rule TEMPLATE: for each param logical axis, the mesh axis that
# controls it WHEN that axis is active in the mesh spec (size > 1), else
# the param replicates (None). Rules for any strategy product — dp×fsdp,
# dp×pipe, dp×fsdp×pipe×tp — derive from this one table instead of a
# fixed enumeration of named strategies; the legacy names below are
# aliases that lower onto specs with byte-identical rules (pinned by
# tests/test_one_mesh.py::test_legacy_alias_rules_byte_identical).
_RULE_TEMPLATE = [
    ("embed", AXIS_FSDP),  # ZeRO-style gather-on-use sharding
    ("embed_out", AXIS_MODEL),
    ("vocab", AXIS_MODEL),
    ("heads", AXIS_MODEL),
    ("kv", None),  # per-head dim: never sharded (heads already split)
    ("mlp", AXIS_MODEL),
]

# Legacy strategy aliases -> the mesh axes they activate. 'dp' activates
# only the (always-on) data axis; 'sp' activates seq, which shards
# activations via the base 'seq_act' rule but no params — hence its rule
# list equals dp's.
_STRATEGY_AXES = {
    "dp": (),
    "sp": (AXIS_SEQ,),
    "fsdp": (AXIS_FSDP,),
    "tp": (AXIS_MODEL,),
    "tp_fsdp": (AXIS_FSDP, AXIS_MODEL),
    "pp": (AXIS_PIPE,),
    "pp_tp": (AXIS_PIPE, AXIS_MODEL),
}


def derive_rules(active) -> list[tuple]:
    """Param-sharding rules for the set of ACTIVE mesh axes.

    An active 'pipe' prepends ``('layers', 'pipe')`` — each pipeline stage
    holds L/P contiguous layers; the pipeline engine runs 'pipe' manually
    (explicit ppermute) and leaves the other axes to the compiler. An active
    'expert' prepends the decoder families' two rules (a layer's experts and
    the vocabulary's rows over it; ``pretrain.make_train_step`` runs that
    axis manually too). Every
    template rule then resolves to its controlling axis when active, else
    to None (replicated). Only param axes appear here; batch/seq_act
    sharding lives in ``_BASE_RULES`` (first-wins matching)."""
    active = frozenset(active)
    rules = []
    if AXIS_PIPE in active:
        rules.append(("layers", AXIS_PIPE))
    if AXIS_EXPERT in active:
        rules += [("experts", AXIS_EXPERT), ("vocab_rows", AXIS_EXPERT)]
    for name, axis in _RULE_TEMPLATE:
        rules.append((name, axis if axis is not None and axis in active
                      else None))
    return rules


# Derived per-alias tables, kept for introspection and the shardlint
# mirror (analysis/axes.py regenerates the same dict from the same two
# literal tables; tests/test_jaxlint.py pins them together by AST).
_STRATEGY_RULES = {
    name: derive_rules(axes) for name, axes in _STRATEGY_AXES.items()
}


class MeshSpecError(ValueError):
    """A mesh spec that cannot be realized, with the reason why."""


# Accepted spelling aliases for spec keys: strategy-flavored names map
# onto the canonical mesh axes.
_SPEC_KEY_ALIASES = {
    "dp": "data",
    "data": "data",
    "fsdp": "fsdp",
    "pipe": "pipe",
    "pp": "pipe",
    "seq": "seq",
    "sp": "seq",
    "ring": "seq",
    "model": "model",
    "tp": "model",
    "expert": "expert",
    "ep": "expert",
    "dcn": "dcn_data",
    "dcn_data": "dcn_data",
}


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative parallelism product: sizes of every mesh axis.

    The one-mesh configuration surface (``--mesh dp=4,fsdp=2,pipe=2``):
    device mesh, logical-axis rules, and collective wiring are all
    DERIVED from this — any axis product is expressible, and the combos
    that cannot work are rejected by :meth:`validate` with the reason.
    ``data == -1`` means 'all remaining devices'.
    """

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    seq: int = 1
    model: int = 1
    expert: int = 1
    dcn_data: int = 1

    @staticmethod
    def parse(text: str) -> "MeshSpec":
        """Parse ``"dp=4,fsdp=2,pipe=2,seq=1"`` (keys accept the
        strategy-flavored aliases pp→pipe, sp/ring→seq, tp→model,
        ep→expert)."""
        sizes = {}
        for item in str(text).split(","):
            item = item.strip()
            if not item:
                continue
            key, sep, value = item.partition("=")
            key = key.strip().lower()
            if key not in _SPEC_KEY_ALIASES:
                raise MeshSpecError(
                    f"unknown mesh-spec key '{key}' in {text!r}; "
                    f"options: {sorted(set(_SPEC_KEY_ALIASES))}")
            canon = _SPEC_KEY_ALIASES[key]
            if not sep:
                raise MeshSpecError(
                    f"mesh-spec entry {item!r} wants KEY=SIZE")
            try:
                size = int(value)
            except ValueError:
                raise MeshSpecError(
                    f"mesh-spec size for '{key}' must be an integer, "
                    f"got {value!r}") from None
            if canon in sizes:
                raise MeshSpecError(
                    f"mesh-spec key '{canon}' given twice in {text!r}")
            sizes[canon] = size
        spec = MeshSpec(**sizes)
        spec.validate()
        return spec

    def canonical(self) -> str:
        """Round-trippable spec string; inactive axes are elided."""
        parts = [f"dp={self.data}"]
        for key in ("fsdp", "pipe", "seq", "model", "expert"):
            size = getattr(self, key)
            if size != 1:
                parts.append(f"{key}={size}")
        if self.dcn_data != 1:
            parts.append(f"dcn={self.dcn_data}")
        return ",".join(parts)

    def as_dict(self) -> dict:
        """Plain-int dict for the (stdlib-only) checkpoint manifest."""
        return {"data": self.data, "fsdp": self.fsdp, "pipe": self.pipe,
                "seq": self.seq, "model": self.model,
                "expert": self.expert, "dcn_data": self.dcn_data}

    @staticmethod
    def from_dict(d: dict) -> "MeshSpec":
        known = {f.name for f in dataclasses.fields(MeshSpec)}
        return MeshSpec(**{k: int(v) for k, v in dict(d).items()
                           if k in known})

    def active_axes(self) -> frozenset:
        """Mesh axes with size > 1 (data counts when -1 = 'remaining')."""
        active = set()
        if self.data != 1:
            active.add(AXIS_DATA)
        for axis, size in ((AXIS_FSDP, self.fsdp), (AXIS_PIPE, self.pipe),
                           (AXIS_SEQ, self.seq), (AXIS_MODEL, self.model),
                           (AXIS_EXPERT, self.expert)):
            if size > 1:
                active.add(axis)
        return frozenset(active)

    def validate(self, *, n_devices: Optional[int] = None,
                 packed: bool = False) -> None:
        """Reject specs that cannot be realized, naming the reason.

        ``packed`` enables the sequence-packing compatibility check; pass
        ``n_devices`` to also enforce the axis-product divisibility."""
        for key in ("fsdp", "pipe", "seq", "model", "expert", "dcn_data"):
            size = getattr(self, key)
            if size < 1:
                raise MeshSpecError(
                    f"mesh-spec axis '{key}' must be >= 1, got {size}")
        if self.data < 1 and self.data != -1:
            raise MeshSpecError(
                f"mesh-spec axis 'data' must be >= 1 or -1 "
                f"(= all remaining devices), got {self.data}")
        if packed and self.seq > 1:
            raise MeshSpecError(
                "sequence packing composes with dp/fsdp/pipe/model but "
                "not with seq>1 (ring context parallelism): the packed "
                "block-diagonal attention mask ties together positions "
                "of one packed row, and the ring shards exactly that "
                "axis — segment boundaries cannot cross seq shards "
                "without a per-segment halo exchange")
        if n_devices is not None:
            try:
                self.mesh_config().resolve(n_devices)
            except MeshSpecError:
                raise
            except ValueError as e:
                # resolve() predates the spec layer; unify its divisibility
                # errors under the one spec-rejection type.
                raise MeshSpecError(str(e)) from None

    def mesh_config(self, *,
                    dcn_process_granule: bool = False) -> MeshConfig:
        return MeshConfig(data=self.data, fsdp=self.fsdp, pipe=self.pipe,
                          seq=self.seq, model=self.model,
                          expert=self.expert, dcn_data=self.dcn_data,
                          dcn_process_granule=dcn_process_granule)

    def rules(self) -> list[tuple]:
        """Full rule list for ``nn.logical_to_mesh_sharding``."""
        return derive_rules(self.active_axes()) + _BASE_RULES


def parse_mesh_spec(text: str) -> MeshSpec:
    """Module-level alias for :meth:`MeshSpec.parse`."""
    return MeshSpec.parse(text)


def logical_axis_rules(strategy="dp") -> list[tuple]:
    """Rule list for ``nn.logical_to_mesh_sharding``.

    Accepts a legacy strategy alias (str) or a :class:`MeshSpec`.
    Derived rules come first: matching is first-wins, and an active
    'pipe' axis overrides the base ``('layers', None)`` with
    ``('layers', 'pipe')``."""
    if isinstance(strategy, MeshSpec):
        return strategy.rules()
    if strategy not in _STRATEGY_AXES:
        raise ValueError(
            f"unknown strategy '{strategy}'; options: {sorted(_STRATEGY_AXES)}"
        )
    return derive_rules(_STRATEGY_AXES[strategy]) + _BASE_RULES


def current_mesh() -> Optional[Mesh]:
    """The concrete mesh of the enclosing ``with mesh:`` block — the
    context this codebase uses throughout — or None outside one.

    Model code asks while it is being traced under ``jit`` (ring
    attention needs the devices for its ``shard_map``). jax 0.9's public
    accessors do not answer that: ``jax.sharding.get_mesh()`` sees only
    ``jax.set_mesh`` and refuses to be called under ``jit``, and
    ``get_abstract_mesh()`` carries no devices. So this is the ONE private
    jax name the package uses; tests/test_mesh.py pins it."""
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh
