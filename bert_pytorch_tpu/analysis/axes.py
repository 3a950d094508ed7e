"""The axes registry: mesh-axis names and per-strategy logical rules,
the declarative authority the sharding checks (SD601/SD602/SD603)
enforce against.

This mirrors ``parallel/mesh.py`` (``MESH_AXES``, the ``AXIS_*``
constants, ``_BASE_RULES``/``_STRATEGY_RULES``) the same way
``analysis/concurrency.py`` mirrors the lock discipline: the analysis
package must stay stdlib-only and jax-free (files are parsed, never
imported), so it cannot import the real tables — instead this module
restates them and ``tests/test_jaxlint.py`` pins the two copies
together by PARSING mesh.py's AST. Drift fails tier-1, not a refactor
three PRs later.

Why a registry at all: the "one mesh" refactor (ROADMAP) rewrites every
collective/PartitionSpec/axis-rule site in the codebase. A collective
over a typo'd axis name traces fine and crashes (or silently
mis-reduces) only under the mesh shape that exercises it; a logical
name with no rule under some strategy silently REPLICATES the parameter
— the exact fsdp bug class the ZeRO lineage warns about. With the
registry, both become lint findings at commit time, and the refactor
updates ONE table (mesh.py) plus its mirror here.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Optional, Tuple

# -- mesh axes (mirror of parallel/mesh.py MESH_AXES + AXIS_*) -----------

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_PIPE = "pipe"
AXIS_SEQ = "seq"
AXIS_MODEL = "model"
AXIS_EXPERT = "expert"

MESH_AXES: Tuple[str, ...] = (
    AXIS_DATA, AXIS_FSDP, AXIS_PIPE, AXIS_SEQ, AXIS_MODEL, AXIS_EXPERT)
BATCH_AXES: Tuple[str, ...] = (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)

# The constant spellings model/runner code must import instead of raw
# literals (the SD603 contract). Name -> axis value, for messages and
# the mesh.py mirror test.
AXIS_CONSTANTS: Dict[str, str] = {
    "AXIS_DATA": AXIS_DATA,
    "AXIS_FSDP": AXIS_FSDP,
    "AXIS_PIPE": AXIS_PIPE,
    "AXIS_SEQ": AXIS_SEQ,
    "AXIS_MODEL": AXIS_MODEL,
    "AXIS_EXPERT": AXIS_EXPERT,
}

# -- logical-axis rules (mirror of mesh.py _BASE_RULES/_RULE_TEMPLATE/
# _STRATEGY_AXES) --
# Values are mesh axes (or None = replicated); only the KEY COVERAGE is
# what SD602 enforces — an unmatched logical name silently replicates —
# but the mirror keeps the values too so the consistency test can pin
# the whole table.

BASE_RULES: Tuple[Tuple[str, object], ...] = (
    ("batch", BATCH_AXES),
    ("seq_act", AXIS_SEQ),
    ("pos", None),
    ("types", None),
    ("classes", None),
    ("layers", None),
    ("experts", None),
    ("vocab_rows", None),
)

# Mirror of mesh.py _RULE_TEMPLATE: per param logical axis, the mesh axis
# that controls it when active in the mesh spec (else replicated). The
# one-mesh refactor derives EVERY strategy product's rules from this one
# table; a new logical name in model code must land here (or in
# BASE_RULES) or SD602 flags it as silently replicating.
RULE_TEMPLATE: Tuple[Tuple[str, object], ...] = (
    ("embed", AXIS_FSDP),
    ("embed_out", AXIS_MODEL),
    ("vocab", AXIS_MODEL),
    ("heads", AXIS_MODEL),
    ("kv", None),
    ("mlp", AXIS_MODEL),
)

# Mirror of mesh.py _STRATEGY_AXES: legacy alias -> activated mesh axes.
STRATEGY_AXES: Dict[str, Tuple[str, ...]] = {
    "dp": (),
    "sp": (AXIS_SEQ,),
    "fsdp": (AXIS_FSDP,),
    "tp": (AXIS_MODEL,),
    "tp_fsdp": (AXIS_FSDP, AXIS_MODEL),
    "pp": (AXIS_PIPE,),
    "pp_tp": (AXIS_PIPE, AXIS_MODEL),
}


def derive_rules(active) -> Tuple[Tuple[str, object], ...]:
    """Stdlib re-derivation of mesh.derive_rules: param rules for a set
    of active mesh axes (an active 'pipe' prepends the stacked-layer
    rule; template rules resolve to their controlling axis when active,
    else None)."""
    active = frozenset(active)
    rules = []
    if AXIS_PIPE in active:
        rules.append(("layers", AXIS_PIPE))
    if AXIS_EXPERT in active:
        rules += [("experts", AXIS_EXPERT), ("vocab_rows", AXIS_EXPERT)]
    for name, axis in RULE_TEMPLATE:
        rules.append((name, axis if axis is not None and axis in active
                      else None))
    return tuple(rules)


# Legacy aliases, regenerated exactly like mesh.py regenerates its
# _STRATEGY_RULES (tests/test_mesh.py pins the two derivations equal).
STRATEGY_RULES: Dict[str, Tuple[Tuple[str, object], ...]] = {
    name: derive_rules(axes) for name, axes in STRATEGY_AXES.items()
}

# Every expressible strategy PRODUCT over the param-sharding axes
# (fsdp × pipe × model, with/without seq): SD602 coverage runs over
# these generated products too, so a logical name that resolves under
# the legacy aliases but not under some composed mesh is still caught.
_PRODUCT_AXES = (AXIS_FSDP, AXIS_PIPE, AXIS_SEQ, AXIS_MODEL, AXIS_EXPERT)

PRODUCT_RULES: Dict[str, Tuple[Tuple[str, object], ...]] = {}
for _mask in range(1 << len(_PRODUCT_AXES)):
    _active = tuple(a for i, a in enumerate(_PRODUCT_AXES)
                    if _mask & (1 << i))
    _name = "dp" if not _active else "dp*" + "*".join(_active)
    PRODUCT_RULES[_name] = derive_rules(_active)
del _mask, _active, _name


def strategies() -> Tuple[str, ...]:
    """Legacy aliases plus every generated axis product."""
    return tuple(sorted(set(STRATEGY_RULES) | set(PRODUCT_RULES)))


def logical_coverage(strategy: str) -> FrozenSet[str]:
    """Logical names that RESOLVE (to a mesh axis or an explicit None =
    replicated) under ``strategy`` (a legacy alias or a generated
    product name): its own rules plus the shared base rules — the
    first-wins matching of mesh.logical_axis_rules means key membership
    in the union is exactly 'has a rule'."""
    rules = (STRATEGY_RULES.get(strategy)
             if strategy in STRATEGY_RULES else PRODUCT_RULES[strategy])
    return frozenset(name for name, _ in rules + BASE_RULES)


def uncovered_strategies(logical_name: str) -> Tuple[str, ...]:
    """Declared strategies under which ``logical_name`` has NO rule (and
    would silently replicate). Empty = fully covered."""
    return tuple(s for s in strategies()
                 if logical_name not in logical_coverage(s))


def is_mesh_axis(name: str) -> bool:
    return name in MESH_AXES


def constant_for(axis: str) -> Optional[str]:
    """The AXIS_* constant name for a mesh axis value (for messages)."""
    for const, value in AXIS_CONSTANTS.items():
        if value == axis:
            return const
    return None
