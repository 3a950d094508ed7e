"""The ``train_joyai`` kind's reading of a traced window: ``scopes.py``'s
reduction under the rules of ``scopes_joyai.json`` (the ``joyai_llm_flash``
family's scopes and kernels), how often each of the core's kernels ran
(``scopes_lm``'s count), and a second reduction in which everything the
multi-token-prediction module runs is the one class ``mtp`` (the table's
``module_part`` rules ahead of its part rules). As ``scopes_keye.py`` and its
siblings for their kinds: a reader gets ``trace_dir`` in its context, and a
trace without the family's scopes (a program that lacks them) gives ``None``:
the readers then report nothing and do not raise.
"""

from __future__ import annotations

import json
import os

from benchmarks.trace import scopes, scopes_lm
# (the readers' roofline floor: operations and bytes over peaks.json's peaks)
from benchmarks.trace.scopes_zaya import least_seconds  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
# the latent attention's projections, and everything of it
PROJ_PARTS = ("mla_q_proj", "mla_kv_proj", "attn_out")
MLA_PARTS = PROJ_PARTS + ("attn_rope", "mla_core", "mla_other")
# parts only the family's scopes produce: a trace without them is not this kind's
FAMILY_PARTS = ("mla_q_proj", "mla_kv_proj", "mla_core", "mla_other",
                "mtp_merge", "mtp_head")
MODULE = "mtp"

_reductions = {}  # path of a trace -> its reduction (one parse per process)


def rules() -> dict:
    with open(os.path.join(HERE, "scopes_joyai.json"),
              encoding="utf-8") as f:
        return json.load(f)


def for_run(ctx: dict) -> dict | None:
    """The reduction of the run's traced window under this kind's rules (with
    ``kernel_calls`` beside ``kernels`` and ``module_s``, the module's device
    seconds), or None where there is nothing to read."""
    if not ctx.get("summary") or not ctx.get("updates"):
        return None
    path = scopes.newest_trace(ctx.get("trace_dir"))
    if path is None:
        return None
    if path not in _reductions:
        planes, table = scopes.read_xspace(path), rules()
        found = scopes.reduce_scopes(planes, table=table)
        found["kernel_calls"] = scopes_lm.kernel_calls(planes, table["kernels"])
        found["has_family"] = any(
            part in found["by_part"] for part in FAMILY_PARTS)
        by_module = dict(table, part=table["module_part"] + table["part"])
        found["module_s"] = scopes.reduce_scopes(
            planes, table=by_module)["by_part"].get(MODULE, 0.0)
        _reductions[path] = found
        print("scopes_joyai: " + json.dumps({
            k: found[k] for k in ("busy_s", "by_pass", "by_part", "module_s",
                                  "unattributed_s", "kernels", "kernel_calls")}))
    found = _reductions[path]
    return found if found["has_family"] else None


def device_ms(ctx: dict, *parts: str) -> float | None:
    """Per update, the device time (all passes) of the parts named."""
    found = for_run(ctx)
    if not found:
        return None
    return 1e3 * sum(found["by_part"].get(p, 0.0) for p in parts) / ctx["updates"]
