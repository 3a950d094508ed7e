"""The ``train_keye`` kind's reading of a traced window: ``scopes.py``'s
reduction under the rules of ``scopes_keye.json`` (the ``KeyeVL2``
family's scopes and kernels), and how often each flash kernel ran
(``scopes_lm``'s count). As ``scopes_lm.py``, ``scopes_laguna.py``,
``scopes_phi4flash.py``, ``scopes_zaya.py`` and ``scopes_qwen3next.py`` for
their kinds: a reader gets
``trace_dir`` in its context, and a trace without the family's scopes (a
program that lacks them) gives ``None``: the readers then report nothing and
do not raise.
"""

from __future__ import annotations

import json
import os

from benchmarks.trace import scopes, scopes_lm
# (the readers' roofline floor: operations and bytes over peaks.json's peaks)
from benchmarks.trace.scopes_zaya import least_seconds  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
# parts only the family's scopes produce: a trace without them is not this kind's
FAMILY_PARTS = ("dsa_index_proj", "dsa_scores", "dsa_select", "dsa_core",
                "dsa_index_loss", "dsa_other")
# everything under the ``dsa`` scope
DSA_PARTS = FAMILY_PARTS
# the indexer: its projections, its scores (the choice's) and its objective
INDEXER_PARTS = ("dsa_index_proj", "dsa_scores", "dsa_index_loss")
# the attention layer outside the ``dsa`` scope
ATTENTION_PROJ_PARTS = ("attn_qkv", "attn_qk_norm", "attn_rope", "attn_out")

_reductions = {}  # path of a trace -> its reduction (one parse per process)


def rules() -> dict:
    with open(os.path.join(HERE, "scopes_keye.json"),
              encoding="utf-8") as f:
        return json.load(f)


def for_run(ctx: dict) -> dict | None:
    """The reduction of the run's traced window under this kind's rules (with
    ``kernel_calls`` beside ``kernels``), or None where there is nothing to
    read."""
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
        _reductions[path] = found
        print("scopes_keye: " + json.dumps({
            k: found[k] for k in ("busy_s", "by_pass", "by_part",
                                  "unattributed_s", "kernels", "kernel_calls")}))
    found = _reductions[path]
    return found if found["has_family"] else None


def device_ms(ctx: dict, *parts: str) -> float | None:
    """Per update, the device time (all passes) of the parts named."""
    found = for_run(ctx)
    if not found:
        return None
    return 1e3 * sum(found["by_part"].get(p, 0.0) for p in parts) / ctx["updates"]
