"""Device time of one learner update under the gated delta rule's scopes
(``pytorch_distributed_tpu/utils/profiling.py``: ``model.gdn``, and inside
it ``gdn.chunk`` around the recurrence proper), and the expert layers' rows
computed over rows routed.

``model_scopes.PARTS`` is the closed list the first hybrid trunk was given;
this reads the scopes a later trunk added by the same rules: only ops inside
whole events of the cell's step module (``model_scopes.ops_in_steps``), self
time per op, per update, averaged over the chips that ran the step.  An
op's model part is the INNERMOST ``model.*`` scope on its ``tf_op`` path;
``gdn.chunk`` is a scope INSIDE ``model.gdn`` and counts wherever it stands
on the path.  A program that names no such scope (another model family, the
parent of the PR that added them) reads nothing: None, never 0.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Sequence, Tuple

from . import model_scopes, phases, trace as T

GDN = "model.gdn"
GDN_CHUNK = "gdn.chunk"
_MODEL_SCOPE = re.compile(r"(?<![\w.])(model\.[a-z_]+)(?![\w.])")
_CHUNK = re.compile(r"(?<![\w.])" + re.escape(GDN_CHUNK) + r"(?![\w.])")


def scopes_of(tf_op: Optional[str]) -> Tuple[bool, bool]:
    """(the op's innermost model scope is ``model.gdn``, and ``gdn.chunk``
    stands on its path too)."""
    found = _MODEL_SCOPE.findall(tf_op) if tf_op else ()
    under = bool(found) and found[-1] == GDN
    return under, under and bool(_CHUNK.search(tf_op))


def per_update_ms(devices: Sequence[phases.DevicePlane],
                  window: Optional[T.Interval], step_modules: Sequence[str],
                  updates_per_dispatch: int) -> Dict[str, float]:
    """``{"gdn": ms, "gdn_chunk": ms}`` per update, or {} where the step
    program names no ``model.gdn``."""
    chips = model_scopes.ops_in_steps(devices, window, step_modules)
    totals = {"gdn": 0.0, "gdn_chunk": 0.0}
    seen = False
    for d, self_ns, _events, steps in chips:
        for meta_id, ns in self_ns.items():
            under, chunk = scopes_of(d.meta[meta_id].tf_op)
            if not under:
                continue
            seen = True
            ms = ns / (1e6 * updates_per_dispatch * steps)
            totals["gdn"] += ms
            if chunk:
                totals["gdn_chunk"] += ms
    if not seen:
        return {}
    return {k: v / len(chips) for k, v in totals.items()}


@functools.lru_cache(maxsize=2)
def _of_file(path: str, step_modules: Tuple[str, ...],
             updates_per_dispatch: int) -> Dict[str, float]:
    devices, window = model_scopes._planes(path)    # decoded once a run
    return per_update_ms(devices, window, step_modules, updates_per_dispatch)


def read(ctx, part: str) -> Optional[float]:
    """ms per update of ``gdn`` or ``gdn_chunk`` in this run's trace, or
    None where there is nothing to read."""
    path = model_scopes._trace_of(ctx)
    if path is None:
        return None
    return _of_file(path, tuple(ctx.cell.traffic.get("step_modules", ())),
                    int(ctx.result.updates_per_dispatch)).get(part)


def rows_computed_over_routed(ctx) -> Optional[float]:
    """The check update's ``learner/moe_rows_computed`` over its
    ``learner/moe_rows_here`` (models/hybrid.py ``moe_stats``, handed over
    in the family's ``agrees`` result): 1 = the grouped matmuls were handed
    the routed rows and no padding.  None where the family counts
    neither."""
    moe = ctx.result.check.get("moe", {})
    computed, here = moe.get("rows_computed"), moe.get("rows_here_mean")
    if computed is None or not here:
        return None
    return computed / here
