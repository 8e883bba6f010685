"""Device time of one learner update under the fourth hybrid trunk's scopes
(``pytorch_distributed_tpu/utils/profiling.py``): ``model.sconv`` (the
gated short-convolution mixers) with ``sconv.mix`` inside it around the
gate-conv-gate middle (``B * x``, the taps, ``C *``).

Read by the rules of ``kda_scopes``: only ops inside whole events of the
cell's step module (``model_scopes.ops_in_steps``), self time per op, per
update, averaged over the chips that ran the step.  An op's model part is
the INNERMOST ``model.*`` scope on its ``tf_op`` path; ``sconv.mix`` is a
scope INSIDE ``model.sconv`` and counts wherever it stands on such an op's
path.  A program that names neither scope (another model family, the
parent of the PR that added them) reads nothing: None, never 0.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Sequence, Tuple

from . import model_scopes, phases, trace as T

SCONV = "model.sconv"
SCONV_MIX = "sconv.mix"
_MODEL_SCOPE = re.compile(r"(?<![\w.])(model\.[a-z_]+)(?![\w.])")
_MIX = re.compile(r"(?<![\w.])" + re.escape(SCONV_MIX) + r"(?![\w.])")


def parts_of(tf_op: Optional[str]) -> Tuple[str, ...]:
    """``("sconv",)`` where an op's innermost model scope is ``model.sconv``,
    ``("sconv", "sconv_mix")`` where ``sconv.mix`` stands on its path too,
    else ()."""
    found = _MODEL_SCOPE.findall(tf_op) if tf_op else ()
    if not found or found[-1] != SCONV:
        return ()
    return ("sconv", "sconv_mix") if _MIX.search(tf_op) else ("sconv",)


def per_update_ms(devices: Sequence[phases.DevicePlane],
                  window: Optional[T.Interval], step_modules: Sequence[str],
                  updates_per_dispatch: int) -> Dict[str, float]:
    """``{"sconv": ms, "sconv_mix": ms}`` per update where the step program
    names ``model.sconv``, or {} where it does not."""
    chips = model_scopes.ops_in_steps(devices, window, step_modules)
    totals: Dict[str, float] = {}
    for d, self_ns, _events, steps in chips:
        for meta_id, ns in self_ns.items():
            parts = parts_of(d.meta[meta_id].tf_op)
            if parts:
                totals.setdefault("sconv_mix", 0.0)
            for part in parts:
                totals[part] = totals.get(part, 0.0) + ns / (
                    1e6 * updates_per_dispatch * steps)
    return {k: v / len(chips) for k, v in totals.items()}


@functools.lru_cache(maxsize=2)
def _of_file(path: str, step_modules: Tuple[str, ...],
             updates_per_dispatch: int) -> Dict[str, float]:
    devices, window = model_scopes._planes(path)    # decoded once a run
    return per_update_ms(devices, window, step_modules, updates_per_dispatch)


def read(ctx, part: str) -> Optional[float]:
    """ms per update of ``sconv`` or ``sconv_mix`` in this run's trace, or
    None where there is nothing to read."""
    path = model_scopes._trace_of(ctx)
    if path is None:
        return None
    return _of_file(path, tuple(ctx.cell.traffic.get("step_modules", ())),
                    int(ctx.result.updates_per_dispatch)).get(part)
