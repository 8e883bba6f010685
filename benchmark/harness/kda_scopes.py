"""Device time of one learner update under the third hybrid trunk's scopes
(``pytorch_distributed_tpu/utils/profiling.py``): ``model.kda`` (the
channel-gated delta-rule mixers) with ``kda.chunk`` inside it around the
recurrence proper, ``model.mla`` (the latent attention) and ``model.mlp``
(the dense feed-forward block).

``model_scopes.PARTS`` is the closed list the first hybrid trunk was given
and ``gdn_scopes`` reads the second's; this reads a later trunk's by the
same rules: only ops inside whole events of the cell's step module
(``model_scopes.ops_in_steps``), self time per op, per update, averaged over
the chips that ran the step.  An op's model part is the INNERMOST
``model.*`` scope on its ``tf_op`` path; ``kda.chunk`` is a scope INSIDE
``model.kda`` and counts wherever it stands on the path.  A program that
names none of these scopes (another model family, the parent of the PR that
added them) reads nothing: None, never 0.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Sequence, Tuple

from . import model_scopes, phases, trace as T

# metric name (layer_metrics/phase_<x>_ms.py) -> the model's scope
PARTS = {"kda": "model.kda", "mla": "model.mla", "mlp": "model.mlp"}
KDA_CHUNK = "kda.chunk"
_MODEL_SCOPE = re.compile(r"(?<![\w.])(model\.[a-z_]+)(?![\w.])")
_CHUNK = re.compile(r"(?<![\w.])" + re.escape(KDA_CHUNK) + r"(?![\w.])")


def parts_of(tf_op: Optional[str]) -> Tuple[str, ...]:
    """The parts an op's time is filed under: its innermost model scope's,
    if that is one of ``PARTS``, and ``kda_chunk`` besides where ``kda.chunk``
    stands on a ``model.kda`` op's path."""
    found = _MODEL_SCOPE.findall(tf_op) if tf_op else ()
    part = next((k for k, v in PARTS.items() if found and v == found[-1]),
                None)
    if part is None:
        return ()
    if part == "kda" and _CHUNK.search(tf_op):
        return ("kda", "kda_chunk")
    return (part,)


def per_update_ms(devices: Sequence[phases.DevicePlane],
                  window: Optional[T.Interval], step_modules: Sequence[str],
                  updates_per_dispatch: int) -> Dict[str, float]:
    """``{part: ms per update}`` for the parts the step program names (and
    ``kda_chunk`` wherever it names ``model.kda``), or {} where it names
    none."""
    chips = model_scopes.ops_in_steps(devices, window, step_modules)
    totals: Dict[str, float] = {}
    for d, self_ns, _events, steps in chips:
        for meta_id, ns in self_ns.items():
            parts = parts_of(d.meta[meta_id].tf_op)
            if "kda" in parts:
                totals.setdefault("kda_chunk", 0.0)
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
    """ms per update of ``kda``, ``kda_chunk``, ``mla`` or ``mlp`` in this
    run's trace, or None where there is nothing to read."""
    path = model_scopes._trace_of(ctx)
    if path is None:
        return None
    return _of_file(path, tuple(ctx.cell.traffic.get("step_modules", ())),
                    int(ctx.result.updates_per_dispatch)).get(part)
