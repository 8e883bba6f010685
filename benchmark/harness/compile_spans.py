"""The program's own account of its set-up: the compile-path record of
``pytorch_distributed_tpu/utils/profiling.py`` (``compile_record``), which
files every trace, lower and compile-or-load span JAX reports under its
program name, on the clock of ``window.PhaseClock``.

Set-up, for these readers, ends when the first program whose module is one
of the cell's ``step_modules`` has its executable ready: the check and
``program_memory`` after the window are left out.  A module ``jit_multi``
is the program ``multi`` (a lower or backend span's ``jit(multi)``, a
trace span's ``multi``).  Every reader returns None where the program
keeps no such record (a tree from before PR 36) or no step program was
made ready, and raises nothing."""

from __future__ import annotations

from typing import Optional


def program_of(module: str) -> str:
    return module[len("jit_"):] if module.startswith("jit_") else module


def step_program(ctx):
    """The cell's step program's ``ProgramRecord``, or None."""
    try:
        from pytorch_distributed_tpu.utils import profiling
    except ImportError:
        return None
    record_of = getattr(profiling, "compile_record", None)
    record = record_of() if record_of is not None else None
    if record is None:
        return None
    modules = ctx.cell.traffic.get("step_modules", ())
    return record.first_ready_of(program_of(m) for m in modules)


def setup_total(ctx, field: str) -> Optional[float]:
    """``field`` of the process's totals when the step program was ready."""
    step = step_program(ctx)
    return getattr(step.at_ready, field) if step is not None else None


def step_span_s(ctx, which: str) -> Optional[float]:
    """Seconds of the step program's first ``which`` span: ``first_trace``
    (outermost), ``first_lower`` or ``first_ready`` (compile or load)."""
    step = step_program(ctx)
    span = getattr(step, which) if step is not None else None
    return span[1] - span[0] if span is not None else None
