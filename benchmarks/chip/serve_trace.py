"""Device time of the program's scopes inside the serving bucket
programs of a traced window (``jit_forward_program`` executions), read
by each op's ``tf_op`` scope path (``program_trace``)."""
from __future__ import annotations

from benchmarks.chip import program_trace

#: The module name XLA gives the engine's bucket programs.
MODULE = "jit_forward_program"


def scope_ms(r, scope: str) -> list[float] | None:
    """Device ms under ``scope`` in each bucket-program execution of the
    traced window on chip 0; None where no op lies under it."""
    pt = program_trace.current()
    if pt is None or not pt.tf_ops:
        return None
    chip = r.trace.chips[0]
    lo, hi = r.window
    per = [sum(o.end - o.start for o in chip.leaves_in(m.start, m.end)
               if pt.under(0, o.name, scope)) * 1e-6
           for m in chip.modules
           if m.name.startswith(MODULE) and lo <= m.start and m.end <= hi]
    return per if any(per) else None
