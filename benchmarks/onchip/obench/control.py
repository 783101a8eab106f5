"""The control of the comparison that decides ``correct``.

The configurations guarantee exact answers.  The shortcut that would tempt
a later change is to drop the executor's capacity retries: run each plan
once, at the engine's starting capacity, and return whatever rows fit.
``ControlEngine`` is the program's executor with that shortcut switched on
(``truncate``); every answer that needed more room than the starting
capacity then comes back short, and the comparison has to call the run not
correct.  It uses the executor's internal steps (``_Run``, ``_eval_node``,
``_program``, ``_collect_program``), so it follows them if they change.
"""
from __future__ import annotations


def control_engine_class():
    import numpy as np

    from obench.harness import traced_engine_class
    from repro.engine import distributed as dist

    class ControlEngine(traced_engine_class()):
        truncate = True

        def _attempt(self, plan, cap):
            if not self.truncate:
                return super()._attempt(plan, cap)
            run = dist._Run(self.cap)
            rel = self._eval_node(plan.root, run)        # overflow flags ignored
            collect = self._program(("collect",), lambda: dist._collect_program(self.mesh))
            data, valid = collect(rel.data, rel.valid)
            return rel, np.asarray(data), np.asarray(valid), run

    return ControlEngine
