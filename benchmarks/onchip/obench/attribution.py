"""Attribute a profiler trace to the program's own spans and programs.

``tracing`` reduces a trace to the harness's view: the device's busy time,
its top operations, and the idle gaps named by the innermost ``bench.``
span.  The program marks its own layers as well (``repro.common.trace``:
``repro.`` spans such as ``serve.plan`` or ``engine.readback``) and gives
each device program a stable name (``jit_fed_star3``, ``jit_fed_exchange``,
``jit_fed_collect``, ``jit_dp_sweep``).  ``reduce`` here gives, over the
same window and with the same busy time and operations:

- each idle gap named by the innermost span of either prefix, the prefix
  cut, so that a gap inside ``bench.execute`` and
  ``repro.engine.assemble`` reads ``engine.assemble``;
- the idle seconds whose innermost span is the executor's (``engine.``);
- the device seconds of each program, from the trace's module line (one
  event per run of a program, so operations that nest are not counted
  twice), keyed by the module name with its fingerprint cut.

Against a program that marks nothing, the labels are the harness's alone.

    python3 benchmarks/onchip/obench/attribution.py <file.xplane.pb>

prints the reduction of one trace as JSON.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from obench import tracing  # noqa: E402
from obench.tracing import Event  # noqa: E402

PROGRAM_PREFIX = "repro."          # repro.common.trace.PREFIX
ENGINE = "engine."                 # the executor's spans, prefix cut


@dataclass
class Attribution(tracing.Summary):
    device_s_by_program: dict = field(default_factory=dict)  # program -> seconds
    idle_s_in_engine: float = 0.0


def program_name(module: str) -> str:
    """A module's name without its fingerprint: ``jit_fed_star3(81058)`` is
    ``jit_fed_star3``."""
    return re.sub(r"\(\d+\)$", "", module)


def read(path: str) -> tuple[list[list[Event]], list[list[Event]], list[Event]]:
    """Device operations and program runs per chip, and the spans of both
    prefixes, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    devices, spans = tracing.read(path)
    modules = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            if tracing._device_line(plane) is None:
                continue
            line = {ln.name: ln for ln in plane.lines}.get("XLA Modules")
            modules.append([Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                            for e in (line.events if line else [])])
        elif plane.name.startswith("/host:"):
            spans += [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                      for ln in plane.lines for e in ln.events
                      if e.name.startswith(PROGRAM_PREFIX)]
    return devices, modules, spans


def reduce(devices: list[list[Event]], modules: list[list[Event]], spans: list[Event],
           top: int = 10) -> Attribution:
    """``tracing.reduce`` with the program's spans among the harness's, and
    the device seconds of each program in the window."""
    # the harness's reduction labels a gap by its span's name with the
    # harness prefix cut: give the program's spans that prefix in its place
    as_harness = [s if s.name.startswith(tracing.SPAN_PREFIX) else
                  Event(tracing.SPAN_PREFIX + s.name[len(PROGRAM_PREFIX):], s.start, s.end)
                  for s in spans]
    base = tracing.reduce(devices, as_harness, top)
    win = next(s for s in spans if s.name == tracing.WINDOW)
    by_program: dict[str, float] = defaultdict(float)
    for mods in modules:
        for m in mods:
            d = min(m.end, win.end) - max(m.start, win.start)
            if d > 0:
                by_program[program_name(m.name)] += d / max(len(modules), 1)
    return Attribution(
        **vars(base), device_s_by_program=dict(by_program),
        idle_s_in_engine=sum(s for k, s in base.idle_by_span.items() if k.startswith(ENGINE)))


if __name__ == "__main__":
    print(json.dumps(dataclasses.asdict(reduce(*read(sys.argv[1]))), indent=1))
