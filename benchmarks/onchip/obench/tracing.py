"""Reduce a profiler trace to what the device did in the measured window.

The harness marks its own spans on the host (``SPAN_PREFIX``): the window,
each ``submit`` and ``poll``, the load generator's waiting for a request,
and each plan's ``execute``.  ``reduce`` takes the device operations and
those spans and gives the device's busy time in the window (the union of
its operations' intervals, averaged over the chips), the operations that
took most time, and the longest idle gaps, each named by the innermost
harness span that was open at its middle: what the host was doing while the
device waited.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "bench."
WINDOW = "bench.window"


@dataclass
class Event:
    name: str
    start: float       # seconds, on the trace's clock
    end: float


@dataclass
class Summary:
    busy_s: float
    window_s: float
    device_ops: list = field(default_factory=list)   # [[name, seconds]], top 10
    idle_gaps: list = field(default_factory=list)    # [[span, seconds]], top 10
    idle_by_span: dict = field(default_factory=dict)  # span -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint, sorted union of intervals ``iv`` (n, 2)."""
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.append(np.flatnonzero(new)[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def _label(spans: list[Event], times: np.ndarray) -> np.ndarray:
    """For each of ``times``, the innermost harness span open then (the one
    that began last), without its prefix; ``"none"`` where none is.  Spans
    of one name never overlap: the harness opens them from one thread."""
    label = np.full(len(times), "none", dtype=object)
    began = np.full(len(times), -np.inf)
    for name in {s.name for s in spans} - {WINDOW}:
        iv = np.array(sorted((s.start, s.end) for s in spans if s.name == name))
        k = np.searchsorted(iv[:, 0], times, side="right") - 1
        kc = np.clip(k, 0, None)
        hit = (k >= 0) & (iv[kc, 1] > times) & (iv[kc, 0] >= began)
        began[hit] = iv[kc[hit], 0]
        label[hit] = name[len(SPAN_PREFIX):]
    return label


def reduce(devices: list[list[Event]], spans: list[Event], top: int = 10) -> Summary:
    """``devices``: each chip's operations; ``spans``: the harness's host
    spans, one of them ``WINDOW``."""
    win = [s for s in spans if s.name == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW} span")
    w0, w1 = win[0].start, win[0].end
    busy, per_op = [], defaultdict(float)
    gap_iv = []
    for ops in devices:
        iv = np.array([(max(e.start, w0), min(e.end, w1)) for e in ops
                       if e.end > w0 and e.start < w1], float).reshape(-1, 2)
        for e in ops:
            d = min(e.end, w1) - max(e.start, w0)
            if d > 0:
                per_op[e.name] += d
        u = _union(iv)
        busy.append(float(np.sum(u[:, 1] - u[:, 0])) if len(u) else 0.0)
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        gap_iv.append(edges[edges[:, 1] > edges[:, 0]])
    n = max(len(devices), 1)
    gap_iv = np.concatenate(gap_iv) if gap_iv else np.zeros((0, 2))
    length = gap_iv[:, 1] - gap_iv[:, 0]
    names = _label(spans, gap_iv.mean(axis=1)) if len(gap_iv) else []
    by_span: dict[str, float] = defaultdict(float)
    for d, name in zip(length.tolist(), names):
        by_span[name] += d / n
    longest = np.argsort(-length, kind="stable")[:top]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        busy_s=sum(busy) / n, window_s=w1 - w0,
        device_ops=[[k, v / n] for k, v in ops],
        idle_gaps=[[names[i], float(length[i])] for i in longest],
        idle_by_span=dict(by_span))


def _device_line(plane):
    """The line of a device plane that holds one event per operation."""
    lines = {ln.name: ln for ln in plane.lines}
    return lines.get("XLA Ops")


def read(path: str) -> tuple[list[list[Event]], list[Event]]:
    """Device operations per chip and harness spans from one ``.xplane.pb``.
    A device operation is named by its program and its operation, where the
    trace's module line tells which program ran it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops_line = _device_line(plane)
            if ops_line is None:
                continue
            mods = {ln.name: ln for ln in plane.lines}.get("XLA Modules")
            mod_ev = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in mods.events), key=lambda m: m[0]) if mods else []
            mod_starts = np.array([m[0] for m in mod_ev], float)
            evs = []
            for e in ops_line.events:
                # an operation's event is named by its HLO instruction: keep its name
                name = e.name.split(" = ", 1)[0].lstrip("%")
                if len(mod_starts):
                    k = int(np.searchsorted(mod_starts, e.start_ns, side="right")) - 1
                    if k >= 0 and e.start_ns < mod_ev[k][1]:
                        name = f"{mod_ev[k][2]}/{name}"
                evs.append(Event(name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns * 1e-9,
                                           (e.start_ns + e.duration_ns) * 1e-9))
    return devices, spans

