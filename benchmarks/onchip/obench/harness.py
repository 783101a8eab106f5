"""One benchmark run: set up a cell, measure a window of served traffic on
the chip, hold every answer to the plain reference, print the result.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json`` names a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<mix>.json``), and every metric is read by a reader
of its own (``metrics/<metric>.py``, a function ``read(window)`` that
returns a number, or ``None`` where it finds nothing to read).

The system under test is ``repro.serve.QueryServeEngine`` over
``repro.engine.distributed.DistributedEngine``, with the options the
configuration file names; ``submit`` and ``poll`` are driven from the
benchmark's own load generator in one thread.  With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are printed in
place of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from obench import attribution, check, datagen, loadgen
from obench.compiles import CompileCounter
from obench.reference import Query, Reference

BENCH = Path("benchmarks") / "onchip"          # relative to the checkout


class Refusal(Exception):
    """The run cannot measure: exit non-zero and print no result."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


# -- the cell, by name --------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    metrics: list          # (entry, reader) of the metrics this run reports


def _reader(path: Path):
    if not path.is_file():
        raise Refusal(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(f"obench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(root: Path, name: str, trace: bool) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refusal(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    bench = root / BENCH
    files = (bench / "configs" / f"{w['config']}.json", bench / "traffic" / f"{w['traffic']}.json")
    for f in files:
        if not f.is_file():
            raise Refusal(f"no file {f}")
    config, mix = (json.loads(f.read_text()) for f in files)
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    if trace:
        mine = {m["name"] for m in e2e}

        def reported(m):
            return name in m["workloads"] if "workloads" in m else m["moves"] in mine

        chosen = [m for m in spec["per_layer"] if reported(m)]
    else:
        chosen = e2e
    metrics = [(m, _reader(bench / "metrics" / f"{m['name']}.py")) for m in chosen]
    return Cell(name, int(w["chips"]), config, mix, metrics)


# -- what the readers read ----------------------------------------------------

@dataclass
class Window:
    """One measured window, as the metric readers see it."""

    loop: str
    seconds: float
    setup_s: float
    latencies_s: list         # every request counted; inf where it failed
    completed_in_window: float  # answers before the close, plus the share of the
                                # one being executed at the close that lay inside
    serve: dict               # ServeStats deltas over the measured phase
    capacity_retries: int     # summed over the answers
    compile_requests: int     # in-process compile requests in the measured phase
    trace: "attribution.Attribution | None" = None
    # per answer: template, latency s, service s (plan ready to answer), capacity, retries
    answered: list = dataclasses.field(default_factory=list)
    compiled: list = dataclasses.field(default_factory=list)  # programs of those requests

    def percentile_ms(self, p: float) -> "float | None":
        """Nearest-rank percentile of the latencies, in ms; ``None`` when
        fewer than ten samples lie beyond it or it is a failure's."""
        xs = sorted(self.latencies_s)
        if not xs:
            return None
        k = max(0, math.ceil(p / 100.0 * len(xs)) - 1)
        if len(xs) - 1 - k < 10 and p > 50:
            return None
        return xs[k] * 1e3 if math.isfinite(xs[k]) else None

    def per_query(self, key: str) -> "float | None":
        n = self.serve["n_served"]
        return self.serve[key] / n if n else None


# -- the system under test ----------------------------------------------------

def traced_engine_class():
    """``DistributedEngine`` whose ``execute`` runs in a harness span and
    notes when it began: the only change the benchmark makes to the
    executor."""
    import jax
    from repro.engine.distributed import DistributedEngine

    class TracedEngine(DistributedEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.starts: list[float] = []     # when each execution began

        def execute(self, plan):
            self.starts.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.execute"):
                return super().execute(plan)

    return TracedEngine


def to_program_query(q: Query):
    """The program's query for ``q``: ``from_algebra`` of its group tree,
    or of its BGP, which gives a plain ``BGPQuery``."""
    from repro.query import algebra as alg

    term = lambda t: alg.Var(t) if isinstance(t, str) else alg.Const(int(t))  # noqa: E731

    def node(n: tuple):
        if n[0] == "bgp":
            return alg.Bgp(tuple(alg.TriplePattern(*(term(t) for t in tp)) for tp in n[1]))
        if n[0] == "optional":
            return alg.LeftJoin(node(n[1]), node(n[2]))
        if n[0] == "union":
            return alg.Union(tuple(node(m) for m in n[1]))
        op, lhs, rhs = n[1]
        return alg.Filter(alg.Comparison(op, term(lhs), term(rhs)), node(n[2]))

    root = q.tree if q.tree is not None else ("bgp", q.patterns)
    return alg.from_algebra(node(root), q.distinct, list(q.projection), q.name)


def _mesh_shape(chips: int) -> tuple[int, int]:
    return {1: (1, 1), 4: (2, 2)}[chips]


def warmup_seed(seed: int) -> int:
    """The seed of the traffic set-up serves: another than the window's."""
    return -1 - seed


class Bench:
    """A cell set up on the device: data, statistics, resident tables, the
    server, and every program its traffic uses warmed up.  ``measure`` runs
    one window; it may be called again with another seed."""

    grace_s = 60.0      # how long past the close an answer due in the window may come

    def __init__(self, root: Path, cell: Cell, t_start: float, seed: int, seconds: float, *,
                 platform: str = "tpu", engine_class=None, server_class=None):
        import jax

        from repro.core.federation import build_federated_stats
        from repro.serve.query import QueryServeEngine

        self.root, self.cell = root, cell
        devices = jax.devices()
        if devices[0].platform != platform:
            raise Refusal(f"needs a {platform.upper()} backend, JAX found "
                          f"{devices[0].platform!r}")
        if len(devices) < cell.chips:
            raise Refusal(f"the cell needs {cell.chips} chips, JAX found {len(devices)}")
        self.devices = devices[:cell.chips]
        self.compiles = CompileCounter()

        cfg = cell.config
        self.data = datagen.generate(cfg)
        if "triples" in cfg and self.data.n_triples != cfg["triples"]:
            raise Refusal(f"{cfg['name']} generated {self.data.n_triples} triples, "
                          f"its file says {cfg['triples']}")
        self.fed = datagen.to_program(self.data)
        stats = build_federated_stats(self.fed)
        mesh = jax.sharding.Mesh(np.array(self.devices).reshape(_mesh_shape(cell.chips)),
                                 ("data", "model"))
        self.engine = (engine_class or traced_engine_class())(
            self.fed, mesh, **cfg.get("engine", {}))
        jax.block_until_ready((self.engine.tables, self.engine.trow))
        self.server = (server_class or QueryServeEngine)(
            self.fed, stats, engine=self.engine, **cfg.get("serve", {}))
        self.traffic = loadgen.Traffic(self.data, cell.mix)
        # every template once, at its heaviest entity: the largest capacities
        for q in self.traffic.warmup():
            self.server.submit(to_program_query(q))
        self.server.drain()
        # then a stretch of the traffic itself, from another seed: whatever
        # programs its batches reach are compiled before the window opens
        stretch = min(float(cell.mix.get("warmup_s", 0)), seconds)
        if stretch > 0:
            self._serve(warmup_seed(seed), seconds, stretch)
        self.setup_s = time.perf_counter() - t_start

    # -- the window -----------------------------------------------------------
    def measure(self, seed: int, seconds: float, trace: bool):
        import jax

        server = self.server
        stats0 = dataclasses.asdict(server.serve_stats)
        c0 = self.compiles.requests
        trace_dir = self.root / BENCH / ".traces" / f"{self.cell.name}.{seed}"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            run = self._serve(seed, seconds, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        stats1 = dataclasses.asdict(server.serve_stats)
        answers, lost, repeated, off_device, failed, latencies, in_window, answered_latencies = run
        in_window += self._share_at_close(answers, self._close)
        window = Window(
            loop=self.traffic.loop, seconds=seconds, setup_s=self.setup_s,
            latencies_s=latencies, completed_in_window=in_window,
            serve={k: stats1[k] - stats0[k] for k in stats0},
            capacity_retries=sum(getattr(r.metrics, "capacity_retries", 0) for _, r in answers),
            compile_requests=self.compiles.requests - c0,
            answered=[(q.name, lat, r.t_done - r.t_planned, getattr(r.metrics, "capacity", 0),
                       getattr(r.metrics, "capacity_retries", 0))
                      for (q, r), lat in zip(answers, answered_latencies)],
            compiled=self.compiles.names[c0:])
        if trace:
            files = sorted(trace_dir.rglob("*.xplane.pb"))
            if not files:
                raise Refusal("the profiler wrote no trace")
            window.trace = attribution.reduce(*attribution.read(str(files[-1])))
            shutil.rmtree(trace_dir, ignore_errors=True)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices)
        # the reference runs on the host, after the window and the memory reading
        ref = Reference(self.data.triples)
        checks = check.compare(ref, [(q, r.rows) for q, r in answers if r.fallback is None],
                               failed, lost, repeated, off_device)
        device = {"platform": self.devices[0].platform, "kind": self.devices[0].device_kind,
                  "count": len(self.devices), "memory_peak_bytes": int(peak)}
        return window, checks, device, len(latencies), failed

    def _share_at_close(self, answers, close: float) -> float:
        """The part of the answer in service when the window closed that was
        done by then, as a share of its execution time.  The executor runs
        one plan at a time: the one in service at the close is the last to
        start before it, if no answer came between its start and the close."""
        begun = [t for t in getattr(self.engine, "starts", []) if t <= close]
        after = [r.t_done for _, r in answers if r.t_done > close]
        if not begun or not after:
            return 0.0
        start, end = max(begun), min(after)
        if any(start < r.t_done <= close for _, r in answers):
            return 0.0
        return (close - start) / (end - start)

    def _answer_state(self):
        return {"answers": [], "seen": set(), "repeated": 0, "failed": 0, "off_device": 0}

    def _poll(self, st, requests: dict):
        """One poll of the server; its answers are sorted into ``st``, and the
        requests answered for the first time are returned."""
        import jax

        from repro.engine.distributed import DistMetrics

        try:
            with jax.profiler.TraceAnnotation("bench.poll"):
                got = self.server.poll()
        except RuntimeError as e:       # CapacityExceededError, NotImplementedError, XLA
            # a request raised: the rest of its batch is lost with it; what
            # finished before it is reported by the next poll
            print(f"bench: a batch raised {type(e).__name__}: {e}", file=sys.stderr)
            waiting = {r.qid for r in self.server.queue} | {r.qid for r in self.server.finished}
            got = []
            for qid, req in requests.items():
                if qid not in st["seen"] and qid not in waiting:
                    st["seen"].add(qid)
                    st["failed"] += 1
                    req.fallback = f"raised {type(e).__name__}"
                    got.append(req)
            return got
        new = []
        for r in got:
            if r.qid in st["seen"]:
                st["repeated"] += 1
                continue
            st["seen"].add(r.qid)
            new.append(r)
            if r.fallback is not None or not isinstance(r.metrics, DistMetrics):
                st["off_device"] += 1
                st["failed"] += 1
        return new

    def _sleep_until(self, t: float):
        import jax

        wait = t - time.perf_counter()
        if wait > 0:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(wait)

    def _serve(self, seed: int, seconds: float, until: float):
        """Serve the traffic of a window of ``seconds`` from ``seed``, sending
        requests for its first ``until`` seconds."""
        if self.traffic.loop == "open":
            return self._open(seed, seconds, until)
        return self._closed(seed, until)

    def _open(self, seed: int, seconds: float, until: float):
        """Open loop: each request is submitted when it is due, whatever the
        server is doing; its latency runs from the due time."""
        import jax

        offsets, plain = self.traffic.schedule(seed, seconds)
        keep = offsets < until
        offsets, plain = offsets[keep], [q for q, k in zip(plain, keep.tolist()) if k]
        queries = [to_program_query(q) for q in plain]
        n = len(queries)
        st = self._answer_state()
        requests: dict = {}
        plain_of: dict = {}
        due_of: dict = {}
        t0 = time.perf_counter()
        due = t0 + offsets
        close, give_up = t0 + until, t0 + until + self.grace_s
        self._close = close
        i = 0
        done_in_window = 0
        window = jax.profiler.TraceAnnotation("bench.window")
        window.__enter__()
        in_window = True
        while True:
            now = time.perf_counter()
            while i < n and due[i] <= now:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    req = self.server.submit(queries[i])
                requests[req.qid], plain_of[req.qid], due_of[req.qid] = req, plain[i], due[i]
                i += 1
            for r in self._poll(st, requests):
                if r.fallback is None and r.t_done <= close:
                    done_in_window += 1
                if r.fallback is None:
                    st["answers"].append((plain_of[r.qid], r))
            now = time.perf_counter()
            if in_window and now >= close:
                window.__exit__(None, None, None)
                in_window = False
            if (i == n and len(st["seen"]) >= n) or now > give_up:
                break
            nxt = due[i] if i < n else give_up
            queued = self.server.queue
            if queued:
                nxt = min(nxt, min(r.deadline for r in queued))
            if in_window:
                nxt = min(nxt, close)
            self._sleep_until(min(nxt, give_up))
        if in_window:
            window.__exit__(None, None, None)
        lat = {r.qid: r.t_done - due_of[r.qid] for _, r in st["answers"]}
        latencies = [lat.get(qid, math.inf) for qid in due_of] \
            + [math.inf] * (n - i)
        lost = n - len(st["seen"])
        return (st["answers"], lost, st["repeated"], st["off_device"], st["failed"],
                latencies, done_in_window, [lat[r.qid] for _, r in st["answers"]])

    def _closed(self, seed: int, seconds: float):
        """Closed loop: ``clients`` callers, each sending its next request as
        soon as its last one is answered, while the window is open; latency
        runs from submission."""
        import jax

        stream = self.traffic.stream(seed)
        st = self._answer_state()
        requests: dict = {}
        plain_of: dict = {}
        t0 = time.perf_counter()
        close, give_up = t0 + seconds, t0 + seconds + self.grace_s
        self._close = close
        idle = self.traffic.mix["clients"]       # callers with no request out
        done_in_window = 0
        window = jax.profiler.TraceAnnotation("bench.window")
        window.__enter__()
        in_window = True
        while True:
            now = time.perf_counter()
            while idle and now < close:
                idle -= 1
                q = next(stream)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    req = self.server.submit(to_program_query(q))
                requests[req.qid], plain_of[req.qid] = req, q
            for r in self._poll(st, requests):
                if r.fallback is None:
                    st["answers"].append((plain_of[r.qid], r))
                    if r.t_done <= close:
                        done_in_window += 1
                idle += 1
            now = time.perf_counter()
            if in_window and now >= close:
                window.__exit__(None, None, None)
                in_window = False
            if (now >= close and len(st["seen"]) >= len(requests)) or now > give_up:
                break
            if idle and now < close:
                continue
            nxt = min((r.deadline for r in self.server.queue), default=give_up)
            if in_window:
                nxt = min(nxt, close)
            self._sleep_until(min(nxt, give_up))
        if in_window:
            window.__exit__(None, None, None)
        lat = {r.qid: r.t_done - r.t_submit for _, r in st["answers"]}
        latencies = [lat.get(qid, math.inf) for qid in requests]
        lost = len(requests) - len(st["seen"])
        return (st["answers"], lost, st["repeated"], st["off_device"], st["failed"],
                latencies, done_in_window, [lat[r.qid] for _, r in st["answers"]])


# -- the result ---------------------------------------------------------------

def result_line(cell: Cell, window: Window, checks, device: dict, attempted: int,
                failed: int) -> dict:
    metrics = {}
    for entry, read in cell.metrics:
        value = read(window)
        if value is not None and math.isfinite(value):
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    out = {"correct": checks.correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dict(device)}
    if window.trace is not None:
        out["device"]["busy_s"] = window.trace.busy_s
        out["device"]["window_s"] = window.trace.window_s
        out["breakdown"] = {"device_ops": window.trace.device_ops,
                            "idle_gaps": window.trace.idle_gaps}
    out["checks"] = checks.as_json()
    return out


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, for the benchmark and the program alike."""
    import jax

    path = str((root / BENCH / ".jax_cache").resolve())
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None, *, root: "Path | None" = None, t_start: "float | None" = None,
         platform: str = "tpu", engine_class=None, server_class=None) -> int:
    """One run; ``platform``, ``engine_class`` and ``server_class`` are for
    the benchmark's own tests, which run it on the CPU and break the timed
    path underneath."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    root = Path(root) if root is not None else Path(__file__).resolve().parents[3]
    try:
        if not (root / "src" / "repro").is_dir():
            raise Refusal(f"no program under {root / 'src'}")
        cell = load_cell(root, args.workload, bool(args.trace))
        enable_compile_cache(root)
        bench = Bench(root, cell, t_start, args.seed, args.seconds, platform=platform,
                      engine_class=engine_class, server_class=server_class)
        window, checks, device, attempted, failed = bench.measure(
            args.seed, args.seconds, bool(args.trace))
    except Refusal as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 1
    line = result_line(cell, window, checks, device, attempted, failed)
    if window.trace is not None:
        print(f"bench: device idle seconds by innermost span: {window.trace.idle_by_span}",
              file=sys.stderr)
    for text in checks.lines():
        print(text, file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
