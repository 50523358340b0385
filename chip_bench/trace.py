"""Capture a profiler trace of part of a run, and reduce it to metrics.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData`` reads
it. The device planes (``/device:TPU:<i>``) carry one line of XLA
module executions (one event per call of a jitted program, named after
it) and one of XLA operations (one event per operation, Pallas kernels
among them, named after their HLO instruction and kernel). The host
plane carries the benchmark's own ``TraceAnnotation`` spans (the traced
window, ``chip_bench:<label>``, and ``bench.<name>`` around calls the
benchmark makes into the program) and the program's own spans, named
``<process>.<name>`` (``train.sync``, ``ckpt.write``) by its enabled
``SpanTracer``.

Busy time is the union of the operation intervals inside the window;
the idle share is one minus busy over the window. Program and kernel
times are sums of event durations; all device numbers are means over
the chips the run uses. Idle time is named by the innermost host span
over each part of it.
"""

from __future__ import annotations

import contextlib
import re
import shutil
from pathlib import Path
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

WINDOW = "chip_bench:"
BENCH = "bench."
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
TOP = 10


@contextlib.contextmanager
def capture(directory: Path, enabled: bool, label: str) -> Iterator[None]:
    """Trace the block (when ``enabled``) into ``directory``, inside one
    host span that marks the window. Python calls are not traced: the
    benchmark's own spans say what the host was doing."""
    if not enabled:
        yield
        return
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW + label):
            yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span around one call into the program (a no-op outside a
    trace)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def annotate(fn, name: str):
    """``fn`` wrapped in a host span."""
    def wrapped(*args, **kwargs):
        with span(name):
            return fn(*args, **kwargs)
    return wrapped


def chrome_processes(events) -> Dict[int, str]:
    """{pid: process name} of a ``SpanTracer``'s Chrome events."""
    return {e["pid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"}


def chrome_spans(events) -> Dict[str, list]:
    """{``<process>.<name>``: [seconds, count]} of the closed B/E pairs
    in a ``SpanTracer``'s Chrome events (microseconds), the same names
    its spans carry in the profiler's trace."""
    procs = chrome_processes(events)
    open_: Dict[Tuple[int, int], list] = {}
    out: Dict[str, list] = {}
    for e in events:
        lane = (e.get("pid", 0), e.get("tid", 0))
        if e.get("ph") == "B":
            open_.setdefault(lane, []).append(e)
        elif e.get("ph") == "E" and open_.get(lane):
            b = open_[lane].pop()
            proc = procs.get(b["pid"])
            name = f"{proc}.{b['name']}" if proc else b["name"]
            s = out.setdefault(name, [0.0, 0])
            s[0] += (e["ts"] - b["ts"]) / 1e6
            s[1] += 1
    return out


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def program_name(module: str) -> str:
    """``jit_run_chunk(123)`` -> ``run_chunk``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_name(op: str) -> str:
    """``%paged_decode_attention.9 = f32[...] custom-call(...)`` ->
    ``paged_decode_attention``: the HLO instruction's name without its
    number (a Pallas kernel's instruction is named after its wrapper)."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_label(op: str) -> str:
    """The instruction and its result shape, for the breakdown."""
    head, _, rest = op.partition(" = ")
    return (head.lstrip("%") + " = " + rest.split(" ", 1)[0])[:120]


CONTAINERS = ("while", "conditional", "call")


class Summary:
    """What one traced window holds."""

    def __init__(self, window_s: float, busy_s: float,
                 programs: Dict[str, float], ops: Dict[str, float],
                 gaps: List[Tuple[str, float]], spans: Dict[str, list]):
        self.window_s = window_s
        self.busy_s = busy_s
        self.programs = programs
        self.ops = ops
        self.gaps = gaps
        self.spans = spans  # program_spans() of the window

    def program_s(self, name: str) -> float:
        """Device seconds of the jitted program ``name``."""
        return sum(v for k, v in self.programs.items()
                   if program_name(k) == name)

    def kernel_s(self, name: str) -> float:
        """Device seconds of the operations named ``name`` (``op_name``)."""
        return sum(v for k, v in self.ops.items() if op_name(k) == name)

    def breakdown(self) -> Dict[str, list]:
        """The operations that took most device time (loops and calls,
        which hold other operations, left out) and the idle time by what
        the host was doing."""
        leaf: Dict[str, float] = {}
        for k, v in self.ops.items():
            if op_name(k) not in CONTAINERS:
                leaf[op_label(k)] = leaf.get(op_label(k), 0.0) + v
        ops = sorted(leaf.items(), key=lambda kv: -kv[1])[:TOP]
        gaps: Dict[str, float] = {}
        for label, s in self.gaps:
            gaps[label] = gaps.get(label, 0.0) + s
        top = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in top]}


def xplane_file(directory: Path) -> Path:
    files = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _host_spans(plane, processes: Iterable[str]
                ) -> Tuple[Optional[Tuple[int, int]], list]:
    """(window interval, [(start, end, name)] of the benchmark's
    ``bench.`` spans and of the spans of the program's ``processes``)."""
    prefixes = (BENCH,) + tuple(f"{p}." for p in processes)
    window, spans = None, []
    for line in plane.lines:
        for e in line.events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if e.name.startswith(WINDOW):
                window = (a, b)
            elif e.name.startswith(prefixes):
                spans.append((a, b, e.name))
    return window, spans


def program_spans(spans, window: Tuple[int, int]) -> Dict[str, list]:
    """{name: [seconds inside the window, count]} of the host spans
    (``(start, end, name)``, in ns) that overlap the window, the
    benchmark's ``bench.`` spans left out."""
    lo, hi = window
    out: Dict[str, list] = {}
    for a, b, name in spans:
        a, b = max(a, lo), min(b, hi)
        if b > a and not name.startswith(BENCH):
            s = out.setdefault(name, [0.0, 0])
            s[0] += (b - a) / 1e9
            s[1] += 1
    return out


def reduce(directory: Path, chips: int,
           processes: Iterable[str] = ()) -> Summary:
    """Reduce the newest trace under ``directory`` over ``chips`` device
    planes; ``processes`` are the program's span processes (``train``,
    ``ckpt``), whose spans name idle gaps and fill ``Summary.spans``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane_file(directory)))
    window, spans = None, []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            w, s = _host_spans(plane, processes)
            window = window or w
            spans += s
        elif plane.name.startswith("/device:TPU:") and \
                not plane.name.endswith(("Core", "SparseCore")):
            devices.append(plane)
    devices = sorted(devices, key=lambda p: p.name)[:chips]
    if window is None or not devices:
        raise ValueError(f"trace holds no window span or no device plane: "
                         f"{[p.name for p in data.planes]}")
    lo, hi = window
    busy_total = 0.0
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for i, plane in enumerate(devices):
        intervals = []
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OP_LINE):
                continue
            table = programs if line.name == MODULE_LINE else ops
            for e in line.events:
                a = max(e.start_ns, lo)
                b = min(e.start_ns + e.duration_ns, hi)
                if b <= a:
                    continue
                table[e.name] = table.get(e.name, 0.0) + (b - a) / 1e9
                if line.name == OP_LINE:
                    intervals.append((a, b))
        merged = _merge(intervals)
        busy_total += sum(b - a for a, b in merged) / 1e9
        if i == 0:
            gaps = _label_gaps(merged, lo, hi, spans)
    n = len(devices)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_total / n,
                   programs={k: v / n for k, v in programs.items()},
                   ops={k: v / n for k, v in ops.items()}, gaps=gaps,
                   spans=program_spans(spans, window))


def _label_gaps(merged, lo, hi, spans) -> List[Tuple[str, float]]:
    """The idle stretches of the first device, each cut where a host
    span begins or ends, every piece named by the innermost (shortest)
    span that covers it (``host loop`` where none does): the idle time
    by what the host was doing."""
    out: List[Tuple[str, float]] = []
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        inside = [sp for sp in spans if sp[0] < b and sp[1] > a]
        cuts = sorted({a, b} | {t for s, e, _ in inside for t in (s, e)
                                if a < t < b})
        pieces: List[list] = []  # [label, ns], one per run of a label
        for x, y in zip(cuts, cuts[1:]):
            around = [(e - s, name) for s, e, name in inside
                      if s <= x and y <= e]
            label = min(around)[1] if around else "host loop"
            if pieces and pieces[-1][0] == label:
                pieces[-1][1] += y - x
            else:
                pieces.append([label, y - x])
        out += [(label, ns / 1e9) for label, ns in pieces]
    return out
