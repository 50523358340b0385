"""Capture a profiler trace of part of a run, and reduce it to metrics.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData`` reads
it. The device planes (``/device:TPU:<i>``) carry one line of XLA
module executions (one event per call of a jitted program, named after
it) and one of XLA operations (one event per operation, Pallas kernels
among them, named after their HLO instruction and kernel). The host
plane carries the benchmark's own ``TraceAnnotation`` spans: the traced
window (``chip_bench:<label>``) and, inside it, spans around the calls
the benchmark makes into the program.

Busy time is the union of the operation intervals inside the window;
the idle share is one minus busy over the window. Program and kernel
times are sums of event durations; all device numbers are means over
the chips the run uses.
"""

from __future__ import annotations

import bisect
import contextlib
import re
import shutil
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

WINDOW = "chip_bench:"
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
                 gaps: List[Tuple[str, float]]):
        self.window_s = window_s
        self.busy_s = busy_s
        self.programs = programs
        self.ops = ops
        self.gaps = gaps

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


def _host_spans(plane) -> Tuple[Optional[Tuple[int, int]], list]:
    """(window interval, [(start, end, name)] of the benchmark's spans)."""
    window, spans = None, []
    for line in plane.lines:
        for e in line.events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if e.name.startswith(WINDOW):
                window = (a, b)
            elif e.name.startswith("bench."):
                spans.append((a, b, e.name))
    return window, spans


def reduce(directory: Path, chips: int) -> Summary:
    """Reduce the newest trace under ``directory`` over ``chips`` device
    planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane_file(directory)))
    window, spans = None, []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            w, s = _host_spans(plane)
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
                   ops={k: v / n for k, v in ops.items()}, gaps=gaps)


def _label_gaps(merged, lo, hi, spans) -> List[Tuple[str, float]]:
    """Each idle stretch of the first device, named by the benchmark span
    around its midpoint (``host loop`` where none is). The benchmark's
    spans do not nest, so the one that starts last before the midpoint
    is the only candidate."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    out = []
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        label = spans[i][2] if i >= 0 and spans[i][1] >= mid else "host loop"
        out.append((label, (b - a) / 1e9))
    return out
