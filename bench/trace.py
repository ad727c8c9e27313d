"""From a profiler trace to device metrics.

`start`/`stop` take a JAX profiler trace of the measured window; `reduce`
reads the `.xplane.pb` it writes with `jax.profiler.ProfileData` and gives,
per chip, the union of the intervals in which an XLA operation ran (busy
time), the executions of each XLA program with their device time, each
operation's total time, and the idle gaps, each named by the benchmark's
own host span (`bench.*`, `jax.profiler.TraceAnnotation`) that covers most
of it.  The window is the host span `bench.window`, which the load
generator opens around the measured window.
"""
from __future__ import annotations

import dataclasses
import re
import shutil
from pathlib import Path

# TPU chips appear as planes "/device:TPU:<n>"; their lines "XLA Ops" hold
# one event per operation and "XLA Modules" one per program execution
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "no bench span"


def start(trace_dir: Path) -> None:
    """Start tracing into an emptied `trace_dir`.  Python function calls
    are not traced (`python_tracer_level` 0): the benchmark's own spans
    and the runtime's are enough, and the Python tracer slows the host."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


_OPCODE = re.compile(r"\s([a-z][\w.-]*)\(")


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(event_name: str) -> str:
    """'%fusion.128 bf16[8,512,4096] fusion' from the trace's whole HLO
    instruction text ('%fusion.128 = bf16[8,512,4096]{2,1,0:T(8,128)}
    fusion(...), kind=...'): name, result shape without its layout, and
    opcode."""
    name, eq, rest = event_name.partition(" = ")
    m = _OPCODE.search(" " + rest) if eq else None
    if m is None:
        return name
    shape = _LAYOUT.sub("", _LAYOUT.sub("", rest[:m.start()])).strip()
    return f"{name} {shape} {m.group(1)}"


def _self_times(events: list[tuple[float, float, str]]):
    """(name, seconds not covered by a nested event) for each event: the
    line holds an operation and, inside it, the operations it runs (a
    while loop and its body)."""
    out = []
    stack: list[list] = []          # [end, name, self ns]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out.append((top[1], top[2] * 1e-9))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out += [(name, ns * 1e-9) for _, name, ns in stack]
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclasses.dataclass
class Summary:
    """Device activity in the traced window, in seconds."""
    window_s: float
    busy_by_chip: dict[int, float]
    # (program name, chip, device seconds) per execution that started in
    # the window
    executions: list[tuple[str, int, float]]
    # operation -> seconds in which it ran and no operation nested in it
    # did, summed over the chips
    ops: dict[str, float]
    gaps: list[tuple[str, int, float]]  # (host span, chip, seconds)

    @property
    def n_chips(self) -> int:
        return len(self.busy_by_chip)

    @property
    def busy_s(self) -> float | None:
        """Busy seconds, the mean over the chips; None without a chip."""
        if not self.busy_by_chip:
            return None
        return sum(self.busy_by_chip.values()) / self.n_chips

    def idle_share(self) -> float | None:
        busy = self.busy_s
        if busy is None or self.window_s <= 0:
            return None
        return 1.0 - busy / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (mean per chip) and
        the longest idle gaps, named by chip and host span."""
        n = max(self.n_chips, 1)
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[2])[:top]
        return {"device_ops": [[name, s / n] for name, s in ops],
                "idle_gaps": [[f"chip{c}: {span}", s]
                              for span, c, s in gaps]}


def _spans(data) -> tuple[tuple[float, float] | None, list]:
    """The window and the benchmark's other host spans, in ns."""
    window = None
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW_SPAN:
                    window = iv if window is None else \
                        (min(window[0], iv[0]), max(window[1], iv[1]))
                else:
                    spans.append((ev.name, *iv))
    return window, spans


def _label(gap: tuple[float, float], spans: list) -> str:
    best, cover = NO_SPAN, 0.0
    for name, s, e in spans:
        c = _clip(s, e, *gap)
        if c is not None and c[1] - c[0] > cover:
            best, cover = name, c[1] - c[0]
    return best


def reduce(path: Path, chips: list[int] | None = None) -> Summary:
    """Reduce one `.xplane.pb`.  `chips`: the device ids the run used
    (None: every TPU plane in the trace)."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(path))
    window, spans = _spans(data)
    if window is None:
        raise ValueError(f"{path}: no '{WINDOW_SPAN}' host span")
    lo, hi = window
    busy, executions, ops, gaps = {}, [], {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m is None:
            continue
        chip = int(m.group(1))
        if chips is not None and chip not in chips:
            continue
        intervals, named = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                              lo, hi)
                    if c is not None:
                        intervals.append(c)
                        named.append((*c, op_name(ev.name)))
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    if lo <= ev.start_ns < hi:
                        executions.append((ev.name, chip,
                                           ev.duration_ns * 1e-9))
        for name, sec in _self_times(named):
            ops[name] = ops.get(name, 0.0) + sec
        merged = _union(intervals)
        busy[chip] = sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_label((s, e), spans), chip, (e - s) * 1e-9))
    return Summary((hi - lo) * 1e-9, busy, executions, ops, gaps)


def reduce_dir(trace_dir: Path, devices) -> Summary:
    """Reduce the one trace that `start`/`stop` wrote under `trace_dir`."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, found "
                         f"{len(files)}")
    return reduce(files[0], [d.id for d in devices])
