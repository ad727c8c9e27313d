"""The daemon's own profiler spans against the chips' idle time.

The daemon opens host spans named `fos.<step>` around each step of the
served path (`repro.core.daemon`), on the profiler's clock.  `reduce`
reads the `.xplane.pb` of a `--trace 1` run a second time, next to
`bench.trace.reduce`, and splits each chip's idle time in the window by
the innermost `fos.*` span covering it: each thread's innermost open span
(a child opens after its parent), and of those the one opened last.  A
thread that only waits for a slot (`fos.slot_wait`) waits on another
thread, so it yields to any other thread with a span open.  Idle time no
such span covers is `"no fos span"`, so the parts sum to the idle time.
Each idle gap keeps its `bench.*` label and appends the `fos.*` span
covering most of it (`chip0: bench.wait > fos.dispatch`).

The trace's device clock runs up to a few milliseconds off the host's,
by a different amount in each trace (0.5 and 1.5 ms in two traces of
TPU v5 lite; 1.0 to 1.7 ms in `bench/tests/data/tpu_daemon.xplane.pb`,
where the daemon's programs showed on the device before the host
dispatched them): as much as the host's time between two chunks.  So
each chip's device events are first moved onto the host's clock, by the
offset that puts the most program executions (`XLA Modules`) inside the
host interval in which their chunk was in flight, from the start of its
`fos.dispatch` to the end of its `fos.wait`: the middle of the offsets
that do (`clock_offset`).

A trace of a program without such spans gives None.
"""
from __future__ import annotations

import bisect
import dataclasses

from bench import trace

FOS_PREFIX = "fos."
NO_FOS_SPAN = "no fos span"
# the largest clock offset considered, ns
MAX_OFFSET_NS = 10e6


@dataclasses.dataclass
class ProgramSpans:
    window_s: float
    n_chips: int
    # program span -> idle seconds under it, the mean over the chips
    idle_under: dict[str, float]
    gaps: list[tuple[str, int, float]]  # (label, chip, seconds)
    # chip -> (least, used, most) offset of its device clock, ms; None
    # where no execution could be placed
    offsets_ms: dict[int, tuple[float, float, float] | None]

    @property
    def idle_s(self) -> float:
        return sum(self.idle_under.values())

    def breakdown(self, top: int = 10) -> dict:
        gaps = sorted(self.gaps, key=lambda g: -g[2])[:top]
        return {"idle_under": dict(sorted(self.idle_under.items(),
                                          key=lambda kv: -kv[1])),
                "clock_offset_ms": {f"chip{c}": o
                                    for c, o in self.offsets_ms.items()},
                "idle_gaps": [[f"chip{c}: {label}", s]
                              for label, c, s in gaps]}


def split_idle(gap: tuple[float, float], spans: list) -> dict[str, float]:
    """{span name: part of `gap` under it} for `spans` [(name, start, end,
    thread)], each instant given to the innermost span covering it (see
    the module's docstring) or to `NO_FOS_SPAN`.  The parts sum to the
    gap."""
    lo, hi = gap
    cut = [(max(s, lo), min(e, hi), s, name, th) for name, s, e, th in spans
           if s < hi and e > lo]
    edges = sorted({lo, hi, *(x for c in cut for x in c[:2])})
    parts: dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        inner: dict = {}         # thread -> its innermost open span
        for s, e, s0, name, th in cut:
            # the latest opened; of two opened at once, the shorter
            key = (s0, -e, name)
            if s <= a and b <= e and (th not in inner or key > inner[th]):
                inner[th] = key
        busy = [v for v in inner.values() if v[2] != "fos.slot_wait"]
        top = max(busy or inner.values(), default=None)
        name = NO_FOS_SPAN if top is None else top[2]
        parts[name] = parts.get(name, 0.0) + (b - a)
    return parts


def gap_label(bench_label: str, parts: dict[str, float]) -> str:
    """The gap's `bench.*` label, then the program span covering most of
    it, if any covers it."""
    fos = {k: v for k, v in parts.items() if k != NO_FOS_SPAN and v > 0}
    if not fos:
        return bench_label
    return f"{bench_label} > {max(fos, key=fos.get)}"


def clock_offset(execs: list, flights: list
                 ) -> tuple[float, float, int] | None:
    """(least, most, n): the range of offsets d (host = device + d) that
    put the most executions `execs` [(start, end)], device clock, inside
    one of `flights` [(start, end)], host clock, and how many that is; of
    several such ranges, the nearest to 0.  Offsets beyond
    `MAX_OFFSET_NS` are not considered.  None if no offset places any."""
    flights = sorted(flights)
    starts = [f[0] for f in flights]
    edges = []
    for s, e in execs:
        i = bisect.bisect_left(starts, s - MAX_OFFSET_NS)
        j = bisect.bisect_right(starts, s + MAX_OFFSET_NS)
        fits = [(a - s, b - e) for a, b in flights[i:j] if a - s <= b - e]
        for lo, hi in trace._union(fits):     # each execution counts once
            edges += [(lo, -1), (hi, 1)]
    if not edges:
        return None
    edges.sort()                              # openings before closings
    ranges, n = [], 0     # (start, end, executions placed) of each stretch
    for (x, step), (nx, _) in zip(edges, edges[1:]):
        n -= step
        ranges.append((x, nx, n))
    most = max(r[2] for r in ranges)
    lo, hi, n = min((r for r in ranges if r[2] == most),
                    key=lambda r: 0 if r[0] <= 0 <= r[1]
                    else min(abs(r[0]), abs(r[1])))
    return lo, hi, n


def _fos_spans(data) -> tuple[list, list]:
    """The `fos.*` spans [(name, start, end, thread)], and each chunk's
    flight [(start of its `fos.dispatch`, end of its `fos.wait`)].  Each
    thread has a line of its own in a host plane."""
    spans, dispatch, wait = [], {}, {}
    for i, plane in enumerate(data.planes):
        if not plane.name.startswith("/host:"):
            continue
        for j, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith(FOS_PREFIX):
                    continue
                end = ev.start_ns + ev.duration_ns
                spans.append((ev.name, ev.start_ns, end, (i, j)))
                aid = dict(ev.stats).get("aid")
                if ev.name == "fos.dispatch":
                    dispatch[aid] = ev.start_ns
                elif ev.name == "fos.wait":
                    wait[aid] = end
    return spans, [(dispatch[a], wait[a]) for a in dispatch if a in wait]


def _near(spans: list, starts: list, longest: float,
          gap: tuple[float, float]) -> list:
    """The spans that may overlap `gap` (`spans` sorted by start)."""
    i = bisect.bisect_left(starts, gap[0] - longest)
    j = bisect.bisect_left(starts, gap[1])
    return [sp for sp in spans[i:j] if sp[2] > gap[0]]


def reduce(path: str, chips: tuple[int, ...] | None = None
           ) -> ProgramSpans | None:
    """Split one `.xplane.pb`'s idle time by program span; None if the
    trace holds no `fos.*` span."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    window, bench_spans = trace._spans(data)
    if window is None:
        raise ValueError(f"{path}: no '{trace.WINDOW_SPAN}' host span")
    lo, hi = window
    spans, flights = _fos_spans(data)
    spans = sorted((sp for sp in spans
                    if trace._clip(sp[1], sp[2], lo, hi) is not None),
                   key=lambda sp: sp[1])
    if not spans:
        return None
    starts = [sp[1] for sp in spans]
    longest = max(sp[2] - sp[1] for sp in spans)
    under: dict[str, float] = {}
    gaps, offsets, n = [], {}, 0
    for plane in data.planes:
        m = trace._DEVICE_PLANE.match(plane.name)
        if m is None or (chips is not None and int(m.group(1)) not in chips):
            continue
        chip, n = int(m.group(1)), n + 1
        lines = {line.name: line for line in plane.lines}
        execs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in getattr(lines.get(trace.MODULES_LINE), "events",
                                   [])
                 if lo - MAX_OFFSET_NS <= ev.start_ns < hi]
        off = clock_offset(execs, flights)
        d = 0.0 if off is None else (off[0] + off[1]) / 2
        offsets[chip] = None if off is None else (
            off[0] * 1e-6, d * 1e-6, off[1] * 1e-6)
        intervals = [c for ev in getattr(lines.get(trace.OPS_LINE), "events",
                                         [])
                     if (c := trace._clip(ev.start_ns + d,
                                          ev.start_ns + ev.duration_ns + d,
                                          lo, hi)) is not None]
        merged = trace._union(intervals)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            parts = split_idle((s, e), _near(spans, starts, longest, (s, e)))
            for name, ns in parts.items():
                under[name] = under.get(name, 0.0) + ns * 1e-9
            gaps.append((gap_label(trace._label((s, e), bench_spans), parts),
                         chip, (e - s) * 1e-9))
    n = max(n, 1)
    return ProgramSpans((hi - lo) * 1e-9, n,
                        {k: v / n for k, v in under.items()}, gaps, offsets)


def for_run(run) -> ProgramSpans | None:
    """The program spans of a `--trace 1` run (`harness.Run`), from the
    trace the harness wrote under `bench/out/trace` of the checkout; None
    without a chip, without `fos.*` spans, or if that trace is not the
    run's."""
    if run.trace is None or run.trace.busy_s is None:
        return None
    files = sorted((run.cell.root / "bench" / "out" / "trace")
                   .rglob("*.xplane.pb"))
    if len(files) != 1:
        return None
    got = reduce(str(files[0]), tuple(sorted(run.trace.busy_by_chip)))
    if got is None or got.window_s != run.trace.window_s:
        return None
    return got
