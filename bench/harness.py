"""One run of one benchmark cell, from `BENCHMARK.json` and data files.

A cell names a configuration (`bench/configs/<file>.json`: the fabric,
the policy, the module descriptors, the model sizes and the limits of the
correctness check) and a traffic mix (`bench/traffic/<traffic>.json`: the
tenants).  Everything that belongs to one module (its inputs, its plain
reference and its comparison) is in `bench/modules/<module>.py`, and every
metric has a reader of its own in `bench/metrics/<metric>.py`.  All of
them are found by name, so a new configuration, traffic mix, module or
metric is a new file and no edit here.

A run, in one process that holds the cell's chips:

1. set-up: build a `Daemon` from the configuration, give each module its
   weight seed, compile and run every placement the fabric can make, and
   pass one job of each tenant through the daemon;
2. the window: tenants submit jobs through `Daemon.submit`, closed loop
   (a fixed number of jobs outstanding) or open loop (arrivals on a
   schedule drawn from the seed), for `seconds`;
3. after the window: wait for every job sent (a minute at most), read the
   device's peak memory, free the daemon, and compare a sample of the
   outputs, drawn from the seed, with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import queue
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Any

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
# a job due in the window may finish this long after it closes
DRAIN_S = 60.0
# extra identifiers mixed into the seed, one per use, so that the streams
# of random numbers never overlap
_POOL, _ARRIVALS, _PICK, _SAMPLE, _WEIGHTS = range(5)


class SpecError(ValueError):
    """BENCHMARK.json or a data file names something that is not there."""


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def use_checkout_cache(root: Path = ROOT) -> None:
    """Keep JAX's persistent compilation cache in `.jax_cache/` at the root
    of the checkout, whatever the environment says, and put every program
    in it however quick its compile.  Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# -- finding things by name --------------------------------------------------


def load_json(path: Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_file_module(path: Path):
    """Import a Python file by its path (module files and metric readers
    are named after modules and metrics, which hold '-' and '.')."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `root/BENCHMARK.json`, with its configuration,
    its traffic mix and the metrics it reports."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown configuration "
                        f"{w['config']!r}; known: {sorted(configs)}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)], root)


def module_file(cell: Cell, module: str):
    return load_file_module(cell.bench_dir / "modules" / f"{module}.py")


def metric_reader(cell: Cell, metric: str):
    """`bench/metrics/<metric>.py`; for a metric named `<base>.<variant>`
    with no file of its own, the reader of `<base>` (one quantity split by
    the cells it is read in)."""
    d = cell.bench_dir / "metrics"
    path = d / f"{metric}.py"
    base = d / f"{metric.rpartition('.')[0]}.py"
    if not path.is_file() and "." in metric and base.is_file():
        path = base
    return load_file_module(path)


def seed_rng(seed: int, *ids: int) -> np.random.Generator:
    """A generator for one use of the seed (`ids` name the use)."""
    return np.random.default_rng([int(seed), *ids])


def weights_key(seed: int) -> int:
    """The integer that the modules' weights are generated from: the
    daemon's module gets it as `AccelModule.weights_key`, and the
    reference makes its own copy of the weights from it."""
    return int(seed_rng(seed, _WEIGHTS).integers(0, 2 ** 31 - 1))


# -- the run's record, which the metric readers read -------------------------


@dataclasses.dataclass
class Job:
    tenant: str
    role: str
    module: str
    items: list[int]            # pool index of each chunk's input
    due: float                  # perf_counter seconds
    sent: float = math.nan
    done: float = math.nan      # result ready (the future resolved)
    error: str | None = None

    @property
    def latency_ms(self) -> float:
        """Due to ready; a job that failed or never came is infinitely
        late."""
        if self.error is not None or math.isnan(self.done):
            return math.inf
        return (self.done - self.due) * 1e3


@dataclasses.dataclass
class Run:
    """What a run observed.  Times are `time.perf_counter()` seconds."""
    cell: Cell
    seed: int
    t0: float = 0.0
    t1: float = 0.0
    setup_s: float = 0.0
    jobs: list[Job] = dataclasses.field(default_factory=list)
    n_slots: int = 1
    # program counters at the window's start and end
    stats0: dict = dataclasses.field(default_factory=dict)
    stats1: dict = dataclasses.field(default_factory=dict)
    fabric0: dict = dataclasses.field(default_factory=dict)
    fabric1: dict = dataclasses.field(default_factory=dict)
    modules0: dict = dataclasses.field(default_factory=dict)
    modules1: dict = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    trace: Any = None           # trace.Summary of a --trace 1 run
    peak: dict = dataclasses.field(default_factory=dict)
    tokens_per_chunk: dict = dataclasses.field(default_factory=dict)
    flops_per_chunk: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def done_in_window(self, role: str) -> list[Job]:
        return [j for j in self.jobs if j.role == role and j.error is None
                and self.t0 <= j.done <= self.t1]

    def due_in_window(self, role: str) -> list[Job]:
        return [j for j in self.jobs
                if j.role == role and self.t0 <= j.due < self.t1]

    def delta(self, key: str) -> float:
        return self.stats1.get(key, 0) - self.stats0.get(key, 0)

    def placement_s(self) -> float:
        """Seconds the daemon spent placing modules in the window: program
        compiles and on-slot weight inits."""
        def total(mods):
            return sum(m["compile_s"] + m["init_s"] for m in mods.values())
        return total(self.modules1) - total(self.modules0)


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (`q` in 0..100) of `values`."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


# -- building the system under test ------------------------------------------


def build_daemon(config: dict, devices: list):
    from repro.core import Daemon, PolicyConfig, Shell
    from repro.core.registry import ImplAlt, ModuleDescriptor, Registry
    from repro.core.shell import uniform_shell

    reg = Registry()
    shells = {}
    for s in config["fabric"]["shells"]:
        spec = uniform_shell(s["name"], tuple(s["grid"]), s["slots"])
        reg.register_shell(spec)
        shells[s["name"]] = Shell(spec, [devices[i] for i in s["devices"]])
    for m in config["modules"]:
        impls = tuple(ImplAlt(i["name"], i["footprint"], i["est_chunk_ms"],
                              dict(i.get("meta", {}))) for i in m["impls"])
        args = dict(m.get("builder_args", {}))
        if m.get("model_from_config"):
            args["model"] = config
        meta = {"builder_args": args} if args else {}
        reg.register_module(ModuleDescriptor(m["name"], m["entrypoint"],
                                             impls, meta=meta))
    policy = PolicyConfig(**config.get("policy", {}))
    return Daemon(shells if len(shells) > 1 else next(iter(shells.values())),
                  reg, policy)


def placements(daemon, config: dict):
    """Every (module, footprint, shell, slot range) the fabric can place:
    each aligned range of each footprint the module has."""
    out = []
    for m in config["modules"]:
        for impl in m["impls"]:
            fp = impl["footprint"]
            for name, shell in daemon.shells.items():
                n = len(shell.slots)
                for start in range(0, n - fp + 1, fp):
                    slot = (shell.slots[start] if fp == 1 else
                            shell.merged_slot(list(range(start, start + fp))))
                    out.append((m["name"], fp, name, slot))
    return out


def warm(daemon, cell: Cell, pools: dict, log_fn=log) -> None:
    """Compile and run every placement, then one job of each tenant
    through the daemon, which leaves a module resident."""
    from repro.core import bus
    from repro.core.module import run_placement

    combos = placements(daemon, cell.config)
    # a one-slot fabric has one placement per module, which the daemon's
    # own warm-up job below makes; placing it here as well would build
    # its weights twice
    if sum(len(s.slots) for s in daemon.shells.values()) > 1:
        for module, fp, shell, slot in combos:
            t = time.perf_counter()
            mod = daemon._module(module)
            pl = mod.place(slot, fp)
            prog = mod.program(slot, fp)
            args, _ = bus.adapt_inputs(pools[module][0], prog.abstract_inputs)
            run_placement(pl, *args)
            del pl
            log_fn(f"warm {module} x{fp} on {shell}/{slot.spec.name}: "
                   f"{time.perf_counter() - t:.3f} s")
    for t in cell.traffic["tenants"]:
        n = t.get("chunks_per_job", 1)
        h = daemon.submit(t["name"], t["module"],
                          [pools[t["module"]][0]] * n,
                          priority=t.get("priority", 0),
                          affinity=t.get("affinity"))
        h.future.result(timeout=1200)


def snapshot(daemon) -> tuple[dict, dict, dict]:
    m = daemon.metrics
    return m["daemon"], dict(daemon.fabric.stats), m["modules"]


# -- the load generator ------------------------------------------------------


def arrival_offsets(rate: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Poisson arrivals at `rate` per second over `seconds`, with the same
    set of gaps for every seed: the n = rate x seconds quantiles of the
    exponential distribution, in an order drawn from the seed.  So every
    seed sends the same number of jobs, and only their order differs."""
    n = int(round(rate * seconds))
    if n == 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    # n gaps from the window's start, the last arrival one mean gap
    # before its end
    gaps *= seconds / (gaps.sum() + gaps.mean())
    return np.cumsum(rng.permutation(gaps))


class StallWatch:
    """While open, logs where each thread of the process stands whenever
    no job has completed for `after_s`: the daemon has no spans of its
    own, so this is what locates a stall in which the chip sits idle.
    `last()` gives the time of the latest completion."""

    def __init__(self, last, after_s: float = 1.0, poll_s: float = 0.25):
        self.last, self.after_s, self.poll_s = last, after_s, poll_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-stall-watch")

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def _watch(self) -> None:
        seen = None
        while not self._stop.wait(self.poll_s):
            last = self.last()
            quiet = time.perf_counter() - last
            if quiet < self.after_s or last == seen:
                continue
            seen = last
            names = {t.ident: t.name for t in threading.enumerate()}
            stacks = [f"thread {names.get(tid, tid)}:\n" + "".join(
                traceback.format_stack(frame, limit=8))
                for tid, frame in sys._current_frames().items()
                if tid != threading.get_ident()]
            log(f"stall: no job completed for {quiet:.3f} s\n"
                + "\n".join(stacks))


class Driver:
    """Drives the tenants of one traffic mix through `Daemon.submit`, from
    one thread: it sends each open-loop job when it is due and refills each
    closed-loop tenant as its jobs complete."""

    def __init__(self, daemon, traffic: dict, pools: dict, seed: int,
                 sample_sizes: dict | None = None):
        self.daemon = daemon
        self.tenants = traffic["tenants"]
        self.pools = pools
        self.seed = seed
        self.jobs: list[Job] = []
        self.samples: dict[str, list] = {}       # tenant -> [(job, outs)]
        self._seen: dict[str, int] = {}
        self._sample_sizes = sample_sizes if sample_sizes is not None else \
            traffic.get("check_sample", {})
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._pick = {t["name"]: seed_rng(seed, _PICK, i)
                      for i, t in enumerate(self.tenants)}
        self._keep = {t["name"]: seed_rng(seed, _SAMPLE, i)
                      for i, t in enumerate(self.tenants)}
        self.outstanding = 0
        self.last_done = time.perf_counter()

    def _annotate(self, name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, t: dict, due: float) -> Job:
        pool = self.pools[t["module"]]
        n = t.get("chunks_per_job", 1)
        items = [int(i) for i in self._pick[t["name"]].integers(0, len(pool),
                                                                n)]
        job = Job(t["name"], t["role"], t["module"], items, due)
        with self._annotate("bench.submit"):
            job.sent = time.perf_counter()
            h = self.daemon.submit(t["name"], t["module"],
                                   [pool[i] for i in items],
                                   priority=t.get("priority", 0),
                                   affinity=t.get("affinity"))
        self.jobs.append(job)
        self.outstanding += 1

        def done(f, job=job):
            job.done = self.last_done = time.perf_counter()
            self._q.put((job, f))
        h.future.add_done_callback(done)
        return job

    def _complete(self, job: Job, fut) -> None:
        self.outstanding -= 1
        err = fut.exception()
        if err is not None:
            job.error = f"{type(err).__name__}: {err}"
            return
        k = self._sample_sizes.get(job.tenant, 0)
        if k <= 0:
            return
        # reservoir sampling, from the seed: every finished job of the
        # tenant is equally likely to be checked
        n = self._seen.get(job.tenant, 0)
        self._seen[job.tenant] = n + 1
        kept = self.samples.setdefault(job.tenant, [])
        if len(kept) < k:
            kept.append((job, fut.result()))
        else:
            j = int(self._keep[job.tenant].integers(0, n + 1))
            if j < k:
                kept[j] = (job, fut.result())

    def window(self, t0: float, seconds: float,
               rates: dict | None = None) -> float:
        """Drive the tenants from `t0` for `seconds`; returns the window's
        end.  `rates` overrides open-loop rates by tenant (the knee sweep)."""
        t1 = t0 + seconds
        due = []
        for i, t in enumerate(self.tenants):
            if t["loop"] == "open":
                rate = (rates or {}).get(t["name"], t["rate_per_s"])
                offs = arrival_offsets(rate, seconds,
                                       seed_rng(self.seed, _ARRIVALS, i))
                due += [(t0 + o, i) for o in offs]
        due.sort()
        closed = {t["name"]: t for t in self.tenants if t["loop"] == "closed"}
        self.last_done = t0
        with self._annotate("bench.window"), \
                StallWatch(lambda: self.last_done):
            for t in closed.values():
                for _ in range(t["outstanding"]):
                    self.submit(t, time.perf_counter())
            k = 0
            while True:
                now = time.perf_counter()
                if now >= t1:
                    break
                while k < len(due) and due[k][0] <= now:
                    self.submit(self.tenants[due[k][1]], due[k][0])
                    k += 1
                wake = min(due[k][0] if k < len(due) else t1, t1)
                try:
                    with self._annotate("bench.wait"):
                        job, fut = self._q.get(
                            timeout=max(0.0, wake - time.perf_counter()))
                except queue.Empty:
                    continue
                while True:
                    self._complete(job, fut)
                    if job.tenant in closed and time.perf_counter() < t1:
                        self.submit(closed[job.tenant], time.perf_counter())
                    try:
                        job, fut = self._q.get_nowait()
                    except queue.Empty:
                        break
        return t1

    def drain(self, deadline: float) -> None:
        """Wait for every job sent, until `deadline`; a job that has not
        come by then never came."""
        while self.outstanding > 0:
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            try:
                job, fut = self._q.get(timeout=left)
            except queue.Empty:
                break
            self._complete(job, fut)
        for j in self.jobs:
            if math.isnan(j.done) and j.error is None:
                j.error = "never came"


# -- the run -----------------------------------------------------------------


def check_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    d = devices[0]
    peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def peaks_for(kind: str, bench_dir: Path) -> dict:
    table = load_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise SpecError(f"no peaks for device kind {kind!r} in peaks.json; "
                        f"known: {sorted(table)}")
    return table[kind]


def _compile_counter():
    """Counts XLA compiles (a persistent-cache read included) from here on."""
    import jax
    count = [0]

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return count


@dataclasses.dataclass
class Prepared:
    """The system under test, built and warmed, with its inputs."""
    cell: Cell
    mods: dict          # module -> its file in bench/modules
    devices: list
    pools: dict         # module -> chunk inputs, drawn from the seed
    daemon: Any


def prepare(workload: str, seed: int, *, root: Path = ROOT,
            require_tpu: bool = True, log_fn=log) -> Prepared:
    """Set-up: the cell's daemon, each module given the seed's weights,
    every placement compiled and run, one job of each tenant served."""
    cell = find_cell(workload, root)
    mods = {m["name"]: module_file(cell, m["name"])
            for m in cell.config["modules"]}
    devices = check_devices(cell.chips, require_tpu)
    pools = {name: mf.make_pool(cell.config, _mod_entry(cell, name),
                                seed_rng(seed, _POOL, i))
             for i, (name, mf) in enumerate(mods.items())}
    daemon = build_daemon(cell.config, devices)
    try:
        wkey = weights_key(seed)
        for name in mods:
            daemon._module(name).weights_key = wkey
        warm(daemon, cell, pools, log_fn)
    except BaseException:
        daemon.shutdown()
        raise
    return Prepared(cell, mods, devices, pools, daemon)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, t_start: float | None = None,
        require_tpu: bool = True, trace_dir: Path | None = None,
        control: bool = False) -> dict:
    """One run of the cell `workload`; returns the result line's object.
    With `control` (`bench/control.py`; the benchmark's runs never set
    it) the line also holds `control_checks` and `control_correct`: the
    same comparison and verdict with the control's outputs put in place
    of the program's (`control_samples`)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = find_cell(workload, root)
    metrics = cell.per_layer if trace else cell.end_to_end
    readers = {m["name"]: metric_reader(cell, m["name"]) for m in metrics}
    compiles = _compile_counter()
    p = prepare(workload, seed, root=root, require_tpu=require_tpu)
    rec = Run(cell, seed)
    rec.n_slots = sum(len(s.slots) for s in p.daemon.shells.values())
    try:
        driver = Driver(p.daemon, cell.traffic, p.pools, seed)
        rec.stats0, rec.fabric0, rec.modules0 = snapshot(p.daemon)
        if trace:
            from bench import trace as trace_mod
            trace_dir = trace_dir or (root / "bench" / "out" / "trace")
            trace_mod.start(trace_dir)
        c0 = compiles[0]
        rec.t0 = time.perf_counter()
        rec.setup_s = rec.t0 - t_start
        rec.t1 = driver.window(rec.t0, seconds)
        rec.stats1, rec.fabric1, rec.modules1 = snapshot(p.daemon)
        rec.compiles_in_window = compiles[0] - c0
        if trace:
            trace_mod.stop()
        driver.drain(rec.t1 + DRAIN_S)
    finally:
        p.daemon.shutdown()
    rec.jobs = driver.jobs
    dev = device_info(p.devices)
    rec.peak = peaks_for(dev["kind"], cell.bench_dir) if require_tpu else {}
    samples = {t: [(j, [np.asarray(o) for o in outs]) for j, outs in kept]
               for t, kept in driver.samples.items()}
    p.daemon = None
    del driver
    gc.collect()
    checks = check_samples(cell, p.mods, samples, p.pools, seed,
                           p.devices[0])
    if control:
        ctl_checks = check_samples(
            cell, p.mods, control_samples(cell, p.mods, samples, p.pools,
                                          seed, p.devices[0]),
            p.pools, seed, p.devices[0])
    for name, mf in p.mods.items():
        entry = _mod_entry(cell, name)
        if hasattr(mf, "tokens_per_chunk"):
            rec.tokens_per_chunk[name] = mf.tokens_per_chunk(cell.config,
                                                             entry)
        if hasattr(mf, "flops_per_chunk"):
            rec.flops_per_chunk[name] = mf.flops_per_chunk(cell.config,
                                                           entry)
    if trace:
        rec.trace = trace_mod.reduce_dir(trace_dir, p.devices)
        if rec.trace.busy_s is not None:
            dev["busy_s"] = rec.trace.busy_s
            dev["window_s"] = rec.trace.window_s
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(rec)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    failed = sum(1 for j in rec.jobs if j.error is not None)
    for e in sorted({j.error for j in rec.jobs if j.error is not None})[:5]:
        log(f"failed job: {e}")
    correct = verdict(failed, checks)
    done = sorted([rec.t0] + [j.done for j in rec.jobs
                              if rec.t0 <= j.done <= rec.t1] + [rec.t1])
    log(f"window {rec.window_s:.3f} s, setup {rec.setup_s:.3f} s, "
        f"jobs {len(rec.jobs)}, longest wait for a completion "
        f"{max(np.diff(done)):.3f} s, "
        f"compiles in window {rec.compiles_in_window}"
        f", stats {json.dumps({k: rec.delta(k) for k in rec.stats1})}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": correct, "attempted": len(rec.jobs), "failed": failed,
           "metrics": values, "device": dev}
    if trace:
        out["breakdown"] = rec.trace.breakdown()
    out["checks"] = checks
    if control:
        for name, c in ctl_checks.items():
            log(f"control {name}: {c['value']!r} (limit {c['limit']!r})")
        out["control_correct"] = verdict(failed, ctl_checks)
        out["control_checks"] = ctl_checks
    return out


def verdict(failed: int, checks: dict) -> bool:
    """`correct`: no job failed, and every number compared is within its
    limit."""
    return failed == 0 and bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())


def _mod_entry(cell: Cell, name: str) -> dict:
    return next(m for m in cell.config["modules"] if m["name"] == name)


def control_samples(cell: Cell, mods: dict, samples: dict, pools: dict,
                    seed: int, device) -> dict:
    """`samples` with each output replaced by the control's: the module's
    plain reference over the same inputs, computed one precision below
    the configuration's (`CONTROL` of the module's file)."""
    items: dict[str, set] = {}
    for kept in samples.values():
        for job, _ in kept:
            items.setdefault(job.module, set()).update(job.items)
    lowp = {}
    for name, its in items.items():
        its = sorted(its)
        mf = mods[name]
        lowp[name] = dict(zip(its, mf.reference(
            cell.config, _mod_entry(cell, name),
            [pools[name][i] for i in its], weights_key(seed),
            device=device, lowp=mf.CONTROL)))
    return {t: [(job, [lowp[job.module][i] for i in job.items])
                for job, _ in kept] for t, kept in samples.items()}


def check_samples(cell: Cell, mods: dict, samples: dict, pools: dict,
                  seed: int, device) -> dict:
    """Compare the sampled outputs with each module's plain reference;
    returns {number: {"value", "limit"}} for the numbers the
    configuration holds to a limit."""
    got: dict[str, list] = {}          # module -> [(pool item, output)]
    for kept in samples.values():
        for job, outs in kept:
            got.setdefault(job.module, []).extend(zip(job.items, outs))
    limits = cell.config["checks"]
    checks = {}
    for name, mf in mods.items():
        pairs = got.get(name, [])
        if not pairs:
            continue
        items = sorted({i for i, _ in pairs})
        entry = _mod_entry(cell, name)
        want = dict(zip(items, mf.reference(
            cell.config, entry, [pools[name][i] for i in items],
            weights_key(seed), device=device)))
        numbers = mf.compare(cell.config, entry, [o for _, o in pairs],
                             [want[i] for i, _ in pairs])
        for k, v in numbers.items():
            if k in limits:
                checks[k] = {"value": float(v), "limit": float(limits[k])}
    # a number the configuration holds to a limit and no sample gave
    for k, lim in limits.items():
        checks.setdefault(k, {"value": math.inf, "limit": float(lim)})
    return checks
