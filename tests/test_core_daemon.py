"""Live daemon integration: multi-tenant jobs on a single-device shell.

(Multi-slot live execution is exercised by benchmarks/single_tenant.py in a
subprocess with xla_force_host_platform_device_count; unit tests must keep
the default 1-device view.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Daemon, Registry, Shell, default_registry, \
    uniform_shell
from repro.core.registry import ImplAlt, ModuleDescriptor
from repro.core import zoo


@pytest.fixture(scope="module")
def daemon():
    spec = uniform_shell("host1_s1", (1, 1), 1)
    reg = default_registry()
    reg.register_shell(spec)
    d = Daemon(Shell(spec), reg)
    yield d
    d.shutdown()


def _mandel_inputs(n=64, seed=0):
    rng = np.random.default_rng(seed)
    re = rng.uniform(-2, 1, (256, 256)).astype(np.float32)
    im = rng.uniform(-1.5, 1.5, (256, 256)).astype(np.float32)
    return re, im


def test_single_job_roundtrip(daemon):
    re, im = _mandel_inputs()
    h = daemon.submit("alice", "mandelbrot", [(re, im)])
    (out,) = h.future.result(timeout=120)
    prog = zoo.build_mandelbrot(daemon.shell.slots[0].mesh, 1)
    expected = jax.jit(prog.fn)(None, re, im)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))


def test_multi_tenant_concurrent_jobs(daemon):
    """Two tenants, different accelerators, data-parallel chunks."""
    re, im = _mandel_inputs(seed=1)
    img = np.random.default_rng(2).random((1024, 1024)).astype(np.float32)
    h1 = daemon.submit("alice", "mandelbrot", [(re, im)] * 3)
    h2 = daemon.submit("bob", "sobel", [(img,)] * 3)
    out1 = h1.future.result(timeout=300)
    out2 = h2.future.result(timeout=300)
    assert len(out1) == 3 and len(out2) == 3
    assert all(np.asarray(o).shape == (256, 256) for o in out1)
    assert all(np.asarray(o).shape == (1024, 1024) for o in out2)
    # cooperative time-multiplexing on one slot across tenants
    assert daemon.stats["chunks"] >= 7


def test_module_reuse_avoids_reload(daemon):
    re, im = _mandel_inputs(seed=3)
    before = daemon.stats["reconfigurations"]
    h = daemon.submit("alice", "mandelbrot", [(re, im)] * 4)
    h.future.result(timeout=300)
    # mandelbrot was already resident from earlier tests
    assert daemon.stats["reconfigurations"] <= before + 1
    assert daemon.stats["reuses"] > 0


def test_bus_adaptor_pads_and_casts(daemon):
    """Caller sends float64 and a smaller tile; adaptors fix it up."""
    re = np.zeros((200, 256), np.float64)
    im = np.zeros((200, 256), np.float64)
    h = daemon.submit("carol", "mandelbrot", [(re, im)])
    (out,) = h.future.result(timeout=120)
    assert np.asarray(out).shape == (256, 256)


def test_failing_chunk_leaves_no_orphaned_state(daemon):
    """Regression: a request resolved via set_exception used to leave its
    entry in `_results` (and its tenant queue head-of-line blocked) forever.
    A failing chunk must abort the request, drop all per-request state, and
    leave the scheduler consistent for subsequent work."""
    import time
    # oversize tiles violate the bus adaptor's signature check -> chunk error
    bad = (np.zeros((512, 512), np.float32),
           np.zeros((512, 512), np.float32))
    h = daemon.submit("erin", "mandelbrot", [bad, bad])
    with pytest.raises(AssertionError):
        h.future.result(timeout=120)
    deadline = time.time() + 30
    while time.time() < deadline:
        with daemon._lock:
            req = daemon.state.requests[h.rid]
            if req.finished and not daemon.state.alloc.busy:
                break
        time.sleep(0.05)
    with daemon._lock:
        assert h.rid not in daemon._results, "orphaned results buffer"
        assert h.rid not in daemon._handles, "orphaned handle"
        req = daemon.state.requests[h.rid]
        assert req.failed and req.finished
        assert not any(r.rid == h.rid for q in daemon.state.queues.values()
                       for r in q), "dead request still queued"
        assert not daemon.state.alloc.busy and not daemon.state.active
    # scheduler stays consistent: the same tenant can submit again
    re, im = _mandel_inputs(seed=9)
    h2 = daemon.submit("erin", "mandelbrot", [(re, im)])
    assert len(h2.future.result(timeout=120)) == 1


def test_registry_roundtrip(tmp_path):
    reg = default_registry()
    reg.save(tmp_path)
    reg2 = Registry.load(tmp_path)
    assert set(reg2.modules) == set(reg.modules)
    assert set(reg2.shells) == set(reg.shells)
    m = reg2.module("mandelbrot")
    assert m.footprints == [1, 2, 4]
    assert m.load_builder() is zoo.build_mandelbrot


def test_builder_args_travel_with_the_descriptor(tmp_path):
    """A descriptor's `meta["builder_args"]` reaches its builder, and
    survives a registry save/load (it is how a reduced `lm-forward` is
    registered)."""
    from repro.core import lm_forward_descriptor
    reg = Registry()
    reg.register_module(lm_forward_descriptor(reduced=True, seq=64))
    reg.save(tmp_path)
    desc = Registry.load(tmp_path).module("lm-forward")
    builder = desc.load_builder()
    assert builder.func is zoo.build_lm_forward
    assert builder.keywords == {"reduced": True, "seq": 64}
    # the default descriptor serves the full-width config
    assert lm_forward_descriptor().load_builder() is zoo.build_lm_forward


def test_lm_forward_through_daemon_matches_direct_forward():
    """`lm-forward` served by the daemon (reduced granite, CPU size) gives
    the logits of the same weights run through `stack.forward` directly."""
    from repro.configs import granite_3_8b
    from repro.core import lm_forward_descriptor
    from repro.models import api, stack
    cfg = granite_3_8b.REDUCED
    spec = uniform_shell("host1_s1", (1, 1), 1)
    reg = default_registry()
    reg.register_module(lm_forward_descriptor(reduced=True, seq=64))
    rng = np.random.default_rng(0)
    chunks = [(rng.integers(0, cfg.vocab, (8, 64)).astype(np.int32),)
              for _ in range(2)]
    d = Daemon(Shell(spec), reg)
    try:
        outs = d.submit("carol", "lm-forward", chunks,
                        priority=3).future.result(timeout=300)
        placed = d.metrics["modules"]["lm-forward"]
    finally:
        d.shutdown()
    assert placed["placements"] == 1 and placed["compile_s"] > 0
    params = api.init_params(cfg, jax.random.PRNGKey(0))

    @jax.jit
    def forward(p, tokens):
        h, _ = stack.forward(p, cfg, {"tokens": tokens})
        return stack.unembed(p, cfg, h[:, -1:])[:, 0]

    for out, (tokens,) in zip(outs, chunks):
        assert out.shape == (8, cfg.padded_vocab)
        np.testing.assert_allclose(np.asarray(out)[:, :cfg.vocab],
                                   np.asarray(forward(params, tokens))
                                   [:, :cfg.vocab], rtol=1e-6, atol=1e-6)


def test_placement_builds_weights_on_the_slot():
    """Placing a module leaves one copy of its weights, made on the
    slot's devices in the slot's sharding (no host-side copy kept)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.module import AccelModule
    shell = Shell(uniform_shell("host1_s1", (1, 1), 1))
    slot = shell.slots[0]
    mod = AccelModule("matmul", zoo.build_matmul, [1])
    before = jax.live_arrays()          # held, so no id is reused
    pl = mod.place(slot, 1)
    seen = {id(a) for a in before}
    new = [a for a in jax.live_arrays() if id(a) not in seen]
    leaves = jax.tree.leaves(pl.weights_on_slot)
    assert sorted(id(a) for a in new) == sorted(id(a) for a in leaves)
    assert pl.weights_on_slot["a"].sharding == \
        NamedSharding(slot.mesh, P(None, None))


@pytest.mark.parametrize("module,builder", [("matmul", "build_matmul"),
                                            ("sobel", "build_sobel")])
def test_placed_program_is_named_after_its_module(module, builder):
    """Each module's compiled program carries the module's name, so the
    device trace's `XLA Modules` line tells modules apart."""
    from repro.core.module import AccelModule
    slot = Shell(uniform_shell("host1_s1", (1, 1), 1)).slots[0]
    pl = AccelModule(module, getattr(zoo, builder), [1]).place(slot, 1)
    assert pl.executable.as_text().startswith(f"HloModule jit_{module},")


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compile_cache_dir(tmp_path, env_dir):
    """The entry points' compile cache: `JAX_COMPILATION_CACHE_DIR` when
    set, else the fixed directory in the checkout.  Run in a child so this
    process's JAX config stays as it is."""
    import os
    import subprocess
    import sys
    from repro.launch import compile_cache
    src = compile_cache.CACHE_DIR.parent / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(compile_cache.CACHE_DIR)
    if env_dir is not None:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]


_CACHE_CODE = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.core import Shell, uniform_shell, zoo
from repro.core.module import AccelModule

cache = os.environ["JAX_COMPILATION_CACHE_DIR"]
def entries():
    return set(os.listdir(cache)) if os.path.isdir(cache) else set()

shell = Shell(uniform_shell("host2_s2", (1, 2), 2))
AccelModule("matmul", zoo.build_matmul, [1]).place(shell.slots[0], 1)
one = entries()
AccelModule("matmul", zoo.build_matmul, [2]).place(
    shell.merged_slot([0, 1]), 2)
two = entries()
AccelModule("sobel", zoo.build_sobel, [1]).place(shell.slots[1], 1)
again = entries()
print(len(one), len(two - one), len(again - two),
      jax.config.jax_enable_compilation_cache)
"""


def test_multi_device_placement_stays_out_of_persistent_cache(tmp_path):
    """A placement on a slot of one device reads and writes the persistent
    compilation cache; one spanning two devices neither reads nor writes
    it, and the cache is on again after it.  Two host devices, in a
    child, so this process keeps its one-device view."""
    import os
    import subprocess
    import sys
    from repro.launch import compile_cache
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(compile_cache.CACHE_DIR.parent / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", _CACHE_CODE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    n_one, n_two, n_again, enabled = out.stdout.split()
    assert int(n_one) > 0, "one-device placement wrote no cache entry"
    assert int(n_two) == 0, "two-device placement wrote to the cache"
    assert int(n_again) > 0, "the cache stayed off after the placement"
    assert enabled == "True"


def test_preempted_placement_never_overlaps_its_successor(monkeypatch):
    """A preempted assignment still placing on its slot finishes before
    the preemptor's placement starts there: two programs never run on the
    same chips at once."""
    import threading
    import time
    from repro.core import PolicyConfig
    from repro.core.module import AccelModule
    place = AccelModule.place
    started = threading.Event()
    lock = threading.Lock()
    on_slot = {"now": 0, "most": 0}

    def slow_place(self, slot, footprint):
        with lock:
            on_slot["now"] += 1
            on_slot["most"] = max(on_slot["most"], on_slot["now"])
        started.set()
        try:
            time.sleep(0.5)
            return place(self, slot, footprint)
        finally:
            with lock:
                on_slot["now"] -= 1

    monkeypatch.setattr(AccelModule, "place", slow_place)
    spec = uniform_shell("host1_s1", (1, 1), 1)
    d = Daemon(Shell(spec), default_registry(),
               PolicyConfig(preemptive=True))
    try:
        re, im = _mandel_inputs(seed=3)
        img = np.random.default_rng(4).random((1024, 1024)) \
            .astype(np.float32)
        lo = d.submit("lo", "mandelbrot", [(re, im)], priority=0)
        assert started.wait(timeout=60)
        hi = d.submit("hi", "sobel", [(img,)], priority=5)
        assert len(hi.future.result(timeout=300)) == 1
        assert len(lo.future.result(timeout=300)) == 1
    finally:
        d.shutdown()
    assert d.stats["preemptions"] >= 1
    assert on_slot["most"] == 1


def _one_slot_daemon(**policy):
    from repro.core import PolicyConfig
    spec = uniform_shell("host1_s1", (1, 1), 1)
    return Daemon(Shell(spec), default_registry(), PolicyConfig(**policy))


def test_stats_counters_reconcile():
    """Every chunk run on the slot is counted once, completed or thrown
    away; every job's queue time is counted once; the timers advance.
    Under preemption, with threads switching often."""
    import sys
    d = _one_slot_daemon(preemptive=True)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        re, im = _mandel_inputs(seed=5)
        img = np.random.default_rng(6).random((1024, 1024)) \
            .astype(np.float32)
        hs = []
        for i in range(4):
            hs.append(d.submit("alice", "mandelbrot", [(re, im)] * 2))
            hs.append(d.submit("bob", "sobel", [(img,)], priority=3))
        for h in hs:
            h.future.result(timeout=300)
    finally:
        sys.setswitchinterval(switch)
        d.shutdown()
    s = d.stats
    assert s["chunks"] == 12
    assert s["runs"] == s["chunks"] + s["discarded"]
    assert s["discarded_ns"] <= s["run_ns"]
    assert s["queue_jobs"] == len(hs)
    for key in ("sched_ns", "queue_ns", "slot_wait_ns", "adapt_ns",
                "run_ns"):
        assert s[key] > 0, key


def test_preempted_run_is_counted_as_discarded(monkeypatch):
    """A chunk preempted while it places still runs to its end; its result
    is thrown away and counted once in `discarded`, and its preemptor
    waited for the slot."""
    import threading
    import time
    from repro.core.module import AccelModule
    place = AccelModule.place
    started = threading.Event()

    def slow_place(self, slot, footprint):
        started.set()
        time.sleep(0.5)
        return place(self, slot, footprint)

    monkeypatch.setattr(AccelModule, "place", slow_place)
    # no aging: the requeued victim cannot come back to preempt `hi`
    d = _one_slot_daemon(preemptive=True, starvation_bound_ms=1e9)
    try:
        re, im = _mandel_inputs(seed=3)
        img = np.random.default_rng(4).random((1024, 1024)) \
            .astype(np.float32)
        lo = d.submit("lo", "mandelbrot", [(re, im)], priority=0)
        assert started.wait(timeout=60)
        hi = d.submit("hi", "sobel", [(img,)], priority=5)
        assert len(hi.future.result(timeout=300)) == 1
        assert len(lo.future.result(timeout=300)) == 1
    finally:
        d.shutdown()
    s = d.stats
    assert s["preemptions"] == 1
    assert s["discarded"] == 1 and s["discarded_ns"] > 0
    assert s["discarded_ns"] <= s["run_ns"]
    assert s["runs"] == s["chunks"] + s["discarded"] == 3
    assert s["slot_wait_ns"] > 0
    assert s["queue_jobs"] == 2


def test_chunk_spans_nest_on_the_profiler_clock(tmp_path):
    """A profiler trace of one job holds its `fos.chunk` span with the
    slot wait, adaptation, copy, dispatch, wait and completion nested
    inside it in that order, each tagged with the job's id."""
    d = _one_slot_daemon()
    re, im = _mandel_inputs(seed=7)
    try:
        d.submit("alice", "mandelbrot", [(re, im)]).future.result(
            timeout=300)
        jax.profiler.start_trace(str(tmp_path))
        try:
            h = d.submit("alice", "mandelbrot", [(re, im)])
            h.future.result(timeout=300)
        finally:
            d.shutdown()          # the worker leaves its spans first
            jax.profiler.stop_trace()
    finally:
        d.shutdown()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(ev.stats))
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("fos.")]
    assert any(n == "fos.schedule" for n, *_ in evs)
    mine = sorted((e for e in evs if e[3].get("gid") == h.rid),
                  key=lambda e: (e[1], -e[2]))
    chunk = mine[0]
    assert chunk[0] == "fos.chunk"
    assert chunk[3] == {"gid": h.rid, "chunk": 0, "aid": chunk[3]["aid"],
                        "tenant": "alice"}
    assert [e[0] for e in mine[1:]] == [
        "fos.slot_wait", "fos.adapt", "fos.put", "fos.dispatch", "fos.wait",
        "fos.complete"]
    for name, s, e, tags in mine[1:]:
        assert chunk[1] <= s <= e <= chunk[2], name
        assert tags == chunk[3], name
