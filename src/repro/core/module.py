"""AccelModule: an AOT-compiled program + weights, placeable into slots.

FOS mapping:
  - compile against a slot *interface* (shape + axes + abstract inputs), in
    isolation from the shell instance -> decoupled compilation;
  - placement into a congruent slot re-lowers against that slot's devices
    with the XLA compilation cache warm -> relocation (BitMan analogue);
  - weights onto the slot's devices = partial reconfiguration; the
    scheduler skips it when the module is already resident (paper 4.4.3).
    Weights are random and seeded, generated on the slot in its sharding
    by a compiled init, so no other copy stays behind.  That init stands
    in for a checkpoint load, which nothing here measures yet.

A ModuleBuilder (referenced by the registry descriptor's entrypoint) returns
a ModuleProgram describing fn / abstract inputs / shardings / weights.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time
from typing import Any, Callable

import jax

from repro.core.shell import Slot


@dataclasses.dataclass
class ModuleProgram:
    """What a builder returns for a given footprint."""
    fn: Callable                         # (weights, *chunk_args) -> outputs
    abstract_weights: Any                # pytree of ShapeDtypeStruct
    abstract_inputs: tuple               # chunk args, ShapeDtypeStructs
    weight_pspecs: Any                   # PartitionSpec pytree (or None)
    input_pspecs: tuple                  # PartitionSpec pytrees
    output_pspecs: Any = None
    init_weights: Callable | None = None  # key -> weights (jit-able)

    def signature(self) -> dict:
        def leaf(s):
            return {"shape": list(s.shape), "dtype": str(s.dtype)}
        return {
            "inputs": jax.tree.map(leaf, list(self.abstract_inputs)),
            "weights": jax.tree.map(leaf, self.abstract_weights),
        }


@dataclasses.dataclass
class Placement:
    """A module implementation resident in a slot."""
    module: "AccelModule"
    footprint: int
    slot: Slot
    executable: Any
    weights_on_slot: Any
    init_time_s: float       # compile and run the on-slot weight init
    compile_time_s: float    # compile the module's program alone


class AccelModule:
    """A named accelerator with implementation alternatives."""

    def __init__(self, name: str, builder: Callable, footprints: list[int],
                 weights_key: int = 0):
        self.name = name
        self.builder = builder
        self.footprints = list(footprints)
        self._programs: dict[tuple, ModuleProgram] = {}
        self.weights_key = weights_key

    # -- decoupled compilation -------------------------------------------------

    def program(self, slot: Slot, footprint: int) -> ModuleProgram:
        key = (slot.congruence_key, footprint)
        if key not in self._programs:
            self._programs[key] = self.builder(slot.mesh, footprint)
        return self._programs[key]

    def place(self, slot: Slot, footprint: int) -> Placement:
        """Compile (cache-mediated) + build the weights on the slot."""
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.launch.compile_cache import persistent_cache_off

        prog = self.program(slot, footprint)
        mesh = slot.mesh
        in_sh = tuple(
            jax.tree.map(lambda p: NamedSharding(mesh, p), ps)
            for ps in prog.input_pspecs)
        w_sh = (jax.tree.map(lambda p: NamedSharding(mesh, p),
                             prog.weight_pspecs)
                if prog.weight_pspecs is not None else None)
        key = jax.device_put(jax.random.PRNGKey(self.weights_key),
                             NamedSharding(mesh, PartitionSpec()))
        args = (prog.abstract_weights, *prog.abstract_inputs)
        shardings = (w_sh, *in_sh) if w_sh is not None else (None, *in_sh)
        # A multi-chip executable read back from the persistent cache
        # halted TPU v5e chips ("Core halted unexpectedly"); compiled
        # afresh it ran.  Programs spanning several devices stay out of it.
        uncached = (persistent_cache_off() if mesh.devices.size > 1
                    else contextlib.nullcontext())
        with uncached:
            t0 = time.perf_counter()
            executable = jax.jit(_named(prog.fn, self.name),
                                 in_shardings=shardings) \
                .lower(*args).compile()
            t1 = time.perf_counter()
            # the weights are generated on the slot's devices in their
            # sharding, never staged elsewhere
            w_dev = (jax.block_until_ready(
                jax.jit(_named(prog.init_weights, f"{self.name}.init"),
                        out_shardings=w_sh)(key))
                if prog.init_weights is not None else None)
            t2 = time.perf_counter()
        return Placement(self, footprint, slot, executable, w_dev,
                         init_time_s=t2 - t1, compile_time_s=t1 - t0)


def _named(fn: Callable, name: str) -> Callable:
    """`fn` under `name`, which `jax.jit` gives its compiled program
    (`jit_<name>`), so the device trace tells modules' programs apart."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


# the tags of the chunk the current thread serves, carried by every
# `fos.*` span it opens (set by `chunk_tags`; empty outside a chunk)
_TAGS: contextvars.ContextVar[dict] = contextvars.ContextVar("fos_tags",
                                                             default={})


def span(step: str) -> jax.profiler.TraceAnnotation:
    """The profiler span `fos.<step>`, tagged with the current chunk.  It
    costs little when no trace is being taken."""
    return jax.profiler.TraceAnnotation(f"fos.{step}", **_TAGS.get())


@contextlib.contextmanager
def chunk_tags(tags: dict):
    """Tag the `fos.*` spans this thread opens inside the block."""
    token = _TAGS.set(tags)
    try:
        yield
    finally:
        _TAGS.reset(token)


def run_placement(placement: Placement, *chunk_args):
    """Generic driver: invoke a resident module on concrete inputs."""
    from jax.sharding import NamedSharding

    prog = placement.module.program(placement.slot, placement.footprint)
    mesh = placement.slot.mesh
    args = []
    with span("put"):
        for a, ps in zip(chunk_args, prog.input_pspecs):
            sh = jax.tree.map(lambda p: NamedSharding(mesh, p), ps)
            args.append(jax.device_put(a, sh))
    with span("dispatch"):
        out = placement.executable(placement.weights_on_slot, *args)
    with span("wait"):
        return jax.block_until_ready(out)
