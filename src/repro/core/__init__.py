"""FOS core: the paper's primary contribution, adapted to TPU pods.

- shell.py      shell/slot geometry (PR-region analogue)
- allocator.py  buddy allocation with adjacent-slot merging
- registry.py   JSON logical-hardware abstraction (shells + modules)
- module.py     decoupled AOT compilation, relocation, weight loading
- bus.py        layout adaptors (bus virtualisation analogue)
- scheduler.py  resource-elastic space-time policy (replicate/replace/reuse)
- arrivals.py   online arrival-rate estimation (predictive reservation)
- slo.py        per-tenant QoS contracts + predictive admission control
- checkpoint.py context save/restore for preempted chunks (priced, migratable)
- fabric.py     one scheduling contract over many shells (locality + stealing)
- simulator.py  discrete-event execution of the policy (tests + Fig 15)
- daemon.py     live multi-tenant execution service (a Fabric executor)
- zoo.py        module builders (mandelbrot/sobel/matmul/LM)
"""
from repro.core.allocator import BuddyAllocator, Range
from repro.core.arrivals import ArrivalEstimator
from repro.core.checkpoint import CheckpointManager, ChunkCheckpoint
from repro.core.daemon import Daemon, JobHandle
from repro.core.fabric import Fabric, FabricJob
from repro.core.network import FabricNetwork, Link, Transfer
from repro.core.registry import FabricDescriptor, ImplAlt, \
    ModuleDescriptor, Registry
from repro.core.scheduler import Assignment, CostModel, PolicyConfig, \
    Request, SchedulerState
from repro.core.shell import Shell, ShellSpec, SlotSpec, uniform_shell
from repro.core.simulator import SimJob, SimResult, simulate
from repro.core.slo import ADMIT, AdmissionController, \
    AdmissionRejected, AdmissionVerdict, DEGRADE, QoSContract, REJECT


def lm_forward_descriptor(**builder_args) -> ModuleDescriptor:
    """The `lm-forward` module: granite-3-8b at its published widths
    (`zoo.build_lm_forward`).  `builder_args` go to the builder through
    the descriptor's meta; CPU tests and examples pass `reduced=True`."""
    # lm-forward carries large activation state: its context save/restore
    # is priced above the policy default (ImplAlt.meta overrides)
    ckpt = {"ckpt_save_ms": 2.0, "ckpt_restore_ms": 2.0}
    return ModuleDescriptor(
        name="lm-forward", entrypoint="repro.core.zoo:build_lm_forward",
        impls=(ImplAlt("x1", 1, 20.0, meta=dict(ckpt)),
               ImplAlt("x2", 2, 11.0, meta=dict(ckpt))),
        kind="fn",
        meta={"builder_args": builder_args} if builder_args else {})


def default_registry() -> Registry:
    """Registry preloaded with the benchmark accelerator zoo."""
    reg = Registry()
    from repro.core.shell import production_shells
    for spec in production_shells().values():
        reg.register_shell(spec)
    reg.register_module(ModuleDescriptor(
        name="mandelbrot", entrypoint="repro.core.zoo:build_mandelbrot",
        impls=(ImplAlt("x1", 1, 12.0), ImplAlt("x2", 2, 6.5),
               ImplAlt("x4", 4, 3.6)), kind="fn"))
    reg.register_module(ModuleDescriptor(
        name="sobel", entrypoint="repro.core.zoo:build_sobel",
        impls=(ImplAlt("x1", 1, 6.0), ImplAlt("x2", 2, 3.4)), kind="fn"))
    reg.register_module(ModuleDescriptor(
        name="matmul", entrypoint="repro.core.zoo:build_matmul",
        impls=(ImplAlt("x1", 1, 4.0), ImplAlt("x2", 2, 2.3)), kind="fn"))
    reg.register_module(lm_forward_descriptor())
    # example multi-shell fabrics (Fabric.from_registry(reg, name))
    reg.register_fabric(FabricDescriptor("pod512", ("pod256_s4",
                                                    "pod256_s8")))
    reg.register_fabric(FabricDescriptor("hostpair", ("host8_s4",
                                                      "host4_s4")))
    # mixed board generations: a reference-clock shell next to a
    # half-clock one, with a modeled 2 ms cross-host payload transfer
    # per stolen chunk in either direction
    reg.register_fabric(FabricDescriptor(
        "hostpair_hetero", ("host8_s4", "host8_s4_lowclk"),
        transfer_ms={"host8_s4->host8_s4_lowclk": 2.0,
                     "host8_s4_lowclk->host8_s4": 2.0}))
    return reg
