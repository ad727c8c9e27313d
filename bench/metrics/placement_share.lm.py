"""Share of the slots' time in the window spent placing modules: the
seconds of program compiles and on-slot weight inits the daemon records
(`Daemon.metrics["modules"]` compile_s + init_s), over the window's
slot-seconds (window x slots)."""


def read(run):
    return 100.0 * run.placement_s() / (run.window_s * run.n_slots)
