"""Backend compiles inside the measured window (jax.monitoring's
backend_compile_duration events, host clock); set-up should leave none."""


def read(run):
    return float(run.compiles_in_window())
