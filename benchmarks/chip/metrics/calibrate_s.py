"""Host time of ``calibrate_lm`` under ``jax.jit`` (the harness jits
it), synced (compile or cache load included)."""


def read(run):
    return run.setup["calibrate_s"]
