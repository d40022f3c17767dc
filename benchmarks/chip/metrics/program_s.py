"""Host time of the jitted ``program_lm`` call, synced (compile or cache
load included)."""


def read(run):
    return run.setup["program_s"]
