"""Device time of the ops tagged ``"kernel": "flash_decode"`` per
execution of the runtime's jitted decode program (device trace)."""

from chipbench import tags


def read(run):
    return tags.decode_ms(run, tags.FLASH_DECODE)
