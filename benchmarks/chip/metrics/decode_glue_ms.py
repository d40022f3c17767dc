"""Device time per decode-program execution outside its two tagged
kernels: ``decode_step_ms`` less ``flash_decode_ms`` and
``fused_mvm_ms.decode`` — the copies, relayout, converts and sampler
(device trace)."""

from chipbench import tags
from chipbench.readers import DECODE_MODULE, modules, ms_per


def read(run):
    step = ms_per(modules(run, DECODE_MODULE))
    flash = tags.decode_ms(run, tags.FLASH_DECODE)
    fused = tags.decode_ms(run, tags.FUSED_MVM)
    if step is None or flash is None or fused is None:
        return None
    return step - flash - fused
