"""Kernels found by the tag the program gives them.

Every Pallas call of the program carries ``name=`` and ``metadata=``
with a ``"kernel"`` key.  The TPU compiler names the instruction after
it (``%flash_decode.1``) and keeps the metadata in the op's own HLO text
as ``frontend_attributes={kernel_metadata={"kernel":"flash_decode",...}}``,
and the device trace names each op by that text.  A program built before
the tags carries ``kernel_metadata={}``: there every reader here finds
nothing, and its metric is left out.
"""

from __future__ import annotations

import re
from typing import List, Optional

from chipbench import readers as RD
from chipbench import trace as TR

KERNEL_TAG = re.compile(r'kernel_metadata=\{[^}]*"kernel":\s*"([^"]+)"')
FUSED_MVM = "fused_mvm"
FLASH_DECODE = "flash_decode"
#: every tag the program gives a kernel (``src/repro/kernels``)
KNOWN_TAGS = (FUSED_MVM, FLASH_DECODE, "paged_attention", "bitline_mvm",
              "analog_bitline_diff", "analog_mvm_diff",
              "analog_mvm_bitserial")


def tag(op_name: str) -> Optional[str]:
    """The ``"kernel"`` tag in an op's HLO text, or None."""
    m = KERNEL_TAG.search(op_name)
    return m.group(1) if m else None


def tagged_ops(run, module: str, kernel: str) -> List[TR.Event]:
    """Ops tagged ``kernel`` that ran inside programs ``module``.

    Nothing found is an empty list where no Pallas call there carries a
    tag (a program built before the tags) or where the kernel is off the
    path; it is :class:`readers.UnidentifiedKernel` where the program
    tags its kernels and a Pallas call ran there with no tag or with a
    tag the readers do not know."""
    inside = [o for o in TR.in_modules(RD.ops(run), RD.modules(run, module))
              if "tpu_custom_call" in o.name]
    tags = [tag(o.name) for o in inside]
    found = [o for o, t in zip(inside, tags) if t == kernel]
    if found or not any(tags):
        return found
    unknown = [o for o, t in zip(inside, tags) if t not in KNOWN_TAGS]
    if unknown:
        raise RD.UnidentifiedKernel(
            f"no op tagged {kernel!r} in {module}, but {len(unknown)} "
            f"Pallas calls carry no known tag, e.g. "
            f"{unknown[0].name[:300]!r}")
    return []


def decode_ms(run, kernel: str) -> Optional[float]:
    """Device time of ``kernel``'s ops per decode-program execution, in
    ms, or None where the window holds no decode step or no such op."""
    steps = RD.modules(run, RD.DECODE_MODULE)
    found = tagged_ops(run, RD.DECODE_MODULE, kernel)
    if not steps or not found:
        return None
    return RD.total_s(found) / len(steps) * 1e3
