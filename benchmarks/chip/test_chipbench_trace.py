"""The trace reduction, checked on a trace recorded on a TPU v5e: the
first 10 s of a ``qwen1.5-4b.chat`` window (seed 101, 16 slots), kept
xz-compressed in ``testdata/``.

Read by hand first: the device plane ``/device:TPU:0`` holds an
``XLA Modules`` line (one event per executed program) and an ``XLA Ops``
line whose events are named by their HLO text, loop ops spanning their
bodies; the ops carry no ``hlo_module`` stat, so ops are placed in a
program by time.  The window held 69 ``bench.step`` spans, 69
``jit_decode`` and 2 ``jit_prefill`` executions; each decode step runs
the fused MVM kernel once per analog site (8 layers x 7 + the head = 57)
and the flash-decode kernel once per layer (8)."""

import lzma
import os
import shutil

import pytest

from chipbench import readers as RD
from chipbench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                    "chat_trace.xplane.pb.xz")
PLANE = "/device:TPU:0"


class _Run:
    def __init__(self, tr):
        self.trace, self.plane = tr, PLANE


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "chat.xplane.pb"
    with lzma.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return _Run(TR.load(str(path)))


def test_planes_window_and_steps(run):
    tr = run.trace
    assert PLANE in tr.ops and PLANE in tr.modules
    lo, hi = tr.window()
    assert (hi - lo) * 1e-9 == pytest.approx(10.0034, abs=1e-3)
    assert len([s for s in tr.steps() if lo <= s.start and s.end <= hi]) == 69


def test_programs_and_kernels_per_step(run):
    assert len(RD.modules(run, RD.DECODE_MODULE)) == 69
    assert len(RD.modules(run, "jit_prefill")) == 2
    fused = RD.kernel_ops(run, RD.DECODE_MODULE, RD.FUSED_MVM_OPS)
    flash = RD.kernel_ops(run, RD.DECODE_MODULE, RD.FLASH_OPS)
    assert len(fused) == 69 * (8 * 7 + 1)
    assert len(flash) == 69 * 8
    # flash-decode takes most of a 137 ms decode step, the fused MVMs ~11 ms
    assert RD.total_s(flash) / 69 == pytest.approx(0.0774, rel=0.01)
    assert RD.total_s(fused) / 69 == pytest.approx(0.01083, rel=0.01)
    assert RD.ms_per(RD.modules(run, RD.DECODE_MODULE)) == pytest.approx(
        136.62, rel=1e-3)


def test_busy_time_is_the_union_of_op_intervals(run):
    lo, hi = run.trace.window()
    ops = RD.ops(run)
    # a plain sweep over the sorted intervals, the loop ops overlapping
    # the ops of their bodies
    busy, end = 0.0, lo
    for s, e in sorted((max(o.start, lo), min(o.end, hi)) for o in ops
                       if o.end > lo and o.start < hi):
        if e > end:
            busy += e - max(s, end)
            end = e
    assert TR.busy_ns(ops, lo, hi) == pytest.approx(busy)
    assert busy * 1e-9 == pytest.approx(9.7131, abs=1e-3)
    gaps = TR.idle_gaps(ops, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)
    idle = __import__("chipbench.spec", fromlist=["metric_reader"]) \
        .metric_reader("idle_share.serve")(run)
    assert idle == pytest.approx(2.902, abs=1e-3)


def test_breakdown_names_ops_and_gaps(run):
    lo, hi = run.trace.window()
    b = TR.breakdown(run.trace, PLANE, lo, hi)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert {name for name, _ in b["idle_gaps"]} <= {
        "bench.step", "bench.read", "outside any step"}


def _synthetic(op_names):
    """A trace with one decode program holding the given ops."""
    ev = TR.Event
    tr = TR.Trace(
        ops={PLANE: [ev(n, 10.0 + i, 1.0) for i, n in enumerate(op_names)]},
        modules={PLANE: [ev("jit_decode(1)", 5.0, 50.0)]},
        host=[ev("bench.window", 0.0, 100.0)])
    return _Run(tr)


@pytest.mark.parametrize("ops,fused,flash", [
    # the kernel taken off the path: its reader finds nothing, silently
    (['%a = f32[16,2560] custom-call(f32[1,1] %x), '
      'custom_call_target="tpu_custom_call"'], 1, 0),
    # a kernel whose operands changed: the other reader still reads
    (['%a = f32[16,2560] custom-call(f32[1,1] %x), '
      'custom_call_target="tpu_custom_call"',
      '%b = f32[16,20,128] custom-call(bf16[16] %f), '
      'custom_call_target="tpu_custom_call"'], 1, None),
])
def test_kernel_reader_fails_on_an_unidentified_kernel(ops, fused, flash):
    run = _synthetic(ops)
    assert len(RD.kernel_ops(run, RD.DECODE_MODULE, RD.FUSED_MVM_OPS)) \
        == fused
    if flash is None:
        with pytest.raises(RD.UnidentifiedKernel):
            RD.kernel_ops(run, RD.DECODE_MODULE, RD.FLASH_OPS)
    else:
        assert len(RD.kernel_ops(run, RD.DECODE_MODULE, RD.FLASH_OPS)) \
            == flash
