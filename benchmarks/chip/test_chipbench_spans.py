"""Readers of the program's kernel tags, spans and counters.

Checked three ways: on the trace that ``test_chipbench_trace.py`` reads
(a program built before the tags: every existing reader's value pinned
as it reads there, and the new readers finding nothing); on synthetic
traces (a renamed or untagged kernel); and on
``testdata/chat_trace_spans.xplane.pb.xz``, the first 10 s of a
``qwen1.5-4b.chat`` window recorded on a TPU v5e with the tagged
kernels and the runtime's spans, beside ``chat_trace_spans.run.json``:
the run's steps (the KV fill of every decoded lane) and its counters.
"""

import json
import lzma
import os
import shutil
import types

import pytest

from chipbench import peaks, serve, spec
from chipbench import readers as RD
from chipbench import spans as S
from chipbench import tags
from chipbench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
PLANE = "/device:TPU:0"
#: every per-layer metric the chat cell reports, and the two that wait
#: for the harness to gather spans and counters
EXISTING = ("idle_share.serve", "decode_step_ms", "mfu.decode",
            "fused_mvm_roofline.decode", "flash_decode_roofline")
TAGGED = ("flash_decode_ms", "fused_mvm_ms.decode", "decode_glue_ms")
PROGRAM = ("idle_share.runtime", "sched_ms.serve")


def _unpack(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name.replace(".xz", "")
    with lzma.open(os.path.join(DATA, name)) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def _run(tr, steps, counters=None):
    return types.SimpleNamespace(
        trace=tr, plane=PLANE, steps=steps, counters=counters or {},
        config=spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                           "qwen1.5-4b.json")),
        peaks=peaks.peaks("TPU v5 lite"), slots=16)


def _read(run, names):
    return {m: spec.metric_reader(m)(run) for m in names}


# -- a program built before the tags ------------------------------------


@pytest.fixture(scope="module")
def old(tmp_path_factory):
    """The kept trace read as ``trace.load`` reads it; its run's fills
    were not kept, so every decoded step reads 16 lanes at fill 400."""
    tr = TR.load(_unpack(tmp_path_factory, "chat_trace.xplane.pb.xz"))
    return _run(tr, [serve.Step(i, 0.0, 0.0, [400] * 16, [])
                     for i in range(len(tr.steps()))])


def test_existing_readers_read_as_before_on_the_kept_trace(old):
    got = _read(old, EXISTING)
    assert got == pytest.approx({
        "idle_share.serve": 2.902123731981532,
        "decode_step_ms": 136.62287114492756,
        "mfu.decode": 0.12361761245040914,
        "fused_mvm_roofline.decode": 46.545486237325726,
        "flash_decode_roofline": 0.8312833461125803}, rel=1e-9)


def test_new_readers_find_nothing_in_a_program_without_tags(old):
    assert set(_read(old, TAGGED + PROGRAM).values()) == {None}
    inside = TR.in_modules(RD.ops(old), RD.modules(old, RD.DECODE_MODULE))
    assert {tags.tag(o.name) for o in inside
            if "tpu_custom_call" in o.name} == {None}


def test_breakdown_of_the_kept_trace_is_unchanged(old):
    lo, hi = old.trace.window()
    b = TR.breakdown(old.trace, PLANE, lo, hi)
    assert [n.split(" ")[0] for n, _ in b["device_ops"]] == [
        "%closed_call.63", "%copy.114", "%copy.113", "%decode.1",
        "%convert_element_type.468", "%copy_bitcast_fusion.46",
        "%convert_element_type.470", "%copy_bitcast_fusion.59",
        "%copy_bitcast_fusion.62", "%dynamic-slice_bitcast_fusion.5"]
    assert b["device_ops"][0][1] == pytest.approx(5.340133182, rel=1e-9)
    assert [n for n, _ in b["idle_gaps"]] == [
        "bench.read", "bench.step", "bench.step"] + ["bench.read"] * 7
    assert b["idle_gaps"][0][1] == pytest.approx(0.084667347, rel=1e-9)
    # with no serve.* span to name them, gaps keep the harness's names
    assert S.idle_gaps(old.trace, [], PLANE, lo, hi) == b["idle_gaps"]


# -- this program's trace ----------------------------------------------


@pytest.fixture(scope="module")
def new(tmp_path_factory):
    """The trace with the tagged kernels and the runtime's spans, read
    as the harness would with ``Trace.spans`` and the window's counters
    gathered; steps and counters from its run."""
    path = _unpack(tmp_path_factory, "chat_trace_spans.xplane.pb.xz")
    tr = TR.load(path)
    tr.spans = S.load(path)
    with open(os.path.join(DATA, "chat_trace_spans.run.json")) as f:
        rec = json.load(f)
    steps = [serve.Step(i, t0, t1, fills, [tuple(p) for p in pre])
             for i, t0, t1, fills, pre in rec["steps"]]
    return _run(tr, steps, rec["counters"])


def test_every_reader_on_the_tagged_trace(new):
    got = _read(new, EXISTING + TAGGED + PROGRAM)
    assert got == pytest.approx({
        "idle_share.serve": 2.077933836288104,
        "decode_step_ms": 136.61585382857143,
        "mfu.decode": 0.12254613638652417,
        "fused_mvm_roofline.decode": 46.54635249598933,
        "flash_decode_roofline": 0.37363448012511463,
        "flash_decode_ms": 77.39398092857144,
        "fused_mvm_ms.decode": 10.829336600000001,
        "decode_glue_ms": 48.39253629999999,
        "idle_share.runtime": 0.3036685917501169,
        "sched_ms.serve": 5.816314901638684}, rel=1e-9)
    assert got["flash_decode_ms"] + got["fused_mvm_ms.decode"] + got[
        "decode_glue_ms"] == pytest.approx(got["decode_step_ms"], rel=1e-9)
    assert got["idle_share.runtime"] <= got["idle_share.serve"]


def test_tags_find_the_ops_the_operand_patterns_find(new):
    n = len(RD.modules(new, RD.DECODE_MODULE))
    assert n == 70
    flash = tags.tagged_ops(new, RD.DECODE_MODULE, tags.FLASH_DECODE)
    fused = tags.tagged_ops(new, RD.DECODE_MODULE, tags.FUSED_MVM)
    assert flash == RD.kernel_ops(new, RD.DECODE_MODULE, RD.FLASH_OPS)
    assert fused == RD.kernel_ops(new, RD.DECODE_MODULE, RD.FUSED_MVM_OPS)
    assert len(flash) == n * 8 and len(fused) == n * (8 * 7 + 1)
    site = '"site":"(\'linear\', 1, 7, 8, None, None)"'
    assert all(site in o.name for o in fused)


def test_breakdown_names_tagged_kernels_and_runtime_phases(new):
    lo, hi = new.trace.window()
    b = TR.breakdown(new.trace, PLANE, lo, hi)
    names = [n.split(" ")[0] for n, _ in b["device_ops"]]
    assert names[0] == "%flash_decode.7" and "%fused_mvm.50" in names
    assert not any(n.startswith("%closed_call") for n in names)
    gaps = S.idle_gaps(new.trace, new.trace.spans, PLANE, lo, hi)
    assert [n for n, _ in gaps] == ["serve.prefill", "serve.decode"] + [
        "bench.read"] * 8
    assert [s for _, s in gaps] == [s for _, s in b["idle_gaps"]]


def test_spans_nest_and_carry_their_args(new):
    lo, hi = new.trace.window()
    spans = [s for s in new.trace.spans if lo <= s.start < hi]
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == 70
    for s in spans:
        if s.name in ("serve.maintain", "serve.admit", "serve.decode",
                      "serve.collect"):
            assert any(t.start <= s.start and s.end <= t.end
                       for t in steps), s
    prefill = [s for s in spans if s.name == "serve.prefill"]
    assert [(p.args["bucket"], p.args["group"],
             len(p.args["uids"].split())) for p in prefill] == [
        (128, 8, 8), (256, 8, 8)]
    submitted = {s.args["uid"] for s in spans if s.name == "serve.submit"}
    assert submitted == {u for p in prefill for u in p.args["uids"].split()}


# -- synthetic traces ---------------------------------------------------


def _op(name, tag=None, operand="f32[1,1]"):
    meta = "{}" if tag is None else '{\n"kernel":"%s"\n}' % tag
    return (f"%{name} = f32[16,128] custom-call({operand} %x), "
            f'custom_call_target="tpu_custom_call", '
            f"frontend_attributes={{kernel_metadata={meta}}}")


def _synthetic(op_names):
    ev = TR.Event
    tr = TR.Trace(
        ops={PLANE: [ev(n, 10.0 + 2 * i, 1e6) for i, n in
                     enumerate(op_names)]},
        modules={PLANE: [ev("jit_decode(1)", 5.0, 1e7)]},
        host=[ev("bench.window", 0.0, 1e8)])
    return _run(tr, [])


@pytest.mark.parametrize("ops,flash", [
    # a program built before the tags: nothing, silently
    ([_op("closed_call.1"), _op("closed_call.2", operand="s32[16]")], None),
    # flash-decode off the path: the other tagged kernel still reads
    ([_op("fused_mvm.1", "fused_mvm")], None),
    # both tagged
    ([_op("fused_mvm.1", "fused_mvm"),
      _op("flash_decode.1", "flash_decode", "s32[16]")], 1.0),
    # a tag renamed, or a kernel left untagged: the reader fails
    ([_op("fused_mvm.1", "fused_mvm"),
      _op("flash_decode.1", "flash_decode_v2", "s32[16]")], "raise"),
    ([_op("fused_mvm.1", "fused_mvm"),
      _op("closed_call.2", operand="s32[16]")], "raise"),
])
def test_tagged_reader_fails_only_on_an_unidentified_kernel(ops, flash):
    run = _synthetic(ops)
    if flash == "raise":
        with pytest.raises(RD.UnidentifiedKernel):
            tags.decode_ms(run, tags.FLASH_DECODE)
    else:
        assert tags.decode_ms(run, tags.FLASH_DECODE) == flash


def test_idle_inside_spans_and_gap_labels():
    ev = TR.Event
    ops = [ev("a", 0.0, 10.0), ev("b", 20.0, 10.0), ev("c", 40.0, 60.0)]
    spans = [S.Span("serve.step", 5.0, 30.0, {"step": 0}),
             S.Span("serve.collect", 25.0, 10.0, {"synced": 1})]
    # gaps [10, 20) and [30, 40): the step covers 10 + 5 of them
    assert S.idle_in_spans_ns(ops, spans, 0.0, 100.0) == 15.0
    host = [ev("bench.window", 0.0, 100.0), ev("bench.step.0", 0.0, 50.0),
            ev("bench.read", 36.0, 6.0)]
    assert S.label(spans, host, 15.0) == "serve.step"
    assert S.label(spans, host, 32.0) == "serve.collect"
    assert S.label(spans, host, 38.0) == "bench.read"
    assert S.label(spans, host, 45.0) == "bench.step"


def test_window_counters_and_sched_ms():
    keys = [p + "_s" for p in S.PHASES] + ["device_syncs"]
    s0 = dict.fromkeys(keys, 1.0)
    s1 = dict(dict.fromkeys(keys, 3.0),
              **{p + "_max_s": 0.5 for p in S.PHASES})
    c = S.window_counters(s0, s1)
    assert c["admit_s"] == 2.0 and c["device_syncs"] == 2.0
    assert c["decode_max_s"] == 0.5
    assert S.sched_ms(c, 4) == pytest.approx(4 * 2.0 / 4 * 1e3)
    # a runtime without the counters: nothing
    assert S.window_counters({"decode_steps": 0}, {"decode_steps": 5}) == {}
    assert S.sched_ms({"decode_steps": 5}, 4) is None
