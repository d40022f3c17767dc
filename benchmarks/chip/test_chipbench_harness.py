"""The harness finds cells, configurations, traffic and metric readers by
name, prints the contract's last line, and refuses to run without a
chip or without the program."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import result, spec
from chipbench import trace as TR

ROOT = os.path.dirname(os.path.dirname(spec.BENCH_DIR))


def _load_run():
    s = importlib.util.spec_from_file_location(
        "chipbench_run_main", os.path.join(spec.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_every_cell_and_metric_of_the_benchmark_has_its_files():
    bench = spec.find_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = spec.load_cell(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "limits",
                                           w["name"] + ".json"))
        spec.reference_module(cell.config["reference"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m.name))
        names = {m.name for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m.moves in names, (w["name"], m.name)
    for c in bench["configs"]:
        f = spec.load_json(os.path.join(ROOT, c["file"]))
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert f["source"] == c["source"]


def test_added_files_are_found_without_an_edit(tmp_path):
    """A later cell brings a configuration, a traffic mix and a metric
    reader as new files and new entries; no harness file changes."""
    d = tmp_path / "chip"
    (d / "configs").mkdir(parents=True)
    (d / "traffic").mkdir()
    (d / "metrics").mkdir()
    src = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "qwen1.5-4b.json"))
    (d / "configs" / "newcfg.json").write_text(json.dumps(
        dict(src, name="newcfg", num_hidden_layers=4)))
    (d / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2}))
    (d / "metrics" / "requests.per.run.py").write_text(
        "def read(run):\n    return float(len(run.reqs))\n")
    bench = spec.find_benchmark(ROOT)
    bench["configs"].append({"name": "newcfg"})
    bench["workloads"].append({"name": "newcfg.burst", "config": "newcfg",
                               "traffic": "burst", "chips": 1})
    bench["per_layer"].append({
        "name": "requests.per.run", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "scheduler and host",
        "moves": "tok_per_s", "workloads": ["newcfg.burst"]})
    cell = spec.load_cell(bench, "newcfg.burst", bench_dir=str(d))
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["clients"] == 2
    assert "requests.per.run" in [m.name for m in cell.per_layer]
    read = spec.metric_reader("requests.per.run", bench_dir=str(d))
    assert read(type("R", (), {"reqs": {"a": 1, "b": 2}})()) == 2.0
    # a metric scoped to other cells is not reported here
    other = spec.load_cell(bench, "qwen1.5-4b.chat")
    assert "requests.per.run" not in [m.name for m in other.per_layer]


class _Run:
    def __init__(self):
        self.reqs = {"r0": 0, "r1": 1}
        self.trace = None
        self.plane = ""


def test_last_line_format(capsys):
    checks = {"bad_requests": {"value": 0, "limit": 0, "ok": True},
              "logit_gap_mean_codes": {"value": 3.5, "limit": 12.0, "ok": True}}
    out = result.line(_Run(), {"tok_per_s": {"value": 201.5,
                                             "unit": "tokens/s"}},
                      checks, {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1, "memory_peak_bytes": 123})
    result.emit(out, checks)
    cap = capsys.readouterr()
    last = json.loads(cap.out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert last["correct"] is True and last["attempted"] == 2
    assert last["checks"]["logit_gap_mean_codes"] == {"value": 3.5, "limit": 12.0}
    assert cap.err.strip().splitlines()[-1].startswith(
        "check logit_gap_mean_codes 3.5 limit 12.0 ok")
    checks["logit_gap_mean_codes"].update(value=40.0, ok=False)
    assert result.line(_Run(), {}, checks, {})["correct"] is False


def test_traced_line_has_busy_window_and_breakdown():
    run = _Run()
    run.plane = "/device:TPU:0"
    ev = TR.Event
    run.trace = TR.Trace(
        ops={run.plane: [ev("fusion.1", 100, 50), ev("fusion.2", 300, 100)]},
        modules={run.plane: []},
        host=[ev("bench.window", 0, 1000), ev("bench.step.0", 0, 500),
              ev("bench.read", 400, 100)])
    out = result.line(run, {}, {"x": {"value": 0, "limit": 0, "ok": True}},
                      {"platform": "tpu"})
    assert out["device"]["busy_s"] == pytest.approx(150e-9)
    assert out["device"]["window_s"] == pytest.approx(1000e-9)
    name, secs = out["breakdown"]["device_ops"][0]
    assert name == "fusion.2" and secs == pytest.approx(100e-9)
    assert out["breakdown"]["idle_gaps"][0][1] == pytest.approx(600e-9)
    assert list(out)[-1] == "checks"


def test_no_chip_no_result(capsys):
    run = _load_run()
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen1.5-4b.chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen1.5-4b.chat", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout == ""
