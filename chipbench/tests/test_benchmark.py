"""BENCHMARK.json against the benchmark's contract, and the harness as data."""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = harness.ROOT
BENCH = harness.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_and_command():
    assert set(BENCH) == KEYS["top"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    for p in BENCH["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_entries_have_just_their_keys_and_plain_names(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.fullmatch(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer"):
            if key in e:
                assert _line(e[key]), (e["name"], key)
        if section == "configs":
            assert _line(e["source"])


def test_configs_resolve_and_list_every_reduced_key():
    for c in BENCH["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        cfg = harness.config(c["name"])
        assert cfg["name"] == c["name"]
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        assert os.path.exists(os.path.join(harness.HERE, "drivers",
                                           cfg["driver"] + ".py"))
        assert cfg["check"], "a configuration compares at least one number"
        for name, spec in cfg["check"].items():
            assert NAME.fullmatch(name)
            assert spec["gap"] in ("rel", "abs") and spec["limit"] >= 0
        used = [w for w in BENCH["workloads"] if w["config"] == c["name"]]
        assert used, f"{c['name']} is used by no cell"


def test_cells_resolve_and_report_what_they_must():
    from chipbench.traffic import generate

    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.fullmatch(w["traffic"]) and NAME.fullmatch(w["config"])
        generate.load(w["traffic"])
        harness.config(w["config"])
        ends = [m["name"] for m in harness.metrics_for(BENCH, w["name"],
                                                       "end_to_end")]
        assert "setup_s" in ends and len(ends) >= 2
        assert harness.metrics_for(BENCH, w["name"], "per_layer")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25


def test_every_metric_has_its_reader():
    for section in ("end_to_end", "per_layer"):
        for m in BENCH[section]:
            mod = harness.load_module("metrics", m["name"])
            assert callable(mod.read), m["name"]


def test_per_layer_metrics_move_a_metric_their_cells_report():
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            ends = [e["name"] for e in harness.metrics_for(BENCH, cell,
                                                           "end_to_end")]
            assert m["moves"] in ends, (m["name"], cell)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert all(len(v) == 1 for v in layers.values()), layers
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert layer in perf, f"PERF.md does not name the layer {layer!r}"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_a_new_cell_is_new_files_and_new_entries(tmp_path):
    """A configuration, traffic mix, driver and per-layer metric added as
    files, with entries in BENCHMARK.json, resolve by name; no existing
    file changes."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = {p: open(p, "rb").read() for p in
              map(str, (tmp_path / "chipbench").rglob("*")) if os.path.isfile(p)}
    base = tmp_path / "chipbench"
    cfg = harness.config("kits19-cohort")
    cfg.update(name="clinic-service", driver="clinic")
    (base / "configs" / "clinic-service.json").write_text(json.dumps(cfg))
    (base / "traffic" / "clinic-open.json").write_text(json.dumps(
        {"cases": [c for c, _ in cfg["images"] if c.endswith("-2")],
         "order": "dataset"}))
    (base / "drivers" / "clinic.py").write_text(
        "class Driver:\n    pass\n")
    (base / "metrics" / "clinic.host_ms.py").write_text(
        "def read(run):\n    return run.record['host_ms']\n")
    bench["configs"].append({"name": "clinic-service", "source": "x",
                             "file": "chipbench/configs/clinic-service.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "clinic-open",
                               "config": "clinic-service",
                               "traffic": "clinic-open", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "clinic.host_ms", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "drop-in stages",
                               "moves": "cases_per_s",
                               "workloads": ["clinic-open"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    h = _load(base / "harness.py", "copied_harness")
    g = _load(base / "traffic" / "generate.py", "copied_generate")
    b = h.benchmark(str(tmp_path))
    cell = h.cell(b, "clinic-open")
    assert h.config(cell["config"])["driver"] == "clinic"
    assert h.load_module("drivers", "clinic").Driver
    mix = g.load(cell["traffic"])
    assert len(g.select(h.config(cell["config"]), mix)) == 10
    metrics = h.metrics_for(b, "clinic-open", "per_layer")
    assert [m["name"] for m in metrics] == ["clinic.host_ms"]

    class Run:
        record = {"host_ms": 1.5}

    assert h.read_metrics(Run, metrics) == {
        "clinic.host_ms": {"value": 1.5, "unit": "ms"}}
    # the per-layer metrics of the existing cells are untouched
    assert [m["name"] for m in h.metrics_for(b, "cohort-tumour",
                                             "per_layer")] == \
        [m["name"] for m in harness.metrics_for(BENCH, "cohort-tumour",
                                                "per_layer")]
    for p, data in before.items():
        assert open(p, "rb").read() == data


def _python(code, **env):
    e = dict(os.environ)
    e.update(env)
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=e,
                          capture_output=True, text=True, timeout=300)


def test_importing_the_benchmark_loads_no_jax():
    code = (
        "import sys\n"
        "from chipbench import run, harness, trace, calibrate, census, "
        "compare, work, metrics_common, reference\n"
        "from chipbench.traffic import generate\n"
        "b = harness.benchmark()\n"
        "for s in ('end_to_end', 'per_layer'):\n"
        "    for m in b[s]:\n"
        "        harness.load_module('metrics', m['name'])\n"
        "for c in b['configs']:\n"
        "    harness.load_module('drivers', harness.config(c['name'])['driver'])\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'libtpu')))\n")
    res = _python(code)
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == "[]"


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "cohort-tumour",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert res.returncode != 0
    assert "{" not in res.stdout
    assert "TPU" in res.stderr
