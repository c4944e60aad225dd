"""Every name in BENCHMARK.json resolves to its files under bench/, and a
run without a TPU prints no result."""
import importlib.util
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import correct, data, harness, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_contract_shapes():
    """The keys and limits the benchmark's file has to keep."""
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and len(c["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


def test_names_and_paths():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / BENCH["command"][1]).is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    got = harness.load_cell(cell)
    assert got["cfg"]["name"] == got["entry"]["config"]
    assert set(got["limits"]) == set(correct.NAMES)
    assert traffic.local_steps(got["cfg"]) >= 1
    sizes = data.client_sizes(got["cfg"])
    assert max(sizes) in traffic.cohort_maxima(got["cfg"], sizes)
    assert {m["name"] for m in got["end_to_end"]} >= {
        "setup_s", "train_examples_per_s"}
    assert got["per_layer"]


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file(config):
    cfg = json.loads((ROOT / config["file"]).read_text())
    assert cfg["name"] == config["name"]
    assert set(config["reduced"]) <= set(cfg["reduced"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_loads(metric):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(metric, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert callable(module.read)


def test_peaks_table_names_the_chip():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert peaks["devices"]["TPU v5 lite"] == {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(SystemExit):
        harness.device_peaks("no such chip")


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", str(2 ** 31 + 7), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "TPU" in out.stderr
