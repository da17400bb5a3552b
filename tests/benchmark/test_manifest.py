"""BENCHMARK.json against the contract, and the harness against its promise
that a new cell is new files and one manifest entry."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import harness

REPO = harness.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_dim", "head_size", "expansion", "experts_per_tok")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def _metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(manifest["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in manifest["paths"])
    assert len(manifest["command"]) <= 32
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    cells = 24  # later PRs add cells under this length, up to the limit
    total = ((2 + 14 * cells) * (manifest["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200


def test_names_units_and_text_fields(manifest):
    names = [m["name"] for m in _metrics(manifest)]
    assert len(names) == len(set(names))
    for m in _metrics(manifest):
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}
        assert m["name"].endswith("_roofline") <= (m["unit"] == "%")
    for entry in manifest["configs"] + manifest["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200
        assert "\n" not in entry["why"] and "\t" not in entry["why"]
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for word in manifest["command"]:
        assert 1 <= len(word) <= 200


def test_cells_and_configs_hold_together(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"]: c for c in manifest["configs"]}
    assert {w["config"] for w in cells} == set(configs), (
        "every configuration is used by a cell, and every cell's exists")
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        held = json.loads((REPO / c["file"]).read_text())
        assert held["reduced"] == c["reduced"]
        assert held["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS), key
        assert (REPO / held["reference"]).is_file()
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    for w in cells:
        cell = json.loads((REPO / "benchmarks" / "workloads"
                           / f"{w['name']}.json").read_text())
        for key in ("config", "traffic", "chips"):
            assert cell[key] == w[key], (w["name"], key)
        assert (REPO / "benchmarks" / "drivers"
                / f"{cell['driver']}.py").is_file()


def test_every_metric_has_a_reader_and_every_cell_its_metrics(manifest):
    e2e = harness.load_readers("end_to_end")
    layer = harness.load_readers("layer_metrics")
    assert {m["name"] for m in manifest["end_to_end"]} <= set(e2e)
    assert {m["name"] for m in manifest["per_layer"]} <= set(layer)
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        mine = [m["name"] for m in manifest["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   and m["moves"] in mine for m in manifest["per_layer"])


def test_paths_hold_only_allowed_file_names(manifest):
    for p in manifest["paths"]:
        for f in (REPO / p).rglob("*"):
            if "__pycache__" in f.parts or not f.is_file():
                continue
            assert PATH.match(str(f.relative_to(REPO))), f


def test_a_new_cell_is_new_files_and_one_manifest_entry(tmp_path):
    """Copy the benchmark, ADD a configuration, a cell, a driver and a
    per-layer metric (editing no file that is there, bar the manifest's new
    entries), and run the new cell through the unchanged harness."""
    shutil.copytree(REPO / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {f: f.read_bytes() for f in (tmp_path / "benchmarks").rglob("*")
              if f.is_file()}
    bench = tmp_path / "benchmarks"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(
        {"name": "throwaway", "source": "none", "reduced": []}))
    (bench / "workloads" / "throwaway.echo.json").write_text(json.dumps(
        {"name": "throwaway.echo", "config": "throwaway", "traffic": "echo",
         "driver": "echo", "chips": 1, "traffic_params": {"answer": 42}}))
    (bench / "drivers" / "echo.py").write_text(
        "import time\n"
        "def run(run, devices):\n"
        "    o = {'kind': 'echo', 'answer': run.cell['traffic_params']['answer'],\n"
        "         't_process_start': run.t_process_start,\n"
        "         't_first_measured': time.time(), 'platform': 'cpu'}\n"
        "    return {'correct': True, 'attempted': 1, 'failed': 0,\n"
        "            'memory_peak_bytes': 1, 'observed': o,\n"
        "            'trace': {'busy_s': 0.5, 'window_s': 1.0}}\n")
    (bench / "layer_metrics" / "echo.py").write_text(
        "READERS = {'echo_answer': lambda o: o.get('answer')}\n")
    manifest = harness.load_manifest()
    manifest["configs"].append(
        {"name": "throwaway", "source": "none", "reduced": [], "why": "test",
         "file": "benchmarks/configs/throwaway.json"})
    manifest["workloads"].append(
        {"name": "throwaway.echo", "config": "throwaway", "traffic": "echo",
         "chips": 1, "why": "test"})
    manifest["per_layer"].append(
        {"name": "echo_answer", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "Echo", "moves": "setup_s",
         "workloads": ["throwaway.echo"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    lines = {}
    for trace in ("0", "1"):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload",
             "throwaway.echo", "--seed", "0", "--seconds", "1", "--trace",
             trace, "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
            text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        lines[trace] = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(lines["0"]["metrics"]) == {"setup_s"}
    assert lines["1"]["metrics"] == {"echo_answer": {"value": 42.0,
                                                     "unit": "count"}}
    assert lines["1"]["device"]["busy_s"] == 0.5
    for f, content in before.items():
        assert f.read_bytes() == content, f"{f} was edited"


def test_a_new_train_cell_is_one_data_file_and_one_manifest_entry(tmp_path):
    """A later PR's train cell, here the mesh path on four (virtual) chips:
    one file under ``workloads/`` and one entry under the manifest's
    ``workloads``. No metric's entry is touched, and the cell reports every
    train metric all the same."""
    shutil.copytree(REPO / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmarks"
    before = {f: f.read_bytes() for f in bench.rglob("*") if f.is_file()}
    cell = json.loads((bench / "workloads"
                       / "siglip_b16_256.train.json").read_text())
    cell.update(name="siglip_b16_256.train_mesh4", traffic="train_mesh4",
                chips=4)
    cell["traffic_params"].update(loss="siglip_ring", cli_args=[
        "--bf16", "--remat", "dots", "--mesh", "data=2,model=2",
        "--max-devices", "4", "--rules", "fsdp_tp", "--loss", "siglip_ring"])
    (bench / "workloads" / f"{cell['name']}.json").write_text(json.dumps(cell))
    manifest = harness.load_manifest()
    manifest["workloads"].append(
        {"name": cell["name"], "config": "siglip_b16_256",
         "traffic": "train_mesh4", "chips": 4, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)  # the harness asks for its own four devices
    lines = {}
    for trace in ("0", "1"):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload",
             cell["name"], "--seed", "7", "--seconds", "2", "--trace", trace,
             "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
            text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-2000:]
        lines[trace] = json.loads(done.stdout.strip().splitlines()[-1])
    assert all(line["correct"] is True and line["device"]["count"] == 4
               for line in lines.values())
    assert set(lines["0"]["metrics"]) == {"train_img_per_s", "setup_s"}
    assert {"step_ms", "data_wait_ms", "host_sync_ms", "flash_calls",
            "hbm_program_gb"} <= set(lines["1"]["metrics"])
    for f, content in before.items():
        assert f.read_bytes() == content, f"{f} was edited"
