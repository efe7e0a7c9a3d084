"""Manifest discovery: every cell, metric, arrival process, kernel and
model family is a file found by its name, and adding files adds a cell or
a metric with no edit to any file that is there."""
import json
import re
import shutil
from types import SimpleNamespace

import pytest

from bench import manifest, report

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench_json():
    return manifest.load_manifest()


def test_every_name_resolves_to_a_file(bench_json):
    for w in bench_json["workloads"]:
        cell = manifest.load_cell(w["name"], bench_json)
        manifest.load_module("families", cell.config["model"]["family"])
        manifest.load_module("arrivals", cell.traffic["arrivals"])
        assert cell.end_to_end and cell.per_layer
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert callable(manifest.load_module("metrics", m["name"]).read)


def test_every_kernel_counts_its_work():
    files = sorted((manifest.BENCH_DIR / "kernels").glob("*.py"))
    assert files
    for f in files:
        k = manifest.load_module("kernels", f.stem)
        assert k.NAMES and callable(k.cost) and callable(k.calls), f.name


def test_every_family_declares_its_program_fields():
    """Each family says which program field each of its file keys sets,
    and every key it names is in each configuration file of the family."""
    files = sorted((manifest.BENCH_DIR / "families").glob("*.py"))
    assert files
    configs = [json.loads(p.read_text())
               for p in (manifest.BENCH_DIR / "configs").glob("*.json")]
    for f in files:
        fam = manifest.load_module("families", f.stem)
        fields = fam.PROGRAM_FIELDS
        assert fields and "num_layers" in fields, f.name
        for cfg in configs:
            if cfg["model"]["family"] == f.stem:
                for key in fields.values():
                    manifest.file_value(cfg, key)


def test_every_cell_builds_its_configuration(bench_json):
    """The program's published config, cut as the file says, agrees with
    the configuration file in every field of the family's table: what
    set-up checks on the chip, without building the model."""
    from bench import harness
    from repro.configs import get_config

    for w in bench_json["workloads"]:
        cfg = manifest.load_cell(w["name"], bench_json).config
        fam = manifest.load_module("families", cfg["model"]["family"])
        prog = get_config(cfg["model"]["arch"]).replace(
            **harness.program_overrides(cfg, fam))
        assert harness.check_program_config(prog, cfg, fam) == [], w["name"]


def test_every_cut_key_is_named_in_reduced(bench_json):
    """A configuration's cut names only keys it lists in ``reduced`` (as
    the manifest does), with the deployment each stands for under
    ``assumed``, and never a width."""
    width = re.compile(r"_dim$|_rank$|hidden_size|intermediate_size|latent"
                       r"|state|proj|head_|expan|per_tok|top_k")
    for p in (manifest.BENCH_DIR / "configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        for key in cfg.get("cut", {}):
            assert key in cfg["reduced"], (p.name, key)
            assert key in cfg["assumed"], (p.name, key)
            assert key in cfg and not width.search(key), (p.name, key)
    for c in bench_json["configs"]:
        cfg = json.loads((manifest.ROOT / c["file"]).read_text())
        assert set(cfg.get("cut", {})) <= set(c["reduced"])


def test_unknown_device_kind_is_refused():
    assert manifest.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        manifest.load_peaks("TPU v99")


def test_unknown_names_are_refused(bench_json):
    with pytest.raises(KeyError):
        manifest.load_cell("nope.none", bench_json)
    with pytest.raises(FileNotFoundError):
        manifest.load_module("metrics", "no_such_metric")


def test_manifest_keeps_the_contract(bench_json):
    assert bench_json["command"] == ["python3", "bench/run.py"]
    assert set(bench_json) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench_json["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench_json["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"]: w for w in bench_json["workloads"]}
    for m in bench_json["end_to_end"]:
        assert NAME.match(m["name"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench_json["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        for w in m["workloads"]:
            moved = e2e[m["moves"]]
            assert w in cells and w in moved.get("workloads", [w])
    for c in bench_json["configs"]:
        cfg = json.loads((manifest.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for w in cells.values():
        assert w["chips"] == 1 and len(w["why"]) <= 200


def test_adding_files_adds_a_cell_and_a_metric(tmp_path, bench_json):
    """A new traffic file, a new metric file and their manifest entries
    give a new cell that reports the new metric; no file that was there
    is touched."""
    bench = tmp_path / "bench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    traffic = json.loads((bench / "traffic" / "dashboard.json").read_text())
    traffic["params"]["per_round"] = 250
    (bench / "traffic" / "dashboard-over.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "queries_done.py").write_text(
        "def read(run):\n    return sum(r.state == 'done' for r in run.queries)\n")
    grown = json.loads(json.dumps(bench_json))
    grown["workloads"].append({"name": "qwen2-0.5b.dashboard-over",
                               "config": "qwen2-0.5b",
                               "traffic": "dashboard-over", "chips": 1,
                               "why": "above the knee"})
    grown["end_to_end"].append({"name": "queries_done", "unit": "queries",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock"})
    cell = manifest.load_cell("qwen2-0.5b.dashboard-over", grown, bench)
    assert cell.traffic["params"]["per_round"] == 250
    assert "queries_done" in [m["name"] for m in cell.end_to_end]
    mod = manifest.load_module("metrics", "queries_done", bench)
    run = SimpleNamespace(queries=[SimpleNamespace(state="done")] * 3)
    assert mod.read(run) == 3
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _empty_run():
    return SimpleNamespace(trace=None, queries=[], config={}, seconds=1.0,
                           finished_in_window=lambda: [], checks={},
                           memory_peak_bytes=0, metrics_missing=[],
                           bench_dir=manifest.BENCH_DIR)


def test_metrics_that_find_nothing_are_left_out():
    entries = [{"name": "device_idle_share", "unit": "%"},
               {"name": "chip_s_per_query", "unit": "chip-s"}]
    assert report.metrics(entries, _empty_run()) == (
        {}, ["device_idle_share", "chip_s_per_query"])


def test_a_named_metric_that_reads_nothing_makes_the_run_incorrect():
    """A metric the cell names that finds nothing (a kernel renamed, a
    counter gone) is left out of the line and the run is not correct."""
    run = _empty_run()
    cell = SimpleNamespace(end_to_end=[{"name": "chip_s_per_query", "unit": "chip-s"}],
                           per_layer=[])
    res = report.result(cell, run, {"platform": "tpu"}, traced=False)
    assert res["metrics"] == {} and res["correct"] is False
    assert res["checks"]["metrics_missing"] == {"value": 1, "limit": 0}
    assert run.metrics_missing == ["chip_s_per_query"]
