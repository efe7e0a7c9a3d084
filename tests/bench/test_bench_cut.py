"""A configuration and a model family are files only: a configuration
whose ``cut`` keeps part of the published model on this chip, of a family
the bench did not have, runs through the harness with no edit to any file
that is there; and a program built off its configuration stops set-up
with the field named."""
import json
import shutil

import pytest

from bench import check, harness, manifest
from bench.faults import half_batch

ARCH = "qwen2-0.5b"


def _grow(bench_json: dict, bench, cut: dict) -> tuple[dict, str]:
    """A new family file (a copy of ``dense_gqa``), a new configuration
    with ``cut`` and a new workload, in the bench directory ``bench``."""
    shutil.copy(bench / "families" / "dense_gqa.py",
                bench / "families" / "dense_gqa_stage.py")
    cfg = json.loads((bench / "configs" / f"{ARCH}.json").read_text())
    cfg.update(name=f"{ARCH}-stage", cut=cut, reduced=sorted(cut))
    cfg["model"]["family"] = "dense_gqa_stage"
    cfg["assumed"] |= {k: f"{cfg[k]} -> {v}: one stage of a pipeline"
                       for k, v in cut.items()}
    (bench / "configs" / f"{ARCH}-stage.json").write_text(json.dumps(cfg))
    grown = json.loads(json.dumps(bench_json))
    grown["configs"].append({
        "name": f"{ARCH}-stage", "source": cfg["source"],
        "file": f"{bench.name}/configs/{ARCH}-stage.json",
        "reduced": sorted(cut), "why": "one pipeline stage"})
    name = f"{ARCH}-stage.dashboard"
    grown["workloads"].append({"name": name, "config": f"{ARCH}-stage",
                               "traffic": "dashboard", "chips": 1,
                               "why": "a cut configuration"})
    return grown, name


def test_a_cut_configuration_of_a_new_family_is_files_only(
        tmp_path, tiny_cell, run_tiny):
    from repro import configs
    from repro.core import live

    bench = tmp_path / "bench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    grown, name = _grow(manifest.load_manifest(), bench, {"num_hidden_layers": 1})

    cell = manifest.load_cell(name, grown, bench)
    assert cell.config["num_hidden_layers"] == 1
    assert cell.config["published"] == {"num_hidden_layers": 24}
    assert cell.bench_dir == bench

    # at CPU size the reduced program has 2 layers; the cut keeps 1
    tiny = tiny_cell(name, grown, bench, keep_cut=True)
    assert tiny.config["published"] == {"num_hidden_layers": 2}
    built = []
    run = run_tiny(tiny, fault=lambda eng: built.append(eng.models._archs[ARCH]))
    assert check.correct(run.checks), run.checks
    assert run.checks["logit_gap"]["value"] <= 1e-3
    prog_cfg, params = built[0][0], built[0][1]
    assert prog_cfg.num_layers == 1 and run.dims["L"] == 1
    assert params["blocks"]["sub0"]["ln1"].shape[0] == 1
    assert run.bench_dir == bench
    assert live.get_config is configs.get_config
    assert {p: p.read_bytes() for p in before} == before


def test_queries_of_several_rows_are_checked_row_by_row(
        tmp_path, tiny_cell, run_tiny):
    """A traffic file whose queries carry two rows each: a sound run is
    correct, and a batch cut in half is caught in rows past the first of
    a query."""
    bench = tmp_path / "bench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = json.loads((bench / "traffic" / "dashboard.json").read_text())
    traffic["batch"] = 2
    (bench / "traffic" / "dashboard-b2.json").write_text(json.dumps(traffic))
    grown = manifest.load_manifest()
    name = f"{ARCH}.dashboard-b2"
    grown["workloads"].append({"name": name, "config": ARCH, "chips": 1,
                               "traffic": "dashboard-b2", "why": "two rows"})
    sound = run_tiny(tiny_cell(name, grown, bench))
    assert check.correct(sound.checks), sound.checks
    assert {len(e.members) for e in sound.executions.values()} == {1, 2}
    cut = run_tiny(tiny_cell(name, grown, bench), fault=half_batch)
    assert cut.checks["logit_gap"]["value"] > cut.checks["logit_gap"]["limit"]


def test_a_program_off_its_configuration_stops_set_up(tiny_cell, run_tiny):
    """The family's table reaches fields the dense check never had:
    ``norm_eps`` against the file's ``rms_norm_eps``."""
    cell = tiny_cell(f"{ARCH}.dashboard")
    cell.config["rms_norm_eps"] = 1e-5
    with pytest.raises(RuntimeError, match="norm_eps: program 1e-06 != config"
                                           " rms_norm_eps 1e-05"):
        run_tiny(cell)


def test_a_file_with_no_cut_loads_as_it_is():
    cell = manifest.load_cell(f"{ARCH}.dashboard")
    entry = {c["name"]: c for c in manifest.load_manifest()["configs"]}[ARCH]
    assert cell.config == json.loads((manifest.ROOT / entry["file"]).read_text())
    fam = manifest.load_module("families", cell.config["model"]["family"])
    assert harness.program_overrides(cell.config, fam) == {}


def test_a_cut_goes_to_the_program_through_the_family_table():
    fam = manifest.load_module("families", "dense_gqa")
    cfg = {"name": "x", "num_hidden_layers": 32, "vocab_size": 32000,
           "reduced": ["num_hidden_layers", "vocab_size"],
           "cut": {"num_hidden_layers": 4, "vocab_size": 4000}}
    cut = manifest.apply_cut(cfg)
    assert cut["num_hidden_layers"] == 4 and cut["vocab_size"] == 4000
    assert cut["published"] == {"num_hidden_layers": 32, "vocab_size": 32000}
    assert cfg["num_hidden_layers"] == 32  # the file's own dict is kept
    assert harness.program_overrides(cut, fam) == {"num_layers": 4,
                                                   "vocab_size": 4000}


def test_a_cut_must_be_named_and_known():
    fam = manifest.load_module("families", "dense_gqa")
    cfg = {"name": "x", "num_hidden_layers": 32, "reduced": [],
           "cut": {"num_hidden_layers": 4}}
    with pytest.raises(ValueError, match="without naming them in its reduced"):
        manifest.apply_cut(cfg)
    with pytest.raises(ValueError, match="which it does not hold"):
        manifest.apply_cut(dict(cfg, reduced=["num_layers"],
                                cut={"num_layers": 4}))
    odd = dict(cfg, moe_layers=8, reduced=["moe_layers"], cut={"moe_layers": 2})
    with pytest.raises(RuntimeError, match="sets no field from the cut's"):
        harness.program_overrides(manifest.apply_cut(odd), fam)
