"""Loading the benchmark's files by name, the rules of ``BENCHMARK.json``,
the peak table and the FLOP arithmetic."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

from bench import spec as S  # noqa: E402

REPO = bench_tiny.REPO


@pytest.fixture(scope="module")
def bench():
    return S.Benchmark(REPO)


def test_every_cell_loads_with_its_files(bench):
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.config["arch"]["classes"] == 10
        assert {m["name"] for m in cell.end_to_end} == {
            "images_per_s", "peak_hbm_gb", "setup_s"}
        names = {m["name"] for m in cell.per_layer}
        assert {"device_idle_share", "step_mfu"} <= names
        for m in cell.per_layer:
            assert m["moves"] == "images_per_s"
            assert callable(bench.reader(m["name"]))
        assert set(cell.limits["limits"]) >= {"loss_first", "smd_steps"}


def test_names_and_units_use_only_the_allowed_characters(bench):
    spec = bench.spec
    names = ([c["name"] for c in spec["configs"]]
             + [w[k] for w in spec["workloads"]
                for k in ("name", "config", "traffic")]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
             + [k for c in spec["configs"] for k in c["reduced"]])
    for n in names:
        assert S.NAME.fullmatch(n), n
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert S.UNIT.fullmatch(m["unit"]), m["unit"]
    assert len(json.dumps(spec)) < 64 * 1024


@pytest.mark.parametrize("bad", [
    {"name": "has space"}, {"name": "a/b"}, {"name": ".hidden"},
    {"unit": "images per s"}, {"better": "more"}, {"bound": 0.5},
    {"source": "program_counter"}, {"why": "x"}])
def test_rules_refuse_a_bad_entry(bad):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    metric = dict(spec["end_to_end"][0])
    if "why" in bad:
        metric["why"] = bad["why"]           # a key no metric may have
    else:
        metric.update(bad)
    spec["end_to_end"][0] = metric
    with pytest.raises(S.SpecError):
        S.validate(spec)


def test_unknown_names_are_refused(bench):
    with pytest.raises(S.SpecError, match="unknown workload"):
        bench.cell("no.such.cell")
    with pytest.raises(S.SpecError, match="unknown config"):
        bench.config("resnet1000")
    with pytest.raises(S.SpecError, match="missing file"):
        bench.mix("no_such_mix")
    with pytest.raises(S.SpecError, match="missing file"):
        bench.reader("no_such_metric")
    with pytest.raises(S.SpecError):
        bench.mix("../configs/resnet74")


def test_a_cell_whose_mix_file_is_missing_is_refused(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    (root / "bench" / "mixes" / "tiny_e2train.json").unlink()
    b = S.Benchmark(root)
    with pytest.raises(S.SpecError, match="missing file"):
        b.cell("tiny.e2train")


def test_a_config_file_missing_is_refused(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    (root / "bench" / "configs" / "tiny_resnet.json").unlink()
    with pytest.raises(S.SpecError, match="missing file"):
        S.Benchmark(root).cell("tiny.baseline")


def test_a_new_mix_and_metric_load_from_new_files_alone(tmp_path):
    """A later change adds a cell and a per-layer metric as new files and
    entries; nothing that exists is edited."""
    root = bench_tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    mix = bench_tiny.tiny_mix("e2train")
    mix["why"] = "a dummy mix"
    (root / "bench" / "mixes" / "dummy_mix.json").write_text(json.dumps(mix))
    (root / "bench" / "limits" / "tiny.dummy.json").write_text(json.dumps(
        {"limits": {"loss_first": 1e-3}, "readings": {}}))
    (root / "bench" / "metrics" / "dummy_metric.py").write_text(
        "def read(record, trace):\n    return 42.0\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.dummy", "config": "tiny_resnet",
                              "traffic": "dummy_mix", "chips": 1,
                              "why": "a dummy cell"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "images_per_s",
                              "workloads": ["tiny.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    b = S.Benchmark(root)
    cell = b.cell("tiny.dummy")
    assert cell.mix["why"] == "a dummy mix"
    assert "dummy_metric" in {m["name"] for m in cell.per_layer}
    assert b.reader("dummy_metric")({}, None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_peak_lookup_refuses_an_unknown_device():
    from bench import run as R
    assert R.peaks(REPO, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(S.SpecError, match="no published peaks"):
        R.peaks(REPO, "TPU v99")


@pytest.mark.parametrize("config,sites", [("resnet74", 75)])
def test_flop_arithmetic_matches_the_pinned_numbers(bench, config, sites):
    cfg = S.load_json(REPO / "bench" / "configs" / f"{config}.json")
    fam = bench.family(cfg["family"])
    assert fam.flop_per_image(cfg["arch"]) == cfg["flop_per_image"]
    assert len(fam.psg_sites(cfg["arch"], 128)) == sites


def test_weights_come_from_the_seed_in_the_programs_layout(bench):
    import jax
    import numpy as np
    cell = bench.cell("resnet74.baseline")
    fam = bench.family("cifar_cnn")
    exp = fam.program_experiment(cell.config, cell.mix, 1, 0)
    a = jax.device_get(fam.init_state(cell.config, exp, 5))
    b = jax.device_get(fam.init_state(cell.config, exp, 5))
    c = jax.device_get(fam.init_state(cell.config, exp, 6))
    assert sum(int(np.size(x)) for x in jax.tree.leaves(a.params)) \
        == cell.config["param_count"]
    w = a.params["stages"][0]["rest"]["conv1"]["w"]
    assert w.shape == (11, 144, 16)
    assert abs(float(np.std(w)) - 1.41 / 12 * 0.88) < 0.01
    assert np.array_equal(w, b.params["stages"][0]["rest"]["conv1"]["w"])
    assert not np.array_equal(w, c.params["stages"][0]["rest"]["conv1"]["w"])
    assert float(np.max(a.model_state["stem_bn"]["var"])) == 1.0


def test_smd_schedule_matches_the_programs_decisions():
    from bench import correct as C
    from repro.core.smd import smd_keep_host
    seed = 1040144449
    keep = C.smd_schedule(seed, 5, 40, 0.5, True)
    assert keep == [smd_keep_host(seed, s, 0.5) for s in range(5, 45)]
    n = C.nominal_steps(seed, 5, 8, 0.5, True)
    assert sum(keep[:n]) == 8 and keep[n - 1]


def test_a_run_without_a_chip_exits_nonzero_and_prints_no_result(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    cmd = [sys.executable, "bench/run.py", "--workload", "resnet74.e2train",
           "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 3
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
    # a checkout with only BENCHMARK.json and the benchmark's own files
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", bare)
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, bare / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
