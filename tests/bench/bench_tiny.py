"""A tiny benchmark root for the harness's CPU tests: ``BENCHMARK.json``
with small cells beside a copy of ``bench/``, so a test may add files
there without touching the real ones."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for _p in (REPO, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

TINY_RESNET = {
    "source": "https://arxiv.org/abs/1512.03385",
    "family": "cifar_cnn", "reference": "cnn", "program_name": "resnet14",
    "arch": {"kind": "resnet", "depth": 14, "width": 4, "classes": 10,
             "image_hw": 8, "channels": 3},
    "paper_train": {"optimizer": "sgdm", "lr": 0.1, "momentum": 0.9,
                    "weight_decay": 0.0001, "schedule": "step",
                    "total_steps": 64000, "decay_points": [0.5, 0.75],
                    "decay_factor": 0.1},
    "init": {"ones": ["scale"], "zeros": ["bias", "fc_b", "lstm_b", "head_b"],
             "gain": {"w": 1.41}, "default_gain": 1.0, "state_ones": ["var"]},
    "assumed": [], "reduced": ["depth", "image_hw"],
    "flop_per_image": 0,
}


def tiny_mix(name: str, batch: int = 8, chunk: int = 2) -> dict:
    mix = json.loads((REPO / "bench" / "mixes" / f"{name}.json").read_text())
    mix["batch_per_chip"], mix["chunk_steps"] = batch, chunk
    return mix


def make_root(tmp: Path, limits=None) -> Path:
    """``tmp`` as a benchmark root with the cells ``tiny.e2train`` and
    ``tiny.baseline`` (ResNet-14, width 4, 8x8 images, batch 8, chunks of
    2 steps); ``limits`` maps a cell to its correctness limits."""
    from bench.families import cifar_cnn
    root = Path(tmp)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = dict(TINY_RESNET)
    cfg["flop_per_image"] = cifar_cnn.flop_per_image(cfg["arch"])
    (root / "bench" / "configs" / "tiny_resnet.json").write_text(
        json.dumps(cfg))
    for mix in ("e2train", "baseline"):
        (root / "bench" / "mixes" / f"tiny_{mix}.json").write_text(
            json.dumps(tiny_mix(mix)))
        (root / "bench" / "limits" / f"tiny.{mix}.json").write_text(
            json.dumps({"limits": (limits or {}).get(
                f"tiny.{mix}", {"loss_first": 1e-4, "smd_steps": 0}),
                "readings": {}}))
    spec["configs"] = [{"name": "tiny_resnet", "source": cfg["source"],
                        "file": "bench/configs/tiny_resnet.json",
                        "reduced": cfg["reduced"], "why": "CPU tests"}]
    spec["workloads"] = [
        {"name": f"tiny.{mix}", "config": "tiny_resnet",
         "traffic": f"tiny_{mix}", "chips": 1, "why": "CPU tests"}
        for mix in ("e2train", "baseline")]
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.e2train"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
