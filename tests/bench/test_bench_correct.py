"""``correct`` on the CPU at a tiny size: the program agrees with the plain
reference, and the control (the reference in bfloat16 in the program's
place) and each fault of the timed path planted in the trainer make
``correct`` false.  These drive the harness's own run, skipping only its
look for a chip."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

from bench import calibrate  # noqa: E402
from bench import run as R  # noqa: E402

# what the program reaches on the CPU, where its products are exact f32;
# the E2-Train numbers after the first update allow for PSG signs that
# f32 summation order flips (one flip reads up to 0.005 and 0.15 here)
LIMITS = {
    "tiny.baseline": {"loss_first": 1e-5, "loss_rest": 1e-5,
                      "grad_norm_gap": 1e-4, "update_norm_gap": 1e-4,
                      "smd_steps": 0, "slu_executed": 0, "step_counter": 0},
    "tiny.e2train": {"gate_first": 1e-5, "loss_first": 1e-5,
                     "loss_rest": 0.03,
                     "update_norm_gap": 0.5, "smd_steps": 0,
                     "slu_executed": 0, "step_counter": 0},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"),
                                limits=LIMITS)


def run_cell(root, cell, seed=2 ** 31 + 11, trainer_cls=None):
    return R.run(["--workload", cell, "--seed", str(seed), "--seconds",
                  "0.5", "--trace", "0"], root=root, platform="cpu",
                 trainer_cls=trainer_cls)


@pytest.mark.parametrize("cell", ["tiny.baseline", "tiny.e2train"])
def test_the_program_agrees_with_the_reference(root, cell):
    res = run_cell(root, cell)
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 1
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"images_per_s", "peak_hbm_gb", "setup_s"}


@pytest.mark.parametrize("cell,seeds", [("tiny.baseline", [3]),
                                        ("tiny.e2train", [3])])
def test_the_bfloat16_control_fails(root, cell, seeds):
    rows = calibrate.readings(root, cell, seeds, set(seeds), platform="cpu")
    limits = LIMITS[cell]
    for row in rows:
        over = [k for k, v in limits.items() if row[k] > v]
        if row["kind"] in ("program", "reversed_rows"):
            assert not over, row
        else:             # the control, half a batch, an unchanged state
            assert over, row


def broken(kind):
    """A ``Trainer`` whose chunk program is broken underneath."""
    from repro.training.trainer import Trainer

    class Broken(Trainer):
        def chunk_program(self):
            real = super().chunk_program()

            def chunk(state, batches, incs):
                if kind == "unchanged":
                    _, metrics = real(state, batches, incs)
                    return state._replace(step=state.step + incs.sum()), \
                        metrics
                cut = 2 if kind == "half_batch" else 4
                part = {k: v[:, : v.shape[1] // cut]
                        for k, v in batches.items()}
                return real(state, part, incs)
            return chunk

    return Broken


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "one_shard"])
def test_a_broken_timed_path_is_not_correct(root, fault):
    res = run_cell(root, "tiny.baseline", trainer_cls=broken(fault))
    assert not res["correct"], res["checks"]
