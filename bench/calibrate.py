"""Readings that set a cell's correctness limits (``bench/limits``).

    python3 bench/calibrate.py --workload resnet74.e2train \
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 1 2 3

In one process on the cell's chips, for each seed: set-up as a benchmark
run makes it (weights, the program's trainer, its first chunk), then the
reference over that chunk, and the numbers of ``bench/correct.py``
(``program``: the lower readings).  For each control seed also:

* ``control``: the reference computed in bfloat16, put in the program's
  place (the nearest precision below the configuration's float32);
* ``half_batch``: the reference on the first half of every batch, the
  mean taken over it, in the program's place;
* ``one_shard`` (cells on several chips): the reference on the first
  chip's share of every batch, as if the exchange between chips were
  left out;
* ``unchanged``: the program's own record with the state it started
  from, as a chunk that returns its state unchanged leaves it (no run);
* ``reversed_rows``: a witness, not a fault: the reference on every batch
  with its rows in reverse order (the same examples, the same math,
  another summation order) in the program's place.

One JSON line per reading.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run as R  # noqa: E402
from bench import spec as S  # noqa: E402


def half(batch):
    return {k: v[: v.shape[0] // 2] for k, v in batch.items()}


def reverse(batch):
    return {k: v[::-1] for k, v in batch.items()}


def shard_of(chips):
    return lambda batch: {k: v[: v.shape[0] // chips]
                          for k, v in batch.items()}


def readings(root: Path, workload: str, seeds, control_seeds,
             platform: str = "tpu", out=None):
    """The readings, under the benchmark run's matmul precision."""
    import jax
    with jax.default_matmul_precision(R.MATMUL_PRECISION):
        return _readings(root, workload, seeds, control_seeds, platform,
                         out)


def _readings(root, workload, seeds, control_seeds, platform, out):
    import jax.numpy as jnp
    from bench import correct as C
    bench = S.Benchmark(root)
    cell = bench.cell(workload)
    devices = R.devices_for(cell.chips, platform)
    if platform == "tpu":
        R.use_cache(root / R.CACHE)
    rows = []

    def emit(kind, seed, numbers, secs):
        row = {"cell": workload, "kind": kind, "seed": seed,
               "seconds": secs, **numbers}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out is not None:
            with open(out, "a") as f:
                f.write(line + "\n")

    for seed in seeds:
        t = time.perf_counter()
        cr = R.CellRun(bench, cell, seed, devices)
        cr.build()
        cr.first_chunk()
        cr.release()
        numbers, prog, ref = cr.check()
        emit("program", seed, numbers, time.perf_counter() - t)
        if seed not in control_seeds:
            continue
        start = cr.start
        unchanged = dict(prog, params_after=start.params,
                         buffer=cr.fam.optimizer_buffer(start.opt),
                         state=start.model_state)
        emit("unchanged", seed, C.compare(unchanged, ref), 0.0)
        faults = [("control", jnp.bfloat16, None),
                  ("half_batch", None, half),
                  ("reversed_rows", None, reverse)]
        if cell.chips > 1:
            faults.append(("one_shard", None, shard_of(cell.chips)))
        for kind, dtype, fault in faults:
            t = time.perf_counter()
            emit(kind, seed, cr.check(control_dtype=dtype, fault=fault)[0],
                 time.perf_counter() - t)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    try:
        readings(ROOT, args.workload, args.seeds, set(args.control_seeds),
                 out=args.out)
    except R.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return R.EXIT_NO_CHIP
    return 0


if __name__ == "__main__":
    sys.exit(main())
