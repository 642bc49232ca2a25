"""The benchmark's one command: run one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload resnet74.e2train --seed 7 --seconds 30 \
        --trace 0

A run, in one process that holds the cell's chips:

1. set-up: make the weights on the device from the seed, build the
   program's trainer (``Trainer`` in chunked mode, the data pipeline
   prefetching batches from the benchmark's generator), drive it through
   its first chunk (which compiles the cell's one chunk shape, or loads it
   from the persistent cache) and keep what the first chunk produced for
   the correctness check; then run a few more chunks to learn the rate;
2. the window: one ``Trainer.run(n)`` call whose ``n`` nominal steps hold
   a whole number of chunks and last about ``--seconds`` at that rate,
   timed by the host clock up to ``block_until_ready`` on the state;
   with ``--trace 1`` the profiler records the window instead;
3. after the window: read the memory peak, free the program's state, run
   the plain reference over the first chunk and compare.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (executed steps in the window), ``failed`` (those with a
non-finite loss), ``metrics``, ``device`` and, traced, ``breakdown``; the
numbers compared, each with its limit, come last under ``checks`` and on
standard error.  Without the chips the cell asks for, the run exits with
code 3 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import correct as C  # noqa: E402
from bench import spec as S  # noqa: E402
from bench import trace as T  # noqa: E402
from bench import traffic  # noqa: E402

CACHE = ".jax_cache"
TRACE = ".bench_trace"
MATMUL_PRECISION = "highest"
CALIBRATE_CHUNKS = 3
TRACE_SECONDS = 3.0
EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(chips: int, platform: str = "tpu"):
    """The first ``chips`` devices, which must be of ``platform``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"no accelerator: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def use_cache(path: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so only a cell's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.active and "backend_compile" in name:
            self.count += 1


def peaks(root: Path, kind: str):
    table = S.load_json(root / "bench" / "peaks.json")
    if kind not in table["devices"]:
        raise S.SpecError(f"no published peaks for device kind {kind!r} in "
                          "bench/peaks.json")
    return table["devices"][kind]


class CellRun:
    """One run of one cell: set-up, window, reference."""

    def __init__(self, bench: S.Benchmark, cell: S.Cell, seed: int, devices):
        self.bench, self.cell, self.devices = bench, cell, devices
        self.fam = bench.family(cell.config["family"])
        self.seeds = self.fam.seeds(seed)
        self.arch = cell.config["arch"]
        self.batch = int(cell.mix["batch_per_chip"]) * cell.chips
        self.k = int(cell.mix["chunk_steps"])
        smd = cell.mix["e2train"]["smd"]
        self.smd = (float(smd["drop_prob"]), bool(smd["enabled"]))
        self.make = traffic.make_batches(cell.mix["data"], self.arch,
                                         self.seeds["data"], self.batch)

    def nominal(self, start: int, executed: int) -> int:
        return C.nominal_steps(self.seeds["train"], start, executed,
                               *self.smd)

    def feed(self, step, shard):
        import jax
        with jax.profiler.TraceAnnotation("bench_make_batch"):
            return self.make(step, shard)

    def build(self, trainer_cls=None):
        """Weights from the seed and the program's trainer over them."""
        import jax
        exp = self.fam.program_experiment(self.cell.config, self.cell.mix,
                                          self.cell.chips,
                                          self.seeds["train"])
        state = self.fam.init_state(self.cell.config, exp,
                                    self.seeds["weights"])
        self.start = jax.device_get(state)
        mesh = None
        if self.cell.chips > 1:
            from jax.sharding import Mesh
            import numpy as np
            mesh = Mesh(np.array(self.devices).reshape(self.cell.chips, 1),
                        ("data", "model"))
        if trainer_cls is None:
            from repro.training.trainer import Trainer as trainer_cls
        self.trainer = trainer_cls(exp, state, self.feed, chunk_steps=self.k,
                                   deadline_s=0.0, mesh=mesh)
        return self.trainer

    def first_chunk(self):
        """Drive the trainer through its first chunk and keep what it
        produced for the check."""
        import jax
        tr = self.trainer
        hist = tr.run(self.nominal(0, self.k))
        self.first = {"hist": [dict(h) for h in hist],
                      "after": jax.device_get(tr.state),
                      "executed": tr.executed_steps}

    def calibrate(self) -> float:
        """Executed steps per second over a few warm chunks."""
        import jax
        tr = self.trainer
        n = self.nominal(int(tr.state.step), CALIBRATE_CHUNKS * self.k)
        t = time.perf_counter()
        tr.run(n)
        jax.block_until_ready(tr.state)
        return CALIBRATE_CHUNKS * self.k / (time.perf_counter() - t)

    def window(self, rate: float, seconds: float, counter, annotate=False):
        """One ``Trainer.run`` over whole chunks lasting about
        ``seconds``; returns (executed steps, wall seconds, history)."""
        import jax
        tr = self.trainer
        chunks = max(1, round(rate * seconds / self.k))
        n = self.nominal(int(tr.state.step), chunks * self.k)
        h0, e0 = len(tr.history), tr.executed_steps
        counter.active = True
        ctx = (jax.profiler.TraceAnnotation(T.WINDOW) if annotate
               else contextlib.nullcontext())
        with ctx:
            t = time.perf_counter()
            tr.run(n)
            jax.block_until_ready(tr.state)
            wall = time.perf_counter() - t
        counter.active = False
        return tr.executed_steps - e0, wall, tr.history[h0:]

    def memory_peak(self) -> int:
        """Peak device memory on the fullest device: buffers in use plus
        the region the TPU runtime reserves for compiled programs'
        temporaries, which ``peak_bytes_in_use`` leaves out."""
        def peak(stats):
            return (int(stats.get("peak_bytes_in_use", 0))
                    + int(stats.get("peak_bytes_reserved", 0)))
        return max(peak(d.memory_stats() or {}) for d in self.devices)

    def release(self) -> None:
        """Drop the program's state so the reference has the chip."""
        self.trainer = None
        gc.collect()

    def check(self, control_dtype=None, fault=None):
        """Run the reference over the first chunk and compare; returns
        (numbers, program record, reference record).  ``control_dtype``
        and ``fault`` put the reference, so computed or so broken, in the
        program's place (the control and the planted faults)."""
        import jax.numpy as jnp
        ref_mod = self.bench.reference(self.cell.config["reference"])
        first, start = self.first, self.start
        hist = first["hist"][:self.k]
        steps = [int(h["step"]) for h in hist]
        kept = self.nominal(0, self.k)
        keep = C.smd_schedule(self.seeds["train"], 0, kept, *self.smd)
        steps_kept = [s for s, k in zip(range(kept), keep) if k]
        e2 = self.cell.mix["e2train"]
        train = self.fam.train_settings(self.cell.config, self.cell.mix,
                                        self.seeds["train"])
        slots = ref_mod.n_gate_slots(self.arch)
        batches = [self.make(s, 0) for s in steps_kept]
        buffer_of = self.fam.optimizer_buffer
        if control_dtype is not None or fault is not None:
            dtype = control_dtype or jnp.float32
            pb = [fault(b) for b in batches] if fault else batches
            p, m, bn, outs = ref_mod.run_steps(
                self.arch, e2, train, start.params, buffer_of(start.opt),
                start.model_state, pb, steps_kept, dtype)
            prog = {"losses": [float(o["loss"]) for o in outs],
                    "keep_mean": [float(jnp.mean(o["keep_p"]))
                                  for o in outs],
                    "executed": [float(jnp.sum(o["executed"])) for o in outs],
                    "params_before": start.params, "params_after": p,
                    "buffer": m, "state": bn, "steps": steps_kept,
                    "step": steps_kept[-1] + 1}
        else:
            prog = {"losses": [h["total_loss"] for h in hist],
                    "keep_mean": [h["slu_cost"] for h in hist],
                    "executed": [h["slu_exec_ratio"] * slots for h in hist],
                    "params_before": start.params,
                    "params_after": first["after"].params,
                    "buffer": buffer_of(first["after"].opt),
                    "state": first["after"].model_state, "steps": steps,
                    "step": int(first["after"].step)}
        choose = C.chooser(prog["losses"], [e / slots for e in
                                            prog["executed"]], slots)
        p, m, bn, outs = ref_mod.run_steps(
            self.arch, e2, train, start.params, buffer_of(start.opt),
            start.model_state, batches, steps_kept, choose=choose)
        ref = {"losses": [float(o["loss"]) for o in outs],
               "keep_mean": [float(jnp.mean(o["keep_p"])) for o in outs],
               "executed": [float(jnp.sum(o["executed"])) for o in outs],
               "params_before": start.params, "params_after": p,
               "buffer": m, "state": bn,
               "grads_first": outs[0]["grads_raw"],
               "steps_kept": steps_kept, "step": steps_kept[-1] + 1}
        return C.compare(prog, ref), prog, ref


def device_info(devices, peak_bytes):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes}


def run(argv=None, root: Path = ROOT, platform: str = "tpu",
        trainer_cls=None) -> dict:
    """One run; returns the result object (the caller prints it).
    ``platform`` and ``trainer_cls`` let the harness's tests drive a run
    on the CPU and with a broken trainer.

    The configurations state float32; on the TPU, JAX's default matmul
    precision for float32 is one bfloat16 pass, so the whole run is under
    ``jax.default_matmul_precision(MATMUL_PRECISION)``, the option with
    which the program computes what the configuration states."""
    import jax
    with jax.default_matmul_precision(MATMUL_PRECISION):
        return _run(parse(argv), root, platform, trainer_cls)


def phase(name: str) -> None:
    """Set-up and check phases on standard error, seconds since start."""
    print(f"bench: {name} at {time.perf_counter() - T0:.3f} s",
          file=sys.stderr, flush=True)


def _run(args, root: Path, platform: str, trainer_cls) -> dict:
    bench = S.Benchmark(root)
    cell = bench.cell(args.workload)
    devices = devices_for(cell.chips, platform)
    import jax
    phase("devices")
    if platform == "tpu":
        use_cache(root / CACHE)
    counter = CompileCounter()
    kind = devices[0].device_kind
    peak = peaks(root, kind) if platform == "tpu" else None
    trace_dir = root / TRACE
    cr = CellRun(bench, cell, args.seed, devices)
    cr.build(trainer_cls)
    phase("weights and trainer")
    cr.first_chunk()
    phase("first chunk")
    rate = cr.calibrate()
    setup_s = time.perf_counter() - T0
    phase("calibrated")
    metrics, breakdown = {}, None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            steps, wall, hist = cr.window(rate, min(args.seconds,
                                                    TRACE_SECONDS),
                                          counter, annotate=True)
        finally:
            jax.profiler.stop_trace()
        tr = T.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = {"chips": cell.chips, "batch": cr.batch,
                  "images_per_s": steps * cr.batch / wall,
                  "flop_per_image": float(cell.config["flop_per_image"]),
                  "peak": peak, "arch": cr.arch,
                  "psg": cell.mix["e2train"]["psg"],
                  "psg_sites": cr.fam.psg_sites(cr.arch, int(
                      cell.mix["batch_per_chip"])),
                  "history": hist, "executed": steps}
        for m in cell.per_layer:
            value = bench.reader(m["name"])(record, tr)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.top_gaps(tr)}
        device_extra = {"busy_s": tr.busy_s(), "window_s": tr.window_s}
    else:
        steps, wall, hist = cr.window(rate, args.seconds, counter)
        values = {"images_per_s": steps * cr.batch / wall,
                  "setup_s": setup_s}
        device_extra = {}
    peak_bytes = cr.memory_peak()
    if not args.trace:
        values["peak_hbm_gb"] = peak_bytes / 1e9
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device = dict(device_info(devices, peak_bytes), **device_extra)
    failed = sum(1 for h in hist if not math.isfinite(h["total_loss"]))
    cr.release()
    phase("window")
    numbers, _, _ = cr.check()
    phase("reference")
    ok, checks = C.verdict(numbers, cell.limits["limits"])
    result = {"correct": ok, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = counter.count
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
