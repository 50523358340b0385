"""The benchmark's one command.

  python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. The cell (a ``workloads`` entry of ``BENCHMARK.json``)
names a configuration file, a traffic mix (``chip_bench/traffic/``), and
through its name a limits file (``chip_bench/limits/``); the mix's
``kind`` picks the driver (``serve`` or ``train``). With ``--trace 0``
the last line of standard output is the result with the cell's
end-to-end metrics, with ``--trace 1`` the one with its per-layer
metrics. It exits non-zero, printing no result, without a TPU, with
fewer chips than the cell asks for, or without the program beside it.

Development options, never passed by the benchmark's own runs:
``--rate-scale`` scales the offered rate (the knee sweep) and
``--calibrate N`` prints the check's readings for N seeds from
``--seed`` instead of running a window: for serving, the program's and
the bfloat16 and FP8 controls' on one round at the cell's load; for
training, the program's, the controls' and that of half of each batch
left out.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT_DIR = ROOT / "chip_bench_out"  # run-time files: traces, checkpoints


def fail(msg: str) -> None:
    print(f"chip_bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def load_cell(name: str):
    """(benchmark, workload entry, configuration, mix, limits)."""
    from chip_bench import traffic
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    with open(ROOT / confs[cell["config"]]["file"]) as f:
        conf = json.load(f)
    with open(ROOT / "chip_bench" / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    return bench, cell, conf, traffic.load_mix(cell["traffic"]), limits


def execute(run, bench) -> str:
    """Run the cell on the devices in ``run`` and return the result line."""
    from chip_bench import common
    counter = common.CompileCounter()
    driver = importlib.import_module(f"chip_bench.{run.mix['kind']}")
    return driver.run_cell(run, bench, counter)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate-scale", type=float, default=1.0)
    ap.add_argument("--calibrate", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is missing: no {ROOT / 'src' / 'repro'}")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    bench, cell, conf, mix, limits = load_cell(args.workload)

    import jax
    from chip_bench import common, peaks, program
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        fail(f"the cell needs {cell['chips']} chips, {len(devices)} found")
    devices = devices[:cell["chips"]]
    try:
        chip_peaks = peaks.peaks_for(devices[0].device_kind)
    except KeyError as e:
        fail(str(e))
    program.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    OUT_DIR.mkdir(exist_ok=True)
    run = common.Run(
        name=args.workload, cell=cell, conf=conf, mix=mix, limits=limits,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, devices=devices, peaks=chip_peaks,
        out_dir=OUT_DIR, rate_scale=args.rate_scale,
        calibrate=args.calibrate)
    print(execute(run, bench), flush=True)


if __name__ == "__main__":
    main()
