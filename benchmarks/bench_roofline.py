"""§Roofline: render the dry-run's per-cell roofline table from
results/dryrun/dryrun.jsonl (produced by repro.launch.dryrun). Emits one
row per (arch x shape x mesh) with the three terms, the dominant bound,
MODEL_FLOPS/HLO ratio, and the napkin (TPU-projected) terms."""

import json
import os

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results", "dryrun", "dryrun.jsonl")


def load_records(path: str = RESULTS):
    if not os.path.exists(path):
        return []
    recs = {}
    with open(path) as fh:
        for line in fh:
            r = json.loads(line)
            recs[(r["arch"], r["shape"], r["mesh"])] = r  # last write wins
    return list(recs.values())


def run(emit) -> None:
    recs = load_records()
    if not recs:
        emit("roofline/missing", 0,
             "run: JAX_PLATFORMS=cpu XLA_FLAGS="
             "--xla_force_host_platform_device_count=512 PYTHONPATH=src "
             "python -m repro.launch.dryrun --all --both-meshes "
             "--out results/dryrun")
        return
    n_ok = sum(r["status"] == "ok" for r in recs)
    n_skip = sum(r["status"] == "skipped" for r in recs)
    emit("roofline/cells_ok", n_ok, f"skipped={n_skip} "
         f"failed={len(recs) - n_ok - n_skip}")
    for r in sorted(recs, key=lambda x: (x["mesh"], x["arch"], x["shape"])):
        key = f"{r['mesh']}/{r['arch']}/{r['shape']}"
        if r["status"] == "skipped":
            emit(f"roofline/{key}", -1, "SKIP: " + r["skip_reason"][:60])
            continue
        if r["status"] != "ok":
            emit(f"roofline/{key}", -1, "FAILED")
            continue
        rf = r["roofline"]
        nap = r.get("napkin", {})
        emit(f"roofline/{key}", rf["roofline_frac"],
             f"bound={rf['bound']} t=({rf['t_compute_s']}|"
             f"{rf['t_memory_s']}|{rf['t_collective_s']})s "
             f"napkin={nap.get('bound', '?')}"
             f"({nap.get('t_compute_s', 0)}|{nap.get('t_memory_s', 0)}|"
             f"{nap.get('t_collective_s', 0)})s "
             f"useful={rf['useful_ratio']} mem={rf['mem_gib_per_chip']}GiB")
