"""Every cell of BENCHMARK.json finds its files by name, and every
configuration's reference module gives what the readers read."""

from __future__ import annotations

import json

from chip_bench import common, traffic
from chip_bench.tests.helpers import ROOT


def test_every_cell_finds_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    confs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        conf = json.loads((ROOT / confs[cell["config"]]["file"]).read_text())
        mix = traffic.load_mix(cell["traffic"])
        assert mix["kind"] in ("serve", "train")
        limits = json.loads((ROOT / "chip_bench" / "limits"
                             / f"{cell['name']}.json").read_text())
        assert limits and all(v >= 0 for v in limits.values())
        for part in ("reference", "adapters"):
            assert (ROOT / "chip_bench" / part
                    / f"{conf['model_type']}.py").is_file()
        ref = common.reference_module(conf)
        assert callable(ref.dims) and callable(ref.train_flops)
    for m in bench["per_layer"]:
        assert (ROOT / "chip_bench" / "metrics" / f"{m['name']}.py").is_file()
