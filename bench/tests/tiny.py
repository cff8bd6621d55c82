"""A copy of the benchmark at a size the CPU runs in seconds, in a
temporary checkout: the real harness files with a tiny configuration and
short mixes."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent

TINY_CONFIG = {
    "graph": {"generator": "chung_lu", "n": 3000, "mean_out_degree": 8,
              "theta": 2.2, "seed": 0},
    "p_T": 0.15,
    "serving": {"segments_per_vertex": 16, "segment_len": 4,
                "build_shards": 2, "max_walks": 4096, "max_queries": 4,
                "max_steps": 32},
}


def make(root: Path) -> Path:
    """Writes the tiny checkout under ``root`` and returns it."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU tests"}]
    for w in spec["workloads"]:
        w["config"] = "tiny"
    # the open-loop mix, which the benchmark keeps for a later cell
    # (PERF.md, Open questions), runs here so that its path stays tested
    spec["workloads"].append({"name": "lj-ppr-interactive", "config": "tiny",
                              "traffic": "interactive", "chips": 1,
                              "why": "the open-loop path"})
    spec["end_to_end"].append({"name": "answer_p90_s", "unit": "s",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["lj-ppr-interactive"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    shutil.copy(root / "bench/limits/livejournal.json",
                root / "bench/limits/tiny.json")
    traffic = root / "bench" / "traffic"
    for name, edit in (
            ("refresh", {"warmup_s": 0.5, "outstanding": 6,
                         "max_requests": 2000}),
            ("interactive", {"warmup_s": 0.5}),
            ("batch", {"num_frogs": 20000})):
        mix = json.loads((traffic / f"{name}.json").read_text())
        mix.update(edit)
        if name == "interactive":
            mix["arrivals"]["rate_per_s"] = 6.0
        (traffic / f"{name}.json").write_text(json.dumps(mix))
    return root
