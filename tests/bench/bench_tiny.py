"""Tiny cells of the benchmark, for runs on the CPU.

:func:`tiny_root` builds a checkout-like directory that holds a copy of
the benchmark's traffic, straggler mixes and layer readers, plus tiny
configurations and cells of its own, added the way a later change adds
one: new files and a ``workloads`` entry.
"""

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_MV = {
    "name": "tiny-mv", "source": "test", "matrix": "dense_gaussian",
    "rows": 2400, "cols": 256,
    "code": {"n": 12, "k": 10, "generator": "systematic_cauchy", "chunks": 4},
    "precision": {"host": "float64", "shards": "float32",
                  "matmul": "highest", "decode": "float64"},
    "limits": {"max_rel_err": 1e-3, "median_rel_err": 1e-6},
}
def _traffic(base: str, **over) -> dict:
    with open(ROOT / "bench" / "traffic" / f"{base}.json") as f:
        t = json.load(f)
    t.update(over)
    return t


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A root with the benchmark's files and three tiny cells."""
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    for sub in ("traffic", "mixes", "layers", "matrices", "jobs"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub)
    (tmp / "bench" / "configs").mkdir(parents=True)
    cells = []
    for cfg in (TINY_MV,):
        (tmp / "bench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
        man["configs"].append({"name": cfg["name"], "source": "test",
                               "file": f"bench/configs/{cfg['name']}.json",
                               "reduced": [], "why": "tiny"})
    few = {"clients": 2, "settle_jobs": 1, "vector_pool": 16}
    for name, base, over in (
            ("tiny-b1", "b1-strag2", few),
            ("tiny-b1-balanced", "b1-balanced", few),
            ("tiny-b16", "b16-strag2", {**few, "batch": 4})):
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(_traffic(base, **over)))
    for cell, cfg, traffic in (
            ("tiny-mv.b1", "tiny-mv", "tiny-b1"),
            ("tiny-mv.b1-balanced", "tiny-mv", "tiny-b1-balanced"),
            ("tiny-mv.b16", "tiny-mv", "tiny-b16")):
        cells.append({"name": cell, "config": cfg, "traffic": traffic,
                      "chips": 1, "why": "tiny"})
    man["workloads"] += cells
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp


def run_tiny(root: pathlib.Path, cell: str, seed: int = 7, trace: bool = False,
             backend_wrap=None, seconds: float = 0.5) -> dict:
    import time
    from bench.harness import run_cell
    from bench.manifest import load_cell
    return run_cell(load_cell(cell, root), seed, seconds, trace,
                    t_process=time.perf_counter(), backend_wrap=backend_wrap,
                    require_chip=False)
