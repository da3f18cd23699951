"""Cells of the benchmark at a size a CPU test can hold: the cell's own
traffic, limits and metrics, with a tiny configuration and a short job."""
import copy
import json
import pathlib
import time

from chipbench import bench

DATA = pathlib.Path(__file__).resolve().parent / "data"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny_cell(workload: str, traffic: str = None, moe: bool = True,
              limits: dict = None) -> bench.Cell:
    """A declared cell (``workload``), or a traffic file and limits that no
    declared cell uses yet (``traffic``, ``limits``), at a tiny size."""
    if traffic is None:
        cell = copy.deepcopy(bench.load_cell(workload))
    else:
        cell = bench.Cell(workload, 1, {}, bench.load_json(
            bench.HERE / "traffic" / f"{traffic}.json"), {"checks": limits},
            [{"name": "setup_s", "unit": "s"}], [])
    cell.config = json.load(open(DATA / ("tiny-moe.json" if moe
                                         else "tiny-dense.json")))
    t = cell.traffic
    if t["kind"] == "train":
        t.update(seq_len=256, batch=1 if t["batch"] == 1 else 2)
    else:
        t.update(slots=2, buf_len=512, rate_per_s=4.0,
                 prompt_len=dict(t["prompt_len"], median=48, min=8, max=192),
                 output_len=dict(t["output_len"], median=8, min=2, max=24),
                 check={"sample": 3})
    return cell


def run(cell, seed=2**33 + 17, seconds=1.0, trace=False):
    from chipbench.run import run_cell
    return run_cell(cell, seed, seconds, trace, CPU, time.perf_counter())
