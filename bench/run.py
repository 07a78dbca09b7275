#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name. The cell is an entry of ``BENCHMARK.json``'s
``workloads``; its configuration is the file that ``configs`` names, its mix
is ``bench/traffic/<traffic>.json``, its driver ``bench/drivers/<kind>.py``
(``kind`` is in the configuration), and each per-layer metric is read by
``bench/metrics/<metric>.py``. New cells, mixes and metrics are new files.

The run loads, warms up, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON line as the
last line of standard output. With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1`` the run profiles a short stretch of
its window and reports the per-layer metrics, the device's busy time and a
breakdown. The numbers that decide ``correct`` are printed beside their
limits as the last lines of standard error and under ``checks``.

It refuses to measure anything but a TPU whose ``device_kind`` is in
``bench/peaks.json``, or fewer chips than the cell asks for.
"""
import time

T_START = time.time()

import argparse                                                # noqa: E402
import importlib                                               # noqa: E402
import importlib.util                                          # noqa: E402
import json                                                    # noqa: E402
import math                                                    # noqa: E402
import shutil                                                  # noqa: E402
import sys                                                     # noqa: E402
from dataclasses import dataclass                              # noqa: E402
from pathlib import Path                                       # noqa: E402
from typing import Any, Optional                               # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import workload                                     # noqa: E402


class Refused(SystemExit):
    """The run cannot measure what the cell asks for."""


@dataclass
class Ctx:
    root: Path
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    chips: int
    t_start: float
    peaks: dict
    trace_dir: Optional[str] = None


@dataclass
class RunView:
    """What a per-layer metric's reader gets."""
    cell: dict
    cfg: dict
    traffic: dict
    peaks: dict
    result: dict
    trace: Any = None

    @property
    def win(self) -> dict:
        return self.result["win"]


def load_cell(root: Path, name: str):
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] == name]
    if not cells:
        raise Refused(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    cell = cells[0]
    conf = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    cfg = workload.load(root / conf["file"])
    traffic = workload.load(workload.find(root, "traffic", cell["traffic"]))
    return bench, cell, cfg, traffic


def applies(metric: dict, cell: dict, e2e_of_cell=()) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_of_cell
    return True


def reader(root: Path, name: str):
    """``bench/metrics/<name>.py`` under the checkout, else beside this
    file."""
    for base in (root / "bench" / "metrics", BENCH / "metrics"):
        path = base / f"{name}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{name.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader bench/metrics/{name}.py")


def device_check(jax, chips: int, platforms, peaks_file: Path):
    devs = jax.devices()
    d = devs[0]
    if d.platform not in platforms:
        raise Refused(f"refused: platform {d.platform!r} ({d.device_kind}) "
                      f"is not an accelerator this benchmark measures")
    with open(peaks_file) as f:
        table = json.load(f)["devices"]
    kind = d.device_kind if d.platform != "cpu" else "TPU v5 lite"
    if kind not in table:
        raise Refused(f"refused: device_kind {d.device_kind!r} is not in "
                      f"{peaks_file}")
    if len(devs) < chips:
        raise Refused(f"refused: the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    return devs, table[kind]


def main(argv=None, root=None, platforms=("tpu",),
         keep_trace: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root or ROOT)
    bench, cell, cfg, traffic = load_cell(root, args.workload)
    if importlib.util.find_spec("repro") is None:
        raise Refused(f"refused: the program is missing (no {ROOT / 'src'})")

    import jax
    # the checkout's own cache, at a fixed path, whatever the environment
    # names: two checkouts on one machine share no compiled program
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    devs, peaks = device_check(jax, cell["chips"], platforms,
                               BENCH / "peaks.json")
    trace_dir = None
    if args.trace:
        trace_dir = str(root / ".bench_trace" / args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Ctx(root, cell, cfg, traffic, args.seed, args.seconds,
              cell["chips"], T_START, peaks, trace_dir)
    driver = importlib.import_module(f"bench.drivers.{cfg['kind']}")
    res = driver.run(ctx)

    e2e_names = [m["name"] for m in bench["end_to_end"]
                 if applies(m, cell)]
    metrics = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": res["memory_peak_bytes"] or 0}
    out = {}
    if not args.trace:
        for m in bench["end_to_end"]:
            if m["name"] in e2e_names:
                v = res["setup_s"] if m["name"] == "setup_s" \
                    else res["e2e"].get(m["name"])
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from bench import trace as trace_mod
        red = None
        if res["win"].get("trace_dir"):
            t = time.perf_counter()
            red = trace_mod.reduce(res["win"]["trace_dir"])
            print(f"trace: {len(red.ops)} device ops, {len(red.spans)} bench "
                  f"spans, window {red.window_s:.6f} s, reduced in "
                  f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            out["breakdown"] = red.breakdown()
            if not keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        view = RunView(cell, cfg, traffic, peaks, res, red)
        for m in bench["per_layer"]:
            if applies(m, cell, e2e_names):
                v = reader(root, m["name"])(view)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for k in list(metrics):
        v = metrics[k]["value"]
        if v is None or not math.isfinite(v):
            print(f"metric {k}: no finite value ({v}); left out",
                  file=sys.stderr)
            del metrics[k]
    checks = {k: {"value": v if math.isfinite(v) else None, "limit": lim}
              for k, (v, lim) in res["checks"].items()}
    result = {"correct": bool(res["correct"]), "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device,
              **out, "checks": checks}
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(e.code, file=sys.stderr)
        sys.exit(2)
