import os
import tempfile
# Post-SPMD, pre-legalization HLO is the TPU-faithful analysis artifact:
# per-device shapes + collectives, but BEFORE the CPU backend's bf16->f32
# float-normalization (which would double byte/collective sizes) and before
# CPU-grain fusion decisions. Dumped per cell, analyzed, then deleted.
_SPMD_DUMP_DIR = tempfile.mkdtemp(prefix="repro_spmd_")
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    f"--xla_dump_to={_SPMD_DUMP_DIR} "
    "--xla_dump_hlo_pass_re=spmd-partitioning "
    "--xla_dump_hlo_module_re=.*(train_step|prefill_fn|serve_fn).*")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
placeholder devices, print memory/cost analysis, and record roofline inputs.

MUST be run as its own process (the device-count flag binds at first jax
init). ``--all`` mode spawns one subprocess per cell for isolation.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl -j 4
"""
import argparse      # noqa: E402
import glob          # noqa: E402
import json          # noqa: E402
import shutil        # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ASSIGNED_ARCHS, get_config, shapes_for  # noqa: E402
from repro.configs.base import ALL_SHAPES                          # noqa: E402
from repro.launch import hlo_analysis                              # noqa: E402
from repro.launch.mesh import make_production_mesh                 # noqa: E402
from repro.launch.specs import build_step, rules_for               # noqa: E402
from repro.sharding.rules import ShardingRules, use_mesh           # noqa: E402


def _read_spmd_dump():
    """Largest after_spmd-partitioning dump = the step module (helpers are
    tiny). Cleared between cells; each process runs one cell."""
    files = glob.glob(os.path.join(_SPMD_DUMP_DIR,
                                   "*after_spmd-partitioning*.txt"))
    if not files:
        return None
    best = max(files, key=os.path.getsize)
    with open(best) as f:
        text = f.read()
    shutil.rmtree(_SPMD_DUMP_DIR, ignore_errors=True)
    os.makedirs(_SPMD_DUMP_DIR, exist_ok=True)
    return text


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_override: dict = None, dump_hlo: str = None,
             kv_cache_dtype: str = None) -> dict:
    cfg = get_config(arch)
    if kv_cache_dtype:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, quant=dataclasses.replace(cfg.quant,
                                           kv_cache_dtype=kv_cache_dtype))
    shape = {s.name: s for s in ALL_SHAPES}[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, shape)
    if rules_override:
        rules = rules.with_(**rules_override)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "rules": {k: v for k, v in rules.__dict__.items()}}
    t0 = time.time()
    with use_mesh(mesh, rules), mesh:
        fn, args, donate, meta = build_step(cfg, shape, rules, mesh)
        rec.update(meta)
        in_shardings = jax.tree.map(lambda a: a.sharding, args)
        lowered = jax.jit(fn, in_shardings=in_shardings,
                          donate_argnums=donate).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "peak_gb": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes
                        - mem.alias_size_in_bytes) / 1e9,
        }
        print(f"[{arch} x {shape_name} x {rec['mesh']}] memory_analysis:")
        print(f"  args={rec['memory']['argument_gb']:.2f}GB "
              f"temp={rec['memory']['temp_gb']:.2f}GB "
              f"out={rec['memory']['output_gb']:.2f}GB "
              f"(per device)")
        ca = compiled.cost_analysis() or {}
        rec["xla_cost"] = {k: float(v) for k, v in ca.items()
                          if k in ("flops", "bytes accessed",
                                   "optimal_seconds")}
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e} (loop bodies x1)")

        hlo_text = _read_spmd_dump()
        if hlo_text is None:            # fallback: final compiled HLO
            hlo_text = compiled.as_text()
            rec["hlo_source"] = "compiled"
        else:
            rec["hlo_source"] = "post_spmd_pre_legalization"
        if dump_hlo:
            import gzip
            os.makedirs(dump_hlo, exist_ok=True)
            fn = f"{arch}__{shape_name}__{rec['mesh']}.hlo.gz"
            with gzip.open(os.path.join(dump_hlo, fn), "wt") as f:
                f.write(hlo_text)
        summ = hlo_analysis.analyze(hlo_text)
        rec["hlo"] = {
            "dot_flops": summ.dot_flops,
            "hbm_bytes": summ.hbm_bytes,
            "hbm_bytes_raw": summ.hbm_bytes_raw,
            "collective_bytes": summ.collective_bytes,
            "collective_counts": summ.collective_counts,
            "trip_counts": summ.trip_counts,
        }
        rec["roofline"] = hlo_analysis.roofline_terms(summ)
        print(f"  hlo (loop-expanded): dot_flops={summ.dot_flops:.3e} "
              f"hbm={summ.hbm_bytes:.3e}B "
              f"coll={summ.total_collective_bytes:.3e}B {summ.collective_counts}")
        r = rec["roofline"]
        dom = max(r, key=r.get)
        rec["dominant"] = dom
        print(f"  roofline terms (s): compute={r['compute_s']:.4f} "
              f"memory={r['memory_s']:.4f} collective={r['collective_s']:.4f}"
              f"  -> {dom.replace('_s','')}-bound")
    rec["ok"] = True
    return rec


def list_cells():
    cells = []
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for s in shapes_for(cfg):
            cells.append((arch, s.name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--rules", default=None,
                    help="JSON ShardingRules overrides (hillclimb knob)")
    ap.add_argument("--preset", default=None,
                    help="named ShardingRules preset (baseline/fsdp/zero3)")
    ap.add_argument("--kv-cache-dtype", default=None,
                    help="override QuantConfig.kv_cache_dtype (e.g. int8)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--dump-hlo", default=None,
                    help="directory for gzipped compiled HLO per cell")
    ap.add_argument("-j", type=int, default=2, help="parallel cells (--all)")
    args = ap.parse_args()

    if args.all:
        cells = [(a, s, m) for (a, s) in list_cells()
                 for m in ("single", "multi")]
        procs, results = [], []
        def drain(block=False):
            for p, meta in list(procs):
                if p.poll() is None and not block:
                    continue
                out, _ = p.communicate()
                tail = [l for l in out.decode().splitlines() if l.strip()]
                ok = p.returncode == 0
                results.append((meta, ok, tail[-12:]))
                status = "OK " if ok else "FAIL"
                print(f"[{status}] {meta}")
                if not ok:
                    print("      " + "\n      ".join(tail[-6:]))
                procs.remove((p, meta))
        for arch, shape, m in cells:
            while len(procs) >= args.j:
                drain()
                time.sleep(2)
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", m]
            if args.out:
                cmd += ["--out", args.out]
            if args.rules:
                cmd += ["--rules", args.rules]
            if args.dump_hlo:
                cmd += ["--dump-hlo", args.dump_hlo]
            if args.preset:
                cmd += ["--preset", args.preset]
            # each cell analyses placeholder CPU devices: a child must
            # never reach for a chip this process may hold
            procs.append((subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                env=dict(os.environ, JAX_PLATFORMS="cpu")),
                f"{arch} x {shape} x {m}"))
        while procs:
            drain(block=True)
        nfail = sum(1 for _, ok, _ in results if not ok)
        print(f"\n{len(results) - nfail}/{len(results)} cells passed")
        sys.exit(1 if nfail else 0)

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    overrides = json.loads(args.rules) if args.rules else None
    if args.preset:
        from repro.sharding.rules import PRESETS
        preset = PRESETS[args.preset].__dict__
        overrides = {**preset, **(overrides or {})}
    for mp in meshes:
        try:
            rec = run_cell(args.arch, args.shape, mp, overrides,
                           dump_hlo=args.dump_hlo,
                           kv_cache_dtype=args.kv_cache_dtype)
        except Exception as e:
            rec = {"arch": args.arch, "shape": args.shape,
                   "mesh": "2x16x16" if mp else "16x16", "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
            traceback.print_exc()
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        if not rec.get("ok"):
            sys.exit(1)


if __name__ == "__main__":
    main()
