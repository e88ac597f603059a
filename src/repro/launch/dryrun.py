import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production mesh, print memory/cost analysis, and emit the roofline terms.

The three lines above MUST stay first: jax locks the platform and the
device count on first init, and the dry-run needs 512 placeholder host
devices for the 2x16x16 multi-pod mesh. Nothing else in the repo sets this
flag. It is a CPU analysis tool: pinned to the CPU platform, it and the
cell processes it starts (which inherit the environment) never take a TPU
from another process.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--workers 2] \
      [--out artifacts/dryrun]
  python -m repro.launch.dryrun --all --both-meshes   # full 40x2 matrix

``--all`` fans cells out as subprocesses (isolation: one cell's failure or
OOM cannot poison the rest; results land as JSON per cell).
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

from repro.analysis.hlo_analysis import analyze_compiled
from repro.analysis.roofline import roofline_from_report
from repro.configs import ARCHS, SHAPES, get_arch, get_shape, shape_applicable

TARGET_KIND = "TPU v5 lite"  # the chip the production mesh is made of


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    """Lower + compile one cell; return the full analysis record."""
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh

    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
    }
    if not ok:
        rec.update(status="SKIP", reason=reason)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    jitted, args, meta = build_cell(arch, shape_name, mesh)
    from repro.distributed.act_sharding import activation_policy

    with mesh:
        with activation_policy(meta.get("policy")):
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    # memory_analysis proves the program fits; cost/collective terms feed
    # the roofline (scan-aware parse; see analysis/hlo_analysis.py).
    report = analyze_compiled(compiled)
    mem = report.get("memory", {})
    print(f"[{arch} x {shape_name} x {rec['mesh']}] memory_analysis:", mem)
    print(f"[{arch} x {shape_name} x {rec['mesh']}] cost_analysis:",
          report.get("xla_cost_analysis"))
    rec.update(
        status="OK",
        mode=meta["mode"],
        tokens_per_step=meta["tokens_per_step"],
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        analysis=report,
        roofline=roofline_from_report(
            cfg, report, chips=rec["chips"], mode=meta["mode"],
            tokens=meta["tokens_per_step"], device_kind=TARGET_KIND,
        ),
    )
    return rec


def _cell_cmd(arch, shape, multi_pod, out_path):
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--json-out", str(out_path),
    ]
    if multi_pod:
        cmd.append("--multi-pod")
    return cmd


def run_all(multi_pod_options, out_dir: Path, workers: int, archs=None, shapes=None):
    out_dir.mkdir(parents=True, exist_ok=True)
    cells = []
    for mp in multi_pod_options:
        for a in (archs or ARCHS):
            for s in (shapes or SHAPES):
                tag = f"{a}__{s}__{'2x16x16' if mp else '16x16'}"
                cells.append((a, s, mp, out_dir / f"{tag}.json"))
    procs: list = []
    pending = list(cells)
    results = {}
    while pending or procs:
        while pending and len(procs) < workers:
            a, s, mp, path = pending.pop(0)
            if path.exists():  # incremental: reuse finished cells
                results[path.name] = json.loads(path.read_text())
                print(f"cached   {path.stem}")
                continue
            log = open(path.with_suffix(".log"), "w")
            p = subprocess.Popen(
                _cell_cmd(a, s, mp, path), stdout=log, stderr=subprocess.STDOUT,
                cwd=str(Path(__file__).resolve().parents[3]),
                env={**os.environ, "PYTHONPATH": "src"},
            )
            procs.append((p, a, s, mp, path, log, time.time()))
        for item in procs[:]:
            p, a, s, mp, path, log, t0 = item
            rc = p.poll()
            if rc is None:
                if time.time() - t0 > 3600:
                    p.kill()
                    rc = -9
                else:
                    continue
            procs.remove(item)
            log.close()
            if rc == 0 and path.exists():
                results[path.name] = json.loads(path.read_text())
                st = results[path.name].get("status")
                print(f"done     {path.stem}: {st}")
            else:
                rec = {"arch": a, "shape": s, "status": "FAIL", "rc": rc,
                       "mesh": "2x16x16" if mp else "16x16",
                       "log": str(path.with_suffix(".log"))}
                path.write_text(json.dumps(rec))
                results[path.name] = rec
                print(f"FAILED   {path.stem} rc={rc} (log: {rec['log']})")
        time.sleep(0.5)
    # summary
    n_ok = sum(1 for r in results.values() if r.get("status") == "OK")
    n_skip = sum(1 for r in results.values() if r.get("status") == "SKIP")
    n_fail = sum(1 for r in results.values() if r.get("status") == "FAIL")
    print(f"\n=== dry-run matrix: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL "
          f"of {len(results)} cells ===")
    (out_dir / "summary.json").write_text(json.dumps(list(results.values()), indent=1))
    return 1 if n_fail else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS))
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--json-out")
    args = ap.parse_args()

    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        sys.exit(run_all(meshes, Path(args.out), args.workers))

    rec = run_cell(args.arch, args.shape, args.multi_pod)
    js = json.dumps(rec, indent=1, default=str)
    if args.json_out:
        Path(args.json_out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.json_out).write_text(js)
    print(js)
    if rec["status"] == "FAIL":
        sys.exit(1)


if __name__ == "__main__":
    main()
