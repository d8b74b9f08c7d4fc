import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: AOT compile on
512 placeholder CPU devices through the real SPMD partitioner.  Per cell we
record memory_analysis (fits), cost_analysis (FLOPs/bytes), and the
collective schedule parsed from optimized HLO.

Scan correction (see analysis/roofline.py): XLA costs a lax.scan body once,
so alongside the full-depth compile we compile 1-group and 2-group variants
and extrapolate per-group costs linearly.  Whisper gets an extra encoder
differential (its encoder is a second scan).

Usage:
    python -m repro.launch.dryrun --arch all --shape all --mesh both
    python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
"""

import argparse
import json
import time
import traceback

import jax

from repro.analysis.hlo import collective_bytes
from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch.steps import build_step, input_specs

RESULT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "experiments", "dryrun")


def _reduced(cfg, dec_groups: int, enc_groups: int = 1):
    # scan_layers=False: the reduced configs UNROLL their groups, so
    # cost_analysis counts every layer (a scanned body is costed once no
    # matter the trip count -- 1-group and 2-group scans would look equal).
    kw = {"n_layers": dec_groups * cfg.period, "scan_layers": False}
    if cfg.arch_kind == "encdec":
        kw["encoder_layers"] = enc_groups
    return cfg.replace(**kw)


def _compile_cell(cfg, preset, mesh):
    bundle = build_step(cfg, preset, mesh)
    with mesh:
        lowered = bundle.lower()
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collective_wire_bytes_per_device": coll.total_wire_bytes,
        "collective_summary": coll.summary(),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "dropped_shardings": bundle.sharder.dropped,
    }


def run_cell(arch: str, shape: str, mesh_name: str,
             with_differential: bool = True) -> dict:
    cfg = get_config(arch)
    preset = SHAPES[shape]
    ok, reason = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "kind": preset.kind, "status": "skipped", "reason": reason}
    if not ok:
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    rec["chips"] = mesh_chips(mesh)
    t0 = time.time()
    full = _compile_cell(cfg, preset, mesh)
    rec["full"] = full
    rec["compile_s"] = time.time() - t0

    if with_differential:
        g = cfg.n_groups
        c1 = _compile_cell(_reduced(cfg, 1), preset, mesh)
        c2 = _compile_cell(_reduced(cfg, 2), preset, mesh)
        rec["diff"] = {"groups": g, "g1": c1, "g2": c2}
        if cfg.arch_kind == "encdec":
            e2 = _compile_cell(_reduced(cfg, 1, enc_groups=2), preset, mesh)
            rec["diff"]["enc_groups"] = cfg.encoder_layers
            rec["diff"]["e2"] = e2

    rec["status"] = "ok"
    return rec


def corrected_costs(rec: dict) -> dict:
    """Scan-corrected totals for one dry-run record (see module docstring)."""
    if "diff" not in rec:
        return {k: rec["full"][k] for k in
                ("flops", "bytes", "collective_wire_bytes_per_device")}
    d = rec["diff"]
    g = d["groups"]
    out = {}
    for key in ("flops", "bytes", "collective_wire_bytes_per_device"):
        c1, c2 = d["g1"][key], d["g2"][key]
        pg = c2 - c1
        total = (c1 - pg) + pg * g
        if "e2" in d:
            pg_e = d["e2"][key] - d["g1"][key]
            total += pg_e * (d["enc_groups"] - 1)
        out[key] = max(total, rec["full"][key])
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--no-differential", action="store_true")
    ap.add_argument("--out", default=RESULT_DIR)
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                tag = f"{arch}__{shape}__{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rec = run_cell(arch, shape, mesh_name,
                                   with_differential=(
                                       not args.no_differential
                                       and mesh_name == "single"))
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    m = rec["full"]["memory"]
                    extra = (f" args={m['argument_bytes']/2**30:.2f}GiB "
                             f"temp={m['temp_bytes']/2**30:.2f}GiB "
                             f"compile={rec['compile_s']:.1f}s")
                elif status == "skipped":
                    extra = f" ({rec['reason'][:60]})"
                else:
                    extra = f" {rec['error'][:120]}"
                print(f"[{status:7s}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
