"""Perf diagnostics for one dry-run cell: top traffic instructions and top
collectives from the trip-weighted HLO analysis (the 'profile' of the
hypothesis loop — see EXPERIMENTS.md §Perf).

    PYTHONPATH=src python -m repro.launch.diagnose --arch yi-34b \
        --shape train_4k --mesh single [--top 25]
"""
import os
if __name__ == "__main__":   # a script run only: importing sets nothing
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse      # noqa: E402
import collections   # noqa: E402

from repro.configs.base import SHAPES  # noqa: E402
from repro.launch.dryrun import lower_cell  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline import hlo_parser  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--dump-hlo", default=None,
                    help="write optimized HLO text to this path")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    shape = SHAPES[args.shape]

    # lower_cell returns the artifact dict; we need the HLO, so re-run the
    # tail of it here via a tiny shim: lower_cell stores no HLO (artifacts
    # stay small), so recompute.
    import repro.launch.dryrun as dr
    out = {}
    orig_build = dr.build_report
    captured = {}

    def capture_report(**kw):
        captured["hlo"] = kw["hlo_text"]
        return orig_build(**kw)

    dr.build_report = capture_report
    try:
        out = dr.lower_cell(args.arch, shape, mesh, args.mesh)
    finally:
        dr.build_report = orig_build
    hlo = captured.get("hlo", "")
    if args.dump_hlo and hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(hlo)

    r = out["roofline"]
    print(f"== {args.arch} x {args.shape} x {args.mesh} ==")
    print(f"compute_s={r['compute_s']:.3f} memory_s={r['memory_s']:.3f} "
          f"collective_s={r['collective_s']:.3f} dominant={r['dominant']}")
    print(f"peak/dev={out['memory']['peak_per_device']/2**30:.2f}GiB "
          f"useful_flops={r['useful_flops_ratio']:.3f}")
    print(f"collectives: {r['collective_counts']}")
    bk = r["collective_breakdown"]
    for k, v in sorted(bk.items(), key=lambda kv: -kv[1]):
        if v:
            print(f"  {k:20s} {v/1e9:12.2f} GB/dev")

    # top traffic instructions
    print(f"\n-- top {args.top} traffic instructions (trip-weighted) --")
    rows = hlo_parser.top_traffic(hlo, n=args.top)
    for traffic, mult, comp, op, name, tstr in rows:
        print(f"{traffic/1e9:10.1f} GB x{mult:<6g} {op:22s} {tstr:42s} "
              f"{comp[:28]}/{name[:40]}")

    # top collectives individually
    print(f"\n-- collectives by instruction --")
    coll = []

    def cb(comp, ins, mult, traffic):
        if ins.op in hlo_parser.COLLECTIVE_OPS:
            coll.append((traffic * 0.5 * mult, mult, ins.op, ins.type_str[:48],
                         comp.name[:40]))
    hlo_parser.analyze_module(hlo, on_instr=cb)
    coll.sort(reverse=True)
    for b, mult, op, tstr, comp in coll[:args.top]:
        print(f"{b/1e9:10.2f} GB x{mult:<6g} {op:20s} {tstr:50s} {comp}")

    # loop structure
    print("\n-- while loops --")
    comps = hlo_parser.parse_module(hlo)
    for c in comps.values():
        for ins in c.instrs:
            if ins.op == "while":
                tm = hlo_parser._TRIP_RE.search(ins.attrs() + ins.rest)
                trips = tm.group(1) if tm else "?"
                print(f"  trips={trips:6s} in {c.name[:40]} result="
                      f"{ins.type_str[:60]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
