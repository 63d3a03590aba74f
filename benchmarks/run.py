"""Benchmark driver: one bench per paper table/figure + the roofline table.

`python -m benchmarks.run [--quick] [--only fig6,fig9] [--json out.json]`
prints `name,us_per_call,derived` CSV rows, then the roofline table if
dry-run artifacts exist; `--json` additionally writes the rows as a JSON
artifact (what the CI bench job uploads).  Every row — CSV header comment
and JSON alike — is stamped with the device that produced it (platform,
device kind, device count), so archived artifacts say what hardware they
measured.

The `engine` lane (and the engine rows inside fig8) time the compiled
`lax.while_loop` peel engine against the eager dense round loop it replaced;
the `hierarchy` lane times fused-on-device ANH-EL against host trace-replay
and the two-phase build; the `facade` lane records the decompose-once/
query-many serving claim (`.cut(c)` sweep qps vs from-scratch connectivity,
plus the serialized-artifact load cost); the `build` lane compares the
memory-bounded chunked incidence builder against the eager one (peak
memory + wall-clock vs chunk size, fresh subprocess per cell); the
`session` lane records the warm-pool claim (cold per-shape `decompose()`
compiles vs one shape-bucketed `Session` executable); the `stream` lane
records the live-graph claim (single-edge `update(delta)` vs full
re-decompose of the edited graph); the `server` lane records the
multi-tenant server claims (persistent-cache restart warm path, fresh
subprocess per cell, plus coalesced-batch throughput through the
`Frontend`).  Compile time is excluded via a warmup call — except in the
`session`, `stream`, and `server` lanes, where per-shape compile time IS
(part of) the measurand — so the rows measure steady-state wall-clock
(what EXPERIMENTS.md records).  A lane that raises is recorded as a
`<lane>/ERROR` row and the driver exits non-zero after the remaining lanes.
Lanes that run cells in child processes (`build`, `distbuild`, `server`)
need `JAX_PLATFORMS=cpu`: a chip belongs to one process, so they fail with
a message rather than fight this process for it or fall back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys


def _parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def _device_meta() -> dict:
    """Which hardware produced the rows — stamped into every lane's JSON
    output so EXPERIMENTS.md tables (and the planner-calibration story)
    can say what they were measured on."""
    import jax
    return {
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced graph suite / grid")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of benches")
    ap.add_argument("--list", action="store_true",
                    help="list available benches and exit")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--json", default="",
                    help="also write rows to this path as a JSON artifact")
    args = ap.parse_args()

    # device decisions once, before the first jax op initializes a
    # backend (honors JAX_PLATFORMS etc.; --platform-style overrides
    # belong to the entrypoints, the bench driver just pins the timing)
    from repro.launch.platform import setup_platform
    from repro.serve.cache import init_persistent_cache
    setup_platform()
    init_persistent_cache()

    from . import bench_paper
    if args.list:
        for name, fn in bench_paper.ALL.items():
            doc = (fn.__doc__ or "").strip().splitlines()[0]
            print(f"{name}: {doc}")
        return
    only = set(filter(None, args.only.split(",")))
    meta = _device_meta()
    collected = []
    failed = []
    print("name,us_per_call,derived")
    print(f"# device: platform={meta['platform']} "
          f"kind={meta['device_kind']!r} count={meta['device_count']}",
          flush=True)
    for name, fn in bench_paper.ALL.items():
        if only and name not in only:
            continue
        try:
            for r in fn(quick=args.quick):
                print(r, flush=True)
                collected.append({**_parse_row(r), **meta})
        except Exception as e:  # keep the suite running; fail at the end
            failed.append(name)
            print(f"{name}/ERROR,0,{e!r}", flush=True)
            collected.append({"name": f"{name}/ERROR", "us_per_call": 0.0,
                              "derived": repr(e), **meta})

    if args.json:
        # the dead-module inventory (repro.analysis, DESIGN.md §12) rides
        # the bench artifact so the unreachable set is tracked per commit;
        # pure-AST analysis, so a failure must never redden the bench lane
        try:
            from repro.analysis import dead_module_report
            dead = dead_module_report("src")
        except Exception as e:
            dead = {"error": repr(e)}
        with open(args.json, "w") as f:
            json.dump({"meta": meta, "rows": collected,
                       "dead_modules": dead}, f, indent=1)
            f.write("\n")

    if not args.skip_roofline and not only:
        from . import roofline
        rows = roofline.full_table()
        if rows:
            print("\n# Roofline (single-pod 16x16, per chip):")
            print(roofline.format_table(rows))
    if failed:
        sys.exit(f"benchmark lanes failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
