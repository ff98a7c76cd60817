# One function per paper table/figure.  Prints ``name,us_per_call,derived``
# CSV (one row per measurement) and exits non-zero on any module failure.
#
#   python -m benchmarks.run                 # full suite
#   python -m benchmarks.run --smoke         # tiny CI mode (see common.SMOKE)
#   python -m benchmarks.run --out bench.csv # also write the CSV to a file
from __future__ import annotations

import argparse
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny path counts / sweep sizes for CI")
    ap.add_argument("--seed", type=int, default=None,
                    help="global seed offset threaded through every "
                         "benchmark (reproducible CI artifacts)")
    ap.add_argument("--force-devices", type=int, default=None,
                    metavar="N",
                    help="fake an N-device CPU mesh via "
                         "--xla_force_host_platform_device_count (set "
                         "before any jax import — required for the "
                         "benchmarks.shard_bench rows on a 1-CPU host)")
    ap.add_argument("--out", default=None,
                    help="also write the CSV to this path")
    ap.add_argument("--json-out", default=None,
                    help="also write a JSON timing artifact (e.g. "
                         "BENCH_solver.json) with every row plus run "
                         "metadata — the machine-readable bench "
                         "trajectory uploaded from CI")
    ap.add_argument("--trace-out", default=None,
                    help="record obs spans for the whole run and write "
                         "a Chrome trace-event JSON here (open in "
                         "ui.perfetto.dev or chrome://tracing)")
    ap.add_argument("--profile-dir", default=None,
                    help="also run the jax profiler over the suite, "
                         "writing its trace into this directory; obs "
                         "spans mirror into jax named scopes so host "
                         "spans line up with device activity")
    args = ap.parse_args()
    for path in (args.out, args.json_out):
        if path:
            # fail fast on an unwritable path, not after minutes of benchmarks
            with open(path, "w"):
                pass
    if args.smoke:
        # must precede benchmark imports: common.SMOKE is read at import
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    if args.seed is not None:
        os.environ["REPRO_BENCH_SEED"] = str(args.seed)
    if args.force_devices:
        # must precede benchmark imports too: XLA reads the flag when jax
        # initialises its CPU backend, and every benchmark module imports
        # jax transitively
        assert "jax" not in sys.modules, (
            "--force-devices must be applied before jax is imported")
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.force_devices}").strip()

    # after --force-devices: enabling the cache imports jax
    from repro import compile_cache
    compile_cache.enable()

    # The FULL spot-market policy benchmark and the serving benchmark are
    # NOT in this list: each is its own CLI (``python -m
    # benchmarks.market_bench`` / ``benchmarks.serving_bench``) with the
    # same --smoke/--seed/--out flags, run as a separate CI step so its
    # CSV lands in its own artifact instead of double-running here.  The
    # fused-episode subset (market_fused_bench) IS included: its rows are
    # cheap and belong in the gated BENCH_solver.json trajectory.
    from benchmarks import (fig2_latency_error, fig3_pareto,
                            market_fused_bench, mc_kernel_bench,
                            obs_bench, solver_bench, table2_platforms,
                            table3_cost_model, table4_tradeoff)
    from repro import obs
    modules = [
        ("table2", table2_platforms),
        ("table3", table3_cost_model),
        ("table4", table4_tradeoff),
        ("fig2", fig2_latency_error),
        ("fig3", fig3_pareto),
        ("solver", solver_bench),
        ("mc_kernel", mc_kernel_bench),
        ("obs", obs_bench),
        ("market_fused", market_fused_bench),
    ]
    if args.force_devices and args.force_devices > 1:
        # the sharded rows only mean something on a multi-device mesh, so
        # the module rides behind the flag rather than in the default list
        from benchmarks import shard_bench
        modules.append(("shard", shard_bench))
    if args.profile_dir:
        import jax
        jax.profiler.start_trace(args.profile_dir)
    if args.trace_out or args.profile_dir:
        obs.enable(jax_profiler=bool(args.profile_dir))
    lines = ["name,us_per_call,derived"]
    print(lines[0])
    failed = 0
    for name, mod in modules:
        try:
            with obs.span(f"bench.{name}"):
                rows = mod.run()
            for row in rows:
                n, us, derived = row
                line = f"{n},{us:.1f},{derived}"
                lines.append(line)
                print(line, flush=True)
        except Exception:
            failed += 1
            traceback.print_exc()
            line = f"{name}.FAILED,0,error"
            lines.append(line)
            print(line, flush=True)
    if args.trace_out or args.profile_dir:
        obs.disable()
    if args.profile_dir:
        import jax
        jax.profiler.stop_trace()
    if args.trace_out:
        n_spans = obs.export_chrome_trace(args.trace_out)
        print(f"# wrote {n_spans} spans to {args.trace_out}",
              flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    if args.json_out:
        import json
        rows_json = []
        for line in lines[1:]:
            name, us, derived = line.split(",", 2)
            try:
                us_f = float(us)
            except ValueError:
                us_f = None
            rows_json.append({"name": name, "us_per_call": us_f,
                              "derived": derived})
        payload = {
            "meta": {"smoke": bool(args.smoke),
                     "seed": int(os.environ.get("REPRO_BENCH_SEED", "0")),
                     "failed_modules": failed},
            "rows": rows_json,
        }
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
