"""Benchmark runner — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines (harness contract).

Full runs write JSON artifacts under results/bench/; `--quick` shrinks the
step counts so the whole suite finishes in a few minutes on CPU.
"""
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
for _p in (_REPO, _REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import argparse


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced step counts (CI-scale)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI schema gate: only kernel+serve+learner+loop+lm "
                         "benches at tiny dims/batches (interpret mode on "
                         "CPU); emits the same BENCH_*.json shapes for "
                         "benchmarks/schema.py")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig7,fig8,fig9,fig10,"
                         "tableii,kernel,serve,learner,loop,lm")
    args = ap.parse_args(argv)
    if args.smoke and (args.only or args.quick):
        ap.error("--smoke fixes its own bench set/scale; drop --only/--quick")
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    from benchmarks import (fig7_accuracy, fig8_throughput, fig9_breakdown,
                            fig10_accelerator, kernel_bench, learner_bench,
                            lm_bench, loop_bench, serve_bench, tableii_compare)

    if args.smoke:
        # calibration order: kernel FIRST — serving's dispatcher calibrates
        # from the fresh BENCH_fused_mlp.json; the rest have no calibration
        # dependency
        kernel_bench.main(["--smoke"])
        serve_bench.main(["--smoke"])
        learner_bench.main(["--smoke"])
        loop_bench.main(["--smoke"])
        lm_bench.main(["--smoke"])
        return

    if want("kernel"):
        kernel_bench.main([])
    if want("serve"):
        # after kernel so the dispatcher calibrates from a fresh
        # BENCH_fused_mlp.json when both run
        serve_bench.main(["--quick"] if args.quick else [])
    if want("learner"):
        learner_bench.main(["--quick"] if args.quick else [])
    if want("loop"):
        loop_bench.main(["--quick"] if args.quick else [])
    if want("lm"):
        lm_bench.main(["--quick"] if args.quick else [])
    if want("fig8"):
        fig8_throughput.main(["--steps", "400" if args.quick else "2000"])
    if want("fig9"):
        fig9_breakdown.main(["--steps", "60" if args.quick else "200"])
    if want("fig10"):
        fig10_accelerator.main(["--iters", "5" if args.quick else "10"])
    if want("tableii"):
        tableii_compare.main([])
    if want("fig7"):
        fig7_accuracy.main(["--steps", "3000" if args.quick else "25000"])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
