"""Benchmark suite: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Run as
``PYTHONPATH=src python -m benchmarks.run [--only table1]``.
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module names")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: modeled suites + shortened "
                         "wallclock runs (CPU interpret mode)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the emitted rows as a JSON array "
                         "(machine-readable sidecar to the CSV stream)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from . import (fig4_loop_rearrangement, kernels_wallclock,
                   program_exec, program_lm, quant_profile, strip_storage,
                   table1_auto_vs_hand, table2_models, table3_load_balance)
    suites = [
        ("table1", table1_auto_vs_hand),
        ("table2", table2_models),
        ("fig4", fig4_loop_rearrangement),
        ("table3", table3_load_balance),
        ("strips", strip_storage),
        ("program", program_exec),
        ("program_lm", program_lm),
        ("quant", quant_profile),
        ("kernels", kernels_wallclock),
    ]
    if args.smoke:
        strip_storage.SMOKE = True
        program_exec.SMOKE = True
        program_lm.SMOKE = True
        # drop the wallclock-heavy suites; keep every modeled one
        suites = [s for s in suites if s[0] not in ("kernels", "quant")]
    print("name,us_per_call,derived")
    for name, mod in suites:
        if args.only and args.only not in name:
            continue
        try:
            mod.run()
        except Exception as e:   # keep the suite going; record the failure
            from .common import emit
            emit(f"{name}/ERROR", 0.0, f"{type(e).__name__}:{e}")
            import traceback
            traceback.print_exc(file=sys.stderr)
    if args.json:
        from .common import write_json
        write_json(args.json)


if __name__ == "__main__":
    main()
