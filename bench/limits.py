"""Readings that the limits of ``bench/limits/<workload>.json`` are set
from. Not part of a benchmark run.

    python3 bench/limits.py --workload <name> --seeds 1 2 3 --seconds 20 \
        [--control]

For each seed, in one process (set-up is paid once): one window of the
cell at its own load, then the logit gaps of the served tokens against the
reference, as a run computes them, and the verdict that the cell's limits
give them. ``--control`` adds the controls' readings on the same sample,
each with its verdict: the program's own lower-precision path,
teacher-forced over the same prompts and served tokens, with the
configuration's precision one step down (``step_down``: bf16 ops to
fp8_e4m3, fp8_e4m3 ops to fp4_e2m1) and, under a plan, with only the plan's
bf16 ops stepped down (``plan_all_fp8``).
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu_logs"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np
    from bench import check, harness, stats
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(harness.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    config = harness.load_json(harness.BENCH / "configs"
                               / f"{cell['config']}.json")
    mix = harness.load_json(harness.BENCH / "traffic"
                            / f"{cell['traffic']}.json")
    limits = harness.load_json(harness.BENCH / "limits"
                               / f"{args.workload}.json")
    clock = harness.CompileClock()
    server = harness.Server(cell["config"], config, mix, clock)
    print(json.dumps({"setup_s": time.perf_counter() - STARTED,
                      **server.parts, "plan": server.plan_info}), flush=True)
    controls = {}
    if args.control:
        controls["step_down"] = check.control_first(server)
        if server.plan is not None:
            controls["plan_all_fp8"] = check.control_first(server,
                                                           keep_plan=True)
    rows_pad = mix["answer_tokens"]["max"]
    for seed in args.seeds:
        data, results, items = server.run(seed, args.seconds, False)
        server.release()
        out = {"seed": seed, "window_tokens": stats.tokens_in_window(
            data.token_times, data.t_open, data.t_close),
            "rate": stats.rate(data.token_times, data.t_open, data.t_close),
            "counters": data.counters}
        sample = check.sample(check.finished(results, items),
                              np.random.default_rng([seed, 3]),
                              **mix["check"])
        t = time.perf_counter()

        def ref(ids, rows):
            return server.ref.logits_at(server.params, config, ids, rows,
                                        mix["server"]["max_len"])
        out["program"] = check.summary(
            check.reference_gaps(ref, sample, rows_pad))
        out["program"]["correct"] = harness.verdict(
            out["program"], limits, data.counters)[0]
        out["reference_s"] = time.perf_counter() - t
        out["sampled"] = [len(x[2]) for x in sample]
        for name, first in controls.items():
            t = time.perf_counter()
            out[name] = check.summary(
                check.control_gaps(ref, first, sample, rows_pad))
            out[name]["correct"] = harness.verdict(
                out[name], limits, data.counters)[0]
            out[name + "_s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
