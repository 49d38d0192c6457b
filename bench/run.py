"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace 0|1

Exits non-zero, printing no result, where JAX finds no accelerator or fewer
chips than the cell asks for. JAX's persistent compilation cache lives at
``.bench_cache/jax`` in the checkout, or where ``JAX_COMPILATION_CACHE_DIR``
points.
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
# the TPU runtime's logs stay in the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = harness.load_json(harness.BENCH / "configs"
                               / f"{cell['config']}.json")
    mix = harness.load_json(harness.BENCH / "traffic"
                            / f"{cell['traffic']}.json")
    limits = harness.load_json(harness.BENCH / "limits"
                               / f"{args.workload}.json")

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(harness.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform not in ("tpu", "gpu"):
        print(f"no accelerator: JAX found {dev.platform} ({dev.device_kind})",
              file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"the cell needs {cell['chips']} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    from bench.peaks import peaks_for
    try:
        peaks = peaks_for(dev.device_kind)
    except LookupError as e:
        print(e, file=sys.stderr)
        return 3
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    clock = harness.CompileClock()
    result, lines = harness.execute(spec, args.workload, config, mix, limits,
                                    args.seed, args.seconds,
                                    bool(args.trace), peaks, device, STARTED,
                                    clock)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
