"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything that defines the measurement lives here: traffic generation,
weights, the plain reference, the trace reduction, the table of chip peaks
and the FLOP and byte counts.
"""
