"""The benchmark of seaweedfs_tpu: harness, yardstick and data files.

Everything `BENCHMARK.json` names lives here: the command
(`python3 -m benchmark.run`), one file per configuration (`configs/`),
per traffic mix (`traffic/`) and per per-layer metric (`metrics/`),
the peaks table, the trace reduction and the plain Reed-Solomon
reference.  From the program it takes only the system under test.
"""
