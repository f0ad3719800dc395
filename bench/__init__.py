"""On-chip benchmark of the k-bisimulation system.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` on the chip it finds and prints one
JSON result line.  Everything that defines the measurement lives here:
configurations (`configs/`) and the graph families they name
(`generators/`), traffic mixes (`traffic/`) and the drivers they name
(`drivers/`), per-layer metric readers (`metrics/`), the trace
reduction (`trace.py`), the HBM byte counts (`bytecount.py`), the peak
table (`peaks.json`), the plain reference that decides `correct`
(`reference.py`) and the controls that must fail it (`control.py`).
"""
