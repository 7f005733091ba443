"""The on-chip benchmark: `python3 bench/run.py --workload <cell> ...`.

`BENCHMARK.json` at the checkout's root names the cells; everything that
belongs to one configuration, traffic mix or per-layer metric sits in a file
of its own under this directory, found by its name:

    configs/<config>.json     sizes, dtypes and source of a model
    reference/<net>.py        its plain float32 reference (imports nothing
                              of the program)
    traffic/<mix>.json        parameters read by `loadgen.py`
    limits/<cell>.json        the limit of each number the cell's check
                              compares, set from that cell's readings
    metrics/<metric>.py       a reader: `read(ctx) -> float | None`
"""
