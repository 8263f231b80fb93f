"""On-chip benchmark of the FeDLRT system's serving of low-rank factors,
driven by data.

One command runs one cell once::

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one entry lives in a file of its own, found by
name: ``configs/<config>.json`` (sizes, and the architecture they are
of), ``references/<architecture>.py`` (everything the benchmark knows of
that architecture's shape and math: its factorized weights, its other
leaves and how each is seeded, the program's fields it must match, the
map from the program's parameter paths, the plain reference's logits and
a decode step's FLOPs and bytes; ``harness.py`` lists the functions),
``workloads/<cell>.json`` (configuration, traffic mix and correctness
limits), ``traffic/<mix>.json`` (the mix's parameters, read by the
generator ``traffic/<kind>.py``), ``metrics/<metric>.py`` (a per-layer
reader) and ``peaks.json`` (device peaks by ``device_kind``).
"""
