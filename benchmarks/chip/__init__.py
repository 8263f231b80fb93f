"""On-chip benchmark of the FeDLRT system's serving of low-rank factors,
driven by data.

One command runs one cell once::

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one entry lives in a file of its own, found by
name: ``configs/<config>.json`` (sizes, with the plain reference named in
``architecture``), ``workloads/<cell>.json`` (configuration, traffic mix
and correctness limits), ``traffic/<mix>.json`` (the mix's parameters,
read by the generator ``traffic/<kind>.py``), ``metrics/<metric>.py`` (a
per-layer reader) and ``peaks.json`` (device peaks by ``device_kind``).
"""
