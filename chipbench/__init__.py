"""The chip benchmark of the in-database forest engine.

One cell of ``BENCHMARK.json`` (a deployment under a traffic mix) runs
once per process: ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that measures lives here and
nothing of it is imported by the engine under ``src/``: the seeded data,
the traffic generator, the plain reference that decides ``correct``, the
table of peaks, the operation and byte counts, and the reduction of the
profiler's trace to per-layer metrics.

Configurations (``configs/<name>.json``), traffic mixes
(``traffic/<name>.json``), the loops that drive them (``loops/<loop>.py``,
named by a mix's ``loop`` key) and metric readers (``metrics/<name>.py``)
are found by the names ``BENCHMARK.json`` and the mixes give them, so a
new cell, mix, kind of traffic or metric is a new file and a new entry.
"""
