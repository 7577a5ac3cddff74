"""The chip benchmark: one command runs one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``workloads/<cell>.json``. It names a configuration
(``configs/<config>.json`` with its plain reference beside it), a
traffic mix (``traffic/<mix>.json``, read by the generator it names in
``generators/``) and a driver (``drivers/<driver>.py``). Per-layer
metrics are readers in ``metrics/<metric>.py``. Adding any of them is
adding a file; ``harness.py`` finds each by its name.
"""
