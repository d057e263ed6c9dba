"""Chip benchmark of the FlowLog-JAX Datalog engine.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chips
of this machine and prints one JSON result line. Everything a cell
needs is found by name: its configuration in ``configs/<config>.json``,
the generator and reference that configuration names in
``generators/`` and ``references/``, its traffic mix in
``traffic/<traffic>.json``, the driver that mix names in
``drivers/<driver>.py``, and each metric's reader in
``metrics/<metric>.py``.
"""
