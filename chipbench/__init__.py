"""On-chip benchmark of the radiomics extractor, driven by data.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything a cell is made of
is found by name: its configuration in ``configs/``, its traffic mix in
``traffic/``, the entry point that configuration calls in ``drivers/``,
and each metric's reader in ``metrics/``.  ``reference/`` holds the plain
numpy reference that decides ``correct``.
"""
