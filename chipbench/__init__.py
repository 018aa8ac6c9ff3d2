"""chipbench: the ledgered benchmark of metaopt-tpu (see README.md here).

Everything that measures lives in this directory; from the program it
takes only the system under test. One command::

    python3 -m chipbench --workload W --seed N --seconds S --trace 0|1
"""
