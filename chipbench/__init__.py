"""Chip benchmark of the HiFrames serving path (``python3 chipbench/run.py``).

Everything that belongs to one configuration, traffic mix, query, answer
limit or metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    chipbench/configs/<config>.json     deployment: tables, scale, layout
    chipbench/traffic/<mix>.json        query, loop, rate: read by loops.py
    chipbench/queries/<query>.py        the query, its numpy reference
    chipbench/limits/<config>.<query>.json   limit of each number compared
    chipbench/metrics/<metric>.py       read(run) -> value or None; a
                                        metric ``<q>.<cells>`` without a file
                                        of its own is read by ``<q>.py``
"""
