"""Plain float32 references, written from the published descriptions.

They read the benchmark's configuration file and the parameter tree of
``bench/weights.py``, and import nothing of the program under test.
``quant`` computes the same function in a lower precision: the control
that the comparison must fail.
"""
