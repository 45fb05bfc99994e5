"""Operations and least bytes that each unit of work needs, worked out
from the configuration file's sizes and never from the program, so a
roofline reads the same work whatever implements it.

A unit is an image (CNN), a prefill of L real rows, or a decode token at
context c (the rows it attends to, itself included).  Only the work the
algorithm needs counts: no padding rows, no masked-out keys, no logits
that nobody reads.  Bytes are the least traffic with device memory:
each operand read once and each result written once.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def itemsize(dtype: str) -> int:
    return ITEMSIZE[dtype]
