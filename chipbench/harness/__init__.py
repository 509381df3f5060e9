"""Shared pieces of the chip benchmark: the spec loader and result line
(``bench``), the device check and peak table (``device``), weights from the
seed (``weights``), FLOP/byte counts from shapes (``flops``), the trace
reducer (``trace``) and the comparison helpers (``compare``)."""
