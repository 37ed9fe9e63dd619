"""Experts whose weights a decode program's expert GEMMs read over the
experts the chip holds x expert layers (the program's own count, on its
``uccl.ep.experts`` span inside ``uccl.wire.decode``), in %; median over
the window's decode spans. 100 is a program that reads every expert held
whatever the rows; ``None`` on a program that reports no count."""
from chipbench.experts_read import decode_experts_read_share as read  # noqa: F401
