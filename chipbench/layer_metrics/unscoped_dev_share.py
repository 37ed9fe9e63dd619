"""Share of the window's device-busy time in which no operation under one of
the family's named scopes ran: how much the per-scope metrics cannot see
(what the compiler inserted without metadata, the few operations between
the blocks, and the turns of a loop whose body is scoped)."""
from chipbench.scopes import unscoped_share as read  # noqa: F401
