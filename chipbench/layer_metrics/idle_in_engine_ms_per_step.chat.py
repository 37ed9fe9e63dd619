"""Device idle time during the engine's own bookkeeping — innermost program
span ``uccl.engine.step``, ``engine.admit``, ``engine.retire`` or a
``wire.*`` span outside the backend's three — per engine step of the
window."""

from chipbench import program_trace as pt


def read(view):
    return pt.idle_ms_per_step(view, pt.IDLE_ENGINE)
