"""Seconds of the backend stages that HIT the persistent compile cache before the
window opened — retrieval, deserialisation and loading of the executable —
slowest group: the stat ``load_s`` of the program's ``tft.build.counters``
(``build_account.py``). With ``build_compile_s_in_setup`` it reads from inside
what ``compile_s_in_setup`` scrapes from JAX's log; an exact 0 is a reading (a
cold start)."""

import build_account

NAME, UNIT, SOURCE = "build_load_s_in_setup", "s", "program_counter"
LAYER = "compile cache"
MOVES = "setup_s"


def compute(run):
    return build_account.slowest_group(run, "load_s")
