"""A four-chip stand-in plane for the tests: the world of ``standin.py``
(``standin_plane.py``, beside it in ``planes/``), handed over as a plane
that solves on a mesh would hand it over. The solve check is a
``solve_check``: the plain reference's answer, computed only when the
harness asks for it after the window, beside the program's. The planner's
facts report its task table spread over ``table_devices`` devices (the
configuration's, else the cell's chips). With ``solve_check`` false in the
configuration it hands over no solve check at all."""

import os

from benchmarks.reference import greedy
from benchmarks.spec import _load_module

standin = _load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "standin.py"),
    "benchmarks.planes.standin")


def run(ctx) -> dict:
    rec = standin.run(ctx)
    inputs, pad_prio = rec.pop("solve_inputs"), rec.pop("pad_prio")
    got = rec.pop("solve_got")
    if ctx.config.get("solve_check", True):
        rec["solve_check"] = {
            "want": lambda: greedy.greedy_assign(*inputs, pad_prio),
            "got": got}
    rec["device"]["count"] = ctx.chips
    rec["facts"]["path"] = "standin-mesh"
    rec["facts"]["table_devices"] = ctx.config.get("table_devices",
                                                   ctx.chips)
    return rec
