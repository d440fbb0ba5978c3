"""Lowering of a configuration's statement under a traffic mix.

A loop (``loops/``) lowers what its step needs through these functions
and hands the kernels back to the harness, which logs each and refuses a
run in which any fell back. A step of several statements calls
:func:`lower` once for each.
"""
from __future__ import annotations

import spec


def lower(stmt, mix: dict):
    """Lower under the traffic's machine and its named default schedule
    (``default_<schedule>_schedule`` of the program)."""
    import repro.core as rc
    machine = rc.Machine(*[tuple(ax) for ax in mix["machine"]])
    sched = getattr(rc, f"default_{mix['schedule']}_schedule", None)
    if sched is None:
        raise LookupError(f"the program has no schedule "
                          f"{mix['schedule']!r}")
    return rc.lower_stmt(stmt, machine, schedule=sched(stmt, machine))


def lower_statement(cfg: dict, B, dense, mix: dict, parts):
    """The configuration's statement (its ``reference`` module) over ``B``
    and ``dense``, lowered; the host seconds are the set-up part
    ``lower_s``."""
    stmt = spec.module("reference", cfg["reference"]).statement(cfg, B,
                                                                 dense)
    return parts.time("lower_s", lower, stmt, mix)
