"""Share of the transform's dense multiply-adds that block-ESOP skipped
over the traced cycle, in percent: 100 * (1 - delta ``engine.macs_effective``
/ delta ``engine.macs``), the program's own counters.  A program without
the ``engine.macs_effective`` counter reads nothing."""


def read(ctx):
    traced = ctx.traced or {}
    macs = traced.get("engine.macs", 0)
    effective = traced.get("engine.macs_effective", 0)
    if macs <= 0 or effective <= 0:
        return None
    return 100.0 * (1.0 - effective / macs)
