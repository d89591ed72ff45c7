"""Scheduler occupancy: ``ServeStats.occupancy_sum`` over pooled steps,
summed over the window's ``PoolEngine.run`` calls (so weighted by steps),
in % of the slots."""


def read(ctx):
    rec = ctx["record"]
    return 100.0 * rec["occupancy_sum"] / rec["steps"]
