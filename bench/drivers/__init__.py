"""One module per program entry that a cell's window drives.

A configuration names its driver (``"driver"`` in its file); the harness
imports ``bench.drivers.<driver>`` and uses its ``Driver`` class.
"""


def scheduler_config(sched: dict):
    """The program's ``SchedulerConfig`` from a configuration's ``sched`` group."""
    from repro.sched import Objective, SchedulerConfig

    fields = {k: v for k, v in sched.items() if k != "objective"}
    return SchedulerConfig(objective=Objective(kind=sched["objective"]), **fields)

