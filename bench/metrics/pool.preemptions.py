"""Requests the paged pool preempted during the window (gateway counter
``preempted``)."""


def read(run):
    return run.counters["after"]["preempted"] - run.counters["before"]["preempted"]
