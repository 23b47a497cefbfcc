"""Median ``ServeResult.time_in_queue_s`` of the requests completed."""

import statistics


def read(view):
    waits = view.record.get("queue_wait_s")
    return statistics.median(waits) if waits else None
