"""Median queue wait of the window's answered requests, from the
service's own ``queue_wait_s`` on each response."""
import math


def read(view):
    wait = view.counters.get("queue_wait_median_s")
    if wait is None or math.isnan(wait):
        return None
    return wait * 1e3
