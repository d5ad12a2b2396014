"""Median host time of one Executor.run dispatch in the window."""
from perf.lib import stats


def read(facts):
    spans = facts.get("dispatch_ms")
    return stats.median(spans) if spans else None
