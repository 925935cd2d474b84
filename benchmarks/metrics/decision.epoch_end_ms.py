"""Median host milliseconds of the ``decision.run()`` calls at a class
end: they hold the metric fetch that drains the device."""


def read(ctx):
    return ctx["median_ms"]("decision.epoch_end")
