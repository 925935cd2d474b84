"""Median host milliseconds of one ``loader.run()`` firing."""


def read(ctx):
    return ctx["median_ms"]("loader.run")
