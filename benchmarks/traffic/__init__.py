"""Traffic kinds: one module per kind, found by the name in a
workload file's ``traffic``.  A kind is the adapter between the
benchmark and the program's entry point; a mix of a kind is a data
file under ``benchmarks/workloads/``.

A kind's module has a class ``Cell(mix, cfg, seed, seconds, trace,
device=, t_start=, chip_start_s=)`` that ``run.py`` drives in this
order: ``run(sabotage)`` (set-up, warm-up, the measured window),
``memory_peak_bytes()``, ``context()`` (what the per-layer readers
see), ``end_to_end()`` ({metric: value}), ``attempted()`` ((attempted,
failed)), ``summary()`` (the run line's own numbers), ``release()``
(free the program's device state), then ``judge()`` ((correct,
{name: {"value", "limit"}}, detail) — the plain reference runs here,
last).  ``trace_dir`` and ``marks`` are read as attributes."""
